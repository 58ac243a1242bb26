"""Process start to the window's opening instant: loading, compiling,
warming up, the lead-in."""


def read(obs, params):
    return obs['setup_s']
