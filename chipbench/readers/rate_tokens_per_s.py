"""Training tokens per second over all steps and all the time between
the window's first and last step boundary."""


def read(obs, params):
    b = obs['boundaries']
    if len(b) < 2:
        return None
    return (len(b) - 1) * obs['tokens_per_step'] / (b[-1] - b[0])
