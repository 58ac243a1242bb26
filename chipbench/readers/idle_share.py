"""Share of the traced window in which no operation ran on the device,
averaged over the chips."""


def read(obs, params):
    tr = obs.get('trace')
    if not tr or not tr.get('window_s'):
        return None
    return 100.0 * (1.0 - tr['busy_s'] / tr['window_s'])
