"""Device time per step, in ms, of the operations whose short name
(xplane.short_name) matches params['pattern']: their self time in the
trace over the whole steps the traced window holds, per chip."""
import re

import xplane


def read(obs, params):
    tr = obs.get('trace')
    if not tr or not tr.get('steps'):
        return None
    pat = re.compile(params['pattern'])
    hit = sum(s for name, s in tr['ops_s']
              if pat.search(xplane.short_name(name)))
    return 1e3 * hit / (tr['steps'] * tr['chips']) if hit else None
