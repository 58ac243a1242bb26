"""Host time per step, in ms, of one of the program's own spans in the
traced window: the median, over the whole `train.step` spans the trace
holds, of the time of the spans called params['span'] inside a step
(of the step itself for `train.step`), less that of the spans inside
those called one of params['less'] (xplane_host.per_step). The spans
are read once a run, in a child pinned to the CPU as run.py reads the
device planes, and kept in `obs` (`spans`, which host_span_ms_each
shares). A program that writes no such span gives None."""
import json
import subprocess

import common
import xplane
import xplane_host


def spans(obs) -> list:
    if '_host_spans' not in obs:
        obs['_host_spans'] = []
        path = xplane.find_trace(obs['profile_dir'])
        if path:
            res = subprocess.run(
                [common.python(), common.bench_path('xplane_host.py'), path],
                env=common.child_env('cpu', {}), cwd=common.ROOT,
                capture_output=True, text=True, timeout=300)
            if res.returncode == 0:
                obs['_host_spans'] = [tuple(s) for s in json.loads(
                    res.stdout.strip().splitlines()[-1])]
            else:
                common.say(f'host span reader failed: {res.stderr[-400:]}')
    return obs['_host_spans']


def read(obs, params):
    if not obs.get('profile_dir'):
        return None
    found = spans(obs)
    if not any(s[0] == params['span'] for s in found):
        return None
    per_step = xplane_host.per_step(found, params['span'],
                                    tuple(params.get('less', ())))
    return common.median(per_step) if per_step else None
