"""From a profiler trace (xplane.pb) to device busy and idle time,
time per operation and the longest idle gaps. Read with
jax.profiler.ProfileData, which needs JAX but no device; the harness
calls this in a child pinned to the CPU (python chipbench/xplane.py
<file>), because the parent of a cell never imports JAX.

A TPU plane '/device:TPU:<n>' has the lines 'XLA Modules' (one event per
executed program) and 'XLA Ops' (one per HLO operation, nested: a while
loop's event contains its body's). Busy time is the union of the op
intervals. The window is cut at whole programs: from the start of the
first module event that begins inside the trace to the end of the last
one that ends inside it — for a training trace with `whole_steps`, from
the start of the first whole step to the start of the last, so the
profiler's own start and stop are not counted as idle. `steps` is the
number of whole runs, inside the window, of the program that took most
of the time (the training step): what a time per step divides by.
"""
import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r'^/device:TPU:(\d+)$')


def short_name(name: str) -> str:
    """'%fusion.1 = bf16[..] fusion(...), custom_call_target="x"' ->
    'fusion.1 [x]': the text before the first ' = ', and the
    custom-call target where there is one."""
    head = name.split(' = ', 1)[0].lstrip('%')
    m = re.search(r'custom_call_target="([^"]+)"', name)
    return f'{head} [{m.group(1)}]' if m else head


def _union(intervals: list) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _self_times(events: list) -> dict:
    """Self time per name over one line's nested events (start, end,
    name): an event's duration less its children's."""
    total = {}
    stack = []   # (end, name, [self])
    for a, b, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack:
            stack[-1][2][0] -= min(b, stack[-1][0]) - a
        cell = [b - a]
        stack.append((b, name, cell))
        total.setdefault(name, []).append(cell)
    return {n: sum(c[0] for c in cells) for n, cells in total.items()}


def reduce_plane(modules: list, ops: list, whole_steps: bool) -> dict:
    """modules, ops: [(start_ns, end_ns, name)] of one device."""
    if not ops or not modules:
        return None
    modules = sorted(modules)
    if whole_steps and len(modules) >= 3:
        lo, hi = modules[1][0], modules[-1][0]
    else:
        lo, hi = modules[0][0], max(b for _, b, _ in modules)
    clipped = [(max(a, lo), min(b, hi), n) for a, b, n in ops
               if b > lo and a < hi]
    merged = _union([(a, b) for a, b, _ in clipped])
    busy = sum(b - a for a, b in merged)
    gaps = sorted(((b2 - b1, b1) for (_, b1), (b2, _) in
                   zip([[lo, lo]] + merged, merged + [[hi, hi]])),
                  reverse=True)
    spent = {}
    for a, b, n in modules:
        spent[n] = spent.get(n, 0) + b - a
    main = max(spent, key=spent.get)   # the step, or the decode chunk
    return {'window_ns': hi - lo, 'busy_ns': busy,
            'steps': sum(1 for a, b, n in modules
                         if n == main and a >= lo and b <= hi),
            'self_ns': _self_times(clipped),
            'gaps_ns': [g for g, _ in gaps[:10] if g > 0],
            'programs': sorted({n for _, _, n in modules})}


def reduce_file(path: str, whole_steps: bool = False) -> dict:
    """The reduction over all TPU planes of one trace file: busy and
    window seconds averaged over the chips, self time per operation
    summed over them."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: [(e.start_ns, e.start_ns + e.duration_ns,
                              e.name) for e in line.events]
                 for line in plane.lines}
        red = reduce_plane(lines.get('XLA Modules', []),
                           lines.get('XLA Ops', []), whole_steps)
        if red:
            planes.append(red)
    if not planes:
        return {'chips': 0}
    ops = {}
    for p in planes:
        for name, ns in p['self_ns'].items():
            ops[name] = ops.get(name, 0) + ns
    top = sorted(ops.items(), key=lambda kv: -kv[1])
    n = len(planes)
    return {
        'chips': n,
        'busy_s': sum(p['busy_ns'] for p in planes) / n / 1e9,
        'window_s': sum(p['window_ns'] for p in planes) / n / 1e9,
        'steps': min(p['steps'] for p in planes),
        'ops_s': [[name, ns / 1e9] for name, ns in top],
        'gaps_s': sorted((g / 1e9 for p in planes for g in p['gaps_ns']),
                         reverse=True)[:10],
        'programs': sorted({x for p in planes for x in p['programs']}),
    }


def find_trace(directory: str):
    """The newest *.xplane.pb under a profile directory, or None."""
    found = glob.glob(os.path.join(directory, '**', '*.xplane.pb'),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


if __name__ == '__main__':
    print(json.dumps(reduce_file(sys.argv[1], sys.argv[2:] == ['steps'])))
