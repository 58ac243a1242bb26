"""The program's own host spans in a profiler trace, on the clock the
device events of the same file stand on.

`jax.profiler.TraceAnnotation` and `StepTraceAnnotation` events land in
the `.xplane.pb`'s plane '/host:CPU', on the line of the thread that made
them, beside the profiler's own events of that thread. sft's step loop
writes `train.step` (one an iteration, with the stat `step_num`) and in
it `train.input_wait`, `train.dispatch` and `train.log` with
`train.pull` inside it; the prefetcher's producer thread writes
`prefetch.build` and `prefetch.place` on a line of its own. Read with
jax.profiler.ProfileData, which needs JAX but no device; a reader calls
this in a child pinned to the CPU (python chipbench/xplane_host.py
<file>), because the parent of a cell never imports JAX.

`start_ns` and `end_ns` are ProfileData's, as xplane.py reads a device
plane's: an idle gap of the device and the host span that covers it
compare directly.
"""
import json
import sys

HOST_PLANE = '/host:CPU'
PREFIXES = ('train.', 'prefetch.')
STEP = 'train.step'


def spans(path: str, prefixes: tuple = PREFIXES) -> list:
    """[(name, thread line, start_ns, end_ns, step_num)] of the host
    events whose name starts with one of `prefixes`, by start. A thread
    line is '<the line's name>#<its place in the plane>' (two Python
    threads' lines share a name). `step_num` is the `train.step` span's
    own stat, for a span inside one on its line that step's, else
    None."""
    from jax.profiler import ProfileData
    found = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != HOST_PLANE:
            continue
        for place, line in enumerate(plane.lines):
            thread = f'{line.name}#{place}'
            mine = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats).get('step_num') if e.name == STEP
                     else None)
                    for e in line.events if e.name.startswith(prefixes)]
            steps = [s for s in mine if s[0] == STEP]
            for name, a, b, step_num in mine:
                if name != STEP:
                    step_num = next((n for _, sa, sb, n in steps
                                     if sa <= a and b <= sb), None)
                found.append((name, thread, a, b, step_num))
    return sorted(found, key=lambda s: (s[2], -s[3]))


def per_step(found: list, name: str, less: tuple = ()) -> list:
    """Per whole `train.step` span, in ms: the time of the spans called
    `name` inside it (of the step itself for 'train.step'), less that of
    the spans inside those called one of `less`."""
    out = []
    for kind, thread, a, b, step_num in found:
        if kind != STEP:
            continue
        inside = [(n, sa, sb) for n, t, sa, sb, k in found
                  if t == thread and k == step_num and n != STEP
                  and a <= sa and sb <= b]
        named = [(a, b)] if name == STEP else [
            (sa, sb) for n, sa, sb in inside if n == name]
        taken = sum(sb - sa for n, sa, sb in inside if n in less
                    and any(na <= sa and sb <= nb for na, nb in named))
        out.append((sum(nb - na for na, nb in named) - taken) / 1e6)
    return out


def each(found: list, name: str) -> list:
    """In ms, the time of each span called `name` that lies inside the
    stretch of the whole `train.step` spans, on whichever line: what a
    thread beside the step loop spent a turn (the prefetcher's producer
    builds and places one batch for every step the loop takes)."""
    steps = [(a, b) for kind, _, a, b, _ in found if kind == STEP]
    if not steps:
        return []
    first, last = min(a for a, _ in steps), max(b for _, b in steps)
    return [(b - a) / 1e6 for n, _, a, b, _ in found
            if n == name and first <= a and b <= last]


if __name__ == '__main__':
    print(json.dumps(spans(sys.argv[1])))
