"""HLO communication census: what a compiled step MOVES, per mesh axis.

Walks a jax stage's module text counting collective ops — all-reduce /
all-gather / reduce-scatter / collective-permute — with bytes-moved and
mesh-axis attribution, so ``census × profile`` (comms_profile.py)
predicts a per-step comms-time breakdown per axis: the number that
says "step time is 31% DCN all-gather" (docs/observability.md "Comms
plane").

Two dialects, one walker:

  * **Lowered StableHLO** — the same stage PR 8's MFU estimator reads
    (``step_fn.lower(...)``; no backend compile). Collectives written
    explicitly through ``shard_map`` — the pipeline's ppermute ring,
    ring attention, the probe itself — are present here with their
    ``replica_groups``. pjit/GSPMD programs carry only *sharding
    annotations* at this stage: their collectives are inserted by the
    SPMD partitioner at compile time and census as zero.
  * **Compiled HLO** — ``lowered.compile().as_text()``: the post-SPMD
    module where GSPMD's inserted collectives are visible. Costs one
    AOT backend compile (seconds for the debug model, minutes at 70B),
    so ``SKYT_COMMS_CENSUS=compiled`` is opt-in; the dryrun harness,
    bench, and tests use it on tiny models.

Axis attribution needs no device ids: replica groups name positions in
the executable's device *assignment*, which jax builds as the
row-major flattening of ``mesh.devices`` — so ``unravel_index`` over
the mesh shape recovers each participant's coordinates, and the axes
that VARY within a group are the axes the collective rides. This stays
correct under the measured-placement permutation (mesh.py), which
permutes which physical device sits at each coordinate, not the
coordinate math.

Estimate caveats (documented in the ops tables too): counts are
*static sites* — a collective inside a scanned layer loop counts once,
so scanned models' byte totals are per-site lower bounds (the repo's
models unroll small configs and scan large ones); and predicted
seconds assume no compute/comms overlap, so they bound the exposed
comms time from above.
"""
import dataclasses
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from skypilot_tpu.utils import env
from skypilot_tpu.utils import log_utils
from skypilot_tpu.utils import metrics as metrics_lib

logger = log_utils.init_logger(__name__)

OPS = ('all_reduce', 'all_gather', 'reduce_scatter',
       'collective_permute')

_DTYPE_BYTES = {
    'f64': 8, 'f32': 4, 'f16': 2, 'bf16': 2,
    'f8e4m3fn': 1, 'f8e5m2': 1, 'f8e4m3b11fnuz': 1,
    'i64': 8, 'ui64': 8, 'i32': 4, 'ui32': 4, 's32': 4, 'u32': 4,
    'i16': 2, 'ui16': 2, 's16': 2, 'u16': 2,
    'i8': 1, 'ui8': 1, 's8': 1, 'u8': 1, 'i1': 1, 'pred': 1,
    'i4': 1, 'ui4': 1, 's4': 1, 'u4': 1,
}


@dataclasses.dataclass
class CensusEntry:
    """One collective site found in the module."""
    op: str                    # all_reduce | all_gather | ...
    axes: Tuple[str, ...]      # mesh axes the groups vary over
    ranks: int                 # participants per group
    payload_bytes: int         # nccl-convention payload per site
    count: int = 1


# ------------------------------------------------------- type parsing
def _tensor_bytes(tok: str) -> int:
    """'2x4x64xf32' or 'f32' (stablehlo) -> byte size."""
    parts = tok.strip().split('x')
    dtype = parts[-1]
    size = _DTYPE_BYTES.get(dtype)
    if size is None:
        return 0
    n = 1
    for p in parts[:-1]:
        try:
            n *= int(p)
        except ValueError:
            return 0
    return n * size


def _hlo_shape_bytes(tok: str) -> int:
    """'f32[4,64]' (layout braces already stripped) -> byte size."""
    m = re.match(r'([a-z0-9]+)\[([0-9,]*)\]', tok.strip())
    if not m:
        return 0
    size = _DTYPE_BYTES.get(m.group(1))
    if size is None:
        return 0
    n = 1
    for p in m.group(2).split(','):
        if p:
            n *= int(p)
    return n * size


# -------------------------------------------------- group -> mesh axes
def _attribute(groups: Sequence[Sequence[int]], mesh
               ) -> Tuple[Tuple[str, ...], int]:
    """(axes that vary within the groups, ranks per group). Group
    members are positions in the row-major flattening of mesh.devices
    (the executable's device assignment)."""
    shape = tuple(mesh.devices.shape)
    names = tuple(mesh.axis_names)
    total = int(np.prod(shape))
    varying: set = set()
    ranks = 1
    for group in groups:
        group = [g for g in group if 0 <= g < total]
        if len(group) < 2:
            continue
        ranks = max(ranks, len(group))
        coords = np.array([np.unravel_index(g, shape) for g in group])
        for i, name in enumerate(names):
            if len(set(coords[:, i].tolist())) > 1:
                varying.add(name)
    return tuple(sorted(varying)), ranks


def _parse_dense_groups(text: str) -> List[List[int]]:
    """'dense<[[0, 1], [2, 3]]> : tensor<2x2xi64>' (or a splat
    'dense<0> : tensor<1x1xi64>') -> [[0,1],[2,3]]."""
    m = re.match(r'dense<\[\[(.*)\]\]>', text, re.DOTALL)
    if m:
        return [[int(v) for v in row.split(',') if v.strip()]
                for row in m.group(1).split('], [')]
    m = re.match(r'dense<(\d+)>\s*:\s*tensor<(\d+)x(\d+)xi64>', text)
    if m:   # splat: every element the same value
        rows, cols = int(m.group(2)), int(m.group(3))
        return [[int(m.group(1))] * cols for _ in range(rows)]
    return []


def _expand_iota_groups(n_groups: int, group_size: int,
                        dims: Sequence[int],
                        perm: Optional[Sequence[int]]
                        ) -> List[List[int]]:
    """HLO iota replica-group form '[G,S]<=[d...]T(p...)': iota over
    prod(dims), reshaped to dims, transposed by p, flattened, then cut
    into G groups of S."""
    arr = np.arange(int(np.prod(dims))).reshape(tuple(dims))
    if perm is not None:
        arr = arr.transpose(tuple(perm))
    flat = arr.reshape(-1)
    if flat.size != n_groups * group_size:
        return []
    return flat.reshape(n_groups, group_size).tolist()


_HLO_GROUPS_RE = re.compile(
    r'replica_groups=(?:\{(?P<lit>[{}0-9,]*)\}|'
    r'\[(?P<g>\d+),(?P<s>\d+)\]<=\[(?P<dims>[\d,]+)\]'
    r'(?:T\((?P<perm>[\d,]+)\))?)')
_HLO_PAIRS_RE = re.compile(r'source_target_pairs=\{(?P<lit>[{}0-9,]*)\}')


def _parse_hlo_groups(line: str) -> List[List[int]]:
    m = _HLO_GROUPS_RE.search(line)
    if m:
        if m.group('lit') is not None:
            return [[int(v) for v in grp.split(',') if v.strip()]
                    for grp in m.group('lit').strip('{}').split('},{')
                    if grp.strip()]
        dims = [int(v) for v in m.group('dims').split(',')]
        perm = ([int(v) for v in m.group('perm').split(',')]
                if m.group('perm') else None)
        return _expand_iota_groups(int(m.group('g')), int(m.group('s')),
                                   dims, perm)
    m = _HLO_PAIRS_RE.search(line)
    if m:
        return [[int(v) for v in pair.split(',') if v.strip()]
                for pair in m.group('lit').strip('{}').split('},{')
                if pair.strip()]
    return []


# --------------------------------------------------------- the walkers
_STABLEHLO_OP_RE = re.compile(
    r'"?stablehlo\.(all_reduce|all_gather|reduce_scatter|'
    r'collective_permute)"?\(')
_STABLEHLO_SIG_RE = re.compile(
    r':\s*\((tensor<[^)]*?)\)\s*->\s*\(?\s*(tensor<[^>]+>)')
_STABLEHLO_GROUPS_RE = re.compile(
    r'(?:replica_groups|source_target_pairs)\s*=\s*'
    r'(dense<[^>]*(?:>\s*:\s*tensor<[^>]+>)?)', re.DOTALL)

_HLO_OP_RE = re.compile(
    r'^\s*(?:ROOT\s+)?%\S+\s*=\s*(\(?[a-z0-9]+\[[^=]*?)\s'
    r'(all-reduce|all-gather|reduce-scatter|collective-permute)'
    r'(-start)?\(')


def _census_stablehlo(text: str, mesh) -> List[CensusEntry]:
    out: List[CensusEntry] = []
    # The window must span the op's whole attribute block up to its
    # type signature; a dense replica_groups literal prints every
    # participating device id, so scale with the mesh size (~8 chars
    # per id, 4x margin) instead of silently dropping sites on large
    # device counts.
    window_len = 8000 + 32 * int(mesh.devices.size)
    for m in _STABLEHLO_OP_RE.finditer(text):
        op = m.group(1)
        window = text[m.start():m.start() + window_len]
        sig = _STABLEHLO_SIG_RE.search(window)
        if sig is None:
            continue
        operand_toks = re.findall(r'tensor<([^>]+)>', sig.group(1))
        result_tok = re.search(r'tensor<([^>]+)>', sig.group(2))
        operand_bytes = sum(_tensor_bytes(t) for t in operand_toks)
        result_bytes = _tensor_bytes(result_tok.group(1)) \
            if result_tok else 0
        gm = _STABLEHLO_GROUPS_RE.search(window[:sig.start()] or window)
        groups = _parse_dense_groups(gm.group(1)) if gm else []
        axes, ranks = _attribute(groups, mesh)
        payload = result_bytes if op == 'all_gather' else operand_bytes
        if payload <= 0 or ranks < 2:
            continue
        out.append(CensusEntry(op=op, axes=axes, ranks=ranks,
                               payload_bytes=payload))
    return out


def _census_hlo(text: str, mesh) -> List[CensusEntry]:
    out: List[CensusEntry] = []
    for line in text.splitlines():
        m = _HLO_OP_RE.match(line)
        if m is None:
            continue
        op = m.group(2).replace('-', '_')
        # The dump names operands without their types
        # ('all-reduce(%fusion.1)'), so the payload comes from the
        # result type: equal to the operand for all-reduce and
        # collective-permute, the gathered whole for all-gather, and
        # one rank's share of the operand for reduce-scatter.
        result_toks = re.findall(r'([a-z0-9]+\[[0-9,]*\])',
                                 m.group(1))
        if m.group(3) and op in ('all_gather', 'collective_permute'):
            # Their '-start' results are (operands..., results...)
            # tuples, plus scalar context words for the permute.
            result_toks = [t for t in result_toks
                           if not t.endswith('[]')]
            result_toks = result_toks[len(result_toks) // 2:]
        result_bytes = sum(_hlo_shape_bytes(t) for t in result_toks)
        groups = _parse_hlo_groups(line)
        axes, ranks = _attribute(groups, mesh)
        payload = result_bytes * ranks if op == 'reduce_scatter' \
            else result_bytes
        if payload <= 0 or ranks < 2:
            continue
        out.append(CensusEntry(op=op, axes=axes, ranks=ranks,
                               payload_bytes=payload))
    return out


def census_text(text: str, mesh) -> List[CensusEntry]:
    """Count the collectives in one module dump (either dialect)."""
    if 'stablehlo.' in text or 'mhlo.' in text:
        entries = _census_stablehlo(text, mesh)
        if entries:
            return entries
    return _census_hlo(text, mesh)


def census_mode() -> str:
    """'lowered' (default) | 'compiled' | 'off' from
    SKYT_COMMS_CENSUS; unknown values degrade to the default."""
    raw = (env.get('SKYT_COMMS_CENSUS') or 'lowered').strip().lower()
    if raw in ('0', 'off', 'false', 'no'):
        return 'off'
    if raw in ('compiled', 'compile', 'hlo'):
        return 'compiled'
    if raw not in ('lowered', '1', 'on', 'auto'):
        logger.warning('SKYT_COMMS_CENSUS=%r is not one of '
                       'off|lowered|compiled; using "lowered"', raw)
    return 'lowered'


def census_step(step_fn, *args, mesh, mode: Optional[str] = None,
                lowered=None) -> Tuple[List[CensusEntry], str]:
    """Census one jitted step -> (entries, source).

    source: 'stablehlo_lowered' (explicit shard_map collectives, no
    compile) or 'hlo_compiled' (post-SPMD; mode='compiled' descends
    there when the lowered walk finds nothing — one AOT backend
    compile, opt-in because it stalls for minutes on large models) or
    'off'. Never raises: a census failure costs the report, not the
    caller."""
    mode = mode or census_mode()
    if mode == 'off':
        return [], 'off'
    try:
        if lowered is None:
            lower = getattr(step_fn, 'lower', None)
            if lower is None:
                return [], 'unavailable'
            lowered = lower(*args)
        entries = census_text(lowered.as_text(), mesh)
        if entries or mode != 'compiled':
            return entries, 'stablehlo_lowered'
        compiled = lowered.compile()
        texts = compiled.as_text()
        if not isinstance(texts, (list, tuple)):
            texts = [texts]
        entries = []
        for t in texts:
            if t:
                entries.extend(_census_hlo(t, mesh))
        return entries, 'hlo_compiled'
    except Exception as e:  # pylint: disable=broad-except
        logger.warning('comms census failed (%s: %s); no report',
                       type(e).__name__, e)
        return [], 'error'


# ---------------------------------------------------------- estimates
def estimate(entries: Sequence[CensusEntry],
             profile: Optional[Dict[str, Any]] = None,
             dcn_axes: Sequence[str] = (),
             link_classes: Optional[Dict[str, str]] = None
             ) -> Dict[str, Dict[str, Any]]:
    """census × profile -> per-axis breakdown::

        {'<axis or a+b>': {'bytes': ..., 'seconds': float|None,
                           'link': 'ici'|'dcn',
                           'ops': {'<op>': {'count', 'bytes'}}}}

    bytes are per step (summed over sites); seconds use the profile's
    measured busbw for the nearest (op, link, payload) entry and stay
    None when the link was never probed. Partial coverage is explicit:
    ``unpriced_bytes`` counts the bytes of ops the profile could NOT
    price (e.g. a probe entry skipped by a comms.probe fault), so a
    seconds sum is never silently missing a dominant op."""
    from skypilot_tpu.parallel import collectives
    from skypilot_tpu.parallel import comms_profile
    link_classes = link_classes or {}
    out: Dict[str, Dict[str, Any]] = {}
    for e in entries:
        name = '+'.join(e.axes) if e.axes else 'unknown'
        link = 'dcn' if any(
            a in dcn_axes or link_classes.get(a) == 'dcn'
            for a in e.axes) else 'ici'
        row = out.setdefault(name, {'bytes': 0, 'seconds': None,
                                    'unpriced_bytes': 0,
                                    'link': link, 'ops': {}})
        row['link'] = link
        row['bytes'] += e.payload_bytes * e.count
        op_row = row['ops'].setdefault(e.op, {'count': 0, 'bytes': 0})
        op_row['count'] += e.count
        op_row['bytes'] += e.payload_bytes * e.count
        profile_op = 'ppermute' if e.op == 'collective_permute' \
            else e.op
        busbw = comms_profile.busbw_bytes_per_s(
            profile, profile_op, link, e.ranks, e.payload_bytes)
        if busbw:
            t = (e.payload_bytes *
                 collectives.busbw_factor(profile_op, e.ranks) /
                 busbw) * e.count
            row['seconds'] = (row['seconds'] or 0.0) + t
        elif profile is not None:
            row['unpriced_bytes'] += e.payload_bytes * e.count
    return out


def report(entries: Sequence[CensusEntry], source: str,
           profile: Optional[Dict[str, Any]] = None,
           dcn_axes: Sequence[str] = (),
           link_classes: Optional[Dict[str, str]] = None
           ) -> Dict[str, Any]:
    """The loggable/serializable comms report (sft log line, postmortem
    state.json, dryrun tail, /fleet/comms)."""
    axes = estimate(entries, profile, dcn_axes, link_classes)
    total_bytes = sum(r['bytes'] for r in axes.values())
    secs = [r['seconds'] for r in axes.values()
            if r['seconds'] is not None]
    return {
        'source': source,
        'sites': sum(e.count for e in entries),
        'axes': axes,
        'total_bytes': total_bytes,
        'total_seconds': (sum(secs) if secs else None),
    }


def format_report(rep: Dict[str, Any]) -> str:
    """One log line: 'dp: 1.2MiB dcn ~3.1ms; tp: 0.5MiB ici ~0.2ms'."""
    if not rep.get('axes'):
        return (f"no collectives found (source={rep.get('source')}; "
                f"SPMD-inserted collectives need "
                f"SKYT_COMMS_CENSUS=compiled)")
    parts = []
    for axis, row in sorted(rep['axes'].items()):
        txt = f"{axis}: {row['bytes'] / 2**20:.2f}MiB {row['link']}"
        if row['seconds'] is not None:
            txt += f" ~{row['seconds'] * 1e3:.2f}ms"
            if row.get('unpriced_bytes'):
                # The profile priced only part of this axis's traffic
                # (a probe entry was skipped): the estimate is a
                # known-incomplete lower bound.
                txt += (f" (+{row['unpriced_bytes'] / 2**20:.2f}MiB "
                        f"unpriced)")
        parts.append(txt)
    return '; '.join(parts)


def publish_metrics(rep: Dict[str, Any], steps: int = 1,
                    registry: Optional[
                        'metrics_lib.MetricsRegistry'] = None) -> None:
    """skyt_train_comm_bytes_total{axis,op} (+= per-step bytes ×
    steps) and skyt_train_comm_seconds_estimate{axis} (predicted
    seconds per step; absent without a probed profile)."""
    reg = registry or metrics_lib.REGISTRY
    bytes_total = reg.counter(
        'skyt_train_comm_bytes_total',
        'Collective bytes moved (census estimate × steps)',
        ('axis', 'op'))
    sec_gauge = reg.gauge(
        'skyt_train_comm_seconds_estimate',
        'Predicted per-step comms seconds (census × measured profile)',
        ('axis',))
    for axis, row in rep.get('axes', {}).items():
        for op, op_row in row.get('ops', {}).items():
            bytes_total.labels(axis, op).inc(op_row['bytes'] * steps)
        if row.get('seconds') is not None:
            sec_gauge.labels(axis).set(row['seconds'])
