"""Comms-plane link profile: a structured ICI/DCN topology probe.

The collectives benchmark (parallel/collectives.py) prints prose; this
module turns the same sweep into a *profile* the rest of the system can
consume: per (op, mesh axis, payload bucket, link class ici|dcn)
bandwidth/latency entries, classified via ``device.slice_index`` (or an
explicit ``dcn_axes`` hint on emulated CPU "slices"), persisted —
atomic tmp+rename writes, a corrupt/foreign/unreadable cache degrades
to a cold start, never a crash — under ``SKYT_COMMS_CACHE`` (default
``~/.cache/skypilot_tpu/comms_profile.json``).

Consumers (docs/observability.md "Comms plane"):

  * the HLO communication census (parallel/comms_census.py) multiplies
    its bytes-moved counts by this profile's measured bus bandwidth to
    predict a per-step per-axis comms-time breakdown;
  * the measurement-driven mesh placement advisor
    (``mesh.build_hybrid_mesh(..., placement='measured')``) scores
    candidate DCN-axis slice permutations against the per-pair costs
    here (Cloud Collectives' rank reorder, arXiv 2105.14088, restricted
    to the DCN factor so the ICI layout is untouched);
  * ``skyt_comms_probe_busbw_gbps{axis,op,link}`` gauges, the fleet
    plane (``GET /fleet/comms``), and the bench comms phase.

Failure discipline: every measurement rides the ``comms.probe`` fault
point (``SKYT_FAULTS=comms.probe=error[,where=op:<op>]``) and any
failure — injected or real — skips that entry and continues; the probe
can degrade to an empty profile but never takes the caller down. The
sweep respects a soft wall-clock budget (``SKYT_COMMS_PROBE_TIMEOUT_S``,
checked between entries: a single collective dispatch cannot be
interrupted, so the budget bounds the *sweep*, not one hung dispatch).
"""
import json
import math
import os
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from skypilot_tpu.utils import env
from skypilot_tpu.utils import faults
from skypilot_tpu.utils import log_utils
from skypilot_tpu.utils import metrics as metrics_lib

logger = log_utils.init_logger(__name__)

_VERSION = 1
_KIND = 'comms_profile'

FAULT_POINT = 'comms.probe'

# Default per-device payload sweep (MiB). Small-to-large so a
# latency-bound small message and a bandwidth-bound large one both get
# an entry; override with SKYT_COMMS_PROBE_MB="0.25,4,64". The op set
# is collectives.DEFAULT_OPS (one canonical list).
DEFAULT_PAYLOADS_MB = (1.0, 16.0)


def cache_path() -> str:
    return env.get('SKYT_COMMS_CACHE') or os.path.expanduser(
        '~/.cache/skypilot_tpu/comms_profile.json')


def payload_sweep_mb() -> List[float]:
    """The probe's payload buckets (MiB) from SKYT_COMMS_PROBE_MB;
    malformed values degrade to the default with a warning."""
    raw = env.get('SKYT_COMMS_PROBE_MB')
    if not raw:
        return list(DEFAULT_PAYLOADS_MB)
    try:
        vals = [float(v) for v in raw.split(',') if v.strip()]
        if not vals or any(v <= 0 for v in vals):
            raise ValueError(raw)
        return vals
    except ValueError:
        logger.warning('SKYT_COMMS_PROBE_MB=%r is not a comma-separated '
                       'list of positive MiB sizes; using default %s',
                       raw, list(DEFAULT_PAYLOADS_MB))
        return list(DEFAULT_PAYLOADS_MB)


class CommsProfileCache:
    """Thread-safe persistent key -> dict cache: atomic writes,
    corrupt/foreign/unreadable file == cold start (never a crash),
    unwritable path == in-memory only."""

    def __init__(self, path: str) -> None:
        self.path = path
        self._lock = threading.Lock()
        self._entries: Optional[Dict[str, Dict[str, Any]]] = None

    def _load_locked(self) -> Dict[str, Dict[str, Any]]:  # guarded-by: _lock
        if self._entries is not None:
            return self._entries
        entries: Dict[str, Dict[str, Any]] = {}
        try:
            with open(self.path, encoding='utf-8') as f:
                data = json.load(f)
            if (isinstance(data, dict) and
                    data.get('version') == _VERSION and
                    data.get('kind') == _KIND and
                    isinstance(data.get('entries'), dict)):
                entries = {k: v for k, v in data['entries'].items()
                           if isinstance(v, dict)}
            else:
                # A foreign file (another JSON cache pointed at by a
                # mis-set SKYT_COMMS_CACHE) must not be adopted as a
                # comms profile OR destroyed silently — cold start and
                # say why; the next put() overwrites it.
                logger.warning(
                    'comms profile cache %s has unexpected layout '
                    '(kind %r, version %r); starting cold', self.path,
                    data.get('kind') if isinstance(data, dict) else
                    type(data).__name__,
                    data.get('version') if isinstance(data, dict)
                    else None)
        except FileNotFoundError:
            pass
        except (OSError, ValueError) as e:
            logger.warning('comms profile cache %s unreadable (%s); '
                           'starting cold', self.path, e)
        self._entries = entries
        return entries

    def get(self, key: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            return self._load_locked().get(key)

    def put(self, key: str, value: Dict[str, Any]) -> None:
        with self._lock:
            entries = self._load_locked()
            entries[key] = value
            payload = json.dumps(
                {'version': _VERSION, 'kind': _KIND, 'entries': entries},
                indent=1, sort_keys=True)
            try:
                d = os.path.dirname(self.path) or '.'
                os.makedirs(d, exist_ok=True)
                fd, tmp = tempfile.mkstemp(dir=d, prefix='.comms.')
                try:
                    with os.fdopen(fd, 'w', encoding='utf-8') as f:
                        f.write(payload)
                    os.replace(tmp, self.path)   # atomic on POSIX
                except BaseException:
                    try:
                        os.unlink(tmp)
                    except OSError:
                        pass
                    raise
            except OSError as e:
                # Read-only FS / ENOSPC: the in-memory profile still
                # serves this process; only persistence is lost.
                logger.warning('comms profile cache %s not persisted '
                               '(%s)', self.path, e)

    def entries(self) -> Dict[str, Dict[str, Any]]:
        """Snapshot of every cached entry (fleet /fleet/comms reads
        the probed profiles through this)."""
        with self._lock:
            return dict(self._load_locked())

    def forget_loaded(self) -> None:
        """Drop the in-memory copy so the next access re-reads disk
        (tests simulating a fresh process)."""
        with self._lock:
            self._entries = None


_caches: Dict[str, CommsProfileCache] = {}
_caches_lock = threading.Lock()


def get_cache(path: Optional[str] = None) -> CommsProfileCache:
    path = path or cache_path()
    with _caches_lock:
        c = _caches.get(path)
        if c is None:
            c = _caches[path] = CommsProfileCache(path)
        return c


def reset_for_tests() -> None:
    with _caches_lock:
        _caches.clear()


# ------------------------------------------------------- link classes
def axis_link_classes(mesh, dcn_axes: Sequence[str] = ()
                      ) -> Dict[str, str]:
    """'ici' | 'dcn' per active (>1) mesh axis. An axis is DCN when
    walking it (other coords fixed at 0) changes ``device.slice_index``
    — real multi-slice TPUs set it; emulated CPU slices don't, so
    ``dcn_axes`` names them explicitly (the caller built the hybrid
    mesh and knows its dcn spec)."""
    arr = mesh.devices
    out: Dict[str, str] = {}
    for i, axis in enumerate(mesh.axis_names):
        size = arr.shape[i]
        if size <= 1:
            continue
        idx: List[Any] = [0] * arr.ndim
        slices = set()
        for k in range(size):
            idx[i] = k
            slices.add(getattr(arr[tuple(idx)], 'slice_index', 0))
        out[axis] = 'dcn' if (len(slices) > 1 or axis in dcn_axes) \
            else 'ici'
    return out


def format_topology_key(kind: str, n_devices: int,
                        axis_sizes: Sequence[Tuple[str, int]],
                        dcn_axes: Sequence[str]) -> str:
    """THE topology-key format, shared by topology_key (probed meshes)
    and mesh.build_hybrid_mesh's advisor lookup (pre-mesh specs) — one
    formatter so the two can never drift into silent cache misses."""
    axes = '.'.join(f'{a}{s}{"d" if a in dcn_axes else "i"}'
                    for a, s in axis_sizes if s > 1)
    return f'{kind}|d{n_devices}|{axes or "single"}'


def topology_key(mesh, dcn_axes: Sequence[str] = ()) -> str:
    """Cache key for one probed topology: device kind, per-axis sizes,
    and which axes are DCN."""
    kinds = axis_link_classes(mesh, dcn_axes)
    dev0 = mesh.devices.reshape(-1)[0]
    kind = getattr(dev0, 'device_kind', 'unknown')
    return format_topology_key(
        kind, int(mesh.devices.size),
        [(a, mesh.shape[a]) for a in mesh.axis_names],
        [a for a, l in kinds.items() if l == 'dcn'])


# --------------------------------------------------------------- probe
def probe_mesh(mesh, dcn_axes: Sequence[str] = (),
               payloads_mb: Optional[Sequence[float]] = None,
               ops: Optional[Sequence[str]] = None,
               iters: Optional[int] = None,
               budget_s: Optional[float] = None,
               num_slices: Optional[int] = None,
               clock: Callable[[], float] = time.perf_counter,
               bench: Optional[Callable[..., Dict[str, float]]] = None
               ) -> Dict[str, Any]:
    """Run the structured sweep; returns the profile dict.

    Profile layout (the cache entry)::

        {'device_kind': ..., 'n_devices': ..., 'truncated': false,
         'entries': {'<op>|<axis>|<link>|r<n>|mb<mb>':
                     {'op','axis','link','ranks','payload_mb',
                      'time_ms','algbw_gbps','busbw_gbps'}},
         'dcn_pairs': {'<i>,<j>': {'busbw_gbps': ...}}}

    ``dcn_pairs`` — per SLICE-pair bandwidth, the placement advisor's
    input — is measured only on meshes with a DCN axis and more than
    two slices. ``num_slices`` names the DCN factor of the merged
    dcn-crossing axis when it cannot be read off ``slice_index``
    (emulated CPU slices where the merged axis also has an ICI
    factor); tests and the bench inject heterogeneous pair costs
    directly.
    """
    from skypilot_tpu.parallel import collectives
    bench = bench or collectives.bench_collective
    payloads = list(payloads_mb) if payloads_mb is not None \
        else payload_sweep_mb()
    ops = tuple(ops) if ops is not None else collectives.DEFAULT_OPS
    if iters is None:
        iters = env.get_int('SKYT_COMMS_PROBE_ITERS', 5, minimum=1)
    if budget_s is None:
        budget_s = env.get_float('SKYT_COMMS_PROBE_TIMEOUT_S', 120.0)
    links = axis_link_classes(mesh, dcn_axes)
    dev0 = mesh.devices.reshape(-1)[0]
    profile: Dict[str, Any] = {
        'device_kind': getattr(dev0, 'device_kind', 'unknown'),
        'n_devices': int(mesh.devices.size),
        'truncated': False,
        'entries': {},
        'dcn_pairs': {},
    }
    deadline = clock() + budget_s if budget_s and budget_s > 0 else None
    for axis, link in sorted(links.items()):
        for op in ops:
            for mb in payloads:
                if deadline is not None and clock() >= deadline:
                    profile['truncated'] = True
                    logger.warning(
                        'comms probe budget (%.0fs) exhausted; profile '
                        'truncated at %s/%s', budget_s, axis, op)
                    return profile
                try:
                    faults.inject('comms.probe', axis=axis, op=op)
                    r = bench(mesh, axis, op, mb, iters=iters,
                              clock=clock)
                except Exception as e:  # pylint: disable=broad-except
                    # Injected or real: one sick (op, payload) costs
                    # its own entry, never the sweep.
                    logger.warning('comms probe %s/%s/%.2gMiB failed '
                                   '(%s: %s); skipped', axis, op, mb,
                                   type(e).__name__, e)
                    continue
                key = f'{op}|{axis}|{link}|r{r["ranks"]}|mb{mb:g}'
                profile['entries'][key] = {
                    'op': op, 'axis': axis, 'link': link,
                    'ranks': int(r['ranks']),
                    'payload_mb': float(mb),
                    'time_ms': float(r['time_ms']),
                    'algbw_gbps': float(r['algbw_gbps']),
                    'busbw_gbps': float(r['busbw_gbps']),
                }
    dcn_axis = next((a for a, l in links.items() if l == 'dcn'), None)
    if dcn_axis is not None:
        merged = mesh.shape[dcn_axis]
        slice_ids = {getattr(d, 'slice_index', 0)
                     for d in mesh.devices.reshape(-1)}
        n_slices = (len(slice_ids) if len(slice_ids) > 1
                    else (num_slices or merged))
        profile['num_slices'] = n_slices
        if n_slices > 2 and merged % n_slices == 0:
            profile['dcn_pairs'] = _probe_dcn_pairs(
                mesh, dcn_axis, n_slices, clock=clock,
                deadline=deadline)
            if deadline is not None and clock() >= deadline:
                profile['truncated'] = True
    return profile


def _probe_dcn_pairs(mesh, axis: str, n_slices: int,
                     clock: Callable[[], float] = time.perf_counter,
                     payload_mb: float = 1.0,
                     iters: int = 3,
                     deadline: Optional[float] = None
                     ) -> Dict[str, Dict[str, float]]:
    """Per SLICE-pair DCN bandwidth: a ppermute where only one
    representative position of slice i and one of slice j exchange.
    The merged dcn-crossing axis is DCN-MAJOR (build_hybrid_mesh), so
    slice s owns positions [s*f, (s+1)*f) with f = merged/n_slices —
    probing positions (i*f, j*f) always crosses the slice boundary,
    never an intra-slice ICI hop. Keys are slice indices in the
    mesh's CURRENT (row-major) placement — exactly what the advisor
    permutes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = mesh.shape[axis]
    f = n // n_slices
    out: Dict[str, Dict[str, float]] = {}
    elems = max(n, int(payload_mb * (2 ** 20) / 4) // n * n)
    sharding = NamedSharding(mesh, P(axis))
    x = jax.jit(lambda: jnp.ones((elems,), jnp.float32),
                out_shardings=sharding)()
    for i in range(n_slices):
        for j in range(i + 1, n_slices):
            if deadline is not None and clock() >= deadline:
                return out
            try:
                faults.inject('comms.probe', axis=axis, op='pair',
                              pair=f'{i},{j}')

                def _pair(xs, a=i * f, b=j * f):
                    y = jax.lax.ppermute(xs, axis, [(a, b), (b, a)])
                    return jax.lax.psum(jnp.sum(y[..., :1]), axis)

                fn = jax.jit(jax.shard_map(
                    _pair, mesh=mesh, in_specs=P(axis), out_specs=P(),
                    check_vma=False))
                fn(x).block_until_ready()
                t0 = clock()
                for _ in range(iters):
                    r = fn(x)
                r.block_until_ready()
                dt = max((clock() - t0) / iters, 1e-12)
            except Exception as e:  # pylint: disable=broad-except
                logger.warning('comms pair probe (%d,%d) failed: %s',
                               i, j, e)
                continue
            out[f'{i},{j}'] = {
                'busbw_gbps': (elems // n) * 4 / dt / 1e9,
                'time_ms': dt * 1e3,
            }
    return out


def load_cached(mesh=None, dcn_axes: Sequence[str] = (),
                path: Optional[str] = None,
                key: Optional[str] = None) -> Optional[Dict[str, Any]]:
    """The cached profile for this topology, or None (no probe run)."""
    key = key or topology_key(mesh, dcn_axes)
    entry = get_cache(path).get(f'profile|{key}')
    if entry is not None and not isinstance(entry.get('entries'), dict):
        return None   # stale/hand-edited entry: behave as a miss
    return entry


def load_or_probe(mesh, dcn_axes: Sequence[str] = (),
                  path: Optional[str] = None,
                  force: bool = False,
                  **probe_kwargs) -> Tuple[Dict[str, Any], str]:
    """Cache-or-probe: returns (profile, 'cache' | 'probed'). Probed
    profiles persist under the topology key unless truncated (a
    partial profile must not mask the links it never measured)."""
    key = topology_key(mesh, dcn_axes)
    if not force:
        hit = load_cached(key=key, path=path)
        if hit is not None:
            return hit, 'cache'
    profile = probe_mesh(mesh, dcn_axes=dcn_axes, **probe_kwargs)
    if profile['entries'] and not profile.get('truncated'):
        get_cache(path).put(f'profile|{key}', profile)
    return profile, 'probed'


# ------------------------------------------------------------ lookups
def busbw_bytes_per_s(profile: Optional[Dict[str, Any]], op: str,
                      link: str, ranks: int,
                      payload_bytes: float) -> Optional[float]:
    """Measured bus bandwidth (bytes/s) for the nearest profile entry:
    same op, same link class preferred, nearest payload bucket (log
    distance), then nearest rank count. None when the profile has no
    usable entry — the census then reports bytes without seconds."""
    if not profile or not isinstance(profile.get('entries'), dict):
        return None
    cands = [e for e in profile['entries'].values()
             if isinstance(e, dict) and e.get('op') == op and
             e.get('busbw_gbps')]
    if not cands:
        return None
    same_link = [e for e in cands if e.get('link') == link]
    cands = same_link or cands

    def _dist(e: Dict[str, Any]) -> Tuple[float, float]:
        bucket = max(float(e.get('payload_mb', 1.0)) * 2 ** 20, 1.0)
        return (abs(math.log(max(payload_bytes, 1.0) / bucket)),
                abs(int(e.get('ranks', 1)) - ranks))
    best = min(cands, key=_dist)
    return float(best['busbw_gbps']) * 1e9


def summary(profile: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """Compact per-(link, op) view for logs and /fleet/comms: best
    busbw over the payload sweep."""
    out: Dict[str, Any] = {}
    if not profile or not isinstance(profile.get('entries'), dict):
        return out
    for e in profile['entries'].values():
        if not isinstance(e, dict) or not e.get('busbw_gbps'):
            continue
        key = f"{e.get('link', '?')}.{e.get('op', '?')}"
        cur = out.get(key)
        if cur is None or e['busbw_gbps'] > cur['busbw_gbps']:
            out[key] = {'busbw_gbps': round(float(e['busbw_gbps']), 3),
                        'axis': e.get('axis'),
                        'ranks': e.get('ranks')}
    return out


def publish_profile_metrics(profile: Optional[Dict[str, Any]],
                            registry: Optional[
                                'metrics_lib.MetricsRegistry'] = None
                            ) -> None:
    """Expose the profile as skyt_comms_probe_busbw_gbps{axis,op,link}
    gauges (docs/observability.md "Comms plane")."""
    if not profile or not isinstance(profile.get('entries'), dict):
        return
    reg = registry or metrics_lib.REGISTRY
    gauge = reg.gauge(
        'skyt_comms_probe_busbw_gbps',
        'Measured collective bus bandwidth from the comms-plane link '
        'probe (best over the payload sweep)', ('axis', 'op', 'link'))
    best: Dict[Tuple[str, str, str], float] = {}
    for e in profile['entries'].values():
        if not isinstance(e, dict) or not e.get('busbw_gbps'):
            continue
        key = (str(e.get('axis')), str(e.get('op')),
               str(e.get('link')))
        best[key] = max(best.get(key, 0.0), float(e['busbw_gbps']))
    for (axis, op, link), v in best.items():
        gauge.labels(axis, op, link).set(v)


# ------------------------------------------- placement (advisor side)
def pair_cost_fn(profile: Optional[Dict[str, Any]]
                 ) -> Callable[[int, int], float]:
    """(slice_i, slice_j) -> relative cost (seconds per unit payload;
    only ratios matter to the advisor). Per-pair measurements in
    ``profile['dcn_pairs']`` win; pairs without one fall back to the
    profile's DCN ppermute busbw, then to a uniform 1.0."""
    pairs: Dict[str, Any] = {}
    default_bw = None
    if profile and isinstance(profile.get('dcn_pairs'), dict):
        pairs = profile['dcn_pairs']
    if profile:
        default_bw = busbw_bytes_per_s(profile, 'ppermute', 'dcn', 2,
                                       2 ** 20)

    def cost(i: int, j: int) -> float:
        for key in (f'{i},{j}', f'{j},{i}'):
            e = pairs.get(key)
            if isinstance(e, dict) and e.get('busbw_gbps'):
                return 1.0 / float(e['busbw_gbps'])
        if default_bw:
            return 1e9 / default_bw
        return 1.0
    return cost


def ring_score(perm: Sequence[int],
               cost: Callable[[int, int], float]) -> float:
    """Cost of one ring pass over slices in ``perm`` order — the shape
    of ring all-reduce/all-gather/reduce-scatter traffic over the DCN
    axis (neighbor exchanges, wrap included)."""
    n = len(perm)
    return sum(cost(perm[k], perm[(k + 1) % n]) for k in range(n))


def choose_dcn_permutation(n_slices: int,
                           profile: Optional[Dict[str, Any]]
                           ) -> Dict[str, Any]:
    """The cheapest slice ordering for the DCN axis under the measured
    (or injected) pair costs. Exhaustive over (n-1)! orderings with the
    first slice fixed (ring scores are rotation-invariant) up to 8
    slices, greedy nearest-neighbor beyond. Returns
    {'perm', 'score', 'rowmajor_score'}."""
    import itertools
    identity = list(range(n_slices))
    cost = pair_cost_fn(profile)
    row_score = ring_score(identity, cost) if n_slices > 1 else 0.0
    if n_slices <= 2:
        return {'perm': identity, 'score': row_score,
                'rowmajor_score': row_score}
    if n_slices <= 8:
        best_perm, best_score = identity, row_score
        for tail in itertools.permutations(range(1, n_slices)):
            perm = [0, *tail]
            s = ring_score(perm, cost)
            if s < best_score - 1e-12:
                best_perm, best_score = perm, s
        return {'perm': list(best_perm), 'score': best_score,
                'rowmajor_score': row_score}
    # Greedy nearest-neighbor for big slice counts.
    remaining = set(range(1, n_slices))
    perm = [0]
    while remaining:
        nxt = min(remaining, key=lambda j: cost(perm[-1], j))
        perm.append(nxt)
        remaining.discard(nxt)
    return {'perm': perm, 'score': ring_score(perm, cost),
            'rowmajor_score': row_score}


def _profile_fingerprint(profile: Optional[Dict[str, Any]]) -> str:
    """Stable digest of the measurements the advisor scores with: a
    cached placement winner is valid only for the profile it was
    computed from (a re-probe — or an explicitly passed profile —
    must invalidate it, never lose to it)."""
    import hashlib
    if not profile:
        return 'none'
    payload = json.dumps(
        {'dcn_pairs': profile.get('dcn_pairs') or {},
         'busbw': {k: v.get('busbw_gbps')
                   for k, v in (profile.get('entries') or {}).items()
                   if isinstance(v, dict)}},
        sort_keys=True)
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def placement_for(key: str, n_slices: int,
                  profile: Optional[Dict[str, Any]] = None,
                  path: Optional[str] = None) -> List[int]:
    """Cached advisor decision for one (topology, spec) key — computed
    once per PROFILE and persisted. The cached
    entry carries the fingerprint of the profile it was scored
    against: a new probe (or an explicitly passed profile) with
    different measurements recomputes and overwrites; an unusable
    cached entry (wrong length, not a permutation) recomputes too."""
    cache = get_cache(path)
    cache_key = f'placement|{key}'
    if profile is None:
        profile = load_cached(key=key.split('#')[0], path=path)
    fp = _profile_fingerprint(profile)
    hit = cache.get(cache_key)
    if hit is not None and hit.get('profile_fp') == fp:
        perm = hit.get('perm')
        if isinstance(perm, list) and sorted(perm) == \
                list(range(n_slices)):
            return [int(p) for p in perm]
    decision = choose_dcn_permutation(n_slices, profile)
    cache.put(cache_key, {'perm': decision['perm'],
                          'score': decision['score'],
                          'rowmajor_score': decision['rowmajor_score'],
                          'profile_fp': fp})
    if decision['perm'] != list(range(n_slices)):
        logger.info('comms placement %s: measured slice order %s '
                    '(ring score %.3g vs row-major %.3g)', key,
                    decision['perm'], decision['score'],
                    decision['rowmajor_score'])
    return decision['perm']
