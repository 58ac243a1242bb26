"""In-place weight hot-swap for a live inference engine.

The zero-downtime-rollout enabler (docs/robustness.md "Zero-downtime
rollouts", ROADMAP item 5): a fine-tune push replaces a replica's
weights WITHOUT a relaunch — no recompile, no cold KV cache, no
drained connections. The manager owns the swap lifecycle:

  1. **stage** — load the new checkpoint into host memory and
     ``jax.device_put`` each leaf onto the LIVE tree's sharding while
     decoding continues (staging shares HBM with the old tree for its
     duration; the apply itself is a reference swap);
  2. **validate** — the new tree must match the live one in structure,
     per-leaf shape, and dtype (sharding is imposed at stage time from
     the live leaves). Any mismatch aborts with the old weights
     intact and the offending path named;
  3. **apply** — the engine installs the staged tree at a decode-tick
     boundary (engine.request_weight_swap): in-flight requests drain
     to the boundary by default (``SKYT_SWAP_DRAIN=0`` lets them
     continue onto the new weights), the prefix cache is flushed
     (stale-KV correctness), and ``skyt_infer_weight_version`` bumps.

Single-flight: a second swap while one is in flight raises
SwapInFlight (the server's 409). The previous checkpoint reference is
retained so a canary that fails its bake can ``swap_back()`` — the
rollout orchestrator's rollback lever. Every attempt runs through the
``weights.swap`` fault point (kinds error/hang/latency), so the
abort-keeps-old-weights contract is chaos-testable.

The base params are the only thing swapped: LoRA adapter stacks and
draft-model params are untouched (adapters are versioned by their own
export flow).
"""
import threading
import time
from typing import Any, Dict, Optional

import jax

from skypilot_tpu.utils import env
from skypilot_tpu.utils import faults
from skypilot_tpu.utils import log_utils
from skypilot_tpu.utils import metrics as metrics_lib

logger = log_utils.init_logger(__name__)


class WeightSwapError(RuntimeError):
    """A swap attempt failed; the old weights are still live."""


class SwapInFlight(WeightSwapError):
    """A swap is already in progress (single-flight; HTTP 409)."""


class AdapterInUse(WeightSwapError):
    """Unload refused: live requests still reference the adapter id
    (the server's 409 — retry after those requests drain)."""


def _path_str(path) -> str:
    out = []
    for p in path:
        out.append(str(getattr(p, 'key', getattr(p, 'name',
                                                 getattr(p, 'idx', p)))))
    return '/'.join(out) or '<root>'


def validate_tree(live, new) -> None:
    """Reject a replacement params tree that does not match the live
    one in structure, per-leaf shape, or dtype. Raises WeightSwapError
    naming the first offending path — the swap must abort BEFORE any
    device state changes."""
    live_leaves = jax.tree.leaves_with_path(live)
    new_leaves = jax.tree.leaves_with_path(new)
    live_map = {_path_str(p): leaf for p, leaf in live_leaves}
    new_map = {_path_str(p): leaf for p, leaf in new_leaves}
    missing = sorted(set(live_map) - set(new_map))
    extra = sorted(set(new_map) - set(live_map))
    if missing or extra:
        raise WeightSwapError(
            f'param tree structure mismatch: '
            f'{len(missing)} missing (e.g. {missing[:3]}), '
            f'{len(extra)} unexpected (e.g. {extra[:3]})')
    for path, leaf in live_map.items():
        cand = new_map[path]
        l_shape = tuple(getattr(leaf, 'shape', ()))
        c_shape = tuple(getattr(cand, 'shape', ()))
        if l_shape != c_shape:
            raise WeightSwapError(
                f'param {path}: shape {c_shape} does not match the '
                f'live {l_shape}')
        l_dtype = getattr(leaf, 'dtype', None)
        c_dtype = getattr(cand, 'dtype', None)
        if l_dtype is not None and c_dtype is not None and \
                str(l_dtype) != str(c_dtype):
            raise WeightSwapError(
                f'param {path}: dtype {c_dtype} does not match the '
                f'live {l_dtype}')


class WeightSwapManager:
    """Owns staging, validation, single-flight, history, and metrics
    for one engine's in-place weight swaps. One instance per replica
    server (infer/server.py exposes it at ``POST /admin/weights``)."""

    def __init__(self, engine, loader=None,
                 checkpoint: Optional[str] = None,
                 registry: Optional['metrics_lib.MetricsRegistry'] = None
                 ) -> None:
        self.engine = engine
        self._loader = loader if loader is not None \
            else getattr(engine, 'param_loader', None)
        self.checkpoint: Optional[str] = checkpoint if checkpoint \
            else getattr(engine, 'checkpoint_path', None)
        # (version, {'checkpoint': path} | {'params': tree}) of the
        # weights the LAST successful swap replaced — the swap_back
        # target. A host/path reference, never a retained device tree:
        # pinning the old tree in HBM for the whole bake would double
        # weight memory (swap-back restages instead).
        self._prev: Optional[tuple] = None
        self._old_params = None
        self._flight = threading.Lock()
        self.last: Optional[Dict[str, Any]] = None
        reg = registry or getattr(engine, 'metrics_registry', None) \
            or metrics_lib.REGISTRY
        self._m_swaps = reg.counter(
            'skyt_infer_weight_swaps_total',
            'In-place weight swap attempts by result (ok / aborted — '
            'aborted leaves the old weights live)', ('result',))
        self._m_swap_s = reg.histogram(
            'skyt_infer_weight_swap_seconds',
            'End-to-end weight swap duration (stage + validate + '
            'tick-boundary apply)')
        # Elastic resharding (docs/robustness.md "Elastic capacity"):
        # previous virtual-node layout retained for reshard_back — the
        # controller's rollback lever, mirroring _prev for weights.
        self._prev_layout: Optional[int] = None
        self.last_reshard: Optional[Dict[str, Any]] = None
        self._m_reshards = reg.counter(
            'skyt_infer_reshards_total',
            'In-place elastic reshard attempts by result (ok / aborted '
            '— aborted leaves the old layout live)', ('result',))
        self._m_reshard_s = reg.histogram(
            'skyt_infer_reshard_seconds',
            'End-to-end reshard duration (re-stage + tick-boundary '
            'apply)')

    # ------------------------------------------------------------ views
    def info(self) -> Dict[str, Any]:
        return {
            'weight_version': self.engine.weight_version,
            'checkpoint': self.checkpoint,
            'swap_back_available': self._prev is not None,
            'last_swap': dict(self.last) if self.last else None,
            'virtual_nodes': getattr(self.engine, 'virtual_nodes',
                                     None),
            'reshard_back_available': self._prev_layout is not None,
            'last_reshard': (dict(self.last_reshard)
                             if self.last_reshard else None),
        }

    # ------------------------------------------------------------ swaps
    def swap(self, checkpoint: Optional[str] = None,
             params=None, version: Optional[int] = None,
             drain: Optional[bool] = None) -> Dict[str, Any]:
        """Stage + validate + apply one weight swap. Exactly one of
        `checkpoint` (loaded via the engine's param loader) or
        `params` (an already-built tree; tests and in-process pushes)
        must be given. Raises SwapInFlight on concurrency,
        WeightSwapError on any failure — the old weights are intact in
        both cases."""
        if not self._flight.acquire(blocking=False):
            raise SwapInFlight(
                'a weight swap is already in flight on this replica')
        try:
            return self._swap_locked(checkpoint, params, version,
                                     drain)
        finally:
            self._flight.release()

    def swap_back(self, drain: Optional[bool] = None) -> Dict[str, Any]:
        """Restage + apply the weights the last successful swap
        replaced (the rollout orchestrator's rollback lever)."""
        if not self._flight.acquire(blocking=False):
            raise SwapInFlight(
                'a weight swap is already in flight on this replica')
        try:
            if self._prev is None:
                raise WeightSwapError(
                    'no previous weights retained: nothing to swap '
                    'back to')
            version, ref = self._prev
            return self._swap_locked(ref.get('checkpoint'),
                                     ref.get('params'), version, drain,
                                     is_back=True)
        finally:
            self._flight.release()

    def _swap_locked(self, checkpoint, params, version, drain,
                     is_back: bool = False) -> Dict[str, Any]:
        t0 = time.perf_counter()
        old_version = self.engine.weight_version
        old_checkpoint = self.checkpoint
        target = int(version) if version is not None \
            else old_version + 1
        try:
            # Chaos hook (docs/robustness.md fault catalog): 'error'
            # aborts the swap with the old weights intact — the canary
            # auto-rollback drill's lever; latency/hang stretch the
            # single-flight window (concurrent swaps then 409).
            faults.inject('weights.swap', version=target,
                          checkpoint=checkpoint or '')
            if (checkpoint is None) == (params is None):
                raise WeightSwapError(
                    'exactly one of checkpoint= or params= is '
                    'required')
            if params is None:
                if self._loader is None:
                    raise WeightSwapError(
                        'this replica has no checkpoint loader (engine '
                        'built without build_engine); push a params '
                        'tree instead')
                try:
                    params = self._loader(checkpoint)
                except WeightSwapError:
                    raise
                except Exception as e:
                    raise WeightSwapError(
                        f'loading checkpoint {checkpoint!r} failed: '
                        f'{e}') from e
            validate_tree(self.engine.params, params)
            staged = self._stage(params)
            result = self.engine.request_weight_swap(
                staged, version=target, drain=drain)
        except faults.FaultError as e:
            self._abort(t0, target, checkpoint, f'injected fault: {e}')
            raise WeightSwapError(
                f'weight swap aborted (old weights intact): {e}'
            ) from e
        except WeightSwapError as e:
            self._abort(t0, target, checkpoint, str(e))
            raise
        except Exception as e:  # pylint: disable=broad-except
            self._abort(t0, target, checkpoint, str(e))
            raise WeightSwapError(
                f'weight swap failed (old weights intact): {e}') from e
        dur = time.perf_counter() - t0
        # Retain what we REPLACED so a failed bake can roll back (a
        # swap_back re-points history at what IT replaced, so repeated
        # flips keep working). A checkpoint PATH when the old weights
        # came from one — swap-back restages from disk instead of
        # pinning a second full tree in HBM for the whole bake; the
        # old tree reference otherwise (params-tree swaps: tests and
        # in-process pushes, where trees are debug-sized).
        if old_checkpoint is not None:
            self._prev = (old_version, {'checkpoint': old_checkpoint})
            # Release the staging-time reference to the REPLACED
            # device tree: with a path to restage from, keeping it
            # would pin 2x weight HBM for the whole bake window.
            self._old_params = None
        else:
            self._prev = (old_version, {'params': self._old_params})
        # The live weights now correspond to what was pushed: the new
        # path, or no path at all for a params-tree push.
        self.checkpoint = checkpoint
        self._m_swaps.labels('ok').inc()
        self._m_swap_s.observe(dur)
        self.last = {
            'ok': True, 'weight_version': result['weight_version'],
            'from_version': old_version,
            'checkpoint': checkpoint, 'swap_back': is_back,
            'duration_s': round(dur, 4),
            'apply_s': result['apply_s'],
            'flushed_prefix_pages': result['flushed_prefix_pages'],
            'at': time.time(),
        }
        logger.info('weight swap ok: v%d -> v%d in %.3fs (%s)',
                    old_version, result['weight_version'], dur,
                    checkpoint or 'params tree')
        return dict(self.last)

    def _abort(self, t0: float, target: int, checkpoint,
               error: str) -> None:
        self._m_swaps.labels('aborted').inc()
        self.last = {
            'ok': False, 'weight_version': self.engine.weight_version,
            'target_version': target, 'checkpoint': checkpoint,
            'error': error,
            'duration_s': round(time.perf_counter() - t0, 4),
            'at': time.time(),
        }
        logger.warning('weight swap to v%d aborted (old weights '
                       'intact): %s', target, error)

    def _stage(self, params):
        """Device-stage the validated tree onto the live leaves'
        placements (sharded engines keep their NamedShardings), fully
        materialized BEFORE the tick-boundary apply so the engine-side
        swap is a reference assignment, not a transfer."""
        self._old_params = self.engine.params

        def put(new_leaf, live_leaf):
            sharding = getattr(live_leaf, 'sharding', None)
            if sharding is not None:
                return jax.device_put(new_leaf, sharding)
            return jax.device_put(new_leaf)

        staged = jax.tree_util.tree_map(put, params,
                                        self.engine.params)
        try:
            jax.block_until_ready(staged)
        except AttributeError:   # very old jax: per-leaf fallback
            for leaf in jax.tree_util.tree_leaves(staged):
                getattr(leaf, 'block_until_ready', lambda: None)()
        return staged

    # --------------------------------------------------------- reshard
    def reshard(self, virtual_nodes: int,
                drain: Optional[bool] = None) -> Dict[str, Any]:
        """Change the per-replica virtual-node layout at a decode-tick
        boundary, weights and weight_version unchanged. Rides the same
        single-flight + stage + tick-boundary-apply contract as weight
        swaps (a reshard and a swap cannot overlap). Raises
        SwapInFlight on concurrency, WeightSwapError on any failure —
        the old layout stays live in both cases."""
        if not self._flight.acquire(blocking=False):
            raise SwapInFlight(
                'a weight swap or reshard is already in flight on '
                'this replica')
        try:
            return self._reshard_locked(virtual_nodes, drain)
        finally:
            self._flight.release()

    def reshard_back(self, drain: Optional[bool] = None
                     ) -> Dict[str, Any]:
        """Re-apply the layout the last successful reshard replaced
        (the controller's mid-reshard rollback lever)."""
        if not self._flight.acquire(blocking=False):
            raise SwapInFlight(
                'a weight swap or reshard is already in flight on '
                'this replica')
        try:
            if self._prev_layout is None:
                raise WeightSwapError(
                    'no previous layout retained: nothing to reshard '
                    'back to')
            return self._reshard_locked(self._prev_layout, drain,
                                        is_back=True)
        finally:
            self._flight.release()

    def _reshard_locked(self, virtual_nodes, drain,
                        is_back: bool = False) -> Dict[str, Any]:
        t0 = time.perf_counter()
        old_layout = int(getattr(self.engine, 'virtual_nodes', 1) or 1)
        try:
            try:
                target = int(virtual_nodes)
            except (TypeError, ValueError):
                raise WeightSwapError(
                    f'virtual_nodes must be an integer, got '
                    f'{virtual_nodes!r}')
            if target < 1:
                raise WeightSwapError(
                    f'virtual_nodes must be >= 1, got {target}')
            mesh_size = int(getattr(self.engine.mesh, 'size', 1) or 1) \
                if self.engine.mesh is not None else 1
            # Each physical device must hold an integer number of
            # virtual nodes (or vice versa) or the layout cannot tile.
            if target % mesh_size and mesh_size % target:
                raise WeightSwapError(
                    f'virtual_nodes={target} does not tile the '
                    f'{mesh_size}-device mesh (one must divide the '
                    f'other)')
            # Chaos hook (docs/robustness.md fault catalog): 'error'
            # aborts with the old layout intact — the mid-reshard
            # SIGKILL/rollback drill's lever; latency/hang stretch the
            # single-flight window (concurrent reshards then 409).
            faults.inject('reshard', virtual_nodes=target,
                          from_nodes=old_layout)
            if target == old_layout:
                # Idempotent no-op: the controller retries through
                # restarts and must be able to re-assert a layout.
                self._m_reshards.labels('ok').inc()
                self.last_reshard = {
                    'ok': True, 'virtual_nodes': old_layout,
                    'from_nodes': old_layout, 'reshard_back': is_back,
                    'noop': True, 'duration_s': 0.0, 'at': time.time(),
                }
                return dict(self.last_reshard)
            # Re-stage the LIVE weights onto the target layout's
            # placements. On a single-device/CPU engine this is an
            # identity restage (same shardings); on a real mesh the
            # virtual-node count maps to different NamedShardings —
            # either way the engine-side apply stays a reference
            # assignment at a tick boundary. _stage would clobber
            # _old_params (the swap_back retention), so save/restore
            # it: a reshard must not eat weight-rollback history.
            keep_old = self._old_params
            try:
                staged = self._stage(self.engine.params)
            finally:
                self._old_params = keep_old
            result = self.engine.request_reshard(
                staged, virtual_nodes=target, drain=drain)
        except faults.FaultError as e:
            self._abort_reshard(t0, virtual_nodes, f'injected fault: '
                                f'{e}')
            raise WeightSwapError(
                f'reshard aborted (old layout intact): {e}') from e
        except WeightSwapError as e:
            self._abort_reshard(t0, virtual_nodes, str(e))
            raise
        except Exception as e:  # pylint: disable=broad-except
            self._abort_reshard(t0, virtual_nodes, str(e))
            raise WeightSwapError(
                f'reshard failed (old layout intact): {e}') from e
        dur = time.perf_counter() - t0
        # Retain what we REPLACED; a reshard_back re-points history at
        # what IT replaced so repeated flips keep working.
        self._prev_layout = old_layout
        self._m_reshards.labels('ok').inc()
        self._m_reshard_s.observe(dur)
        self.last_reshard = {
            'ok': True, 'virtual_nodes': result['virtual_nodes'],
            'from_nodes': old_layout, 'reshard_back': is_back,
            'weight_version': result['weight_version'],
            'duration_s': round(dur, 4), 'apply_s': result['apply_s'],
            'flushed_prefix_pages': result['flushed_prefix_pages'],
            'at': time.time(),
        }
        logger.info('reshard ok: %d -> %d virtual nodes in %.3fs',
                    old_layout, result['virtual_nodes'], dur)
        return dict(self.last_reshard)

    def _abort_reshard(self, t0: float, target, error: str) -> None:
        self._m_reshards.labels('aborted').inc()
        self.last_reshard = {
            'ok': False,
            'virtual_nodes': getattr(self.engine, 'virtual_nodes',
                                     None),
            'target_nodes': target, 'error': error,
            'duration_s': round(time.perf_counter() - t0, 4),
            'at': time.time(),
        }
        logger.warning('reshard to %r virtual nodes aborted (old '
                       'layout intact): %s', target, error)


class AdapterRegistry:
    """Dynamic multi-LoRA registry: hot-load/unload adapters into a
    live engine's stacked 'lora' collection at decode-tick boundaries
    (docs/serving.md "Adapter fleet"). One instance per replica server
    (infer/server.py exposes it at ``POST /admin/adapters``).

    The lifecycle mirrors weight swaps — build/stage off the engine
    loop, validate against the live param tree, apply as a reference
    assignment at a tick boundary (engine.request_adapter_update) —
    and SHARES the WeightSwapManager's single-flight lock, so an
    adapter update can never race a weight swap or reshard (HTTP 409).

    Invariants:

    * **Stable ids.** A load takes the lowest free slot (or the same
      slot when replacing by name); an unload ZEROES its slot instead
      of renumbering. In-flight requests therefore stay pinned to
      their adapter across any update.
    * **Old stack intact on any error.** Loading, structure
      validation, staging, and the ``adapter.load`` fault point all
      fire before the engine sees anything.
    * **Unload refuses while referenced.** AdapterInUse (409) while
      any waiting/active request carries the id — a zeroed slot under
      a live request would silently serve base-model outputs. (A
      request that resolves the name and submits in the tick between
      the check and the apply can still slip through — one
      resolve-to-submit race, accepted; the prefix flush keeps its
      pages from polluting the cache.)
    * **Replacement drains.** Reloading a name in place changes the
      values behind a possibly-referenced id, so the apply waits for
      empty slots by default (drain=True); fresh ids apply immediately.
    """

    def __init__(self, engine, swap_mgr: 'WeightSwapManager',
                 dtype: Optional[str] = None,
                 reserved_names=(),
                 on_change=None,
                 registry: Optional['metrics_lib.MetricsRegistry'] = None
                 ) -> None:
        self.engine = engine
        # Shared single-flight with swaps/reshards — one lock, three
        # mutation planes, zero interleavings.
        self._flight = swap_mgr._flight  # pylint: disable=protected-access
        self._dtype = dtype or str(getattr(engine.cfg, 'dtype',
                                           'bfloat16'))
        self._reserved = set(reserved_names)
        self._on_change = on_change
        # name -> {'id', 'alpha', 'path', 'version', 'rank',
        # 'loaded_at'}; per-name versions surface in /stats so the
        # controller can converge "name@version" fleet-wide.
        self._adapters: Dict[str, Dict[str, Any]] = {}
        # Host trees retained per id: tiny (MBs) and they make a full
        # rebuild possible when a new adapter's rank outgrows the
        # stack's padding.
        self._trees: Dict[int, tuple] = {}
        # Every id that ever held an adapter: reusing one must flush
        # the prefix cache (pages are salted by lora_id, and the salt
        # would collide across occupants).
        self._used_ids: set = set()
        self.last: Optional[Dict[str, Any]] = None
        reg = registry or getattr(engine, 'metrics_registry', None) \
            or metrics_lib.REGISTRY
        self._m_loaded = reg.gauge(
            'skyt_infer_adapters_loaded',
            'Adapters currently loaded on this replica (excluding the '
            'id-0 base slot)')
        self._m_loads = reg.counter(
            'skyt_infer_adapter_loads_total',
            'Adapter hot-load attempts by result (ok / aborted — '
            'aborted leaves the old stack live)', ('result',))
        self._m_unloads = reg.counter(
            'skyt_infer_adapter_unloads_total',
            'Adapter unload attempts by result (ok / refused — live '
            'requests still reference the id / aborted)', ('result',))
        self._m_loaded.set(0)

    # ------------------------------------------------------------ seeding
    def seed(self, specs) -> None:
        """Boot-time adapters (--lora flags): register under the same
        ids build_stack_from_specs assigned (spec order, 1-based) and
        retain the host trees for future rebuilds. The engine already
        holds the boot stack; this is bookkeeping only."""
        from skypilot_tpu.infer import lora as lora_lib
        for i, s in enumerate(specs, 1):
            tree = lora_lib.load_adapter_dir(s.path)
            self._trees[i] = (tree, float(s.alpha))
            self._adapters[s.name] = {
                'id': i, 'alpha': float(s.alpha), 'path': s.path,
                'version': 1, 'rank': lora_lib.adapter_rank(tree),
                'loaded_at': time.time()}
            self._used_ids.add(i)
        self._m_loaded.set(len(self._adapters))

    def seed_names(self, name_ids: Dict[str, int]) -> None:
        """Bookkeeping-only seed for engines handed a prebuilt stack
        (tests, embedded use): ids registered without retained trees,
        so a later rank-growing load needs every OTHER adapter
        reloaded first (grafts within the stack's rank always work)."""
        for name, lid in name_ids.items():
            self._adapters[name] = {
                'id': int(lid), 'alpha': None, 'path': None,
                'version': 1, 'rank': None, 'loaded_at': time.time()}
            self._used_ids.add(int(lid))
        self._m_loaded.set(len(self._adapters))

    # ------------------------------------------------------------ views
    def name_ids(self) -> Dict[str, int]:
        """{adapter name: stack id} — the server's routing map."""
        return {n: a['id'] for n, a in self._adapters.items()}

    def snapshot(self) -> Dict[str, Any]:
        """The /stats 'adapters' block: per-adapter id/version/rank —
        what the controller scrapes and the LB routes on."""
        return {
            'count': len(self._adapters),
            'stack_slots': int(getattr(self.engine, 'num_adapters', 0)
                               or 0),
            'adapters': {
                n: {'id': a['id'], 'version': a['version'],
                    'alpha': a['alpha'], 'rank': a['rank'],
                    'path': a['path']}
                for n, a in self._adapters.items()},
        }

    # ------------------------------------------------------------- load
    def load(self, name: str, checkpoint: Optional[str] = None,
             params=None, alpha: float = 16.0,
             drain: Optional[bool] = None) -> Dict[str, Any]:
        """Stage + validate + apply one adapter load (new name) or
        in-place replacement (existing name; same id, version bump).
        Exactly one of `checkpoint` (an Orbax dir an `sft --lora-rank`
        run wrote) or `params` (an adapter tree; tests and in-process
        pushes) must be given. Raises SwapInFlight on concurrency,
        WeightSwapError on any failure — the old stack is intact in
        both cases."""
        if not self._flight.acquire(blocking=False):
            raise SwapInFlight(
                'a weight swap, reshard, or adapter update is already '
                'in flight on this replica')
        try:
            return self._load_locked(name, checkpoint, params, alpha,
                                     drain)
        finally:
            self._flight.release()

    def _load_locked(self, name, checkpoint, params, alpha,
                     drain) -> Dict[str, Any]:
        t0 = time.perf_counter()
        from skypilot_tpu.infer import lora as lora_lib
        try:
            # Chaos hook (docs/robustness.md fault catalog): 'error'
            # aborts the load with the old stack intact; latency/hang
            # stretch the single-flight window (concurrent admin
            # mutations then 409).
            faults.inject('adapter.load', name=str(name),
                          checkpoint=checkpoint or '', op='load')
            if not isinstance(name, str) or not name:
                raise WeightSwapError(
                    'adapter name must be a non-empty string')
            if name in self._reserved:
                raise WeightSwapError(
                    f'adapter name {name!r} collides with the served '
                    f'model id')
            if (checkpoint is None) == (params is None):
                raise WeightSwapError(
                    'exactly one of checkpoint= or params= is required')
            if params is None:
                try:
                    tree = lora_lib.load_adapter_dir(checkpoint)
                except Exception as e:
                    raise WeightSwapError(
                        f'loading adapter {checkpoint!r} failed: '
                        f'{e}') from e
            else:
                tree = params
            try:
                rank = lora_lib.adapter_rank(tree)
                alpha = float(alpha)
            except Exception as e:
                raise WeightSwapError(
                    f'not a LoRA adapter tree: {e}') from e
            replacing = name in self._adapters
            if replacing:
                aid = self._adapters[name]['id']
            else:
                limit = env.get_int('SKYT_ADAPTER_MAX', 32)
                if len(self._adapters) >= limit:
                    raise WeightSwapError(
                        f'adapter limit reached ({limit} loaded; '
                        f'raise SKYT_ADAPTER_MAX)')
                taken = {a['id'] for a in self._adapters.values()}
                aid = 1
                while aid in taken:
                    aid += 1
            # The stack never shrinks (stable shapes = no retrace
            # churn); it grows one slot at a time as ids append.
            num_slots = max(int(getattr(self.engine, 'num_adapters',
                                        0) or 0), aid + 1, 2)
            stack = self._build_with(aid, tree, alpha, num_slots,
                                     lora_lib)
            # A layout/family mismatch must abort loudly BEFORE the
            # engine sees anything (a mismatched projection would
            # otherwise serve base outputs silently).
            lora_lib.validate_stack(stack, self.engine.params['params'])
            stack = self._stage_stack(stack)
            if drain is None:
                drain = replacing
            flush = aid in self._used_ids
            result = self.engine.request_adapter_update(
                stack, num_adapters=num_slots, flush_prefix=flush,
                drain=bool(drain))
        except faults.FaultError as e:
            self._abort_load(t0, name, checkpoint,
                             f'injected fault: {e}')
            raise WeightSwapError(
                f'adapter load aborted (old stack intact): {e}') from e
        except WeightSwapError as e:
            self._abort_load(t0, name, checkpoint, str(e))
            raise
        except Exception as e:  # pylint: disable=broad-except
            self._abort_load(t0, name, checkpoint, str(e))
            raise WeightSwapError(
                f'adapter load failed (old stack intact): {e}') from e
        dur = time.perf_counter() - t0
        self._trees[aid] = (tree, alpha)
        version = self._adapters[name]['version'] + 1 if replacing \
            else 1
        self._adapters[name] = {
            'id': aid, 'alpha': alpha, 'path': checkpoint,
            'version': version, 'rank': rank, 'loaded_at': time.time()}
        self._used_ids.add(aid)
        self._m_loaded.set(len(self._adapters))
        self._m_loads.labels('ok').inc()
        self.last = {
            'ok': True, 'op': 'load', 'name': name, 'id': aid,
            'version': version, 'rank': rank, 'alpha': alpha,
            'replaced': replacing, 'num_adapters': num_slots,
            'flushed_prefix_pages': result['flushed_prefix_pages'],
            'duration_s': round(dur, 4), 'apply_s': result['apply_s'],
            'at': time.time(),
        }
        if self._on_change is not None:
            self._on_change()
        logger.info('adapter load ok: %r -> id %d v%d (rank %d, '
                    'alpha %g) in %.3fs', name, aid, version, rank,
                    alpha, dur)
        return dict(self.last)

    def _build_with(self, aid, tree, alpha, num_slots, lora_lib):
        """The new stack with `tree` at slot `aid`: graft into the
        live stack when the rank fits (no other trees needed), else a
        full rebuild from retained trees."""
        live = getattr(self.engine, '_lora_stack', None)
        if live is None:
            return lora_lib.build_stack_assigned(
                {aid: (tree, alpha)}, num_slots, self._dtype)
        try:
            return lora_lib.graft_adapter(live, aid, tree, alpha)
        except ValueError as graft_err:
            assigned = {i: t for i, t in self._trees.items()
                        if i != aid}
            missing = sorted(
                n for n, a in self._adapters.items()
                if a['id'] != aid and a['id'] not in self._trees)
            if missing:
                raise WeightSwapError(
                    f'cannot graft adapter ({graft_err}) and cannot '
                    f'rebuild the stack: no retained trees for '
                    f'{missing} (loaded before this registry; reload '
                    f'them first)') from graft_err
            assigned[aid] = (tree, alpha)
            return lora_lib.build_stack_assigned(assigned, num_slots,
                                                 self._dtype)

    def _stage_stack(self, stack):
        """Device-stage the new stack (replicated under a mesh —
        adapters are tiny) fully materialized BEFORE the tick-boundary
        apply, so the engine-side install is a reference assignment."""
        if self.engine.mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec
            stack = jax.device_put(
                stack, NamedSharding(self.engine.mesh,
                                     PartitionSpec()))
        try:
            jax.block_until_ready(stack)
        except Exception as e:  # pylint: disable=broad-except
            # Best-effort pre-materialization only: a failed wait
            # just moves the device copy to the tick-boundary apply.
            logger.debug('adapter stack pre-stage wait failed: %s', e)
        return stack

    def _abort_load(self, t0, name, checkpoint, error: str) -> None:
        self._m_loads.labels('aborted').inc()
        self.last = {
            'ok': False, 'op': 'load', 'name': name,
            'checkpoint': checkpoint, 'error': error,
            'duration_s': round(time.perf_counter() - t0, 4),
            'at': time.time(),
        }
        logger.warning('adapter load %r aborted (old stack intact): '
                       '%s', name, error)

    # ----------------------------------------------------------- unload
    def unload(self, name: str,
               drain: Optional[bool] = None) -> Dict[str, Any]:
        """Zero one adapter's slot (id retired until reused). Raises
        AdapterInUse (409) while live requests reference the id,
        SwapInFlight on concurrency, WeightSwapError otherwise — the
        old stack is intact in every error case."""
        if not self._flight.acquire(blocking=False):
            raise SwapInFlight(
                'a weight swap, reshard, or adapter update is already '
                'in flight on this replica')
        try:
            return self._unload_locked(name, drain)
        finally:
            self._flight.release()

    def _unload_locked(self, name, drain) -> Dict[str, Any]:
        t0 = time.perf_counter()
        from skypilot_tpu.infer import lora as lora_lib
        aid = None
        try:
            faults.inject('adapter.load', name=str(name),
                          checkpoint='', op='unload')
            if name not in self._adapters:
                raise WeightSwapError(
                    f'adapter {name!r} is not loaded')
            aid = self._adapters[name]['id']
            if self.engine.adapter_in_use(aid):
                raise AdapterInUse(
                    f'adapter {name!r} (id {aid}) is still referenced '
                    f'by live requests; retry after they drain')
            live = getattr(self.engine, '_lora_stack', None)
            if live is None:
                raise WeightSwapError(
                    'engine has no adapter stack loaded')
            stack = self._stage_stack(lora_lib.zero_slot(live, aid))
            result = self.engine.request_adapter_update(
                stack,
                num_adapters=int(self.engine.num_adapters),
                flush_prefix=True,
                drain=bool(drain) if drain is not None else False)
        except AdapterInUse:
            self._m_unloads.labels('refused').inc()
            raise
        except faults.FaultError as e:
            self._abort_unload(t0, name, f'injected fault: {e}')
            raise WeightSwapError(
                f'adapter unload aborted (old stack intact): '
                f'{e}') from e
        except WeightSwapError as e:
            self._abort_unload(t0, name, str(e))
            raise
        except Exception as e:  # pylint: disable=broad-except
            self._abort_unload(t0, name, str(e))
            raise WeightSwapError(
                f'adapter unload failed (old stack intact): '
                f'{e}') from e
        dur = time.perf_counter() - t0
        del self._adapters[name]
        self._trees.pop(aid, None)
        self._m_loaded.set(len(self._adapters))
        self._m_unloads.labels('ok').inc()
        self.last = {
            'ok': True, 'op': 'unload', 'name': name, 'id': aid,
            'flushed_prefix_pages': result['flushed_prefix_pages'],
            'duration_s': round(dur, 4), 'apply_s': result['apply_s'],
            'at': time.time(),
        }
        if self._on_change is not None:
            self._on_change()
        logger.info('adapter unload ok: %r (id %d freed) in %.3fs',
                    name, aid, dur)
        return dict(self.last)

    def _abort_unload(self, t0, name, error: str) -> None:
        self._m_unloads.labels('aborted').inc()
        self.last = {
            'ok': False, 'op': 'unload', 'name': name, 'error': error,
            'duration_s': round(time.perf_counter() - t0, 4),
            'at': time.time(),
        }
        logger.warning('adapter unload %r aborted (old stack intact): '
                       '%s', name, error)
