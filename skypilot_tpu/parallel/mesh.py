"""Device-mesh presets: the TPU-native replacement for the reference's
"export SKYPILOT_NODE_* and let the user's NCCL launcher sort it out"
(SURVEY.md §2.10).

One canonical 6-axis mesh covers every parallelism the reference's recipes
delegate to workload internals:

  pp    pipeline stages          (reference: deepspeed-multinode recipes)
  dp    pure data parallel       (reference: resnet_distributed_torch DDP)
  cp    context/sequence parallel — ring attention (absent in reference)
  fsdp  sharded data parallel    (reference: DeepSpeed ZeRO recipes)
  ep    expert parallel          (reference: llm/mixtral via megablocks)
  tp    tensor parallel          (reference: llm/vllm --tensor-parallel-size)

Axis order is chosen so the *innermost* axes (tp, ep) land on adjacent ICI
neighbors when JAX maps the mesh onto the slice torus, and the outermost
(pp, dp) cross DCN in multi-slice deployments — collectives that need the
most bandwidth ride the fastest links. Size-1 axes are free: every model in
this framework is written against all six names.
"""
import dataclasses
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

MESH_AXES: Tuple[str, ...] = ('pp', 'dp', 'cp', 'fsdp', 'ep', 'tp')


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """A named parallelism layout. Multiply to the device count."""
    pp: int = 1
    dp: int = 1
    cp: int = 1
    fsdp: int = 1
    ep: int = 1
    tp: int = 1

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.pp, self.dp, self.cp, self.fsdp, self.ep, self.tp)

    @property
    def num_devices(self) -> int:
        return math.prod(self.shape)

    def axis_sizes(self) -> Dict[str, int]:
        return dict(zip(MESH_AXES, self.shape))

    def __str__(self) -> str:
        active = [f'{a}={s}' for a, s in self.axis_sizes().items() if s > 1]
        return 'MeshSpec(' + (', '.join(active) or '1 device') + ')'


def current_mesh() -> Optional[Mesh]:
    """The ambient mesh from an enclosing `with mesh:` block, or None.

    Reads jax's thread-local resource env (the pjit-era mechanism that the
    Mesh context manager populates; stable across jax releases for years).
    """
    from jax._src import mesh as jax_mesh_internal
    m = jax_mesh_internal.thread_resources.env.physical_mesh
    return None if m.empty else m


def build_mesh(spec: MeshSpec,
               devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Create a jax.sharding.Mesh with the canonical axis names.

    Devices are laid out in row-major order over the spec shape, so the
    innermost axis (tp) strides over consecutive devices — on a TPU slice,
    consecutive devices are ICI neighbors within a host before crossing
    hosts, which is exactly where tp's all-reduces belong.
    """
    if devices is None:
        devices = jax.devices()
    n = spec.num_devices
    if n > len(devices):
        raise ValueError(
            f'{spec} needs {n} devices, only {len(devices)} available')
    dev_array = np.array(devices[:n]).reshape(spec.shape)
    return Mesh(dev_array, MESH_AXES)


def hybrid_topology_key(ici: MeshSpec, dcn: MeshSpec,
                        devices: Sequence[jax.Device]) -> str:
    """The comms-profile topology key this hybrid layout probes as
    (same formatter as comms_profile.topology_key of the built mesh),
    so the placement advisor can find the measured profile before the
    mesh exists."""
    from skypilot_tpu.parallel import comms_profile
    ici_sizes = ici.axis_sizes()
    dcn_sizes = dcn.axis_sizes()
    return comms_profile.format_topology_key(
        getattr(devices[0], 'device_kind', 'unknown'),
        ici.num_devices * dcn.num_devices,
        [(a, ici_sizes[a] * dcn_sizes[a]) for a in MESH_AXES],
        [a for a in MESH_AXES if dcn_sizes[a] > 1])


def _interleave_chunks(devices: Sequence[jax.Device], ici: MeshSpec,
                       dcn: MeshSpec) -> np.ndarray:
    """Contiguous n_ici-sized chunks = slices. Shape the array as
    dcn_axes + ici_axes, then interleave to (dcn_0, ici_0, ...) and
    merge each pair — identical semantics to
    mesh_utils.create_hybrid_device_mesh."""
    arr = np.array(devices[:ici.num_devices * dcn.num_devices]).reshape(
        dcn.shape + ici.shape)
    order = []
    for i in range(len(MESH_AXES)):
        order += [i, i + len(MESH_AXES)]
    arr = arr.transpose(order)
    return arr.reshape(tuple(
        d * i for d, i in zip(dcn.shape, ici.shape)))


def _permute_dcn_slices(dev_array: np.ndarray, ici: MeshSpec,
                        dcn: MeshSpec,
                        perm: Sequence[int]) -> np.ndarray:
    """Reorder WHOLE SLICES along the DCN factor of an already-built
    hybrid device array: position k of the dcn ordering gets the
    slice that row-major position perm[k] held. Each slice's internal
    (ICI) assignment — including the topology-aware layout
    mesh_utils.create_hybrid_device_mesh computed on real TPUs — is
    moved as an opaque block, never rearranged."""
    nd = len(MESH_AXES)
    # Merged axes are dcn-major: split each back into (dcn_a, ici_a),
    # bring the dcn dims together as one slice-position axis, permute,
    # and merge back.
    inter = dev_array.reshape(
        [x for pair in zip(dcn.shape, ici.shape) for x in pair])
    t = inter.transpose([2 * i for i in range(nd)] +
                        [2 * i + 1 for i in range(nd)])
    flat = t.reshape((dcn.num_devices,) + tuple(ici.shape))
    flat = flat[list(perm)]
    back = flat.reshape(tuple(dcn.shape) + tuple(ici.shape))
    order = []
    for i in range(nd):
        order += [i, i + nd]
    back = back.transpose(order)
    return back.reshape(tuple(
        d * i for d, i in zip(dcn.shape, ici.shape)))


def build_hybrid_mesh(ici: MeshSpec, dcn: MeshSpec,
                      devices: Optional[Sequence[jax.Device]] = None,
                      num_slices: Optional[int] = None,
                      placement: Optional[str] = None,
                      profile=None) -> Mesh:
    """Multi-slice mesh: `ici` axes live within a slice (fast ICI
    torus), `dcn` axes cross slices (data-center network). Final mesh
    axis size = ici_axis * dcn_axis, DCN-major — so e.g.
    ici=MeshSpec(fsdp=4), dcn=MeshSpec(dp=2) over 2 slices of 4 chips
    gives a (dp=2, fsdp=4) mesh whose dp collectives ride DCN and fsdp
    collectives ride ICI. This is the multi-slice/megascale analog of
    the reference's multi-node NCCL-over-Ethernet
    (examples/nccl_test.yaml); SURVEY.md §5 "Distributed communication
    backend".

    Real TPU slices are detected via device.slice_index (set by the
    runtime under multi-slice env vars — runtime/gang.py exports them);
    CPU/test devices are chunked into `num_slices` contiguous groups so
    the same code dry-runs on a forced-host-platform mesh.

    ``placement`` (default from ``SKYT_COMMS_PLACEMENT``, 'rowmajor'):

      * ``'rowmajor'`` — today's layout, byte-identical to the
        pre-advisor behavior;
      * ``'measured'`` — Cloud Collectives-style rank reorder
        (arXiv 2105.14088) restricted to the DCN factor: the
        row-major layout is built first (so each slice keeps the
        exact internal ICI assignment row-major would have given it,
        including mesh_utils' topology-aware layout on real TPUs),
        then whole slices are reordered along the dcn axis by the
        cheapest ring permutation under the measured comms profile's
        per-pair costs (``profile`` argument, else the cached probe
        for this topology — parallel/comms_profile.py). The winner is
        cached per (topology, spec). Without any profile the
        permutation is the identity, i.e. exactly the row-major mesh.
    """
    if devices is None:
        devices = jax.devices()
    n_dcn = dcn.num_devices
    n_ici = ici.num_devices
    if num_slices is None:
        slice_ids = {getattr(d, 'slice_index', 0) for d in devices}
        num_slices = len(slice_ids) if len(slice_ids) > 1 else n_dcn
    if n_dcn != num_slices:
        raise ValueError(
            f'dcn spec {dcn} needs {n_dcn} slices, have {num_slices}')
    if n_ici * n_dcn > len(devices):
        raise ValueError(
            f'{ici} x {dcn} needs {n_ici * n_dcn} devices, '
            f'have {len(devices)}')
    if placement is None:
        from skypilot_tpu.utils import env
        placement = env.get('SKYT_COMMS_PLACEMENT') or 'rowmajor'
    if placement not in ('rowmajor', 'measured'):
        raise ValueError(f"placement must be 'rowmajor' or 'measured',"
                         f' got {placement!r}')

    have_slice_attr = len({getattr(d, 'slice_index', 0)
                           for d in devices}) > 1
    if have_slice_attr:
        from jax.experimental import mesh_utils
        dev_array = mesh_utils.create_hybrid_device_mesh(
            ici.shape, dcn.shape, devices=devices,
            allow_split_physical_axes=True)
    else:
        dev_array = _interleave_chunks(devices, ici, dcn)
    if placement == 'measured':
        from skypilot_tpu.parallel import comms_profile
        key = (f'{hybrid_topology_key(ici, dcn, devices)}'
               f'#ici{ici.shape}|dcn{dcn.shape}')
        perm = comms_profile.placement_for(key, n_dcn, profile=profile)
        if perm != list(range(n_dcn)):
            dev_array = _permute_dcn_slices(dev_array, ici, dcn, perm)
    return Mesh(dev_array, MESH_AXES)


def auto_spec(n_devices: int,
              tp: Optional[int] = None,
              fsdp: Optional[int] = None,
              pp: int = 1,
              cp: int = 1,
              ep: int = 1,
              model_params_b: Optional[float] = None,
              hbm_gib_per_device: float = 16.0) -> MeshSpec:
    """Pick a sensible layout for `n_devices`.

    Heuristic (the scaling-book recipe): shard the model with fsdp until
    params fit comfortably (~4 bytes/param train state with bf16 + f32 adam
    moments), use tp only when a single layer's working set outgrows HBM or
    the user asks, and give the rest to dp.
    """
    remaining = n_devices
    for name, val in (('pp', pp), ('cp', cp), ('ep', ep)):
        if remaining % val != 0:
            raise ValueError(f'{name}={val} does not divide {remaining}')
        remaining //= val
    if tp is None:
        tp = 1
    if remaining % tp != 0:
        raise ValueError(f'tp={tp} does not divide {remaining}')
    remaining //= tp
    if fsdp is None:
        if model_params_b is None:
            fsdp = remaining  # default: full parameter sharding (ZeRO-3-ish)
        else:
            # ~18 bytes/param full train state (bf16 params+grads, f32
            # master + two adam moments); find the min fsdp that fits.
            state_gib = model_params_b * 1e9 * 18.0 / (2**30)
            fsdp = 1
            while (state_gib / (fsdp * max(tp, 1)) >
                   0.6 * hbm_gib_per_device and fsdp < remaining):
                fsdp *= 2
    if remaining % fsdp != 0:
        raise ValueError(f'fsdp={fsdp} does not divide {remaining}')
    dp = remaining // fsdp
    return MeshSpec(pp=pp, dp=dp, cp=cp, fsdp=fsdp, ep=ep, tp=tp)


def mesh_for_topology(topology, tp: Optional[int] = None,
                      **kwargs) -> MeshSpec:
    """Spec for a TPU slice: defaults tp to the chips-per-host (tp inside a
    host rides the fastest ICI hop) and fsdp across hosts."""
    n = topology.chips
    if tp is None:
        tp = min(topology.chips_per_host, n)
    return auto_spec(n, tp=tp, **kwargs)
