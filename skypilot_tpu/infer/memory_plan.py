"""Serving memory plan: will this model + KV pool fit this slice?

The reference answers "can I serve a 70B on this hardware" empirically
(vLLM OOMs or it doesn't, llm/vllm/serve.yaml just picks A100-80GB×8);
on TPU slices the budget is static enough to check up front: weights
are a pure function of the config and quant mode, the paged KV pool is
sized explicitly (engine pool_tokens), and the engine's sharding rule
is deterministic. `plan_serving` reproduces EXACTLY the engine's
placement arithmetic (infer/engine.py __init__: kv sharded over tp iff
tp divides n_kv_heads, else replicated; params sharded tp-wide) so the
plan is an assertion about the real engine, not a back-of-envelope.

Used by: tests/test_memory_plan.py (pins the 70B-on-v5e recipes),
examples/llama_70b_serve.yaml (documents its own plan), and anyone
sizing a slice before `skyt serve up`.
"""
import dataclasses
import math
from typing import Optional

# HBM per chip for the TPU generations in the catalog (GiB). v5e is the
# serving workhorse; v5p/v6e for completeness (catalog/fetch_gcp.py).
HBM_GIB = {'v4': 32.0, 'v5e': 16.0, 'v5p': 95.0, 'v6e': 32.0}

_GIB = 1024 ** 3


@dataclasses.dataclass
class ServingMemoryPlan:
    """All byte counts are PER CHIP (the binding constraint)."""
    param_bytes: int
    kv_pool_bytes: int
    kv_sharded: bool           # engine rule: tp divides n_kv_heads
    logits_bytes: int          # decode logits + sampling workspace
    workspace_bytes: int       # XLA temps/fragmentation allowance
    hbm_bytes: int
    tp: int

    @property
    def total_bytes(self) -> int:
        return (self.param_bytes + self.kv_pool_bytes +
                self.logits_bytes + self.workspace_bytes)

    @property
    def fits(self) -> bool:
        return self.total_bytes <= self.hbm_bytes

    @property
    def headroom_gib(self) -> float:
        return (self.hbm_bytes - self.total_bytes) / _GIB

    def summary(self) -> str:
        g = _GIB
        return (f'per-chip: params {self.param_bytes / g:.2f} GiB + '
                f'kv {self.kv_pool_bytes / g:.2f} GiB'
                f'{" (sharded)" if self.kv_sharded else " (REPLICATED)"}'
                f' + logits {self.logits_bytes / g:.2f} GiB + '
                f'workspace {self.workspace_bytes / g:.2f} GiB = '
                f'{self.total_bytes / g:.2f} / {self.hbm_bytes / g:.0f} '
                f'GiB -> {"FITS" if self.fits else "DOES NOT FIT"} '
                f'(headroom {self.headroom_gib:+.2f} GiB)')


def kv_bytes_per_token(cfg, kv_dtype: str = 'auto') -> int:
    """KV pool bytes one token costs across all layers (k + v).

    'auto': head_dim values at cfg.dtype width. 'int8': head_dim int8
    bytes plus one f32 per-token per-head scale
    (infer/paged_cache.py), so the ratio auto/int8 — the
    pages-per-pool multiplier at equal HBM — is
    d*itemsize / (d + 4): 1.94x for bf16 d=128, 3.76x for f32 d=64.
    """
    dtype_bytes = 2 if cfg.dtype == 'bfloat16' else 4
    if kv_dtype == 'int8':
        per_head = cfg.head_dim * 1 + 4
    elif kv_dtype in ('auto', None, ''):
        per_head = cfg.head_dim * dtype_bytes
    else:
        raise ValueError(f'unknown kv_dtype {kv_dtype!r}')
    return cfg.n_layers * 2 * cfg.n_kv_heads * per_head


def kv_pages_ratio(cfg, kv_dtype: str = 'int8') -> float:
    """Pages a fixed HBM budget holds at `kv_dtype` relative to the
    float pool — the concurrent-users-per-chip multiplier the
    quantized KV cache buys."""
    return kv_bytes_per_token(cfg, 'auto') / \
        kv_bytes_per_token(cfg, kv_dtype)


def plan_serving(cfg, *, tp: int, num_slots: int = 8,
                 max_seq_len: int = 4096,
                 pool_tokens: Optional[int] = None,
                 quantize: str = 'none',
                 kv_dtype: str = 'auto',
                 accelerator: str = 'v5e',
                 page_size: int = 64) -> ServingMemoryPlan:
    """Per-chip memory plan for the paged engine serving `cfg` tp-wide.

    Mirrors the engine's actual layout:
      * params: every projection kernel tp-sharded (megatron rules);
        int8 = 1 byte/param + f32 per-output-channel scales; embeddings
        and norms stay at cfg.dtype width (models/quant.py).
      * KV pool (infer/paged_cache.py for_engine): pool_tokens rounded
        up to pages, +1 dummy page, × n_layers × 2 × n_kv_heads ×
        head_dim at cfg.dtype width; sharded over tp ONLY when tp
        divides n_kv_heads (engine __init__ kv_axis rule), else every
        chip holds the whole pool.
      * logits/sampling: [num_slots, vocab] f32 logits + the int32
        penalty-count table the decode step keeps resident.
      * workspace: 12% of the above for XLA temps + fragmentation
        (empirical allowance; the 8B-int8-on-one-v5e config measured
        ~10%).
    """
    dtype_bytes = 2 if cfg.dtype == 'bfloat16' else 4
    n_params = cfg.num_params()
    if quantize == 'int8':
        # Projections are ~all params outside embeddings; embeddings
        # (+ output head when untied) stay at dtype width.
        embed = cfg.vocab_size * cfg.dim * \
            (1 if cfg.tie_embeddings else 2)
        proj = n_params - embed
        # Per-output-channel f32 scales: out-features per kernel is
        # >= 1/8192 of its elements for these shapes — bounded at 1%.
        scale_overhead = proj // 100
        param_total = proj * 1 + scale_overhead + embed * dtype_bytes
    elif quantize == 'none':
        param_total = n_params * dtype_bytes
    else:
        raise ValueError(f'unknown quantize mode {quantize!r}')
    param_bytes = math.ceil(param_total / tp)

    # Paged pool geometry (PagedConfig.for_engine). kv_dtype='int8'
    # sizes by the quantized itemsize + the f32 scale pools
    # (kv_bytes_per_token), which is what roughly doubles
    # pages-per-pool at equal HBM (engine SKYT_KV_DTYPE/kv_dtype).
    tokens = pool_tokens if pool_tokens is not None \
        else num_slots * max_seq_len
    n_pages = -(-tokens // page_size) + 1
    kv_total = n_pages * page_size * kv_bytes_per_token(cfg, kv_dtype)
    kv_sharded = tp > 1 and cfg.n_kv_heads % tp == 0
    kv_pool_bytes = kv_total // tp if kv_sharded else kv_total

    logits_bytes = num_slots * cfg.vocab_size * (4 + 4)  # f32 + counts
    workspace_bytes = int(
        0.12 * (param_bytes + kv_pool_bytes + logits_bytes))
    return ServingMemoryPlan(
        param_bytes=param_bytes, kv_pool_bytes=kv_pool_bytes,
        kv_sharded=kv_sharded, logits_bytes=logits_bytes,
        workspace_bytes=workspace_bytes,
        hbm_bytes=int(HBM_GIB[accelerator] * _GIB), tp=tp)


def stream_load_budget_s(cfg, *, read_gbps: float = 1.0,
                         quantize: str = 'none') -> float:
    """Checkpoint-load time budget for the streamed loader.

    models/weights.py reads the bf16 safetensors shards and (with
    --quantize int8) quantizes each tensor on host as it streams — so
    the bytes READ are always the bf16 checkpoint size regardless of
    the serving dtype; only the bytes RESIDENT shrink. At gcsfuse's
    ~1 GB/s per VM this puts a 70B load at ~2.5 min/host — excluded
    from TTFT by construction (the engine warms up before /health goes
    green; serve readiness probes gate traffic on it).
    """
    del quantize  # read volume is the checkpoint's, not the target's
    ckpt_bytes = cfg.num_params() * 2  # HF bf16 safetensors
    return ckpt_bytes / (read_gbps * 1e9)
