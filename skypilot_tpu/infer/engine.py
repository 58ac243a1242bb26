"""Continuous-batching inference engine for Llama-family models.

The reference serves LLMs by wrapping vLLM in a task YAML
(llm/vllm/serve.yaml — SURVEY.md §2.11); the TPU-native framework makes
the engine itself first-class, JetStream-style:

  * prefill runs one request at a time (B=1, padded to a bucket length)
    and inserts its KV into a slot of the shared decode cache;
  * decode steps the whole slot batch at once — one token per active
    slot per step, so new requests join mid-flight without stalling
    running ones (continuous batching);
  * both paths are jitted once per bucket shape; the decode step is the
    steady-state hot loop (MXU: batched [SLOTS,1] matmuls against the
    weights; HBM: the KV cache).

TTFT = prefill latency + queue wait, the p50 target BASELINE.md sets for
serving. greedy/temperature/top-k/top-p sampling; speculative decoding
covers both greedy (exact) and sampled (rejection sampling, exact
distribution) requests.
"""
import contextlib
import dataclasses
import itertools
import queue
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from skypilot_tpu.infer import kv_tier as kv_tier_lib
from skypilot_tpu.infer import ledger as ledger_lib
from skypilot_tpu.infer import tickstats as tickstats_lib
from skypilot_tpu.infer.paged_cache import page_hashes as paged_cache_hashes
from skypilot_tpu.utils import compile_cache
from skypilot_tpu.utils import faults
from skypilot_tpu.utils import log_utils
from skypilot_tpu.utils import metrics as metrics_lib
from skypilot_tpu.utils import tracing
from skypilot_tpu.utils import env

logger = log_utils.init_logger(__name__)

# Completed request traces kept for /stats?request_id= queries.
_TRACE_KEEP = 2048
# Span events per request trace (batched-admission marks, per-chunk
# delivery marks): bounded so a max_new_tokens=4096 request cannot grow
# its trace without bound.
_TRACE_EVENTS_KEEP = 64

# Device-side top-k sampling supports k up to this (one fixed-size
# top_k sort serves all slots' per-request k values).
_TOPK_BUCKET = 64
# QoS priority classes (serve/qos.py defines the authoritative set;
# duplicated here so SamplingParams.validate stays import-light — the
# engine only imports the QoS module when SKYT_QOS=1).
_QOS_PRIORITIES = ('interactive', 'standard', 'batch')

# Max logit_bias entries per request; applied as a device-side
# scatter-add of a fixed [SLOTS, _BIAS_BUCKET] (idx, val) pair, so the
# cap keeps the decode step free of data-dependent shapes (same
# philosophy as _TOPK_BUCKET). OpenAI clients rarely use more than a
# handful of entries.
_BIAS_BUCKET = 64


@dataclasses.dataclass
class SamplingParams:
    max_new_tokens: int = 128
    temperature: float = 0.0          # 0 => greedy
    top_k: int = 0                    # 0 => off; device path caps at 64
    # Nucleus sampling; >= 1 (or <= 0) => off. The device path bounds
    # the nucleus to the top-64 logits (_TOPK_BUCKET) — for real models
    # the p-nucleus is almost always far smaller.
    top_p: float = 1.0
    # OpenAI-style repetition penalties over OUTPUT tokens (the vLLM
    # counting convention; prompt tokens are not penalized):
    #   logits[v] -= frequency_penalty * count[v]
    #              + presence_penalty * (count[v] > 0)
    # Applied to raw logits before temperature/top-k/top-p; work with
    # greedy too. Speculative decoding falls back to the plain path for
    # penalized requests (the verify target would change within a
    # draft run), matching vLLM.
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    eos_token: Optional[int] = None
    seed: int = 0
    # True: the out_queue yields (token, logprob) pairs — the chosen
    # token's RAW model logprob (pre-filter log-softmax, the OpenAI/
    # vLLM convention) — instead of bare ints.
    logprobs: bool = False
    # OpenAI logit_bias: {token_id: bias in [-100, 100]} added to the
    # raw logits before temperature/top-k/top-p AND before the greedy
    # argmax (OpenAI semantics: -100 bans, +100 effectively forces).
    # Reported logprobs stay RAW model values (same convention as the
    # repetition penalties). Max _BIAS_BUCKET entries.
    logit_bias: Optional[Dict[int, float]] = None
    # Multi-LoRA routing: index into the engine's adapter stack
    # (infer/lora.py build_stack; 0 = base model, no adapter). The
    # OpenAI server maps adapter NAMES to ids; at the engine level the
    # id is just another per-request sampling knob, so it rides the
    # multi-host request broadcast like everything else.
    lora_id: int = 0
    # Absolute wall-clock deadline (time.time() seconds). Past it the
    # request is expired in the decode loop — the slot and its KV
    # pages free at the next delivery boundary instead of generating
    # for an abandoned client (docs/robustness.md). None = no deadline.
    deadline: Optional[float] = None
    # QoS admission class + tenant (docs/qos.md). With SKYT_QOS=1 the
    # waiting queue orders by class (aging prevents starvation) and is
    # DRR-fair across tenants within a class; with QoS off both fields
    # are inert. They ride the multi-host request broadcast like every
    # other per-request knob, so follower hosts schedule identically.
    priority: str = 'standard'
    tenant: str = ''

    def validate(self) -> None:
        """Reject parameters the engine cannot honor exactly, instead
        of silently reshaping the requested distribution.

        The device sampling path computes top-k and the top-p nucleus
        from ONE shared top-64 sort (_TOPK_BUCKET): top_k > 64 would be
        silently clamped, so it is rejected here. The nucleus is
        likewise bounded to the top-64 candidates — that bound cannot
        be checked request-time (it depends on the model's step
        distribution), so it stays a documented approximation: with
        top_p ~1 at high temperature the tail past the 64th candidate
        is excluded. Exact-k sampling for k > 64 would need a second,
        wider sort compiled into every decode step; not worth it for a
        parameter OpenAI clients essentially never use.
        """
        if not isinstance(self.top_k, int) or isinstance(self.top_k,
                                                         bool):
            raise ValueError(f'top_k must be an int, got '
                             f'{self.top_k!r}')
        if self.top_k < 0:
            raise ValueError(f'top_k must be >= 0, got {self.top_k}')
        if self.top_k > _TOPK_BUCKET:
            raise ValueError(
                f'top_k={self.top_k} exceeds the device sampling '
                f'bucket ({_TOPK_BUCKET}); ask for top_k <= '
                f'{_TOPK_BUCKET} (larger values cannot be honored '
                f'exactly)')
        if not 0.0 <= self.top_p <= 1.0:
            raise ValueError(f'top_p must be in [0, 1], got '
                             f'{self.top_p}')
        if self.temperature < 0.0:
            raise ValueError(f'temperature must be >= 0, got '
                             f'{self.temperature}')
        if self.max_new_tokens < 1:
            raise ValueError(f'max_new_tokens must be >= 1, got '
                             f'{self.max_new_tokens}')
        if not isinstance(self.lora_id, int) or self.lora_id < 0:
            raise ValueError(f'lora_id must be an int >= 0, got '
                             f'{self.lora_id!r}')
        if self.priority not in _QOS_PRIORITIES:
            raise ValueError(
                f'priority must be one of {_QOS_PRIORITIES}, got '
                f'{self.priority!r}')
        if not isinstance(self.tenant, str):
            raise ValueError(f'tenant must be a string, got '
                             f'{self.tenant!r}')
        if self.logit_bias:
            if len(self.logit_bias) > _BIAS_BUCKET:
                raise ValueError(
                    f'logit_bias supports at most {_BIAS_BUCKET} '
                    f'entries, got {len(self.logit_bias)}')
            for t, b in self.logit_bias.items():
                if not isinstance(t, int) or isinstance(t, bool) or \
                        t < 0:
                    raise ValueError(
                        f'logit_bias keys must be token ids >= 0, '
                        f'got {t!r}')
                if not -100.0 <= float(b) <= 100.0:
                    raise ValueError(
                        f'logit_bias values must be in [-100, 100], '
                        f'got {b!r} for token {t}')


@dataclasses.dataclass
class _Request:
    req_id: int
    tokens: List[int]
    params: SamplingParams
    out_queue: 'queue.Queue[Optional[int]]'
    submitted_at: float = dataclasses.field(default_factory=time.time)
    # First admission attempt (prefill start) — the queue-wait endpoint
    # for the per-class QoS histograms. First write wins (the chunked
    # path records once at chunk 0).
    prefill_start_at: Optional[float] = None
    first_token_at: Optional[float] = None
    slot: Optional[int] = None
    generated: int = 0
    rng: Any = None
    # Set (from any thread) by InferenceEngine.cancel(); the engine
    # loop releases the slot at the next delivery boundary.
    cancelled: bool = False
    # Set by the loop's deadline scan: the request was cancelled
    # because params.deadline passed (recorded as status='deadline').
    expired: bool = False
    # Prompt page hashes, computed once at first admission attempt (a
    # deferred request retries every loop tick; re-hashing the prompt
    # each time is O(n) host work for an unchanging value).
    page_hashes: Optional[List[bytes]] = None
    # Fleet KV tier (SKYT_KV_TIER=fleet): peer URL the LB's rendezvous
    # ring designates as this prefix's owner (X-KV-Peer header), and
    # the in-flight fetch state dict ({'state': 'pending'|'done'|
    # 'failed', 'deadline': ...}) while the request is parked waiting
    # for the cross-replica page transfer. kv_fetch stays non-None
    # afterwards so one request never fetches twice.
    kv_peer: Optional[str] = None
    kv_fetch: Optional[Dict[str, Any]] = None
    # Tick-plane ITL split (infer/tickstats.py): seconds of this
    # request's decode wall time attributed to the pure-decode floor
    # vs prefill co-residency. Accrued per finished chunk by the
    # engine loop; surfaced in the 'done' trace event and the
    # per-class skyt_interference_* counters at release.
    itl_decode_s: float = 0.0
    itl_interference_s: float = 0.0


def _round_up_pow2(n: int, lo: int = 32) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def _fresh_perf() -> Dict[str, float]:
    """Engine perf counters (one shared shape for init + reset_perf).

    prefill_dispatches counts TARGET-model prefill forwards (batched
    admission amortizes these across requests); admission_batch_size is
    the LARGEST number of requests admitted by one prefill dispatch;
    host_finish_s accumulates host-side time spent in _finish_chunk
    AFTER the device pull (cutoff math + queue delivery — the
    per-token Python work the vectorized path removes)."""
    return {'decode_tokens': 0, 'decode_chunks': 0,
            'steady_tokens': 0, 'steady_time_s': 0.0,
            'spec_steps': 0, 'spec_tokens': 0,
            'spec_verify_steps': 0, 'spec_accepted': 0,
            'prefill_chunks': 0, 'prefill_dispatches': 0,
            'admitted_requests': 0, 'admission_batch_size': 0,
            # Padding accounting across every prefill path: dispatch
            # tokens = positions the prefill forward actually computed
            # (B x bucket / packed T), padded = those holding no real
            # prompt token. padded/dispatch is the wasted-FLOP
            # fraction the ragged path drives toward 0.
            'prefill_dispatch_tokens': 0, 'prefill_padded_tokens': 0,
            'ragged_dispatches': 0,
            'host_finish_s': 0.0}


def _put_many(q, items) -> None:
    """Deliver a run of tokens to a request's out_queue in ONE lock
    acquisition (queue.Queue.put takes the mutex per item — at chunk=32
    x 8 slots that is hundreds of lock round-trips per chunk).
    Non-queue.Queue sinks (multi-host DiscardQueue) fall back to put()."""
    if not items:
        return
    if type(q) is queue.Queue:  # pylint: disable=unidiomatic-typecheck
        with q.mutex:
            q.queue.extend(items)
            q.unfinished_tasks += len(items)
            q.not_empty.notify(len(items))
    else:
        for item in items:
            q.put(item)


def _sampling_filter(scaled, topks, topps):
    """Per-slot top-k AND top-p (nucleus) filter over [..., V]
    temperature-SCALED logits: entries outside the filter become -inf.
    topks: k == 0 disables. topps: p >= 1 or <= 0 disables; the nucleus
    is the smallest prefix of descending-probability tokens whose
    cumulative mass reaches p (the first token always survives).
    Both are computed from one shared top-64 sort (_TOPK_BUCKET); the
    nucleus normalizes within that bucket — a documented bound, and for
    real models the p-nucleus is almost always far smaller than 64.
    topks/topps broadcast over any leading axes after the slot axis."""
    kvals, _ = jax.lax.top_k(scaled, min(_TOPK_BUCKET, scaled.shape[-1]))
    extra = (1,) * (scaled.ndim - topks.ndim)
    # top-k threshold
    k_idx = jnp.clip(topks - 1, 0, kvals.shape[-1] - 1)
    kth = jnp.take_along_axis(kvals, k_idx.reshape(k_idx.shape + extra),
                              axis=-1)
    kmask = topks.reshape(topks.shape + extra) > 0
    out = jnp.where(jnp.logical_and(kmask, scaled < kth),
                    -jnp.inf, scaled)
    # top-p over the top-k-RENORMALIZED distribution (the HF/vLLM
    # warper order, matching the host-side _sample): positions past k
    # in the sorted bucket drop out of the softmax first. Exclusive
    # cumsum so the first token always survives.
    pos = jnp.arange(kvals.shape[-1])
    pos = pos.reshape((1,) * (kvals.ndim - 1) + pos.shape)
    kvals_f = jnp.where(
        jnp.logical_and(kmask,
                        pos >= topks.reshape(topks.shape + extra)),
        -jnp.inf, kvals)
    p = jax.nn.softmax(kvals_f, axis=-1)
    before = jnp.cumsum(p, axis=-1) - p
    pp = topps.reshape(topps.shape + extra)
    inside = jnp.logical_and(before < jnp.clip(pp, 0.0, 1.0),
                             jnp.isfinite(kvals_f))
    # Smallest surviving value = nucleus threshold.
    thresh = jnp.min(jnp.where(inside, kvals, jnp.inf), axis=-1,
                     keepdims=True)
    pmask = jnp.logical_and(pp > 0.0, pp < 1.0)
    return jnp.where(jnp.logical_and(pmask, out < thresh),
                     -jnp.inf, out)


def speculative_sample_step(logits, draft, temps, topks, topps, keys):
    """One slot-batched speculative-sampling verify step (the exact
    rejection rule; standalone so its distribution is unit-testable).

    logits [SLOTS, k+1, V] f32 — target logits at the k draft positions
    plus the bonus position; draft [SLOTS, k] int32 — point-mass draft
    tokens (prompt-lookup); temps/topks/topps [SLOTS]; keys [SLOTS]
    per-slot PRNG keys (this step's draws; caller advances them between
    steps).

    Greedy slots (temp == 0): accept while draft == argmax, emit argmax
    rows — identical to the deterministic verify. Sampled slots: accept
    d_i with probability p_i(d_i) (p = softmax of the top-k/top-p
    filtered logits / temp); at the first rejection sample from the
    residual
    (p_i with d_i zeroed, renormalized), and after k accepts sample the
    bonus token from p_k unmodified. The emitted token stream is
    distributed EXACTLY as sequential sampling from p (Leviathan et al.
    speculative sampling with a deterministic proposer).

    Returns (out [SLOTS, k+1] emitted tokens — first acc+1 valid,
    acc [SLOTS] accepted-draft counts).
    """
    slots, k1, _ = logits.shape
    k = k1 - 1
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [S, k+1]
    g_match = (draft == greedy[:, :k])

    scaled = logits / jnp.maximum(temps, 1e-6)[:, None, None]
    probs = jax.nn.softmax(_sampling_filter(scaled, topks, topps),
                           axis=-1)
    ks = jax.vmap(jax.random.split)(keys)        # [SLOTS, 2, key]
    ku, kr = ks[:, 0], ks[:, 1]
    u = jax.vmap(lambda kk: jax.random.uniform(kk, (k,)))(ku)
    p_draft = jnp.take_along_axis(probs[:, :k, :], draft[:, :, None],
                                  axis=-1)[:, :, 0]
    s_accept = u < p_draft
    accept = jnp.where(temps[:, None] > 0, s_accept, g_match)
    acc = jnp.cumprod(accept.astype(jnp.int32), axis=1).sum(axis=1)

    # Distribution at the emission position (index acc): residual with
    # the rejected draft zeroed when acc < k, the bonus p_k otherwise.
    p_at = jnp.take_along_axis(probs, acc[:, None, None],
                               axis=1)[:, 0, :]            # [S, V]
    d_pad = jnp.concatenate([draft, jnp.zeros((slots, 1), jnp.int32)],
                            axis=1)
    d_at = jnp.take_along_axis(d_pad, acc[:, None], axis=1)[:, 0]
    exclude = (acc < k)
    onehot = jax.nn.one_hot(d_at, probs.shape[-1], dtype=probs.dtype)
    resid = jnp.where(exclude[:, None], p_at * (1.0 - onehot), p_at)
    # All-mass-on-draft yet rejected cannot happen exactly (accept prob
    # would be 1), but guard float dust: fall back to p_at.
    resid = jnp.where(resid.sum(-1, keepdims=True) > 0, resid, p_at)
    repl = jax.vmap(lambda kk, lr: jax.random.categorical(kk, lr))(
        kr, jnp.log(resid)).astype(jnp.int32)

    idx = jnp.arange(k + 1)[None, :]
    s_out = jnp.where(idx < acc[:, None], d_pad,
                      jnp.where(idx == acc[:, None], repl[:, None], 0))
    out = jnp.where(temps[:, None] > 0, s_out, greedy)
    return out, acc


def _np_raw_lp(logits_row, tok: int) -> float:
    """RAW model logprob of one token from a host logits row."""
    row = logits_row.astype(np.float64)
    m = row.max()
    return float(row[tok] - m - np.log(np.exp(row - m).sum()))


def _bias_arrays(params) -> 'tuple[np.ndarray, np.ndarray]':
    """(idx [_BIAS_BUCKET] i32, val [_BIAS_BUCKET] f32) for a request's
    logit_bias; zero padding scatter-adds 0.0 onto token 0 (no-op)."""
    idx = np.zeros(_BIAS_BUCKET, np.int32)
    val = np.zeros(_BIAS_BUCKET, np.float32)
    for j, (t, b) in enumerate((params.logit_bias or {}).items()):
        idx[j] = int(t)
        val[j] = float(b)
    return idx, val


def _update_args(args, slot, first_tok, length, temp, key, topk,
                 topp, pres, freq, bidx, bval):
    """Write one slot's decode args on device (shared by both insert
    impls). The slot's output-token count row resets, then the first
    generated token is counted (penalties cover output tokens only)."""
    (last, lens, temps, keys, topks, topps, press, freqs, counts,
     bidxs, bvals) = args
    counts = counts.at[slot].set(0).at[slot, first_tok].set(1)
    return (last.at[slot].set(first_tok),
            lens.at[slot].set(length),
            temps.at[slot].set(temp),
            keys.at[slot].set(key),
            topks.at[slot].set(topk),
            topps.at[slot].set(topp),
            press.at[slot].set(pres),
            freqs.at[slot].set(freq),
            counts,
            bidxs.at[slot].set(bidx),
            bvals.at[slot].set(bval))


class InferenceEngine:
    """Slot-based continuous batching over a jitted prefill/decode pair."""

    # Attached by build_engine (infer/server.py): a callable(path) ->
    # params tree matching this engine's config, plus the checkpoint
    # the engine booted from — the staging hooks of the weight-swap
    # manager (infer/weight_swap.py). None for hand-built engines.
    param_loader = None
    checkpoint_path: Optional[str] = None

    def __init__(self, model, params, *, num_slots: int = 8,
                 max_seq_len: Optional[int] = None,
                 prefill_buckets: Optional[List[int]] = None,
                 decode_chunk: int = 16,
                 mesh=None, rules=None,
                 cache_mode: str = 'dense',
                 page_size: int = 64,
                 pool_tokens: Optional[int] = None,
                 prefix_caching: bool = True,
                 spec_decode: int = 0,
                 prefill_chunk: int = 0,
                 batch_admission: bool = True,
                 kv_dtype: str = 'auto',
                 ragged_prefill: Optional[bool] = None,
                 lockstep=None,
                 draft_model=None, draft_params=None,
                 lora_stack=None,
                 metrics_registry: Optional[
                     'metrics_lib.MetricsRegistry'] = None) -> None:
        """mesh: optional jax.sharding.Mesh — the engine then runs
        tp-sharded: params must already carry their NamedShardings
        (models/weights.py load_llama_params/shard_params) and the KV
        cache is sharded over the tp axis on kv_heads. This is how a
        model larger than one chip's HBM serves (the reference's
        --tensor-parallel-size, llm/vllm/serve.yaml).

        lockstep: optional infer.multihost.LockstepSync — the engine
        then runs as one host of a multi-host replica: the mesh spans
        every host's devices, and each loop tick starts with a control
        broadcast from the primary host (new requests, cancels, stop)
        so all hosts issue identical device computations. Only the
        primary accepts submit()/cancel(); followers mirror. See
        infer/multihost.py for the protocol.

        draft_model/draft_params (with spec_decode k > 0): DRAFT-MODEL
        speculative decoding — k greedy rollouts of the small draft
        replace the n-gram proposer, all inside the same one-dispatch
        verify step. The draft keeps its own dense KV cache aligned to
        the slot lifecycle; a stale draft entry can only lower
        acceptance, never correctness (the target's acceptance gate /
        rejection sampling is unchanged, so outputs stay exactly the
        plain path's). The reference has nothing here — vLLM-era
        n-gram lookup is our baseline, a real draft model beats it on
        non-repetitive text. Draft vocab must equal the target's."""
        self.model = model
        self.cfg = model.cfg
        self.params = params
        self.mesh = mesh
        # Multi-LoRA: the stacked adapter collection (infer/lora.py
        # build_stack) + a per-slot adapter-id array. The stack rides
        # into every model.apply as the 'lora' collection via _vars();
        # id 0 (zeros) is the base model, so released slots route
        # there. Replicated under a mesh: adapters are tiny.
        self._lora_stack = lora_stack
        self.num_adapters = (int(lora_stack['scaling'].shape[0])
                             if lora_stack is not None else 0)
        self._slot_lora = np.zeros(num_slots, np.int32)
        if lora_stack is not None:
            # A layout mismatch would otherwise serve base-model
            # outputs silently (see infer/lora.py validate_stack).
            from skypilot_tpu.infer import lora as lora_lib
            lora_lib.validate_stack(lora_stack, params['params'])
        if lora_stack is not None and mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            rep = NamedSharding(mesh, P())
            self._lora_stack = jax.device_put(lora_stack, rep)
        if rules is None:
            from skypilot_tpu.parallel import sharding as sharding_lib
            rules = sharding_lib.DEFAULT_RULES
        self.rules = list(rules)
        self.num_slots = num_slots
        self.max_seq_len = max_seq_len or self.cfg.max_seq_len
        # Tokens generated per device dispatch: the host pulls one
        # [chunk, SLOTS] batch per round trip instead of one token — at
        # high dispatch/transfer latency (remote TPU, big pods) this is
        # the difference between RTT-bound and compute-bound decode.
        self.decode_chunk = max(1, decode_chunk)
        self.prefill_buckets = sorted(
            prefill_buckets or
            [b for b in (32, 128, 512, 2048, 8192)
             if b <= self.max_seq_len] or [self.max_seq_len])

        dtype = jnp.dtype(self.cfg.dtype)
        self.cache_mode = cache_mode
        # KV-cache dtype (paged mode): 'int8' stores the k/v pools as
        # int8 with per-token per-head scales — ~2x the pages per HBM
        # byte, so ~2x the concurrent users per chip (docs/
        # performance.md "int8 KV cache"). Knob precedence: an
        # explicit engine kv_dtype='int8' forces it; 'auto' (the
        # default) defers to SKYT_KV_DTYPE, then to the model compute
        # dtype (no quantization).
        explicit_kv = kv_dtype not in (None, '', 'auto')
        kv_req = kv_dtype if explicit_kv \
            else env.get('SKYT_KV_DTYPE', 'auto')
        if kv_req in (None, '', 'auto'):
            kv_req = 'auto'
        if kv_req not in ('auto', 'int8'):
            if explicit_kv:
                raise ValueError(
                    f"kv_dtype must be 'auto' or 'int8', got {kv_req!r}")
            # Env-sourced misconfiguration degrades instead of
            # crash-looping the replica (the registry accessors'
            # malformed-value convention, and the same treatment the
            # dense-mode mismatch below gets).
            logger.warning(
                "SKYT_KV_DTYPE=%r is not 'auto' or 'int8'; serving at "
                'the model dtype (%s)', kv_req, self.cfg.dtype)
            kv_req = 'auto'
        if kv_req == 'int8' and cache_mode != 'paged':
            logger.warning(
                'SKYT_KV_DTYPE/kv_dtype=int8 requires the paged cache; '
                'the dense cache stays at %s', self.cfg.dtype)
            kv_req = 'auto'
        self.kv_dtype = kv_req
        self.kv_quantized = kv_req == 'int8'
        # Prefix caching (paged mode only): admissions whose prompt
        # shares full pages with a published prefix skip both the KV
        # writes AND the prefill compute for the shared span — the
        # shared-system-prompt TTFT win vLLM's automatic prefix caching
        # gives the reference.
        self.prefix_caching = prefix_caching and cache_mode == 'paged'
        # Speculative decoding (greedy batches only): propose
        # `spec_decode` draft tokens per step by n-gram lookup in the
        # slot's own token history (prompt-lookup decoding — model-free,
        # so acceptance gating makes outputs EXACTLY equal to plain
        # greedy), verify all drafts in one s=k+1 forward, and emit
        # accepted_prefix+1 tokens per step. Decode is HBM-bound (each
        # step streams the full weights), so every accepted draft is a
        # nearly-free extra token.
        self.spec_decode = max(0, int(spec_decode))
        # Chunked prefill (paged mode only): a prompt longer than
        # `prefill_chunk` tokens is prefilled one chunk per engine-loop
        # iteration, with decode chunks for running requests in
        # between — one long admission can no longer stall every active
        # stream for its whole prefill (vLLM's chunked prefill).
        # 0 disables (admission prefills whole prompts inline).
        self.prefill_chunk = max(0, int(prefill_chunk))
        if self.prefill_chunk and cache_mode == 'paged':
            # Page-aligned so chunk boundaries land on page boundaries.
            self.prefill_chunk = max(page_size,
                                     (self.prefill_chunk // page_size)
                                     * page_size)
        self.pool = None
        cache_sharding = None
        scale_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            tp = mesh.shape.get('tp', 1)
            # Shard the cache over tp on kv_heads (matching the model's
            # 'act_kv_heads' constraint); replicate if tp doesn't divide.
            # kv_heads is axis 3 of the dense cache [L, slots, S, H, d]
            # and axis 2 of the page-major pool [L, pages, H, P, d]
            # (and of the 4D scale pool [L, pages, H, P]).
            kv_axis = 'tp' if tp > 1 and \
                self.cfg.n_kv_heads % tp == 0 else None
            spec = (P(None, None, kv_axis, None, None)
                    if cache_mode == 'paged'
                    else P(None, None, None, kv_axis, None))
            cache_sharding = NamedSharding(mesh, spec)
            scale_sharding = NamedSharding(
                mesh, P(None, None, kv_axis, None))
        if cache_mode == 'paged':
            # Paged (block-table) cache: HBM scales with tokens actually
            # reserved, not slots x max_seq.
            from skypilot_tpu.infer import paged_cache
            pcfg = paged_cache.PagedConfig.for_engine(
                self.max_seq_len, num_slots, page_size, pool_tokens)
            with self._ctx():
                self.pool = paged_cache.PagePool(
                    pcfg, self.cfg.n_layers, self.cfg.n_kv_heads,
                    self.cfg.head_dim, num_slots, dtype,
                    sharding=cache_sharding, kv_dtype=self.kv_dtype,
                    scale_sharding=scale_sharding)
            self.cache = {'k': self.pool.pools['k'],
                          'v': self.pool.pools['v'],
                          'tables': jnp.zeros(
                              (num_slots, pcfg.max_pages_per_slot),
                              jnp.int32)}
            if self.kv_quantized:
                self.cache['k_scale'] = self.pool.pools['k_scale']
                self.cache['v_scale'] = self.pool.pools['v_scale']
            self.pool.pools = None   # arrays live in self.cache now
        else:
            shape = (self.cfg.n_layers, num_slots, self.max_seq_len,
                     self.cfg.n_kv_heads, self.cfg.head_dim)
            if cache_sharding is not None:
                with self._ctx():
                    self.cache = {
                        'k': jnp.zeros(shape, dtype,
                                       device=cache_sharding),
                        'v': jnp.zeros(shape, dtype,
                                       device=cache_sharding)}
            else:
                self.cache = {'k': jnp.zeros(shape, dtype),
                              'v': jnp.zeros(shape, dtype)}
        # FIFO head deferred by pool exhaustion (paged mode only).
        self._deferred: Optional[_Request] = None
        # In-progress chunked prefill (at most one): {req, slot, row,
        # hashes, start, n}. The slot holds its reservation but stays
        # OUT of the decode batch (its device table row is only
        # installed by the final chunk's insert, so zombie decode writes
        # land in the dummy page) until the first token is produced.
        self._chunked: Optional[Dict[str, Any]] = None
        # Host-side slot table. _lengths is an UPPER-BOUND estimate used
        # for chunk sizing (with speculative decode an in-flight chunk's
        # true advance is only known at pull time); _conf_lengths is the
        # confirmed actual length, updated as chunks are pulled. last
        # tokens, rng keys, and top-ks live ONLY on device
        # (self._dev_args).
        self._slots: List[Optional[_Request]] = [None] * num_slots
        self._lengths = np.zeros((num_slots,), np.int32)
        self._conf_lengths = np.zeros((num_slots,), np.int32)
        self._temps = np.zeros((num_slots,), np.float32)
        # Draft model (spec_mode 'draft'): its own dense KV cache over
        # the same slots/positions as the target. Small by construction
        # (the whole point of a draft), so never paged and never
        # sharded — replicated params + cache keep the inner draft
        # scan collective-free under a tp mesh.
        self.draft_model = draft_model if self.spec_decode > 0 else None
        self.draft_params = draft_params
        self._draft_cache = None
        if self.draft_model is not None:
            dcfg = self.draft_model.cfg
            assert dcfg.vocab_size == self.cfg.vocab_size, (
                'draft/target vocab mismatch: verification compares '
                f'token ids ({dcfg.vocab_size} vs {self.cfg.vocab_size})')
            dshape = (dcfg.n_layers, num_slots, self.max_seq_len,
                      dcfg.n_kv_heads, dcfg.head_dim)
            self._draft_cache = {
                'k': jnp.zeros(dshape, jnp.dtype(dcfg.dtype)),
                'v': jnp.zeros(dshape, jnp.dtype(dcfg.dtype))}
        # Device-resident token history per slot (prompt + generated) —
        # the n-gram proposer's haystack. Only maintained by the
        # n-gram spec path (a draft model replaces the proposer);
        # +k+2 tail slack keeps the per-step k+1-token write from ever
        # clamping.
        self._dev_hist = (
            jnp.zeros((num_slots,
                       self.max_seq_len + self.spec_decode + 2),
                      jnp.int32)
            if self.spec_decode > 0 and self.draft_model is None
            else None)
        # Waiting queue: plain FIFO by default. With SKYT_QOS=1 the
        # priority-aware ClassedRequestQueue replaces it — a
        # queue.Queue subclass whose deque is kept in scheduled order
        # (class-ordered with aging, DRR-fair across tenants), so
        # every FIFO access pattern below keeps working unchanged.
        # Decided at construction: the queue type cannot change under
        # a live engine, and the SKYT_QOS=0 path stays byte-identical.
        self._qos_queue = None
        # Slots reserved for interactive-class admissions (QoS only):
        # batch/standard requests leave this many slots free, so a
        # batch flood can never occupy the whole replica and an
        # interactive arrival prefills immediately instead of waiting
        # out a batch decode. 0 (default) = no reservation.
        self._qos_reserved = 0
        if env.get('SKYT_QOS', '0') not in ('', '0', 'false'):
            from skypilot_tpu.serve import qos as qos_lib
            self._qos_queue = qos_lib.ClassedRequestQueue(
                meta=lambda r: qos_lib.RequestMeta(
                    cls=r.params.priority,
                    tenant=r.params.tenant or 'default',
                    cost=float(len(r.tokens)
                               + r.params.max_new_tokens),
                    seq=r.req_id, enq_t=r.submitted_at,
                    # Adapter fleet: flows isolate per served model
                    # (the label map is bounded; ids without one
                    # collapse to the id string).
                    model=str(self.model_labels.get(
                        r.params.lora_id, r.params.lora_id))))
            self._waiting: 'queue.Queue[_Request]' = self._qos_queue
            self._qos_reserved = max(0, min(
                num_slots - 1,
                env.get_int('SKYT_QOS_RESERVE_SLOTS', 0)))
        else:
            self._waiting = queue.Queue()
        # In-place weight swap (docs/robustness.md "Zero-downtime
        # rollouts"): a pending request staged by request_weight_swap
        # (new device params + version + drain flag + completion
        # event), applied by the engine loop at a decode-tick boundary
        # — never mid-dispatch, so every chunk is computed entirely
        # under one weight version. weight_version counts applied
        # swaps (gauge skyt_infer_weight_version; starts at 1, the
        # launch weights).
        self.weight_version = 1
        self._swap_req: Optional[Dict[str, Any]] = None
        # Elastic resharding (docs/robustness.md "Elastic capacity"):
        # the logical layout the live weights are laid out over —
        # virtual nodes in the VirtualFlow sense, decoupled from the
        # physical chip count. Starts at the mesh size (one virtual
        # node per device); request_reshard() re-stages the weights
        # onto a new layout at the same tick-boundary contract the
        # weight swap uses.
        self.virtual_nodes = int(getattr(mesh, 'size', 1) or 1) \
            if mesh is not None else 1
        # Last scheduled order broadcast to lockstep followers (seq
        # list); reorders only rebroadcast when the order changed.
        self._last_qorder: Optional[List[int]] = None
        # Multi-host lockstep (see __init__ docstring). On the primary,
        # submit() lands requests in _ingress and the per-tick sync
        # moves them into _waiting AFTER broadcasting them, so follower
        # hosts admit the identical sequence; cancels likewise take
        # effect only at tick boundaries, identically everywhere.
        self._lockstep = lockstep
        self._ingress: 'queue.Queue[_Request]' = queue.Queue()
        self._pending_cancels: List[int] = []
        # Request currently mid-admission (popped but not yet in
        # _slots) — scanned by cancel().
        self._admitting: Optional[_Request] = None
        # Batched admission (see _try_admit_batch): same-bucket waiting
        # requests prefill in ONE dispatch instead of one _admit_one
        # round-trip each. Off => every admission takes the sequential
        # path (the golden reference the overlap tests compare against).
        self.batch_admission = bool(batch_admission)
        # Ragged (packed variable-length) prefill: mixed-length bursts
        # pack into ONE [1, T] dispatch separated by segment ids
        # instead of padding every row to the shared pow2 bucket —
        # padding positions are masked out of the attention FLOPs and
        # the projections/MLP run over ~sum(len_i) tokens instead of
        # B x bucket (docs/performance.md "Ragged prefill"). Rides the
        # batched-admission machinery, so batch_admission=False keeps
        # the sequential golden path and _try_admit_batch stays the
        # padded reference (SKYT_RAGGED_PREFILL=0 restores it as the
        # default batch path).
        if ragged_prefill is None:
            ragged_prefill = env.get_bool('SKYT_RAGGED_PREFILL', True)
        self.ragged_prefill = bool(ragged_prefill) and \
            self.batch_admission and cache_mode == 'paged'
        # Packed-token cap per ragged dispatch (bounds the packed
        # attention shape the same way prefill buckets bound the
        # padded one).
        self._ragged_max = env.get_int(
            'SKYT_RAGGED_MAX_TOKENS', 0) or max(self.prefill_buckets)
        # Requests popped for an in-flight BATCHED admission — scanned
        # by cancel() alongside _admitting.
        self._admitting_many: List[_Request] = []
        # Device-resident decode args (last, lens, temps, keys, topks);
        # built once from the host mirrors, then updated ON DEVICE (the
        # fused insert kernel writes the admitted slot's entries) so the
        # host never re-uploads state another in-flight chunk already
        # advanced — the invariant that makes pipelined decode safe.
        self._dev_args = None
        self._next_id = 0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.ready = threading.Event()
        # Steady-state decode accounting: intervals between consecutive
        # chunk pulls with no admission in between measure the pipelined
        # decode rate with prefill excluded (the serve bench's
        # steady-state metric).
        self.perf = _fresh_perf()
        self._last_pull_t: Optional[float] = None
        self._had_admission = False
        # Rolling TTFT window (seconds) for /stats percentiles.
        # Appended by the engine thread, read by /stats handlers:
        # both sides take _lock (iterating a deque during a concurrent
        # append raises RuntimeError — ADVICE r5).
        import collections as _collections
        self._ttfts = _collections.deque(maxlen=512)
        # --- metrics plane (utils/metrics.py): continuously updated
        # counters/gauges/histograms the server exposes at /metrics.
        # Registry is injectable for tests; get-or-create semantics make
        # repeated engine construction in one process safe.
        self.metrics_registry = metrics_registry or metrics_lib.REGISTRY
        reg = self.metrics_registry
        self._m_requests = reg.counter(
            'skyt_infer_requests_total', 'Requests submitted')
        self._m_prefill_tokens = reg.counter(
            'skyt_infer_prefill_tokens_total',
            'Prompt tokens admitted through prefill')
        self._m_decode_tokens = reg.counter(
            'skyt_infer_decode_tokens_total',
            'Tokens generated by decode')
        self._m_queue_depth = reg.gauge(
            'skyt_infer_queue_depth',
            'Requests queued but not yet admitted to a slot')
        self._m_running = reg.gauge(
            'skyt_infer_running_requests',
            'Requests occupying a decode slot')
        self._m_slots = reg.gauge(
            'skyt_infer_slots_total', 'Configured decode slots')
        self._m_slots.set(num_slots)
        self._m_ttft = reg.histogram(
            'skyt_infer_ttft_seconds',
            'Time to first token (queue wait + prefill)')
        self._m_itl = reg.histogram(
            'skyt_infer_itl_seconds',
            'Inter-token latency (per-chunk mean across active slots)')
        # Host-overlap series: these prove the batched-admission and
        # vectorized-delivery reductions (docs/performance.md).
        self._m_prefill_dispatches = reg.counter(
            'skyt_infer_prefill_dispatches_total',
            'Target-model prefill device dispatches (batched admission '
            'amortizes these across same-bucket requests)')
        self._m_admission_batch = reg.histogram(
            'skyt_infer_admission_batch_size',
            'Requests admitted per prefill dispatch',
            buckets=(1, 2, 4, 8, 16, 32))
        self._m_host_finish = reg.counter(
            'skyt_infer_host_finish_seconds_total',
            'Host seconds spent delivering pulled decode chunks '
            '(post-pull cutoff math + queue delivery)')
        self._m_prefill_disp_tokens = reg.counter(
            'skyt_infer_prefill_dispatch_tokens_total',
            'Token positions prefill dispatches actually computed '
            '(batch x bucket for padded, packed T for ragged)')
        self._m_prefill_padded = reg.counter(
            'skyt_infer_prefill_padded_tokens_total',
            'Prefill dispatch positions holding no real prompt token '
            '(the wasted-FLOP fraction ragged prefill removes)')
        self._m_kv_util = reg.gauge(
            'skyt_infer_kv_cache_utilization',
            'KV cache occupancy fraction (0-1)')
        self._m_weight_version = reg.gauge(
            'skyt_infer_weight_version',
            'Weight version the engine is serving (starts at 1; each '
            'applied in-place swap bumps it to the pushed version)')
        self._m_weight_version.set(self.weight_version)
        self._m_virtual_nodes = reg.gauge(
            'skyt_infer_virtual_nodes',
            'Virtual-node layout the engine is serving (starts at the '
            'mesh size; each applied in-place reshard moves it)')
        self._m_virtual_nodes.set(self.virtual_nodes)
        self._m_deadline_expired = reg.counter(
            'skyt_infer_deadline_expired_total',
            'Requests expired by their per-request deadline (slot and '
            'KV pages reclaimed)')
        self._m_prefix_hit = reg.counter(
            'skyt_infer_prefix_cache_hit_pages_total',
            'Prompt pages served from the prefix cache')
        self._m_prefix_miss = reg.counter(
            'skyt_infer_prefix_cache_miss_pages_total',
            'Prompt pages that missed the prefix cache')
        # Last pool.prefix_stats values already folded into the
        # counters (the pool keeps running totals; counters take the
        # delta so restarts/resets keep Prometheus rate() math valid).
        self._prefix_seen = {'hit_pages': 0, 'miss_pages': 0}
        # Per-class QoS series, created only with SKYT_QOS=1 (the
        # disabled path never touches them — zero overhead).
        self._m_qos_depth = self._m_qos_wait = self._m_qos_ttft = None
        if self._qos_queue is not None:
            self._m_qos_depth = reg.gauge(
                'skyt_qos_queue_depth',
                'Waiting requests by QoS class', ('class',))
            self._m_qos_wait = reg.histogram(
                'skyt_qos_queue_wait_seconds',
                'Queue wait (submit -> prefill start) by QoS class',
                ('class',))
            self._m_qos_ttft = reg.histogram(
                'skyt_qos_ttft_seconds',
                'Time to first token by QoS class', ('class',))
        # Capacity ledger (infer/ledger.py): engine busy seconds
        # attributed per (class, tenant, model) — the chip-seconds-
        # per-good-token numerator. model_labels maps lora stack ids
        # to bounded display names; the server overwrites it with the
        # served model id + loaded adapter names.
        self.ledger = ledger_lib.BusyLedger(reg)
        self.model_labels: Dict[int, str] = {0: 'base'}
        self._busy_mark: Optional[float] = None
        # --- request-phase traces: req_id -> monotonic-free wall-clock
        # timestamps (queued -> prefill_start -> first_token -> done),
        # queryable via the server's /stats?request_id=. Bounded FIFO.
        self._traces: 'Dict[int, Dict[str, Any]]' = \
            _collections.OrderedDict()
        self._traces_lock = threading.Lock()
        self._last_gauge_t = 0.0
        self._last_deadline_scan = 0.0

        self._jit_prefill = jax.jit(self._prefill_impl,
                                    static_argnames=('bucket',))
        self._jit_prefill_ragged = jax.jit(self._prefill_ragged_impl,
                                           static_argnames=('t_bucket',))
        self._jit_prefill_suffix = jax.jit(self._prefill_suffix_impl,
                                           static_argnames=('bucket',))
        self._jit_decode_spec = jax.jit(
            self._decode_spec_impl,
            donate_argnums=(1, 5, 8),   # cache, keys, hist
            static_argnames=('n', 'k', 'sampling'))
        self._jit_decode_spec_draft = jax.jit(
            self._decode_spec_draft_impl,
            donate_argnums=(2, 3, 7),   # cache, draft cache, keys
            static_argnames=('n', 'k', 'sampling'))
        self._jit_draft_prefill = jax.jit(
            self._draft_prefill_impl,
            donate_argnums=(1,),        # draft cache
            static_argnames=('bucket',))
        self._jit_hist_insert = jax.jit(self._hist_insert_impl,
                                        donate_argnums=(0,))
        # Donate the cache: without it XLA materializes a full cache
        # copy every decode step (hundreds of MB at 8 slots x 2k ctx).
        # With spec decode the history buffer rides along (donated too)
        # so plain-path chunks keep the proposer's invariant intact.
        self._jit_decode_n = jax.jit(
            self._decode_n_impl,
            donate_argnums=(1, 10, 11) if self._dev_hist is not None
            else (1, 10),   # cache, counts (+hist under n-gram spec)
            static_argnames=('n', 'sampling', 'penalize', 'biased'))
        # Donate the global cache and the decode-arg arrays (updated in
        # place); the prefill cache is NOT donatable (its buffers cannot
        # alias the B=slots cache, and a batched admission inserts
        # several rows from the same prefill output).
        self._jit_insert = jax.jit(self._insert_impl,
                                   donate_argnums=(0, 4))
        self._jit_insert_paged = jax.jit(self._insert_paged_impl,
                                         donate_argnums=(0, 4))
        self._jit_insert_pages = jax.jit(self._insert_pages_impl,
                                         donate_argnums=(0,))
        self._jit_clear_slot = jax.jit(self._clear_slot_impl,
                                       donate_argnums=(0,))

        # --- tiered prefix cache (infer/kv_tier.py; docs/performance.md
        # "Tiered prefix cache"). SKYT_KV_TIER=off (the default) leaves
        # kv_tier None and the hot path byte-for-byte: no hook on the
        # pool, no per-tick work beyond one `is not None` check.
        self.kv_tier = None
        self._kv_fetching: Optional[_Request] = None
        # /kv/prefix export requests parked for the loop thread:
        # {'hashes', 'max_pages', 'event', 'pages', 'version'}.
        self._kv_export_q = _collections.deque()
        self._m_kv_tier_hits = None
        self._m_prefix_evictions = reg.counter(
            'skyt_infer_prefix_cache_evictions_total',
            'Published prefix pages reclaimed by allocation pressure '
            '(each one is warm KV dropped from HBM — and spilled to '
            'the host tier when SKYT_KV_TIER is on)')
        self._m_prefix_pages = reg.gauge(
            'skyt_infer_prefix_cache_pages',
            'Pages currently holding published (reusable) prefix KV')
        self._m_prefix_occupancy = reg.gauge(
            'skyt_infer_prefix_cache_occupancy',
            'Published prefix pages / allocatable pool pages (0-1)')
        tier = kv_tier_lib.tier_from_env()
        if tier != 'off' and not (self.cache_mode == 'paged'
                                  and self.prefix_caching):
            logger.warning(
                'SKYT_KV_TIER=%s requires the paged cache with prefix '
                'caching; tiering stays off', tier)
            tier = 'off'
        if tier != 'off' and self._lockstep is not None:
            # Same gate as request_weight_swap: per-host tier state
            # (host stores, fetch timing) would desync the lockstep
            # admission sequence across hosts.
            logger.warning('SKYT_KV_TIER=%s is not supported under '
                           'multi-host lockstep; tiering stays off',
                           tier)
            tier = 'off'
        if tier != 'off':
            self.kv_tier = kv_tier_lib.KVTierManager(tier)
            self.pool.on_evict = self._kv_spill
            # Per-page array layout ([L, H, P(, d)] at pool dtype) the
            # tier validates fetched pages against before they can
            # reach the promote/install path.
            self.kv_tier.set_page_layout({
                name: (np.dtype(self.cache[name].dtype),
                       tuple(self.cache[name].shape[:1]
                             + self.cache[name].shape[2:]))
                for name in self._kv_pool_keys()})
            self._m_kv_tier_hits = reg.counter(
                'skyt_infer_kv_tier_hit_pages_total',
                'Prefix pages served per cache tier: hbm = registry '
                'hits, host = pages promoted host->device, fleet = '
                'pages landed by cross-replica fetch', ('tier',))
            self._prefix_seen['tier_hbm'] = 0
            self._kv_tier_seen = {'promoted_pages': 0,
                                  'fetched_pages': 0,
                                  'prewarm_pages': 0}
            # Pages install host->device in chunks of <= 8 ids padded
            # to pow2 (4 compiles: n in {1,2,4,8}); arrays arrive
            # stacked [L, n, H, P(, d)] at pool dtype, so .set() is a
            # pure byte copy — the golden-equality property.
            self._jit_kv_install = jax.jit(self._kv_install_impl,
                                           donate_argnums=(0,))
            self.kv_tier.start()

        # --- tick plane (infer/tickstats.py; docs/observability.md
        # "Tick plane"): one structured record per engine-loop tick +
        # the prefill<->decode interference attributor. SKYT_TICKSTATS=0
        # leaves this None and the loop body contains NO recording call
        # at all (the watchdog-heartbeat precedent — disabled means
        # structurally absent, not branched around).
        self._tickstats = tickstats_lib.from_env(reg)
        self._tick_t0: Optional[float] = None
        self._tick_perf0 = (0, 0, 0)
        # Prefill isolation (the disaggregation counterfactual): admit
        # prefill only from ticks with no active decode slots, so decode
        # chunks never share a tick with prefill. A schedule property
        # fixed at construction, like the recorder itself.
        self._isolate_prefill = env.get_bool(
            'SKYT_TICKSTATS_ISOLATE', False)
        # KV bytes per decoded token at the active kv dtype (PR 12
        # page math) — the disaggregation advisor's transfer-cost
        # input, exported so /fleet/interference can price the
        # prefill->decode page move from a scrape alone.
        try:
            from skypilot_tpu.infer import memory_plan as _memory_plan
            reg.gauge(
                'skyt_infer_kv_bytes_per_token',
                'KV cache bytes per token at the active KV dtype '
                '(memory_plan page math) — the disaggregation '
                'advisor transfer-cost input').set(float(
                    _memory_plan.kv_bytes_per_token(self.cfg,
                                                    self.kv_dtype)))
        except Exception:  # pylint: disable=broad-except
            logger.exception('kv_bytes_per_token gauge export failed')

    def _pull(self, x) -> np.ndarray:
        """Device→host fetch for control decisions (tokens, logits,
        counts). Single-host: plain np.asarray. Multi-host: a
        global-mesh jit output may not be fully replicated (GSPMD
        chooses its sharding), and np.asarray on a partially
        addressable array raises — allgather the global value so every
        host reads identical bytes and makes identical termination /
        sampling decisions."""
        if self._lockstep is not None and isinstance(x, jax.Array) and \
                not (x.is_fully_addressable or x.is_fully_replicated):
            from jax.experimental import multihost_utils
            # Non-addressable global array: process_allgather (which
            # requires tiled=True for this input class) returns the
            # fully-replicated global value on every host.
            return np.asarray(
                multihost_utils.process_allgather(x, tiled=True))
        return np.asarray(x)

    def _ctx(self):
        """Ambient mesh + flax logical axis rules for every device call
        (no-op off-mesh). The model's nn.with_logical_constraint calls
        only bind when these are active."""
        if self.mesh is None:
            return contextlib.nullcontext()
        import flax.linen as nn
        stack = contextlib.ExitStack()
        stack.enter_context(self.mesh)
        stack.enter_context(nn.logical_axis_rules(self.rules))
        return stack

    # ------------------------------------------------------------ jitted
    def _vars(self, lora_ids):
        """The variables pytree for a model call: params plus, when a
        multi-LoRA stack is loaded, the 'lora' collection and the
        per-sequence adapter ids ('lora_ids' pseudo-collection). The
        jitted impls take this as their `params` argument unchanged —
        jit just sees a wider pytree, so no impl signatures change and
        engines without adapters trace exactly as before."""
        if self._lora_stack is None:
            return self.params
        return dict(self.params, lora=self._lora_stack,
                    lora_ids={'ids': jnp.asarray(lora_ids, jnp.int32)})

    def _prefill_impl(self, params, tokens, length, bucket):
        """tokens [1, bucket]; returns (next_logits [1, V],
        prefill_cache {'k','v'} with B=1, S=bucket)."""
        del bucket
        b, s = tokens.shape
        positions = jnp.arange(s)[None, :].repeat(b, 0)
        shape = (self.cfg.n_layers, b, s, self.cfg.n_kv_heads,
                 self.cfg.head_dim)
        dtype = jnp.dtype(self.cfg.dtype)
        cache = {'k': jnp.zeros(shape, dtype),
                 'v': jnp.zeros(shape, dtype)}
        # Logits only at the prompt's last token (128k-vocab lm_head over
        # every prompt position would be ~20% of prefill FLOPs, unused).
        logits, new_cache = self.model.apply(
            params, tokens, positions=positions, cache=cache,
            logit_positions=(length - 1)[:, None])
        logits = logits[:, 0, :]
        # Greedy first token computed on device: the admission path then
        # pulls 4 bytes instead of a [1, 128k] f32 logits row. The full
        # logits row is only pulled for temperature-sampled requests.
        greedy = jnp.argmax(logits.astype(jnp.float32),
                            axis=-1).astype(jnp.int32)
        return greedy, logits, new_cache

    def _prefill_ragged_impl(self, params, tokens, seg_ids, positions,
                             logit_pos, t_bucket):
        """Ragged (packed) prefill: several variable-length prompts in
        ONE [1, T] row. tokens/seg_ids/positions [1, T] — request j's
        tokens carry segment id j+1 with per-request positions
        0..n_j-1; padding (page-rounding tails + the bucket tail)
        carries id 0 and is masked out of attention by the segment
        machinery (models/llama.py packed branch), so the FLOPs spent
        on real tokens are ~sum(n_j) instead of B x bucket.
        logit_pos [1, Bp]: each request's last-token packed index.
        Returns (greedy [Bp], logits [Bp, V], packed dense cache
        {'k','v'} [L, 1, T, H, d] the paged inserts then slice per
        request via src_off)."""
        del t_bucket
        b, s = tokens.shape
        shape = (self.cfg.n_layers, b, s, self.cfg.n_kv_heads,
                 self.cfg.head_dim)
        dtype = jnp.dtype(self.cfg.dtype)
        cache = {'k': jnp.zeros(shape, dtype),
                 'v': jnp.zeros(shape, dtype)}
        logits, new_cache = self.model.apply(
            params, tokens, positions=positions, segment_ids=seg_ids,
            cache=cache, logit_positions=logit_pos)
        logits = logits[0].astype(jnp.float32)        # [Bp, V]
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return greedy, logits, new_cache

    def _prefill_suffix_impl(self, params, tokens, start, length,
                             k_pool, v_pool, k_scale, v_scale,
                             table_row, bucket):
        """Prefix-cached prefill: only the prompt SUFFIX (tokens
        [1, bucket], global positions start..start+bucket) runs through
        the model; the shared prefix KV is gathered from the slot's
        already-populated pages and attended over via the dense
        continuation path. Returns (greedy, logits [1, V], new_cache
        {'k','v'} [L, 1, max_pages*P, H, d]) — the full per-slot view
        including the prefix, which the paged insert then scatters back
        (private pages only, via src_off). k_scale/v_scale: the int8
        pools' scale pools (None for fp pools) — the gather
        dequantizes, so the model sees a float view either way."""
        del bucket
        from skypilot_tpu.infer.paged_cache import PagePool
        b, s = tokens.shape
        positions = start + jnp.arange(s)[None, :].repeat(b, 0)
        dtype = jnp.dtype(self.cfg.dtype)
        if k_scale is not None:
            view = {'k': PagePool.gather_view_q(
                        k_pool, k_scale, table_row[None], dtype),
                    'v': PagePool.gather_view_q(
                        v_pool, v_scale, table_row[None], dtype)}
        else:
            view = {'k': PagePool.gather_view(k_pool, table_row[None]),
                    'v': PagePool.gather_view(v_pool, table_row[None])}
        logits, new_cache = self.model.apply(
            params, tokens, positions=positions, cache=view,
            logit_positions=(length - start - 1)[:, None])
        logits = logits[:, 0, :]
        greedy = jnp.argmax(logits.astype(jnp.float32),
                            axis=-1).astype(jnp.int32)
        return greedy, logits, new_cache

    def _insert_impl(self, cache, prefill_cache, row, slot, args,
                     first_tok, length, temp, key, topk, topp, pres,
                     freq, bidx, bval):
        """ONE fused dispatch per admission: copy row `row` of a prefill
        cache (B>=1, S=bucket) into `slot` of the global cache AND write
        the slot's decode args (last token, length, temp, rng key, topk)
        into the device-resident arg arrays. cache/args donated;
        prefill_cache is NOT (a batched admission inserts several rows
        from the same prefill cache). The S-axis trim/pad to max_seq_len
        happens here, inside the fused program.

        Updating the args on device (vs rebuilding them from host
        mirrors) keeps them consistent with whatever an in-flight decode
        chunk has already advanced — a host re-upload would rewind the
        other slots by one chunk under pipelining."""
        s_tgt = self.max_seq_len

        def upd(big, small):
            small = jax.lax.dynamic_slice_in_dim(small, row, 1, axis=1)
            if small.shape[2] > s_tgt:
                small = small[:, :, :s_tgt]
            elif small.shape[2] < s_tgt:
                small = jnp.pad(small, ((0, 0), (0, 0),
                                        (0, s_tgt - small.shape[2]),
                                        (0, 0), (0, 0)))
            return jax.lax.dynamic_update_slice(
                big, small, (0, slot, 0, 0, 0))
        cache = jax.tree.map(upd, cache, prefill_cache)
        return cache, _update_args(args, slot, first_tok, length, temp,
                                   key, topk, topp, pres, freq,
                                   bidx, bval)

    def _insert_paged_impl(self, cache, prefill_cache, row, slot, args,
                           first_tok, length, temp, key, topk, topp,
                           pres, freq, bidx, bval, page_ids, table_row,
                           src_off):
        """Paged-mode admission: scatter row `row` of the prompt KV into
        the reserved pages, install the slot's block-table row, and
        update the decode args — one fused dispatch, same contract as
        _insert_impl (prefill_cache not donated: batched admissions
        reuse it across rows).

        page_ids: [n_ins] int32 — pages receiving prompt KV positions
        [src_off, src_off + n_ins*P) (n_ins static via the shape, so one
        compile per distinct page count). A prefix-cached admission
        passes src_off = shared_pages*P so only the computed suffix
        pages are written. table_row: [max_pages] int32."""
        from skypilot_tpu.infer import paged_cache
        p = cache['k'].shape[3]    # [L, n_pages, H, P, d] — P axis
        need = page_ids.shape[0] * p
        pk = jax.lax.dynamic_slice_in_dim(prefill_cache['k'], row, 1,
                                          axis=1)
        pv = jax.lax.dynamic_slice_in_dim(prefill_cache['v'], row, 1,
                                          axis=1)
        if pk.shape[2] < need:   # bucket smaller than the page span
            pad = ((0, 0), (0, 0), (0, need - pk.shape[2]), (0, 0),
                   (0, 0))
            pk = jnp.pad(pk, pad)
            pv = jnp.pad(pv, pad)
        if 'k_scale' in cache:   # int8 pool: quantize at the scatter
            qk, sk = paged_cache.PagePool.insert_prompt_q(
                cache['k'], cache['k_scale'], pk, page_ids, src_off)
            qv, sv = paged_cache.PagePool.insert_prompt_q(
                cache['v'], cache['v_scale'], pv, page_ids, src_off)
            new_cache = {
                'k': qk, 'v': qv, 'k_scale': sk, 'v_scale': sv,
                'tables': cache['tables'].at[slot].set(table_row),
            }
        else:
            new_cache = {
                'k': paged_cache.PagePool.insert_prompt(
                    cache['k'], pk, page_ids, src_off),
                'v': paged_cache.PagePool.insert_prompt(
                    cache['v'], pv, page_ids, src_off),
                'tables': cache['tables'].at[slot].set(table_row),
            }
        return new_cache, _update_args(
            args, slot, first_tok, length, temp, key, topk, topp,
            pres, freq, bidx, bval)

    def _insert_pages_impl(self, cache, prefill_cache, page_ids,
                           src_off):
        """Chunked prefill: write one chunk's pages into the pool
        WITHOUT installing the slot's table row or decode args — the
        slot only becomes decodable at the final chunk's full insert."""
        from skypilot_tpu.infer import paged_cache
        if 'k_scale' in cache:   # int8 pool: quantize at the scatter
            qk, sk = paged_cache.PagePool.insert_prompt_q(
                cache['k'], cache['k_scale'], prefill_cache['k'],
                page_ids, src_off)
            qv, sv = paged_cache.PagePool.insert_prompt_q(
                cache['v'], cache['v_scale'], prefill_cache['v'],
                page_ids, src_off)
            new_cache = {'k': qk, 'v': qv, 'k_scale': sk,
                         'v_scale': sv, 'tables': cache['tables']}
        else:
            new_cache = {
                'k': paged_cache.PagePool.insert_prompt(
                    cache['k'], prefill_cache['k'], page_ids, src_off),
                'v': paged_cache.PagePool.insert_prompt(
                    cache['v'], prefill_cache['v'], page_ids, src_off),
                'tables': cache['tables'],
            }
        return new_cache

    def _clear_slot_impl(self, cache, slot):
        """Neutralize a released slot's block-table row (point it at the
        dummy page) so its dummy decode writes can never land in pages a
        later admission re-reserves."""
        return {**cache,
                'tables': cache['tables'].at[slot].set(
                    jnp.zeros_like(cache['tables'][slot]))}

    # ------------------------------------------- tiered prefix cache
    # (infer/kv_tier.py; docs/performance.md "Tiered prefix cache").
    # All methods below are loop-thread-only except kv_export_encoded
    # (server executor threads) and the kv_tier worker internals.

    def _kv_pool_keys(self) -> List[str]:
        return ['k', 'v', 'k_scale', 'v_scale'] if self.kv_quantized \
            else ['k', 'v']

    def _kv_slice_page(self, page: int) -> Dict[str, Any]:
        """Eager per-pool slices of one page ([L, H, P(, d)], pool
        dtype). The slices are fresh device buffers whose fill is
        dispatched NOW — before any later insert overwrites the page —
        so device-stream ordering guarantees they capture the
        pre-overwrite contents even though nothing blocks here."""
        return {name: self.cache[name][:, page]
                for name in self._kv_pool_keys()}

    def _kv_spill(self, page: int, h: bytes) -> None:
        """PagePool.on_evict hook: snapshot the page being reclaimed
        and hand it to the tier writer thread (which pays the
        device->host pull). Never raises into pool accounting."""
        try:
            self.kv_tier.enqueue_spill(h, self.weight_version,
                                       self._kv_slice_page(page))
        except Exception:  # pylint: disable=broad-except
            logger.exception('kv tier spill enqueue failed')

    def _kv_install_impl(self, cache, page_ids, arrays):
        """Scatter promoted page contents ([L, n, H, P(, d)], pool
        dtype) into the pool at `page_ids` ([n] int32). A pure byte
        copy — no re-quantization — so a promoted page is bit-equal to
        the page that spilled. Duplicate ids (pow2 padding repeats the
        last page) scatter identical data, so the result is
        deterministic."""
        new_cache = dict(cache)
        for name, a in arrays.items():
            new_cache[name] = cache[name].at[:, page_ids].set(a)
        return new_cache

    def _kv_install(self, pages: List[int],
                    datas: List[Dict[str, Any]]) -> None:
        """Write host-resident page contents into the pool pages
        install_prefix just allocated. Chunks of <= 8, padded to pow2
        by repeating the last (id, data) pair, bound the compile count
        at 4 shapes per pool layout."""
        i = 0
        while i < len(pages):
            n = min(8, len(pages) - i)
            chunk_ids = list(pages[i:i + n])
            chunk_datas = list(datas[i:i + n])
            m = 1
            while m < n:
                m *= 2
            while len(chunk_ids) < m:
                chunk_ids.append(chunk_ids[-1])
                chunk_datas.append(chunk_datas[-1])
            ids = jnp.asarray(np.asarray(chunk_ids, np.int32))
            arrays = {name: np.stack([d[name] for d in chunk_datas],
                                     axis=1)
                      for name in self._kv_pool_keys()}
            self.cache = self._jit_kv_install(self.cache, ids, arrays)
            i += n

    def _kv_try_promote(self, req: '_Request') -> int:
        """L2 splice: if the HBM registry run for `req` stops short but
        the host store holds the continuation at the current weight
        version, install those pages (refcount 0, warm LRU) and write
        their contents — the try_reserve_prefix that follows then
        shares them exactly as if they had never been evicted. Returns
        pages promoted."""
        if self.kv_tier is None or not req.page_hashes:
            return 0
        psize = self.pool.cfg.page_size
        lookup = req.page_hashes[:(len(req.tokens) - 1) // psize]
        have = self.pool.prefix_peek(lookup)
        if have >= len(lookup):
            return 0
        run = self.kv_tier.host.run(lookup[have:], self.weight_version)
        # Belt-and-suspenders before install_prefix registers anything:
        # a page that does not match the pool layout (should be
        # unreachable — spills come from this pool and fetches are
        # validated on ingest) truncates the run at the first offender,
        # which is also purged so it cannot re-trip every admission.
        for i, (h, arrays) in enumerate(run):
            bad = self.kv_tier.validate_page(arrays)
            if bad is not None:
                logger.warning('kv host page %s rejected: %s',
                               h.hex(), bad)
                self.kv_tier.host.discard(h)
                run = run[:i]
                break
        if not run:
            return 0
        pages = self.pool.install_prefix([h for h, _ in run])
        if pages is None:   # free list can't cover it: recompute
            return 0
        self._kv_install(pages, [arrays for _, arrays in run])
        self.kv_tier.note_promotion(len(pages))
        return len(pages)

    def _kv_missing_run(self, req: '_Request') -> List[bytes]:
        """Full-page hashes of `req` covered by neither the HBM
        registry nor the host store — what a fleet fetch would ask the
        peer for."""
        psize = self.pool.cfg.page_size
        lookup = req.page_hashes[:(len(req.tokens) - 1) // psize]
        have = self.pool.prefix_peek(lookup)
        missing = lookup[have:]
        while missing and self.kv_tier.host.contains(
                missing[0], self.weight_version):
            missing = missing[1:]
        return list(missing)

    def _kv_admission_break(self, req: '_Request', n: int,
                            psize: int) -> bool:
        """Batched-admission peek helper: True when the tier could
        serve this request's prefix without recompute, so it should
        leave the batched path for the sequential one (where the host
        splice / fleet fetch happens). Called only after the HBM peek
        missed, so this covers peek==0 cases: host-resident head, or a
        fetchable peer hint."""
        if self.kv_tier is None:
            return False
        lookup = req.page_hashes[:(n - 1) // psize]
        if not lookup:
            return False
        if self.kv_tier.host.contains(lookup[0], self.weight_version):
            return True
        return self.kv_tier.fleet and bool(req.kv_peer) and \
            req.kv_fetch is None and self._kv_fetching is None

    def _kv_start_fetch(self, req: '_Request') -> bool:
        """L3: park `req` and fetch its missing prefix run from the
        peer the LB designated (X-KV-Peer) into the host store; the
        re-admission then promotes through the L2 splice. At most one
        fetch in flight; every failure mode (fault injection, HTTP
        error, timeout, version mismatch) degrades to recompute.
        Returns True if the request was parked."""
        tier = self.kv_tier
        missing = self._kv_missing_run(req)
        if not missing:
            return False
        req.kv_fetch = {
            'state': 'pending',
            # The loop abandons the wait past this even if the worker
            # is hung inside a kv.fetch=hang injection; an abandoned
            # worker's late host.put is version-gated and harmless.
            'deadline': time.monotonic() + 1.5 * tier.fetch_timeout_s,
        }
        self._kv_fetching = req
        st = req.kv_fetch
        peer, version = req.kv_peer, self.weight_version
        token = env.get('SKYT_ADMIN_TOKEN') or ''
        def _worker():
            try:
                tier.fetch_into_host(peer, missing, version, token)
                st['state'] = 'done'
            except Exception as e:  # pylint: disable=broad-except
                tier.note_fetch_error()
                logger.info('kv fetch from %s failed: %s', peer, e)
                st['state'] = 'failed'
        threading.Thread(target=_worker, daemon=True,
                         name='kv-fetch').start()
        return True

    def _kv_tick(self) -> None:
        """Per-tick tier work on the loop thread: re-admit a parked
        fetch once its worker finished (or its deadline/cancel fired),
        and serve parked /kv/prefix exports."""
        req = self._kv_fetching
        if req is not None:
            st = req.kv_fetch
            if st['state'] != 'pending' or req.cancelled or \
                    time.monotonic() > st['deadline']:
                self._kv_fetching = None
                # Back into admission: promotion picks up whatever the
                # fetch landed; a failed fetch recomputes; a cancelled
                # request takes _admit_one's terminal-None path.
                if self._deferred is None:
                    self._deferred = req
                else:
                    # Head re-queue (the pool-full path's _deferred
                    # discipline): the request already waited out the
                    # fetch — a tail put would additionally forfeit its
                    # FIFO/QoS position to everything that arrived
                    # meanwhile. Direct deque access under the queue
                    # mutex is the sanctioned requeue pattern (see
                    # _reserve_admission_batch); this is an
                    # ALREADY-ADMITTED request whose class was assigned
                    # at submit; no bypass.
                    with self._waiting.mutex:
                        self._waiting.queue.appendleft(req)
        if self._kv_export_q:
            self._kv_drain_exports()

    def _kv_drain_exports(self) -> None:
        """Resolve parked /kv/prefix export requests: walk the leading
        registered run, take eager page slices (lazy — the requester's
        thread pays the device->host pull), stamp the weight version,
        wake the requester."""
        while self._kv_export_q:
            rq = self._kv_export_q.popleft()
            try:
                if rq.get('index'):
                    # Inventory request (/kv/index): the registry read
                    # rides the loop like every other export, so the
                    # snapshot is tick-consistent.
                    rq['hashes_out'] = self.pool.registered_hashes()
                    rq['pages'] = []
                else:
                    out = []
                    for h in rq['hashes']:
                        page = self.pool.registered_page(h)
                        if page is None:
                            break
                        out.append((h, self._kv_slice_page(page)))
                    rq['pages'] = out
            except Exception:  # pylint: disable=broad-except
                logger.exception('kv export slice failed')
                rq['pages'] = []
            rq['version'] = self.weight_version
            rq['event'].set()

    def kv_export_encoded(self, hashes: List[bytes],
                          max_pages: Optional[int] = None
                          ) -> Optional[bytes]:
        """Server-side of GET /kv/prefix (executor thread): the leading
        locally-resident run of `hashes` — HBM registry first, host
        store continuation — encoded for transfer, or None when
        nothing is resident (the server answers 404, never 5xx)."""
        if self.kv_tier is None or self.pool is None:
            return None
        cap = max_pages if max_pages is not None \
            else self.kv_tier.fetch_max_pages
        hashes = list(hashes)[:max(0, cap)]
        if not hashes:
            return None
        rq = {'hashes': hashes, 'pages': None, 'version': None,
              'event': threading.Event()}
        self._kv_export_q.append(rq)
        if not rq['event'].wait(timeout=5.0):
            return None   # loop gone/stuck: miss, not an error
        version = rq['version']
        out = [(h, {k: np.asarray(v) for k, v in arrays.items()})
               for h, arrays in (rq['pages'] or [])]
        if len(out) < len(hashes):
            out.extend(self.kv_tier.host.run(hashes[len(out):],
                                             version))
        if not out:
            return None
        return kv_tier_lib.encode_pages(out, version)

    def kv_index(self) -> Optional[Dict[str, Any]]:
        """Server-side of GET /kv/index (executor thread): every
        locally resident published prefix hash — HBM registry in
        publish order, then host-store-only continuations — plus the
        serving weight version. None when the tier is off or the loop
        never answers (the server 404s, never 5xx)."""
        if self.kv_tier is None or self.pool is None:
            return None
        rq: Dict[str, Any] = {'index': True, 'hashes_out': None,
                              'pages': None, 'version': None,
                              'event': threading.Event()}
        self._kv_export_q.append(rq)
        if not rq['event'].wait(timeout=5.0):
            return None
        hashes: List[bytes] = list(rq['hashes_out'] or [])
        seen = set(hashes)
        version = int(rq['version'])
        hashes.extend(h for h in self.kv_tier.host.keys(version)
                      if h not in seen)
        return {'weight_version': version,
                'hashes': [h.hex() for h in hashes]}

    def kv_prewarm(self, self_node: str, peers: List[str],
                   token: str) -> Dict[str, Any]:
        """Bulk-fetch the prefix pages this replica will own from its
        peers (POST /admin/kv_prewarm, executor thread) — the scale-up
        prewarm of ROADMAP 5c. Pages land in the host store and
        promote on first demand through the normal L2 splice; counted
        under skyt_infer_kv_tier_hit_pages_total{tier="prewarm"}."""
        if self.kv_tier is None or not self.kv_tier.fleet:
            return {'peers': 0, 'owned_pages': 0, 'stored_pages': 0,
                    'errors': 0, 'skipped': 'kv tier is not fleet'}
        return self.kv_tier.prewarm_from_peers(
            self_node, peers, self.weight_version, token)

    def _decode_n_impl(self, params, cache, last_tokens, lengths, temps,
                       keys, topks, topps, press, freqs, counts, hist,
                       bias_idx, bias_val, n, sampling, penalize,
                       biased=False):
        """Generate `n` tokens per slot in ONE dispatch: a device-side
        lax.scan of decode steps with on-device sampling (greedy when
        temps[i] == 0, else temperature categorical). The host pulls one
        [n, SLOTS] token batch per round trip — decode stays
        compute-bound even when dispatch/transfer latency is tens of ms.

        `sampling` is static: the greedy-only variant compiles without
        the top-k sort / categorical / rng-split ops — top_k over a 128k
        vocab costs several ms/step on TPU, pure overhead when every
        active request is greedy (the common serving case).
        Returns (tokens [n, SLOTS], new_cache, new_keys)."""

        n_slots = self.num_slots

        def write_hist(hist, lens, tok):
            # Keep the spec proposer's invariant (hist[b, lens[b]] ==
            # last token) intact across plain-path chunks.
            if hist is None:
                return None
            return hist.at[jnp.arange(n_slots), lens + 1].set(tok)

        def raw_lp(logits, tok):
            # Chosen-token RAW model logprob (one logsumexp over V —
            # noise next to the weight streaming each step costs).
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            return jnp.take_along_axis(logits, tok[:, None],
                                       axis=-1)[:, 0] - lse

        n_range = jnp.arange(n_slots)

        def step(carry, _):
            cache, last, lens, keys, counts, hist = carry
            logits, cache = self.model.apply(params, last[:, None],
                                             positions=lens[:, None],
                                             cache=cache)
            logits = logits[:, 0, :].astype(jnp.float32)
            lp_src = logits          # logprobs report RAW model values
            if penalize:
                # vLLM-convention repetition penalties over OUTPUT
                # token counts, on raw logits before temp/top-k/top-p
                # (greedy included: they change the argmax too).
                logits = logits \
                    - freqs[:, None] * counts.astype(jnp.float32) \
                    - press[:, None] * (counts > 0).astype(jnp.float32)
            if biased:
                # OpenAI logit_bias: scatter-add each slot's (idx, val)
                # pairs; zero padding adds 0.0 to token 0 (no-op).
                logits = logits.at[
                    n_range[:, None], bias_idx].add(bias_val)
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if not sampling:
                tok = greedy
            else:
                keys = jax.vmap(jax.random.split, in_axes=0,
                                out_axes=0)(keys)[:, 0]
                # One top-k/top-p filter serves the plain AND spec
                # sampling paths — their target distributions must stay
                # identical. Filter AFTER temperature scaling (nucleus
                # membership depends on the scaled distribution).
                scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
                filtered = _sampling_filter(scaled, topks, topps)
                sampled = jax.vmap(jax.random.categorical)(keys,
                                                           filtered)
                tok = jnp.where(temps > 0, sampled.astype(jnp.int32),
                                greedy)
            if penalize:
                counts = counts.at[n_range, tok].add(1)
            return (cache, tok, lens + 1, keys, counts,
                    write_hist(hist, lens, tok)), \
                (tok, raw_lp(lp_src, tok))

        (cache, last, lens, keys, counts, hist), (toks, lps) = \
            jax.lax.scan(
                step, (cache, last_tokens, lengths, keys, counts,
                       hist), None, length=n)
        # last/lens returned device-resident so the next chunk's call
        # needs no host->device transfers in the steady state.
        return toks, lps, cache, keys, last, lens, counts, hist

    def _hist_insert_impl(self, hist, slot, tokens, length, first_tok):
        """Install an admitted prompt (+ its first generated token) into
        the slot's device token history. Invariant the spec decoder
        relies on: hist[slot, lens[slot]] == last token fed."""
        hist = jax.lax.dynamic_update_slice(hist, tokens, (slot, 0))
        return hist.at[slot, length].set(first_tok)

    def _decode_spec_impl(self, params, cache, last_tokens, lengths,
                          temps, keys, topks, topps, hist, n, k,
                          sampling):
        """`n` speculative decode iterations in ONE dispatch. Each
        iteration: propose k draft tokens per slot by matching the
        history's trailing bigram against its own past (prompt-lookup
        decoding), run a single s=k+1 forward, accept a draft prefix,
        and emit accepted+1 tokens.

        Greedy slots (temp == 0): accept the longest prefix agreeing
        with the model's argmax — token-identical to the plain greedy
        path (tested). Sampled slots (`sampling` static, like
        _decode_n_impl's): rejection sampling against a point-mass
        draft — accept draft d_i with probability p_i(d_i) under the
        temperature/top-k-filtered target distribution, and on the
        first rejection draw from the residual (p with d_i excluded,
        renormalized), which preserves the exact sequential sampling
        distribution (speculative sampling, tested distributionally via
        speculative_sample_step). Returns (toks [n, SLOTS, k+1],
        counts [n, SLOTS] valid-token counts, ...)."""
        s_hist = hist.shape[1]

        def propose(h, length):
            # Most recent i where (h[i], h[i+1]) equals the trailing
            # bigram (h[L-1], h[L]); draft = the k tokens after it. No
            # match -> a junk draft that verification will reject.
            b0 = h[jnp.clip(length - 1, 0, s_hist - 1)]
            b1 = h[jnp.clip(length, 0, s_hist - 1)]
            idx = jnp.arange(s_hist - 1)
            ok = (h[:-1] == b0) & (h[1:] == b1) & (idx + 1 < length)
            i = jnp.where(ok.any(), jnp.where(ok, idx, -1).max(),
                          length - 1)
            return jax.lax.dynamic_slice(
                h, (jnp.clip(i + 2, 0, s_hist - k),), (k,))

        def step(carry, _):
            cache, last, lens, keys, hist = carry
            draft = jax.vmap(propose)(hist, lens)        # [SLOTS, k]
            toks_in = jnp.concatenate([last[:, None], draft], axis=1)
            positions = lens[:, None] + jnp.arange(k + 1)[None, :]
            logits, cache = self.model.apply(
                params, toks_in, positions=positions, cache=cache)
            out, lps, acc, new_last, step_keys = self._spec_verify_emit(
                logits, draft, temps, keys, topks, topps, sampling, k)
            # Write all k+1 emitted candidates; entries past acc+1 are
            # junk the proposer never reads (its window stops at lens).
            hist = jax.vmap(
                lambda h, row, i: jax.lax.dynamic_update_slice(
                    h, row, (i,)))(hist, out, lens + 1)
            return (cache, new_last, lens + acc + 1, step_keys, hist), \
                (out, lps, acc + 1)

        (cache, last, lens, keys, hist), (toks, lps, counts) = \
            jax.lax.scan(
                step, (cache, last_tokens, lengths, keys, hist), None,
                length=n)
        return toks, lps, counts, cache, last, lens, keys, hist

    def _spec_verify_emit(self, logits, draft, temps, keys, topks,
                          topps, sampling, k):
        """Shared verify half of every speculative step (n-gram AND
        draft-model proposers): accept a draft prefix against the
        target's logits, emit accepted+1 tokens and their RAW logprobs.

        Greedy slots (temp == 0): accept the longest prefix agreeing
        with the model's argmax — token-identical to the plain greedy
        path (tested). Sampled slots (`sampling` static): rejection
        sampling against a point-mass draft — accept draft d_i with
        probability p_i(d_i) under the filtered target distribution,
        first rejection draws from the residual — which preserves the
        exact sequential sampling distribution regardless of WHERE the
        draft came from (any deterministic proposer is a point mass).
        """
        logits = logits.astype(jnp.float32)              # [SLOTS, k+1, V]
        if sampling:
            # Advance each slot's key; this step draws from the
            # sibling so re-runs never reuse a consumed stream.
            ks2 = jax.vmap(jax.random.split)(keys)
            step_keys, draw_keys = ks2[:, 0], ks2[:, 1]
            out, acc = speculative_sample_step(
                logits, draft, temps, topks, topps, draw_keys)
        else:
            # Greedy-only compile: no softmax/top-k/categorical ops.
            step_keys = keys
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            match = (draft == g[:, :k]).astype(jnp.int32)
            acc = jnp.cumprod(match, axis=1).sum(axis=1)  # 0..k
            out = g
        new_last = jnp.take_along_axis(out, acc[:, None], axis=1)[:, 0]
        # RAW model logprobs of the emitted row (OpenAI/vLLM
        # convention: pre-filter log-softmax).
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        lps = jnp.take_along_axis(logits, out[:, :, None],
                                  axis=-1)[:, :, 0] - lse
        return out, lps, acc, new_last, step_keys

    def _decode_spec_draft_impl(self, params, draft_params, cache,
                                dcache, last_tokens, lengths, temps,
                                keys, topks, topps, n, k, sampling):
        """`n` DRAFT-MODEL speculative iterations in one dispatch: k
        greedy single-token rollouts of the small draft model (inner
        scan over its own dense cache), then the target's s=k+1 verify
        forward and the shared accept/emit step.

        Draft-cache invariant (mirrors the target's): entries below
        lens are settled; the token AT lens is fed — and its KV
        written — by the next step that runs, so rejected-draft junk
        above lens is always overwritten before it is attended from a
        masked-in position. A draft entry made stale by a plain-path
        interlude (penalized slots force whole chunks down
        _decode_n_impl) only lowers acceptance; the verify gate keeps
        outputs exactly equal to the plain path's either way."""
        def draft_step(carry, _):
            dc, tok, pos = carry
            dlogits, dc = self.draft_model.apply(
                draft_params, tok[:, None], positions=pos[:, None],
                cache=dc)
            nxt = jnp.argmax(dlogits[:, 0].astype(jnp.float32),
                             axis=-1).astype(jnp.int32)
            return (dc, nxt, pos + 1), nxt

        def step(carry, _):
            cache, dcache, last, lens, keys = carry
            # k+1 rollout steps, not k: the final step's logits are
            # discarded but its KV WRITE matters — it feeds d_k at
            # position lens+k, matching the k+1 positions the target's
            # verify forward writes. Without it the draft cache has a
            # hole at lens+k whenever all k drafts are accepted, and
            # every later rollout attends junk there (measured: ~20%
            # acceptance on a self-draft that should be ~100%).
            (dcache, _, _), drafts = jax.lax.scan(
                draft_step, (dcache, last, lens), None, length=k + 1)
            draft = jnp.moveaxis(drafts, 0, 1)[:, :k]    # [SLOTS, k]
            toks_in = jnp.concatenate([last[:, None], draft], axis=1)
            positions = lens[:, None] + jnp.arange(k + 1)[None, :]
            logits, cache = self.model.apply(
                params, toks_in, positions=positions, cache=cache)
            out, lps, acc, new_last, step_keys = self._spec_verify_emit(
                logits, draft, temps, keys, topks, topps, sampling, k)
            return (cache, dcache, new_last, lens + acc + 1,
                    step_keys), (out, lps, acc + 1)

        (cache, dcache, last, lens, keys), (toks, lps, counts) = \
            jax.lax.scan(
                step, (cache, dcache, last_tokens, lengths, keys),
                None, length=n)
        return toks, lps, counts, cache, dcache, last, lens, keys

    def _draft_prefill_impl(self, draft_params, dcache, tokens, slot,
                            bucket):
        """Admission tail for the draft cache: run the prompt through
        the draft model (one logit position — the lm_head output is
        discarded) and copy its B=1 cache into `slot`. Junk KV from
        bucket padding lands above the slot's length, where the
        feed-at-lens invariant overwrites it before use — the same
        contract as the target's padded prefill."""
        del bucket
        dcfg = self.draft_model.cfg
        b, s = tokens.shape
        positions = jnp.arange(s)[None, :].repeat(b, 0)
        shape = (dcfg.n_layers, b, s, dcfg.n_kv_heads, dcfg.head_dim)
        dtype = jnp.dtype(dcfg.dtype)
        c1 = {'k': jnp.zeros(shape, dtype),
              'v': jnp.zeros(shape, dtype)}
        _, c1 = self.draft_model.apply(
            draft_params, tokens, positions=positions, cache=c1,
            logit_positions=jnp.zeros((b, 1), jnp.int32))
        s_tgt = self.max_seq_len

        def fit(x):
            if x.shape[2] > s_tgt:
                return x[:, :, :s_tgt]
            if x.shape[2] < s_tgt:
                return jnp.pad(x, ((0, 0), (0, 0),
                                   (0, s_tgt - x.shape[2]),
                                   (0, 0), (0, 0)))
            return x

        c1 = jax.tree.map(fit, c1)
        return jax.tree.map(
            lambda big, small: jax.lax.dynamic_update_slice(
                big, small, (0, slot, 0, 0, 0)), dcache, c1)

    # ----------------------------------------------------------- sampling
    def _sample(self, logits: np.ndarray, req: _Request) -> int:
        """Host-side sampling for a request's FIRST token (prefill pulls
        one logits row); same temperature -> top-k -> top-p filter order
        as the device path."""
        p = req.params
        if p.temperature <= 0.0:
            return int(np.argmax(logits))
        logits = logits.astype(np.float64) / p.temperature
        if p.top_k > 0:
            kth = np.partition(logits, -p.top_k)[-p.top_k]
            logits = np.where(logits < kth, -np.inf, logits)
        if 0.0 < p.top_p < 1.0:
            order = np.argsort(-logits)
            s = logits[order]
            sp = np.exp(s - s.max())
            sp /= sp.sum()
            before = np.cumsum(sp) - sp   # exclusive: top-1 survives
            cut = order[before >= p.top_p]
            logits[cut] = -np.inf
        logits -= logits.max()
        probs = np.exp(logits)
        probs /= probs.sum()
        return int(req.rng.choice(len(probs), p=probs))

    # ------------------------------------------------------------- public
    def submit(self, tokens: List[int],
               params: Optional[SamplingParams] = None,
               kv_peer: Optional[str] = None
               ) -> 'tuple[int, queue.Queue]':
        """Enqueue a request; returns (req_id, token queue). The queue
        yields generated token ids, then None when finished.

        kv_peer: peer replica base URL the LB's rendezvous ring
        designates as this prefix's owner (X-KV-Peer). Only consulted
        under SKYT_KV_TIER=fleet on a local prefix miss; ignored
        otherwise."""
        params = params or SamplingParams()
        params.validate()
        if params.lora_id >= max(1, self.num_adapters):
            raise ValueError(
                f'lora_id {params.lora_id} out of range: engine has '
                f'{max(0, self.num_adapters - 1)} adapter(s) loaded')
        if params.logit_bias:
            bad = [t for t in params.logit_bias
                   if t >= self.cfg.vocab_size]
            if bad:
                raise ValueError(
                    f'logit_bias token ids out of vocab '
                    f'(V={self.cfg.vocab_size}): {bad[:5]}')
        if len(tokens) >= self.max_seq_len:
            raise ValueError(f'prompt length {len(tokens)} >= max_seq_len '
                             f'{self.max_seq_len}')
        if self._thread is not None and not self._thread.is_alive() and \
                not self._stop.is_set():
            raise RuntimeError(
                'engine loop is dead (crashed); refusing new requests')
        with self._lock:
            req_id = self._next_id
            self._next_id += 1
        req = _Request(req_id=req_id, tokens=list(tokens), params=params,
                       out_queue=queue.Queue(),
                       rng=np.random.default_rng(params.seed + req_id))
        if kv_peer and self.kv_tier is not None and self.kv_tier.fleet:
            req.kv_peer = kv_peer
        self._m_requests.inc()
        self._trace_event(req_id, 'queued', ts=req.submitted_at,
                          prompt_tokens=len(tokens), status='waiting')
        if self._lockstep is not None:
            if not self._lockstep.is_primary:
                raise RuntimeError(
                    'submit() on a follower host: multi-host requests '
                    'enter through the primary (process 0)')
            # Tick sync broadcasts the request, THEN admits it locally,
            # so followers always see the identical admission stream.
            self._ingress.put(req)
        else:
            self._waiting.put(req)   # qos-admission (lint-sanctioned)
        return req_id, req.out_queue

    def cancel(self, req_id: int) -> bool:
        """Cancel a submitted request (any thread). A running slot is
        released at the next delivery boundary (its queue then yields
        None); a waiting request is dropped at admission. Returns True
        if a live request with req_id was found.

        Multi-host: the flag must flip on every host at the SAME tick
        (slot release changes the next tick's batch), so the cancel is
        queued here and applied by the tick sync on all hosts."""
        if self._lockstep is not None:
            if not self._lockstep.is_primary:
                raise RuntimeError('cancel() on a follower host')
            found = self._find_live(req_id) or any(
                r.req_id == req_id for r in self._drain_peek())
            with self._lock:
                self._pending_cancels.append(req_id)
            return found
        return self._apply_cancel(req_id)

    def _find_live(self, req_id: int) -> bool:
        if any(r is not None and r.req_id == req_id
               for r in self._slots):
            return True
        return any(d is not None and d.req_id == req_id
                   for d in (self._deferred, self._admitting,
                             self._kv_fetching,
                             *self._admitting_many))

    def _drain_peek(self) -> List['_Request']:
        with self._ingress.mutex:
            pending = list(self._ingress.queue)
        with self._waiting.mutex:
            return pending + list(self._waiting.queue)

    def _apply_cancel(self, req_id: int) -> bool:
        found = False
        for req in list(self._slots):
            if req is not None and req.req_id == req_id:
                req.cancelled = True
                found = True
        for d in (self._deferred, self._admitting, self._kv_fetching,
                  *self._admitting_many):
            if d is not None and d.req_id == req_id:
                d.cancelled = True
                found = True
        with self._waiting.mutex:
            for req in self._waiting.queue:
                if req.req_id == req_id:
                    req.cancelled = True
                    found = True
        return found

    def _expire_deadlines(self) -> None:
        """Deadline enforcement point, run by the engine loop each
        tick: a request past params.deadline is cancelled in place, so
        a running slot (and its KV pages) frees at the next delivery
        boundary and a waiting request never occupies a slot at all.
        Slots are scanned every tick (O(num_slots)); the waiting queue
        — O(backlog) under its mutex — is throttled to ~4Hz.

        Multi-host: expiry changes the next tick's batch, so it must
        land on every host at the SAME tick — the primary routes it
        through the cancel broadcast instead of flipping flags
        locally."""
        now = time.time()
        expired: List['_Request'] = []
        # Guard on req.expired as well as req.cancelled: in lockstep
        # mode the cancel only lands via the NEXT tick's broadcast, so
        # without it an already-flagged request would re-match (and
        # re-count) every tick until then.
        for req in (*self._slots, self._deferred, self._admitting,
                    self._kv_fetching, *self._admitting_many):
            if req is not None and not req.cancelled and \
                    not req.expired and \
                    req.params.deadline is not None and \
                    now > req.params.deadline:
                expired.append(req)
        if now - self._last_deadline_scan >= 0.25:
            self._last_deadline_scan = now
            with self._waiting.mutex:
                for req in self._waiting.queue:
                    if not req.cancelled and not req.expired and \
                            req.params.deadline is not None and \
                            now > req.params.deadline:
                        expired.append(req)
        for req in expired:
            req.expired = True
            self._m_deadline_expired.inc()
            if self._lockstep is not None:
                if self._lockstep.is_primary:
                    with self._lock:
                        self._pending_cancels.append(req.req_id)
            else:
                req.cancelled = True

    def generate(self, tokens: List[int],
                 params: Optional[SamplingParams] = None) -> List[Any]:
        """Blocking convenience: submit + drain. Items mirror the queue
        protocol: ints, or (token, logprob) pairs when
        params.logprobs is set."""
        _, q = self.submit(tokens, params)
        out = []
        while True:
            tok = q.get()
            if tok is None:
                return out
            out.append(tok)

    def start(self) -> None:
        self._stop.clear()    # restartable: start after stop works
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self.kv_tier is not None:
            self.kv_tier.stop()
        if self._thread:
            # Lockstep: the loop exits at the next tick broadcast (the
            # stop flag must reach followers), which can be mid-compile
            # on first use — allow for that.
            timeout = 60 if self._lockstep is not None else 10
            self._thread.join(timeout=timeout)

    def join(self, timeout: Optional[float] = None) -> None:
        """Block until the engine loop exits. Follower hosts of a
        multi-host replica have no HTTP server or client; their main
        thread parks here until the primary's stop broadcast."""
        if self._thread is not None:
            self._thread.join(timeout=timeout)

    def warmup(self, buckets: Optional[List[int]] = None) -> None:
        """Pre-compile prefill (per bucket), cache insert, and the greedy
        decode chunk by running real dummy requests through the engine —
        so the first user request after /health goes green pays no
        compile (TTFT SLO). Call before or after start(); runs the loop
        inline when the engine thread isn't up yet.

        Multi-host followers no-op: the primary's warmup requests reach
        them through the tick broadcast, and the resulting (identical)
        device calls compile there too. The primary must be start()ed
        first — the not-started path's inline-loop cleanup would stop()
        the engine, and in lockstep that broadcast permanently releases
        every follower (they exit; a later start() would hang its first
        collective waiting for processes that are gone)."""
        if self._lockstep is not None and not self._lockstep.is_primary:
            return
        started = self._thread is not None and self._thread.is_alive()
        if self._lockstep is not None and not started:
            raise RuntimeError('multi-host warmup requires start() '
                               'first (see docstring)')
        if not started:
            self.start()
        try:
            last_warm = None
            for bi, b in enumerate(buckets or self.prefill_buckets):
                if b >= self.max_seq_len:
                    continue
                n_new = min(self.decode_chunk,
                            self.max_seq_len - 1 - b)
                if n_new < 1:
                    continue
                # Distinct token per bucket: with prefix caching on, a
                # shared token would route later buckets through the
                # suffix path and leave their FULL prefill uncompiled.
                last_warm = ([bi + 2] * b, n_new)
                self.generate(last_warm[0],
                              SamplingParams(max_new_tokens=n_new))
            if self.prefix_caching and last_warm is not None:
                # Re-run the largest warmed prompt to compile the
                # prefix-cached suffix-prefill path.
                self.generate(last_warm[0],
                              SamplingParams(max_new_tokens=last_warm[1]))
            if self.spec_decode > 0:
                # Near max_seq_len the loop falls back to the plain
                # greedy path with small pow2 chunks — pre-trace those
                # here or the first long request pays the compile
                # mid-serving. Distinct token per prompt: no prefix
                # sharing with the warms above.
                c = 1
                while c <= self.spec_decode:
                    n_prompt = self.max_seq_len - 1 - c
                    if n_prompt >= 1:
                        self.generate([50 + c] * n_prompt,
                                      SamplingParams(max_new_tokens=c))
                    c *= 2
        finally:
            if not started:
                self.stop()
                self._stop.clear()
                self._thread = None

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            active = sum(1 for s in self._slots if s is not None)
        waiting = self._waiting.qsize() + (1 if self._deferred is not None
                                           else 0)
        # Which kernel rung each op compiled to (tuned/conservative
        # Pallas or the XLA floor) — silent kernel degradation must be
        # visible wherever operators already look (docs/kernels.md).
        from skypilot_tpu.ops import dispatch as ops_dispatch
        out = {'active_slots': active, 'num_slots': self.num_slots,
               'waiting': waiting,
               'ready': self.ready.is_set(),
               'weight_version': self.weight_version,
               'virtual_nodes': self.virtual_nodes,
               'kernel_paths': ops_dispatch.snapshot(),
               # What the replica runs on, as JAX reports it, and what
               # the compile cache did (chip_smoke.py reads both).
               'device': ops_dispatch.device_info(),
               'compile_cache': compile_cache.snapshot(),
               **self.perf_stats()}
        if self.ledger.enabled:
            out['capacity_ledger'] = self.ledger.snapshot()
        if self._tickstats is not None:
            out['tickstats'] = self._tickstats.summary()
        return out

    @property
    def tickstats(self):
        """The tick-plane recorder (infer/tickstats.py), or None when
        SKYT_TICKSTATS=0 — the server's /debug/ticks handler and the
        flight-recorder snapshot read through this."""
        return self._tickstats

    def perf_stats(self) -> Dict[str, float]:
        """Decode counters; steady_decode_tok_per_sec is the pipelined
        decode rate over pull-to-pull intervals with no admission (i.e.
        prefill excluded) — the serving throughput number."""
        p: Dict[str, float] = dict(self.perf)
        p['steady_decode_tok_per_sec'] = (
            p['steady_tokens'] / p['steady_time_s']
            if p['steady_time_s'] > 0 else 0.0)
        if self.spec_decode > 0:
            # Mean accepted drafts per verify step (tokens/step - 1).
            p['spec_accept_per_step'] = (
                p['spec_accepted'] / p['spec_verify_steps']
                if p['spec_verify_steps'] > 0 else 0.0)
        if self.prefix_caching and self.pool is not None:
            p['prefix_cache'] = dict(self.pool.prefix_stats)
            # Occupancy (cached pages / pool pages): synced through
            # controller -> LB as skyt_lb_replica_prefix_cache — the
            # affinity-routing signal (docs/serving.md, ROADMAP #2).
            total = self.pool.cfg.n_pages - 1   # page 0 is the dummy
            cached = self.pool.prefix_cached_pages()
            p['prefix_cache']['cached_pages'] = cached
            if total > 0:
                p['prefix_cache']['occupancy'] = round(cached / total, 4)
        if self.kv_tier is not None:
            p['kv_tier'] = self.kv_tier.snapshot()
        # Snapshot under the lock: the engine thread appends
        # concurrently, and iterating a mutating deque raises
        # RuntimeError (ADVICE r5) — a /stats request must never 500.
        with self._lock:
            ttfts = tuple(self._ttfts)
        if ttfts:
            arr = np.asarray(ttfts) * 1000.0
            p['ttft_ms'] = {
                'p50': round(float(np.percentile(arr, 50)), 2),
                'p90': round(float(np.percentile(arr, 90)), 2),
                'p99': round(float(np.percentile(arr, 99)), 2),
                'count': int(arr.size)}
        return p

    # -------------------------------------------------- metrics/tracing
    def _trace_event(self, req_id: int, phase: str,
                     ts: Optional[float] = None, **extra) -> None:
        """Record one phase timestamp for a request (first write wins,
        so the chunked-prefill path's repeated calls are safe). The
        table is a bounded FIFO over request ids."""
        now = ts if ts is not None else time.time()
        with self._traces_lock:
            tr = self._traces.get(req_id)
            if tr is None:
                tr = {'request_id': req_id}
                self._traces[req_id] = tr
                while len(self._traces) > _TRACE_KEEP:
                    self._traces.popitem(last=False)
            tr.setdefault(phase, now)
            tr.update(extra)

    def _trace_span_event(self, req_id: int, name: str,
                          **attrs) -> None:
        """Append a timestamped span event to a request's phase trace
        — the per-request view of the overlap machinery (batched
        admission, pipelined chunk delivery) that the server bridges
        into /debug/traces child spans. Bounded per request; only
        called when tracing is enabled (callers gate — this keeps the
        disabled hot path identical to before)."""
        with self._traces_lock:
            tr = self._traces.get(req_id)
            if tr is None:
                return
            evs = tr.setdefault('events', [])
            if len(evs) < _TRACE_EVENTS_KEEP:
                evs.append({'name': name, 'ts': time.time(), **attrs})

    def request_trace(self, req_id: int) -> Optional[Dict[str, Any]]:
        """Phase timestamps for a request (queued, prefill_start,
        first_token, done + prompt_tokens/generated/status + span
        events), or None for an unknown / evicted id."""
        with self._traces_lock:
            tr = self._traces.get(req_id)
            if tr is None:
                return None
            out = dict(tr)
            if 'events' in out:
                out['events'] = [dict(e) for e in out['events']]
            return out

    def _update_metric_gauges(self) -> None:
        """Refresh occupancy gauges. Called every engine-loop tick but
        throttled to ~4Hz: the loop shares cores with XLA's compute
        threads, and scrapes don't need sub-second freshness — the
        counters/histograms (updated at their events) stay exact."""
        now = time.monotonic()
        if now - self._last_gauge_t < 0.25:
            return
        self._last_gauge_t = now
        waiting = self._waiting.qsize() + (
            1 if self._deferred is not None else 0)
        self._m_queue_depth.set(waiting)
        self._m_running.set(
            sum(1 for s in self._slots if s is not None))
        if self._qos_queue is not None:
            for cls, depth in self._qos_queue.depths().items():
                self._m_qos_depth.labels(cls).set(depth)
        if self.pool is not None:
            total = self.pool.cfg.n_pages - 1   # page 0 is the dummy
            if total > 0:
                self._m_kv_util.set(
                    (total - self.pool.free_pages()) / total)
            if self.prefix_caching:
                ps = self.pool.prefix_stats
                for key, metric in (('hit_pages', self._m_prefix_hit),
                                    ('miss_pages',
                                     self._m_prefix_miss),
                                    ('evictions',
                                     self._m_prefix_evictions)):
                    cur = int(ps.get(key, 0))
                    if cur > self._prefix_seen.get(key, 0):
                        metric.inc(cur - self._prefix_seen.get(key, 0))
                        self._prefix_seen[key] = cur
                cached = self.pool.prefix_cached_pages()
                self._m_prefix_pages.set(cached)
                if total > 0:
                    self._m_prefix_occupancy.set(cached / total)
                if self._m_kv_tier_hits is not None:
                    # hbm rides the pool's hit_pages; host/fleet ride
                    # the tier manager's monotone counters — all
                    # delta-folded so rate() math survives resets.
                    cur = int(ps.get('hit_pages', 0))
                    if cur > self._prefix_seen['tier_hbm']:
                        self._m_kv_tier_hits.labels('hbm').inc(
                            cur - self._prefix_seen['tier_hbm'])
                        self._prefix_seen['tier_hbm'] = cur
                    for key, tname in (('promoted_pages', 'host'),
                                       ('fetched_pages', 'fleet'),
                                       ('prewarm_pages', 'prewarm')):
                        cur = int(self.kv_tier.stats.get(key, 0))
                        if cur > self._kv_tier_seen[key]:
                            self._m_kv_tier_hits.labels(tname).inc(
                                cur - self._kv_tier_seen[key])
                            self._kv_tier_seen[key] = cur
        else:
            denom = self.num_slots * self.max_seq_len
            if denom > 0:
                self._m_kv_util.set(
                    float(self._conf_lengths.sum()) / denom)

    def qos_depths(self) -> Optional[Dict[str, int]]:
        """Per-class waiting depths, or None with QoS off. Read by the
        server's /stats QoS snapshot and the flight-recorder engine
        state."""
        if self._qos_queue is None:
            return None
        return self._qos_queue.depths()

    def qos_signals(self) -> Dict[str, float]:
        """Live overload signals for the server's QoS admission
        controller (serve/qos.OverloadController): queue depth, slot
        count, KV/page occupancy, rolling p95 TTFT. Cheap — the
        controller samples it at most every SKYT_QOS_REFRESH_S."""
        sig: Dict[str, float] = {
            'queue_depth': float(
                self._waiting.qsize()
                + (1 if self._deferred is not None else 0)),
            'num_slots': float(self.num_slots),
        }
        if self.pool is not None:
            total = self.pool.cfg.n_pages - 1
            if total > 0:
                sig['kv_util'] = (total - self.pool.free_pages()) / total
        else:
            denom = self.num_slots * self.max_seq_len
            if denom > 0:
                sig['kv_util'] = float(self._conf_lengths.sum()) / denom
        with self._lock:
            ttfts = tuple(self._ttfts)
        if ttfts:
            sig['ttft_p95_s'] = float(np.percentile(
                np.asarray(ttfts), 95))
        return sig

    def reset_perf(self) -> None:
        self.perf = _fresh_perf()
        self._last_pull_t = None
        with self._lock:
            self._ttfts.clear()   # percentiles cover the same window

    # ------------------------------------------------- in-place weight swap
    def request_weight_swap(self, new_params, *,
                            version: Optional[int] = None,
                            drain: Optional[bool] = None,
                            timeout: Optional[float] = None
                            ) -> Dict[str, Any]:
        """Install `new_params` as the live weights at a decode-tick
        boundary (docs/robustness.md "Zero-downtime rollouts").

        The caller (infer/weight_swap.py) has already staged the tree
        onto the live shardings, so the apply is a reference swap plus
        a prefix-cache flush — decoding continues through the staging.
        drain=True (the SKYT_SWAP_DRAIN default) waits for in-flight
        requests to finish on the OLD weights — new admissions hold at
        the queue until the swap lands; drain=False applies at the next
        tick boundary and in-flight requests continue on the new
        weights (their earlier tokens came from the old ones — the
        mid-stream version mix a drain exists to avoid). Blocks until
        applied; returns {'weight_version', 'flushed_prefix_pages',
        'apply_s'}. Raises TimeoutError if the engine never reaches an
        applicable boundary within `timeout` (SKYT_SWAP_TIMEOUT_S) —
        the old weights then stay live."""
        if self._lockstep is not None:
            raise RuntimeError(
                'in-place weight swap is not supported on multi-host '
                'lockstep replicas (the swap boundary would have to '
                'ride the tick broadcast); roll these replicas by '
                'relaunch')
        if drain is None:
            drain = env.get_bool('SKYT_SWAP_DRAIN', True)
        if timeout is None:
            timeout = env.get_float('SKYT_SWAP_TIMEOUT_S', 120.0)
        if version is None:
            version = self.weight_version + 1
        swap: Dict[str, Any] = {'params': new_params,
                                'version': int(version),
                                'drain': bool(drain),
                                'event': threading.Event(),
                                'result': None}
        return self._submit_swap(swap, timeout, 'weight-swap')

    def request_reshard(self, new_params, *,
                        virtual_nodes: int,
                        drain: Optional[bool] = None,
                        timeout: Optional[float] = None
                        ) -> Dict[str, Any]:
        """Install a re-laid-out copy of the CURRENT weights as the
        live params at a decode-tick boundary — the elastic-reshard
        apply (docs/robustness.md "Elastic capacity"). Rides the exact
        weight-swap machinery (same drain semantics, same atomic-claim
        timeout contract, same single pending slot — a reshard and a
        swap cannot race each other), but the weight VERSION does not
        move: the values are unchanged, only their layout over
        `virtual_nodes` virtual nodes is new. The prefix cache is
        still flushed conservatively — page tiling is layout-derived
        and cross-layout reuse is not validated."""
        if self._lockstep is not None:
            raise RuntimeError(
                'in-place resharding is not supported on multi-host '
                'lockstep replicas (the apply boundary would have to '
                'ride the tick broadcast); reshape these replicas by '
                'relaunch')
        if drain is None:
            drain = env.get_bool('SKYT_SWAP_DRAIN', True)
        if timeout is None:
            timeout = env.get_float('SKYT_SWAP_TIMEOUT_S', 120.0)
        swap: Dict[str, Any] = {'params': new_params,
                                'version': self.weight_version,
                                'virtual_nodes': int(virtual_nodes),
                                'drain': bool(drain),
                                'event': threading.Event(),
                                'result': None}
        return self._submit_swap(swap, timeout, 'reshard')

    def request_adapter_update(self, lora_stack, *,
                               num_adapters: int,
                               flush_prefix: bool = True,
                               drain: bool = False,
                               timeout: Optional[float] = None
                               ) -> Dict[str, Any]:
        """Install a new stacked 'lora' collection as the live adapter
        stack at a decode-tick boundary — the adapter-fleet hot-load
        apply (docs/serving.md "Adapter fleet"). Rides the exact
        weight-swap machinery (same single pending slot, same
        atomic-claim timeout contract — an adapter update cannot race
        a swap or reshard), but base params and weight VERSION are
        untouched: only the adapter stack reference moves. Adapter ids
        are stable across updates (the registry appends or zero-fills
        freed slots, never renumbers), so in-flight requests stay
        pinned to their adapter through the apply; drain=True is for
        in-place REPLACEMENT of a referenced id, where pinning demands
        the old values survive until those requests finish. A grown
        stack changes the 'lora' leaves' [N, ...] shapes, so the next
        prefill/decode dispatch retraces (one-time compile cost,
        visible as a tick-time spike)."""
        if self._lockstep is not None:
            raise RuntimeError(
                'adapter hot-load is not supported on multi-host '
                'lockstep replicas (the apply boundary would have to '
                'ride the tick broadcast); roll these replicas by '
                'relaunch')
        if timeout is None:
            timeout = env.get_float('SKYT_ADAPTER_TIMEOUT_S', 120.0)
        swap: Dict[str, Any] = {'lora_stack': lora_stack,
                                'num_adapters': int(num_adapters),
                                'flush_prefix': bool(flush_prefix),
                                'version': self.weight_version,
                                'drain': bool(drain),
                                'event': threading.Event(),
                                'result': None}
        return self._submit_swap(swap, timeout, 'adapter update')

    def adapter_in_use(self, lora_id: int) -> bool:
        """True while any active, chunked, deferred, or waiting request
        references the adapter id — the registry's unload-refusal
        check. A freed id's stack slot zeroes (scaling 0), so an
        in-flight reference surviving an unload would silently serve
        base-model outputs under the adapter's name."""
        lid = int(lora_id)
        with self._lock:
            if any(s is not None and s.params.lora_id == lid
                   for s in self._slots):
                return True
            ch = self._chunked
            if ch is not None and ch['req'].params.lora_id == lid:
                return True
        d = self._deferred
        if d is not None and d.params.lora_id == lid:
            return True
        with self._waiting.mutex:
            return any(r.params.lora_id == lid
                       for r in self._waiting.queue)

    def _submit_swap(self, swap: Dict[str, Any], timeout: float,
                     what: str) -> Dict[str, Any]:
        running = self._thread is not None and self._thread.is_alive()
        with self._lock:
            if self._swap_req is not None:
                raise RuntimeError(
                    'a weight swap or reshard is already pending')
            self._swap_req = swap
        if not running:
            # No engine loop (cold engine, unit tests): every moment
            # is a tick boundary; apply inline.
            self._maybe_apply_swap()
        if not swap['event'].wait(timeout):
            with self._lock:
                if self._swap_req is swap:
                    self._swap_req = None
                    raise TimeoutError(
                        f'engine did not reach a {what} boundary '
                        f'within {timeout}s (drain={swap["drain"]}); '
                        f'old weights stay live')
            # Lost the race: the loop applied it while we timed out.
            swap['event'].wait(5)
        if swap['result'] is None:
            raise RuntimeError(f'engine loop died before the {what} '
                               f'applied; old weights stay live')
        return swap['result']

    def _maybe_apply_swap(self) -> None:
        """Apply a pending weight swap if this tick boundary is
        eligible (engine-loop thread, or inline when no loop runs). A
        draining swap waits until no slot is occupied and no chunked
        prefill is mid-flight; admissions are held while it waits
        (see _loop_body) so the drain converges.

        The eligibility check AND the claim happen under one lock
        hold: once claimed (_swap_req cleared), the waiter's timeout
        path can no longer abort it — without the atomic claim, a
        drain completing exactly at the timeout could apply the new
        weights while the caller records an abort, leaving a replica
        silently serving weights nobody believes it has."""
        with self._lock:
            swap = self._swap_req
            if swap is None:
                return
            if swap['drain'] and (
                    self._chunked is not None or
                    any(s is not None for s in self._slots)):
                return
            self._swap_req = None   # claimed: apply is now inevitable
        t0 = time.perf_counter()
        if 'lora_stack' in swap:
            # Adapter-stack update: base params, weight version, and
            # layout are untouched — only the 'lora' collection
            # reference moves (ids stable; see request_adapter_update).
            self._lora_stack = swap['lora_stack']
            self.num_adapters = int(swap['num_adapters'])
            flushed = 0
            if swap['flush_prefix'] and self.pool is not None and \
                    self.prefix_caching:
                # Prefix pages are salted by lora_id; a reused or
                # re-versioned id would otherwise hit pages computed
                # under the previous adapter's values.
                flushed = self.pool.flush_prefix()
            swap['result'] = {
                'weight_version': self.weight_version,
                'num_adapters': self.num_adapters,
                'flushed_prefix_pages': flushed,
                'apply_s': round(time.perf_counter() - t0, 6)}
            logger.info('adapter stack applied: %d slot(s) at weight '
                        'version %d (drain=%s, %d prefix pages '
                        'flushed)', self.num_adapters,
                        self.weight_version, swap['drain'], flushed)
            swap['event'].set()
            return
        self.params = swap['params']
        self.weight_version = int(swap['version'])
        flushed = 0
        if self.pool is not None and self.prefix_caching:
            # Stale-KV correctness: cached prefixes were computed under
            # the old weights and must never be shared across versions
            # (for a reshard the values are unchanged but the page
            # tiling is layout-derived: flush conservatively).
            flushed = self.pool.flush_prefix()
        if swap.get('virtual_nodes') is not None:
            # Reshard apply: layout moves, version does not — the host/
            # fleet KV tiers stay valid (same weight version), so a
            # freshly resharded replica re-promotes its prefixes from
            # the host store instead of recomputing them.
            self.virtual_nodes = int(swap['virtual_nodes'])
            self._m_virtual_nodes.set(self.virtual_nodes)
            swap['result'] = {
                'weight_version': self.weight_version,
                'virtual_nodes': self.virtual_nodes,
                'flushed_prefix_pages': flushed,
                'apply_s': round(time.perf_counter() - t0, 6)}
            logger.info('reshard applied: %d virtual node(s) at weight '
                        'version %d (drain=%s, %d prefix pages '
                        'flushed)', self.virtual_nodes,
                        self.weight_version, swap['drain'], flushed)
            swap['event'].set()
            return
        if self.kv_tier is not None:
            # The outer tiers obey the same contract: drop every host-
            # store entry of the old version AND gate in-flight spills
            # (a snapshot taken pre-swap must not land post-swap);
            # fetches reject peers on another version, so the fleet
            # tier invalidates transitively.
            self.kv_tier.host.set_version(self.weight_version)
        self._m_weight_version.set(self.weight_version)
        swap['result'] = {'weight_version': self.weight_version,
                          'flushed_prefix_pages': flushed,
                          'apply_s': round(time.perf_counter() - t0, 6)}
        logger.info('weight swap applied: version %d (drain=%s, '
                    '%d prefix pages flushed)', self.weight_version,
                    swap['drain'], flushed)
        swap['event'].set()

    # ---------------------------------------------------------- main loop
    def _bucket_for(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        return _round_up_pow2(n)

    def _ensure_dev_args(self) -> None:
        """Build the INITIAL device-resident decode args (all zero — no
        slot is active before the first admission). After this they are
        only ever updated on device: never set self._dev_args = None
        while slots are active, a host rebuild would rewind state an
        in-flight chunk already advanced."""
        if self._dev_args is None:
            n = self.num_slots
            self._dev_args = (jnp.zeros((n,), jnp.int32),
                              jnp.zeros((n,), jnp.int32),
                              jnp.zeros((n,), jnp.float32),
                              jnp.zeros((n, 2), jnp.uint32),
                              jnp.zeros((n,), jnp.int32),
                              jnp.ones((n,), jnp.float32),
                              jnp.zeros((n,), jnp.float32),
                              jnp.zeros((n,), jnp.float32),
                              # Output-token counts for the repetition
                              # penalties: [SLOTS, V] int32 (~4MB at
                              # 128k vocab — noise next to the cache).
                              jnp.zeros((n, self.cfg.vocab_size),
                                        jnp.int32),
                              # logit_bias scatter pairs (idx 0 + val 0
                              # padding is a harmless +0 on token 0).
                              jnp.zeros((n, _BIAS_BUCKET), jnp.int32),
                              jnp.zeros((n, _BIAS_BUCKET), jnp.float32))

    def _ledger_key(self, req: '_Request') -> 'ledger_lib.Key':
        """Bounded (class, tenant, model) attribution key: class and
        tenant are already parsed/bounded by the server's QoS header
        contract; the model label comes from the bounded lora-id map
        (never a raw request string)."""
        p = req.params
        lid = p.lora_id
        return (p.priority or 'standard', p.tenant or 'default',
                self.model_labels.get(lid) or f'lora{lid}')

    def _count_prefill_dispatch(self, n_requests: int,
                                dispatch_tokens: int = 0,
                                real_tokens: int = 0) -> None:
        """Account one target-model prefill forward serving
        `n_requests` admissions (1 for the sequential path and for
        chunked-prefill pieces). dispatch_tokens/real_tokens feed the
        padding-fraction accounting (perf + /metrics): positions the
        forward computed vs positions holding real prompt tokens."""
        self.perf['prefill_dispatches'] += 1
        self.perf['admission_batch_size'] = max(
            self.perf['admission_batch_size'], n_requests)
        self._m_prefill_dispatches.inc()
        self._m_admission_batch.observe(n_requests)
        if dispatch_tokens > 0:
            padded = max(0, dispatch_tokens - real_tokens)
            self.perf['prefill_dispatch_tokens'] += dispatch_tokens
            self.perf['prefill_padded_tokens'] += padded
            self._m_prefill_disp_tokens.inc(dispatch_tokens)
            if padded:
                self._m_prefill_padded.inc(padded)

    def _first_token(self, req: '_Request', logits_row, greedy):
        """First-token selection for an admitted prompt — the ONE place
        OpenAI first-token semantics live (host-side logit_bias on a
        copied row, host sampling for temp > 0, lazy greedy pull, RAW
        logprob reporting); shared by the sequential, chunked and
        batched admission paths so they cannot drift.

        logits_row: the request's host [V] logits row, or None when no
        path needs it. greedy: zero-arg thunk returning the device
        argmax — called (and its transfer paid) only for unbiased
        greedy requests. Returns (first, first_lp, temp)."""
        temp = max(0.0, req.params.temperature)
        bias = req.params.logit_bias
        sample_row = logits_row
        if bias:
            sample_row = logits_row.copy()
            for t, b in bias.items():
                sample_row[int(t)] += float(b)
        if temp > 0.0:
            first = self._sample(sample_row, req)
        elif bias:
            first = int(np.argmax(sample_row))
        else:
            first = greedy()
        first_lp = _np_raw_lp(logits_row, first) \
            if req.params.logprobs else None
        return first, first_lp, temp

    def _ins_args(self, slot: int, req: '_Request', first: int,
                  temp: float) -> tuple:
        """The decode-arg tail every insert variant takes after
        (cache, prefill_cache, row) — slot id, device args, first
        token, length, sampling knobs, rng key, bias scatter pairs."""
        self._ensure_dev_args()
        bidx, bval = _bias_arrays(req.params)
        key = jax.random.PRNGKey(req.params.seed + req.req_id)
        return (jnp.int32(slot), self._dev_args, jnp.int32(first),
                jnp.int32(len(req.tokens)), jnp.float32(temp), key,
                jnp.int32(min(req.params.top_k, _TOPK_BUCKET)),
                jnp.float32(req.params.top_p),
                jnp.float32(req.params.presence_penalty),
                jnp.float32(req.params.frequency_penalty),
                jnp.asarray(bidx), jnp.asarray(bval))

    def _pop_admission_batch(self, cand: List['_Request']
                             ) -> List['_Request']:
        """Pop `cand` (a snapshot of the queue head) with the cancel
        discipline shared by the batched and ragged admission paths:
        the requests become visible to cancel() via _admitting_many
        BEFORE the pops (between pop and _complete_admission they live
        nowhere else, and a cancel that finds a request in no
        structure would be silently lost), then cancels that landed
        between the snapshot and the pops are honored — a
        cancelled-while-waiting request gets its terminal None without
        costing a slot or any prefill work. Returns the survivors."""
        self._admitting_many = list(cand)   # visible BEFORE the pops
        for _ in cand:
            self._waiting.get_nowait()
        live: List[_Request] = []
        for req in cand:
            if req.cancelled:
                self._trace_event(req.req_id, 'done',
                                  status='deadline' if req.expired
                                  else 'cancelled')
                req.out_queue.put(None)
            else:
                live.append(req)
        # Cancelled requests are terminal; only the survivors still
        # need cancel() visibility (empty -> the window closes).
        self._admitting_many = list(live)
        return live

    def _reserve_admission_batch(self, live: List['_Request'],
                                 free: List[int]):
        """Positional page reservations for a popped admission batch
        (paged mode), shared by the batched and ragged paths. A FIRST
        reservation failure requeues everything and returns
        (live, None) — the sequential path's _deferred handling owns
        the pool-full case; a later failure shrinks the batch with the
        unreserved tail back at the queue HEAD, so FIFO order
        survives. Returns (surviving live, their table rows)."""
        rows: List[np.ndarray] = []
        for j, req in enumerate(live):
            total = min(len(req.tokens) + req.params.max_new_tokens,
                        self.max_seq_len)
            res = self.pool.try_reserve_prefix(free[j], total, ())
            if res is None:
                break
            rows.append(res[0])
        if not rows:
            with self._waiting.mutex:
                self._waiting.queue.extendleft(reversed(live))
            self._admitting_many = []
            return live, None
        if len(rows) < len(live):
            with self._waiting.mutex:
                self._waiting.queue.extendleft(
                    reversed(live[len(rows):]))
            live = live[:len(rows)]
        self._admitting_many = list(live)
        return live, rows

    def _ragged_bucket(self, t: int) -> int:
        """Packed-length bucket for a ragged dispatch: t rounded up to
        a page-aligned step of 1/8th of the enclosing pow2 bucket
        (floor: one page). Compile count stays log-bounded (at most 8
        sub-buckets per octave) while the tail padding is bounded at
        ~12.5% instead of the pow2 bucket's ~50%."""
        psize = self.pool.cfg.page_size
        b = _round_up_pow2(t, lo=max(32, psize))
        step = max(psize, (b // 8) - (b // 8) % psize)
        return -(-t // step) * step

    def _try_admit_ragged(self) -> bool:
        """Ragged admission fast path (paged mode): pack a FIFO prefix
        of waiting requests — page-aligned, ANY mix of lengths — into
        one [1, T] packed prefill separated by segment ids, instead of
        padding every row to the shared pow2 bucket
        (_try_admit_batch). Wins twice: mixed-bucket bursts that the
        padded path cannot batch at all collapse into one dispatch,
        and the FLOPs spent on padding drop from (B x bucket -
        sum n_j) to the page-rounding tails (~0 for page-aligned
        prompts). Same ordering/fallback discipline as the padded
        path: candidates are a FIFO prefix; prefix-cache hits, long
        prompts wanting chunked prefill, QoS reserve gating, and
        pool-full reservations all fall through to the sequential
        path. Candidates may mix adapters: the packed row carries
        PER-TOKEN lora ids (each segment's tokens tagged with its
        request's adapter), dispatched through the ops/lora.py grouped
        path — golden-equal to splitting the pack per adapter."""
        if not self.ragged_prefill or self._deferred is not None:
            return False
        if self._chunked is not None:
            return False
        free = [i for i, r in enumerate(self._slots) if r is None]
        if len(free) < 2 or self._waiting.qsize() < 2:
            return False
        psize = self.pool.cfg.page_size
        with self._waiting.mutex:
            queued = list(itertools.islice(self._waiting.queue,
                                           len(free)))
        cand: List[_Request] = []
        total = 0
        for req in queued:
            if req.cancelled:
                break   # let _admit_one deliver its terminal None
            if self._qos_reserved and \
                    req.params.priority != 'interactive' and \
                    len(cand) >= len(free) - self._qos_reserved:
                break
            n = len(req.tokens)
            if self.prefill_chunk and n > self.prefill_chunk:
                break
            if self.prefix_caching:
                if req.page_hashes is None:
                    req.page_hashes = paged_cache_hashes(
                        req.tokens, psize, salt=req.params.lora_id)
                if self.pool.prefix_peek(
                        req.page_hashes[:(n - 1) // psize]) > 0:
                    break   # prefix hit -> suffix path, sequential
                if self._kv_admission_break(req, n, psize):
                    break   # outer tier can serve it -> sequential
            span = -(-n // psize) * psize
            if cand and total + span > self._ragged_max:
                break
            cand.append(req)
            total += span
        if len(cand) < 2:
            return False
        live = self._pop_admission_batch(cand)
        if not live:
            return True   # progress: the queue head was consumed
        live, rows = self._reserve_admission_batch(live, free)
        if rows is None:
            return False
        cand = live
        nb = len(cand)
        spans = [-(-len(r.tokens) // psize) * psize for r in cand]
        offs = list(itertools.accumulate([0] + spans[:-1]))
        real = sum(len(r.tokens) for r in cand)
        t_bucket = self._ragged_bucket(sum(spans))
        tokens = np.zeros((1, t_bucket), np.int32)
        segs = np.zeros((1, t_bucket), np.int32)
        poss = np.zeros((1, t_bucket), np.int32)
        # Per-token adapter ids: each segment's tokens carry their
        # request's lora_id (page tails + bucket padding stay 0 — the
        # zeros adapter, and those positions are never read). The
        # grouped ops/lora.py path makes a mixed-adapter pack exactly
        # equal to splitting it per adapter.
        lora_row = np.zeros((1, t_bucket), np.int32)
        bp = 1 << (nb - 1).bit_length()       # pow2 pad: fewer compiles
        logit_pos = np.zeros((1, bp), np.int32)
        trace_on = tracing.enabled()
        for j, req in enumerate(cand):
            n = len(req.tokens)
            off = offs[j]
            tokens[0, off:off + n] = req.tokens
            segs[0, off:off + n] = j + 1
            lora_row[0, off:off + n] = req.params.lora_id
            # Page-rounding tail keeps id 0 (masked everywhere); its
            # positions continue the request's arange so the junk KV
            # written above n lands with sane rope — overwritten by
            # the feed-at-lens invariant before it is ever attended,
            # exactly like the padded path's bucket junk.
            poss[0, off:off + spans[j]] = np.arange(spans[j])
            logit_pos[0, j] = off + n - 1
            if req.prefill_start_at is None:
                req.prefill_start_at = time.time()
            self._trace_event(req.req_id, 'prefill_start',
                              status='running')
            if trace_on:
                self._trace_span_event(req.req_id, 'ragged_admission',
                                       batch_size=nb,
                                       packed_tokens=t_bucket)
        self.perf['ragged_dispatches'] += 1
        with self._ctx():
            greedy, logits, prefill_cache = self._jit_prefill_ragged(
                self._vars(lora_row), jnp.asarray(tokens),
                jnp.asarray(segs), jnp.asarray(poss),
                jnp.asarray(logit_pos), t_bucket=t_bucket)
            self._count_prefill_dispatch(nb, dispatch_tokens=t_bucket,
                                         real_tokens=real)
            need_rows = any(
                r.params.temperature > 0.0 or r.params.logprobs
                or r.params.logit_bias for r in cand)
            logits_np = self._pull(logits) if need_rows else None
            greedy_np = self._pull(greedy) if any(
                r.params.temperature <= 0.0 and not r.params.logit_bias
                for r in cand) else None
            p = psize
            for j, req in enumerate(cand):
                slot = free[j]
                n = len(req.tokens)
                logits_row = logits_np[j] \
                    if req.params.temperature > 0.0 or \
                    req.params.logprobs or req.params.logit_bias \
                    else None
                first, first_lp, temp = self._first_token(
                    req, logits_row,
                    lambda j=j: int(greedy_np[j]))
                ins_args = self._ins_args(slot, req, first, temp)
                row = rows[j]
                n_ins = min(-(-n // p), int((row > 0).sum()))
                # Row 0 of the packed cache at src_off = this
                # request's packed offset: insert_prompt slices
                # [off, off + n_ins*P) — exactly the request's span.
                self.cache, self._dev_args = self._jit_insert_paged(
                    self.cache, prefill_cache, jnp.int32(0),
                    *ins_args, jnp.asarray(row[:n_ins]),
                    jnp.asarray(row), jnp.int32(offs[j]))
                if self.prefix_caching and req.page_hashes:
                    self.pool.publish(slot, req.page_hashes[:n // p])
                self._complete_admission(req, slot, n, first, temp,
                                         first_lp=first_lp)
        self._admitting_many = []
        return True

    def _try_admit_batch(self) -> bool:
        """Batched admission fast path: when several WAITING requests
        pad to the same prefill bucket and enough slots are free,
        prefill all of them in ONE device dispatch (tokens [B, bucket])
        and insert each row into its slot, instead of one _admit_one
        round-trip per request. Under a queue burst this collapses B
        prefill forwards + B host sync points into one forward (the
        dominant admission cost) + B cheap fused inserts.

        Candidates are a PREFIX of the FIFO queue (collection stops at
        the first non-batchable request) so admission order — and
        therefore multi-host lockstep determinism and fairness — is
        unchanged. Falls back (returns False) whenever the sequential
        path's special cases apply: a deferred FIFO head, paged prompts
        wanting chunked prefill or a prefix-cache hit (those take the
        suffix path), or a pool too full to reserve. The batch dim is
        padded to a power of two (dummy rows) so distinct burst sizes
        share compiles.
        """
        if not self.batch_admission or self._deferred is not None:
            return False
        free = [i for i, r in enumerate(self._slots) if r is None]
        if len(free) < 2 or self._waiting.qsize() < 2:
            return False
        if self.cache_mode == 'paged' and self._chunked is not None:
            return False
        # Snapshot only the candidates we can seat (a full-queue copy
        # under the mutex would be O(backlog) on the hot loop).
        with self._waiting.mutex:
            queued = list(itertools.islice(self._waiting.queue,
                                           len(free)))
        cand: List[_Request] = []
        bucket = None
        psize = self.pool.cfg.page_size if self.pool is not None else 0
        for req in queued:
            if req.cancelled:
                break   # let _admit_one deliver its terminal None
            if self._qos_reserved and \
                    req.params.priority != 'interactive' and \
                    len(cand) >= len(free) - self._qos_reserved:
                # Slot reservation: this candidate would eat into the
                # interactive reserve. The scheduler keeps interactive
                # requests at the queue head, so stopping here never
                # strands one behind the gate.
                break
            n = len(req.tokens)
            b = self._bucket_for(n)
            if bucket is not None and b != bucket:
                break
            if self.cache_mode == 'paged':
                if self.prefill_chunk and n > self.prefill_chunk:
                    break
                if self.prefix_caching:
                    if req.page_hashes is None:
                        req.page_hashes = paged_cache_hashes(
                            req.tokens, psize, salt=req.params.lora_id)
                    if self.pool.prefix_peek(
                            req.page_hashes[:(n - 1) // psize]) > 0:
                        break   # prefix hit -> suffix path, sequential
                    if self._kv_admission_break(req, n, psize):
                        break   # outer tier can serve it -> sequential
            bucket = b
            cand.append(req)
        if len(cand) < 2:
            return False
        live = self._pop_admission_batch(cand)
        if not live:
            return True   # progress: the queue head was consumed
        rows: List[np.ndarray] = []
        if self.cache_mode == 'paged':
            live, rows = self._reserve_admission_batch(live, free)
            if rows is None:
                return False
        cand = live
        nb = len(cand)
        bp = 1 << (nb - 1).bit_length()          # pow2 pad: fewer compiles
        padded = np.zeros((bp, bucket), np.int32)
        lengths = np.ones((bp,), np.int32)       # dummy rows: length 1
        lora_ids = [0] * bp
        trace_on = tracing.enabled()
        for j, req in enumerate(cand):
            padded[j, :len(req.tokens)] = req.tokens
            lengths[j] = len(req.tokens)
            lora_ids[j] = req.params.lora_id
            if req.prefill_start_at is None:
                req.prefill_start_at = time.time()
            self._trace_event(req.req_id, 'prefill_start',
                              status='running')
            if trace_on:
                # PR 2's overlap machinery, visible per request: this
                # request's prefill was amortized across an nb-wide
                # admission batch.
                self._trace_span_event(req.req_id, 'batch_admission',
                                       batch_size=nb, bucket=bucket)
        with self._ctx():
            greedy, logits, prefill_cache = self._jit_prefill(
                self._vars(lora_ids), jnp.asarray(padded),
                jnp.asarray(lengths), bucket=bucket)
            self._count_prefill_dispatch(
                nb, dispatch_tokens=bp * bucket,
                real_tokens=sum(len(r.tokens) for r in cand))
            # Pull each array at most once, and only when some request
            # needs it (in multi-host mode every _pull is a cross-host
            # collective — same rule as _admit_one's single-pull logic).
            need_rows = any(
                r.params.temperature > 0.0 or r.params.logprobs
                or r.params.logit_bias for r in cand)
            logits_np = self._pull(logits) if need_rows else None
            greedy_np = self._pull(greedy) if any(
                r.params.temperature <= 0.0 and not r.params.logit_bias
                for r in cand) else None
            for j, req in enumerate(cand):
                slot = free[j]
                n = len(req.tokens)
                logits_row = logits_np[j] \
                    if req.params.temperature > 0.0 or \
                    req.params.logprobs or req.params.logit_bias \
                    else None
                first, first_lp, temp = self._first_token(
                    req, logits_row,
                    lambda j=j: int(greedy_np[j]))
                ins_args = self._ins_args(slot, req, first, temp)
                if self.cache_mode == 'paged':
                    row = rows[j]
                    p = self.pool.cfg.page_size
                    reserved = int((row > 0).sum())
                    n_ins = min(-(-bucket // p), reserved)
                    self.cache, self._dev_args = self._jit_insert_paged(
                        self.cache, prefill_cache, jnp.int32(j),
                        *ins_args, jnp.asarray(row[:n_ins]),
                        jnp.asarray(row), jnp.int32(0))
                    if self.prefix_caching and req.page_hashes:
                        self.pool.publish(slot,
                                          req.page_hashes[:n // p])
                else:
                    self.cache, self._dev_args = self._jit_insert(
                        self.cache, prefill_cache, jnp.int32(j),
                        *ins_args)
                self._complete_admission(req, slot, n, first, temp,
                                         first_lp=first_lp)
        self._admitting_many = []
        return True

    def _admit_one(self) -> bool:
        if self._qos_reserved:
            # Slot reservation (QoS): a non-interactive head may not
            # take the last reserved slot(s). Cancelled heads still
            # pass (they must pop to deliver their terminal None and
            # never occupy a slot anyway).
            head = self._deferred
            if head is None:
                with self._waiting.mutex:
                    head = self._waiting.queue[0] \
                        if self._waiting.queue else None
            if head is not None and not head.cancelled and \
                    head.params.priority != 'interactive' and \
                    sum(1 for s in self._slots if s is None) <= \
                    self._qos_reserved:
                return False
        req = self._deferred
        if req is not None:
            self._deferred = None
        else:
            try:
                req = self._waiting.get_nowait()
            except queue.Empty:
                return False
        if req.cancelled:
            # Cancelled while waiting: never occupies a slot. Trace
            # before the None unblocks the waiter.
            self._trace_event(req.req_id, 'done',
                              status='deadline' if req.expired
                              else 'cancelled')
            req.out_queue.put(None)
            return True
        # Visible to cancel() during the admission window (popped from
        # the queue but not yet installed in _slots — a full prefill
        # dispatch wide); the flag is then honored at the first
        # delivery boundary.
        self._admitting = req
        slot = self._slots.index(None)
        n = len(req.tokens)
        bucket = self._bucket_for(n)
        row = None
        n_cached = 0
        hashes: List[bytes] = []
        if self.cache_mode == 'paged':
            # Reserve the worst case this request can touch — prompt +
            # max_new — so decode can never exhaust the pool mid-flight.
            total = min(n + req.params.max_new_tokens, self.max_seq_len)
            psize = self.pool.cfg.page_size
            if self.prefill_chunk and self._chunked is not None and \
                    n > self.prefill_chunk:
                # A long prompt behind an in-progress chunked prefill:
                # defer BEFORE reserving — reserve-then-release every
                # loop iteration would churn the pool and the prefix
                # registry for the whole of the other prompt's prefill.
                # (A full prefix hit could shrink the suffix below the
                # chunk; the reserve path handles that once the current
                # chunked prefill finishes.)
                self._deferred = req
                return False
            if self.prefix_caching:
                if req.page_hashes is None:
                    req.page_hashes = paged_cache_hashes(
                        req.tokens, psize, salt=req.params.lora_id)
                hashes = req.page_hashes
            if self.kv_tier is not None and hashes:
                # Outer tiers, cheapest first: splice any host-resident
                # continuation into the pool (L2), then — still missing
                # pages, with a peer hint and no fetch in flight — park
                # the request behind a cross-replica fetch (L3). The
                # reserve below then shares whatever landed; every
                # failure mode falls through to plain recompute.
                try:
                    self._kv_try_promote(req)
                    parked = (self.kv_tier.fleet and req.kv_peer and
                              req.kv_fetch is None and
                              self._kv_fetching is None and
                              self._kv_start_fetch(req))
                except Exception:  # pylint: disable=broad-except
                    # The tier must never fail admission: any splice
                    # error (poisoned page, install bug) degrades to
                    # plain recompute, not a loop crash that would
                    # fail every in-flight request.
                    logger.exception('kv tier admission splice failed; '
                                     'recomputing')
                    if self._kv_fetching is req:
                        # Never leave the request both parked and
                        # admitted: _kv_tick must not re-admit it.
                        self._kv_fetching = None
                        req.kv_fetch = None
                    parked = False
                if parked:
                    self._admitting = None
                    return True   # parked; _kv_tick re-admits it
            # Cap the shared span at (n-1)//P pages: at least one real
            # token must run through the model to produce next-token
            # logits.
            res = self.pool.try_reserve_prefix(
                slot, total, hashes[:(n - 1) // psize])
            if res is None:
                # Pool full: keep FIFO order, retry after releases.
                self._deferred = req
                return False
            row, n_cached = res
            if self.prefill_chunk and \
                    n - n_cached * psize > self.prefill_chunk:
                # Long prompt: prefill one chunk per loop iteration so
                # running requests keep decoding in between. Evaluated
                # BEFORE the suffix-bucket-overflow fallback — chunk
                # buckets are page-rounded pieces, so the overflow
                # cannot occur on this path and the cached prefix is
                # kept.
                # One chunked prefill at a time: the pre-reserve check
                # above already deferred any long prompt while one is in
                # progress (n - n_cached*psize > chunk implies
                # n > chunk), and nothing between there and here can
                # start one — this is all on the engine loop thread.
                assert self._chunked is None, \
                    'chunked prefill started between defer check and reserve'
                self._slots[slot] = req
                req.slot = slot
                self._slot_lora[slot] = req.params.lora_id
                self._chunked = {'req': req, 'slot': slot, 'row': row,
                                 'hashes': hashes,
                                 'start': n_cached * psize, 'n': n}
                if req.prefill_start_at is None:
                    req.prefill_start_at = time.time()
                self._trace_event(req.req_id, 'prefill_start',
                                  status='running')
                return True
            if n_cached > 0:
                sb = self._bucket_for(n - n_cached * psize)
                max_span = self.pool.cfg.max_pages_per_slot * psize
                if n_cached * psize + sb > max_span:
                    # The suffix bucket's padded writes would spill past
                    # the per-slot view (dynamic_update_slice would
                    # clamp the start and corrupt the cache) — rare;
                    # fall back to a full prefill.
                    self.pool.release(slot)
                    res = self.pool.try_reserve_prefix(slot, total, ())
                    if res is None:
                        self._deferred = req
                        return False
                    row, n_cached = res
        temp = max(0.0, req.params.temperature)
        if req.prefill_start_at is None:
            req.prefill_start_at = time.time()
        self._trace_event(req.req_id, 'prefill_start',
                          status='running')
        if tracing.enabled():
            self._trace_span_event(req.req_id, 'admission',
                                   batch_size=1, cached_pages=n_cached)
        with self._ctx():
            if n_cached > 0:
                psize = self.pool.cfg.page_size
                start = n_cached * psize
                suffix = req.tokens[start:]
                sb = self._bucket_for(len(suffix))
                padded = np.zeros((1, sb), np.int32)
                padded[0, :len(suffix)] = suffix
                greedy, logits, prefill_cache = self._jit_prefill_suffix(
                    self._vars([req.params.lora_id]),
                    jnp.asarray(padded), jnp.int32(start),
                    jnp.asarray([n]), self.cache['k'], self.cache['v'],
                    self.cache.get('k_scale'),
                    self.cache.get('v_scale'),
                    jnp.asarray(row), bucket=sb)
                self._count_prefill_dispatch(
                    1, dispatch_tokens=sb, real_tokens=len(suffix))
            else:
                padded = np.zeros((1, bucket), np.int32)
                padded[0, :n] = req.tokens
                greedy, logits, prefill_cache = self._jit_prefill(
                    self._vars([req.params.lora_id]),
                    jnp.asarray(padded), jnp.asarray([n]),
                    bucket=bucket)
                self._count_prefill_dispatch(
                    1, dispatch_tokens=bucket, real_tokens=n)
            # Pull the logits row at most ONCE (multi-host: every
            # _pull is a cross-host collective, not a cached host
            # copy); greedy is a lazy 4-byte pull. logprobs: the row
            # pull is the documented TTFT cost of asking for them on a
            # greedy request.
            logits_row = self._pull(logits)[0] \
                if temp > 0.0 or req.params.logprobs \
                or req.params.logit_bias else None
            first, first_lp, temp = self._first_token(
                req, logits_row, lambda: int(self._pull(greedy)[0]))
            ins_args = self._ins_args(slot, req, first, temp)
            if self.cache_mode == 'paged':
                reserved = int((row > 0).sum())
                p = self.pool.cfg.page_size
                if n_cached > 0:
                    # Write only the computed suffix pages; the shared
                    # prefix pages already hold this content.
                    n_ins = min(-(-n // p), reserved) - n_cached
                    ids = row[n_cached:n_cached + n_ins]
                    src = n_cached * p
                else:
                    n_ins = min(-(-bucket // p), reserved)
                    ids = row[:n_ins]
                    src = 0
                self.cache, self._dev_args = self._jit_insert_paged(
                    self.cache, prefill_cache, jnp.int32(0), *ins_args,
                    jnp.asarray(ids), jnp.asarray(row), jnp.int32(src))
                if self.prefix_caching:
                    # Publish every full page the slot now holds; later
                    # readers order after this insert via the dispatch
                    # chain.
                    self.pool.publish(slot, hashes[:n // p])
            else:
                # S-axis trim/pad to max_seq_len happens inside the
                # fused insert program.
                self.cache, self._dev_args = self._jit_insert(
                    self.cache, prefill_cache, jnp.int32(0), *ins_args)
        self._complete_admission(req, slot, n, first, temp,
                                 first_lp=first_lp)
        return True

    def _complete_admission(self, req: '_Request', slot: int, n: int,
                            first: int, temp: float,
                            first_lp: Optional[float] = None) -> None:
        """Shared admission tail: device history (n-gram spec) or
        draft-cache prefill (draft spec), first token delivery, host
        slot bookkeeping."""
        if self.draft_model is not None:
            # The draft needs the prompt KV in ITS cache too. Prefix
            # caching never shortcuts this (the draft cache is per-slot
            # dense), which is fine: the draft is small by construction.
            db = self._bucket_for(n)
            padded = np.zeros((1, db), np.int32)
            padded[0, :n] = req.tokens
            with self._ctx():
                self._draft_cache = self._jit_draft_prefill(
                    self.draft_params, self._draft_cache,
                    jnp.asarray(padded), jnp.int32(slot), bucket=db)
        if self._dev_hist is not None:
            # Full prompt (not just a prefix-cached suffix) into the
            # device history for the n-gram proposer.
            # Clamp the insert width to the history buffer: the pow2
            # bucket for a near-max_seq_len prompt can exceed the
            # buffer's max_seq_len + k + 2 width when max_seq_len is
            # not a power of two (n <= max_seq_len < width always, so
            # the clamped slice still holds the whole prompt).
            hb = min(self._bucket_for(n), int(self._dev_hist.shape[1]))
            hist_toks = np.zeros((1, hb), np.int32)
            hist_toks[0, :n] = req.tokens
            with self._ctx():
                self._dev_hist = self._jit_hist_insert(
                    self._dev_hist, jnp.int32(slot),
                    jnp.asarray(hist_toks), jnp.int32(n),
                    jnp.int32(first))
        req.first_token_at = time.time()
        with self._lock:   # /stats readers snapshot under the same lock
            self._ttfts.append(req.first_token_at - req.submitted_at)
        self._m_ttft.observe(req.first_token_at - req.submitted_at)
        if self._qos_queue is not None:
            cls = req.params.priority
            self._m_qos_ttft.labels(cls).observe(
                req.first_token_at - req.submitted_at)
            start = req.prefill_start_at or req.first_token_at
            self._m_qos_wait.labels(cls).observe(
                max(0.0, start - req.submitted_at))
        self._m_prefill_tokens.inc(n)
        self.perf['admitted_requests'] += 1
        # Capacity ledger: this admission's prefill work, weighted by
        # real prompt tokens, lands in the interval being accumulated.
        self.ledger.note(self._ledger_key(req), n)
        self._trace_event(req.req_id, 'first_token',
                          ts=req.first_token_at)
        req.slot = slot
        self._slot_lora[slot] = req.params.lora_id
        req.generated = 1
        req.out_queue.put((first, first_lp) if req.params.logprobs
                          else first)
        self._slots[slot] = req
        # Only now (installed in _slots) does cancel() see it there;
        # no gap between the two scan targets.
        self._admitting = None
        self._lengths[slot] = n
        self._conf_lengths[slot] = n
        self._temps[slot] = temp
        self._had_admission = True
        if self._req_done(req, first):
            self._release(slot)

    def _advance_chunked(self) -> None:
        """Run ONE chunk of the in-progress chunked prefill (if any).
        Every chunk rides the prefix-cache suffix path: gather the
        slot's pages so far, run this chunk's tokens through the model,
        scatter the new pages back (tables untouched until the final
        chunk, so the slot stays out of the decode batch). The final
        chunk produces the first token and activates the slot."""
        st = self._chunked
        if st is None:
            return
        req, slot, row = st['req'], st['slot'], st['row']
        if req.cancelled:
            # Abandon the in-progress chunked prefill; _release drops
            # the slot's pages and clears self._chunked.
            self._release(slot)
            return
        start, n, hashes = st['start'], st['n'], st['hashes']
        psize = self.pool.cfg.page_size
        mp_span = self.pool.cfg.max_pages_per_slot * psize
        piece = min(self.prefill_chunk, n - start)
        self.perf['prefill_chunks'] += 1
        # A prefill chunk shares this iteration with the decode chunk;
        # exclude the interval from the steady-state decode rate (same
        # rule as admissions — 'prefill excluded by construction').
        self._had_admission = True
        final = start + piece >= n
        sb = self._bucket_for(piece)
        if start + sb > mp_span:
            # Padded writes must not spill past the per-slot view; a
            # page-rounded piece always fits (start and mp_span are
            # page-aligned and start + piece <= n <= mp_span).
            sb = -(-piece // psize) * psize
        padded = np.zeros((1, sb), np.int32)
        padded[0, :piece] = req.tokens[start:start + piece]
        # Intermediate chunks pass their own end as `length` (the logit
        # row is computed but unused); the final chunk passes the true
        # prompt length and its logits become the first token.
        length_arg = n if final else start + piece
        first_page = start // psize
        end_page = min(-(-(start + piece) // psize),
                       int((row > 0).sum()))
        ids = row[first_page:end_page]
        with self._ctx():
            greedy, logits, pc = self._jit_prefill_suffix(
                self._vars([req.params.lora_id]),
                jnp.asarray(padded), jnp.int32(start),
                jnp.asarray([length_arg]), self.cache['k'],
                self.cache['v'], self.cache.get('k_scale'),
                self.cache.get('v_scale'), jnp.asarray(row), bucket=sb)
            self._count_prefill_dispatch(
                1, dispatch_tokens=sb, real_tokens=piece)
            if not final:
                self.cache = self._jit_insert_pages(
                    self.cache, pc, jnp.asarray(ids),
                    jnp.int32(first_page * psize))
                if self.prefix_caching:
                    self.pool.publish(
                        slot, hashes[:(start + piece) // psize])
                st['start'] = start + piece
                return
            # One logits pull (multi-host: each pull is a collective);
            # first-token semantics shared with the other admission
            # paths via _first_token.
            logits_row = self._pull(logits)[0] \
                if req.params.temperature > 0.0 or req.params.logprobs \
                or req.params.logit_bias else None
            first, first_lp, temp = self._first_token(
                req, logits_row, lambda: int(self._pull(greedy)[0]))
            self.cache, self._dev_args = self._jit_insert_paged(
                self.cache, pc, jnp.int32(0),
                *self._ins_args(slot, req, first, temp),
                jnp.asarray(ids), jnp.asarray(row),
                jnp.int32(first_page * psize))
            if self.prefix_caching:
                self.pool.publish(slot, hashes[:n // psize])
        self._chunked = None
        self._complete_admission(req, slot, n, first, temp,
                                 first_lp=first_lp)

    def _req_done(self, req: _Request, token: int) -> bool:
        p = req.params
        if p.eos_token is not None and token == p.eos_token:
            return True
        if req.generated >= p.max_new_tokens:
            return True
        if self._lengths[req.slot] + 1 >= self.max_seq_len:
            return True
        return False

    def _release(self, slot: int,
                 status: Optional[str] = None) -> None:
        """status overrides the recorded trace outcome (the crash
        handler passes 'failed' — a killed request must not read as a
        normal completion in /stats)."""
        req = self._slots[slot]
        if req is not None:
            # Tick-plane ITL split: fold the request's accrued
            # decode-floor/interference seconds into the per-class
            # counters and its trace (visible at /stats?request_id=).
            extra: Dict[str, Any] = {}
            if self._tickstats is not None and (
                    req.itl_decode_s or req.itl_interference_s):
                extra = {
                    'itl_decode_s': round(req.itl_decode_s, 6),
                    'itl_interference_s':
                        round(req.itl_interference_s, 6)}
                self._tickstats.note_request(
                    req.params.priority or 'standard',
                    req.itl_decode_s, req.itl_interference_s)
            # Trace BEFORE the terminal None: put() unblocks the HTTP
            # handler, and a client hitting /stats?request_id= right
            # after its response must see the completed trace.
            self._trace_event(
                req.req_id, 'done', generated=req.generated,
                status=status or ('deadline' if req.expired
                                  else 'cancelled' if req.cancelled
                                  else 'done'),
                **extra)
            req.out_queue.put(None)
        if self._chunked is not None and self._chunked['slot'] == slot:
            # Crash-path release mid-chunked-prefill: abandon it.
            self._chunked = None
        self._slots[slot] = None
        self._slot_lora[slot] = 0
        self._lengths[slot] = 0
        self._conf_lengths[slot] = 0
        if self.cache_mode == 'paged' and req is not None:
            # Host: pages back to the free list. Device: point the
            # slot's table row at the dummy page — this dispatch chains
            # AFTER any in-flight chunk, and re-reservation only happens
            # on the next loop iteration, so the old pages cannot be
            # written by this slot once a new owner holds them.
            self.pool.release(slot)
            try:
                with self._ctx():
                    self.cache = self._jit_clear_slot(self.cache,
                                                      jnp.int32(slot))
            except Exception:  # pylint: disable=broad-except
                # _release also runs from the loop's CRASH handler, where
                # self.cache may reference a donated-then-deleted buffer;
                # cleanup (delivering the None sentinels) must not die on
                # a device dispatch. A live loop never takes this branch
                # without the decode dispatch itself having failed first.
                logger.exception('paged slot clear failed during release')

    def _loop(self) -> None:
        self.ready.set()
        try:
            self._loop_body()
        except Exception:  # pylint: disable=broad-except
            logger.exception('engine loop crashed; failing open requests')
            if self._lockstep is not None and self._lockstep.is_primary:
                # Best-effort release of follower hosts parked on the
                # next control broadcast. (A crashed FOLLOWER is the
                # distributed runtime's problem: its missed collective
                # trips the coordinator's failure detection.)
                try:
                    self._lockstep.broadcast(
                        {'new': [], 'cancel': [], 'stop': True})
                except Exception:  # pylint: disable=broad-except
                    pass
            for i, req in enumerate(self._slots):
                if req is not None:
                    self._release(i, status='failed')
            for req in (*self._admitting_many, self._admitting,
                        self._kv_fetching):
                if req is not None and req.slot is None:
                    # Died mid-admission, before _complete_admission
                    # installed it in _slots.
                    self._trace_event(req.req_id, 'done',
                                      status='failed')
                    req.out_queue.put(None)
            self._admitting_many = []
            self._admitting = None
            self._kv_fetching = None
            # Parked /kv/prefix exports must not wedge their server
            # executor threads on a dead loop.
            while self._kv_export_q:
                rq = self._kv_export_q.popleft()
                rq['pages'], rq['version'] = [], self.weight_version
                rq['event'].set()
            if self._deferred is not None:
                self._trace_event(self._deferred.req_id, 'done',
                                  status='failed')
                self._deferred.out_queue.put(None)
                self._deferred = None
            while True:
                try:
                    req = self._waiting.get_nowait()
                except queue.Empty:
                    break
                self._trace_event(req.req_id, 'done', status='failed')
                req.out_queue.put(None)
            self.ready.clear()
        finally:
            # A pending weight swap must not wedge its waiter on a
            # dead or stopped loop: fail it loudly (old weights stay
            # live; request_weight_swap raises on a None result).
            with self._lock:
                swap, self._swap_req = self._swap_req, None
            if swap is not None:
                swap['event'].set()

    def _loop_body(self) -> None:
        # PIPELINED decode: dispatch chunk k+1 BEFORE pulling chunk k's
        # tokens, so the device computes through the host round trip
        # instead of idling for the pull. Cost: slot release (and
        # therefore admission under load) lags by one chunk.
        pending = None  # (kind, toks_dev, counts_dev, entries, chunk)
        while True:
            if self._lockstep is not None:
                # Control broadcast: every host gets the same requests,
                # cancels, and stop decision for this tick. The stop
                # flag rides the broadcast so followers exit the SAME
                # tick as the primary (never mid-computation).
                if self._sync_tick():
                    break
            elif self._stop.is_set():
                break
            # Chaos hook (dormant unless SKYT_FAULTS arms it): 'error'
            # here crashes the loop — the crash handler fails open
            # requests and /health flips 503; 'latency' makes this a
            # slow replica.
            faults.inject('engine.loop')
            # Capacity-ledger busy mark: opened at the first tick of a
            # busy span, advanced at every _finish_chunk settle, and
            # cleared by the idle branch below — so busy intervals
            # cover admission + prefill + the in-flight chunk.
            if self._busy_mark is None:
                self._busy_mark = time.perf_counter()
            # Tick plane: open this tick's measurement window. Perf
            # counters snapshot here so the record can tell what THIS
            # tick admitted (deltas), without threading state through
            # every admission path.
            ts = self._tickstats
            if ts is not None:
                self._tick_t0 = time.perf_counter()
                self._tick_perf0 = (
                    self.perf['admitted_requests'],
                    self.perf['prefill_dispatch_tokens'],
                    self.perf['prefill_dispatches'])
            # In-place weight swap: apply at THIS tick boundary when
            # eligible (immediately, or once a draining swap's
            # in-flight requests have finished). While a draining swap
            # is still pending, admissions hold below so the drain
            # converges instead of racing new seats.
            # Lock-free peek on the hot path: _swap_req is rebound
            # under _lock by request_weight_swap, and a stale read
            # here only delays the apply/hold by ONE tick —
            # _maybe_apply_swap re-reads under the lock before acting.
            if self._swap_req is not None:  # noqa: lock-discipline
                self._maybe_apply_swap()
            swap_draining = \
                self._swap_req is not None  # noqa: lock-discipline
            # Deadline enforcement: expired requests cancel in place
            # (slot + KV pages free at the next delivery boundary).
            self._expire_deadlines()
            # Tiered prefix cache: re-admit a parked fleet fetch and
            # serve parked /kv/prefix exports (off path: one None
            # check).
            if self.kv_tier is not None:
                self._kv_tick()
            # QoS: re-run the fair scheduler over the backlog (class
            # order + aging credit + DRR tenant fairness) before this
            # tick's admissions. Lockstep engines reorder inside
            # _sync_tick instead — the order must ride the broadcast.
            if self._qos_queue is not None and self._lockstep is None \
                    and self._waiting.qsize() > 1:
                self._qos_queue.reorder(time.time())
            # Admit as many waiting requests as there are free slots.
            # Same-bucket bursts take the batched fast path (one prefill
            # dispatch for the group); everything else falls back to the
            # sequential path. Device-side arg/cache updates order after
            # any in-flight chunk via the dispatch chain.
            admitted = False
            # Isolated-prefill schedule (SKYT_TICKSTATS_ISOLATE): hold
            # admission while any decode slot is live, so prefill only
            # runs from all-idle ticks and decode chunks never share a
            # tick with it — the counterfactual the mixed schedule is
            # compared against (tests/test_tickstats.py).
            hold_admission = swap_draining or (
                self._isolate_prefill and
                any(s is not None for s in self._slots))
            while None in self._slots and not hold_admission:
                if self._try_admit_ragged():
                    admitted = True
                    continue
                if self._try_admit_batch():
                    admitted = True
                    continue
                if not self._admit_one():
                    break
                admitted = True
            # Admission over: any request is now findable in _slots /
            # _deferred / _chunked, so drop the mid-admission pointer
            # (defer paths exit _admit_one without clearing it).
            self._admitting = None
            # One chunk of any in-progress long-prompt prefill, then a
            # decode chunk — running requests keep streaming while the
            # long admission fills its pages.
            chunking = self._chunked is not None
            self._advance_chunked()
            active = [i for i, r in enumerate(self._slots)
                      if r is not None and not (
                          self._chunked is not None
                          and self._chunked['slot'] == i)]
            new_pending = None
            upper = 0
            if active:
                # Chunk size: the configured chunk, capped by remaining
                # cache space. Do NOT shrink to the smallest remaining
                # token budget — each distinct n is a separate XLA
                # compile (~seconds), so running the full chunk and
                # discarding post-completion tokens host-side is far
                # cheaper than a recompile ladder.
                rem_space = self.max_seq_len - 1 - int(
                    max(self._lengths[i] for i in active))
                sampling = any(self._temps[i] > 0 for i in active)
                penalize = any(
                    self._slots[i].params.presence_penalty != 0.0 or
                    self._slots[i].params.frequency_penalty != 0.0
                    for i in active)
                biased = any(self._slots[i].params.logit_bias
                             for i in active)
                k = self.spec_decode
                # Speculation needs headroom for the worst case (every
                # draft accepted); sampled slots ride the rejection-
                # sampling verify (speculative_sample_step). Penalized
                # slots fall back to the plain path: the penalty target
                # shifts WITHIN a draft run (each emitted token changes
                # the counts), which the one-shot verify cannot honor —
                # the same fallback vLLM makes.
                use_spec = k > 0 and not penalize and not biased \
                    and rem_space // (k + 1) >= 1
                self._ensure_dev_args()
                (d_last, d_lens, d_temps, d_keys, d_topks, d_topps,
                 d_press, d_freqs, d_counts, d_bidx,
                 d_bval) = self._dev_args
                entries = [(i, self._slots[i]) for i in active]
                if use_spec:
                    bound = max(1, min(self.decode_chunk,
                                       rem_space // (k + 1)))
                    chunk = 1 << (bound.bit_length() - 1)
                    with self._ctx():
                        if self.draft_model is not None:
                            toks, lps, counts, self.cache, \
                                self._draft_cache, d_last, d_lens, \
                                d_keys = self._jit_decode_spec_draft(
                                    self._vars(self._slot_lora),
                                    self.draft_params,
                                    self.cache, self._draft_cache,
                                    d_last, d_lens, d_temps, d_keys,
                                    d_topks, d_topps, n=chunk, k=k,
                                    sampling=sampling)
                        else:
                            toks, lps, counts, self.cache, d_last, \
                                d_lens, d_keys, self._dev_hist = \
                                self._jit_decode_spec(
                                    self._vars(self._slot_lora),
                                    self.cache, d_last,
                                    d_lens, d_temps, d_keys, d_topks,
                                    d_topps, self._dev_hist, n=chunk,
                                    k=k, sampling=sampling)
                    self._dev_args = (d_last, d_lens, d_temps, d_keys,
                                      d_topks, d_topps, d_press,
                                      d_freqs, d_counts, d_bidx, d_bval)
                    new_pending = ('spec', toks, lps, counts,
                                   entries, chunk)
                    upper = chunk * (k + 1)
                else:
                    bound = max(1, min(self.decode_chunk, rem_space))
                    # Power of two: `n` is a static jit arg, arbitrary
                    # values would each trigger a compile.
                    chunk = 1 << (bound.bit_length() - 1)
                    with self._ctx():
                        toks, lps, self.cache, keys, d_last, \
                            d_lens, d_counts, self._dev_hist = \
                            self._jit_decode_n(
                                self._vars(self._slot_lora),
                                self.cache, d_last, d_lens,
                                d_temps, d_keys, d_topks, d_topps,
                                d_press, d_freqs, d_counts,
                                self._dev_hist, d_bidx, d_bval,
                                n=chunk, sampling=sampling,
                                penalize=penalize, biased=biased)
                    self._dev_args = (d_last, d_lens, d_temps, keys,
                                      d_topks, d_topps, d_press,
                                      d_freqs, d_counts, d_bidx, d_bval)
                    new_pending = ('plain', toks, lps, None,
                                   entries, chunk)
                    upper = chunk
            self._update_metric_gauges()
            if pending is not None:
                self._finish_chunk(pending)
            elif not active and not admitted and not chunking:
                # Going idle: settle any unsettled work (a request that
                # finished at admission — prefill-only — never reaches
                # a _finish_chunk pull), then drop the busy mark so
                # idle scanning never counts as busy time.
                if self.ledger.pending() and self._busy_mark is not None:
                    self.ledger.settle(
                        time.perf_counter() - self._busy_mark)
                self._busy_mark = None
                time.sleep(0.002)
            if ts is not None and pending is None and (admitted or
                                                       chunking):
                # Prefill-only tick: admission / chunked-prefill work
                # with no chunk pull. Mixed and pure-decode ticks
                # record inside _finish_chunk at the pipeline sync
                # point instead (before releases, so a request that
                # completes in its first chunk still gets a split);
                # idle ticks are never recorded.
                self._tick_record(time.perf_counter(), (), 0)
            # Resync the sizing estimate: confirmed lengths plus the
            # in-flight chunk's worst-case advance.
            self._lengths = self._conf_lengths + upper
            pending = new_pending
        if pending is not None:
            self._finish_chunk(pending)

    def _sync_tick(self) -> bool:
        """One lockstep control exchange (multi-host only). Returns
        True when this tick is the stop tick. See infer/multihost.py
        for the protocol rationale."""
        if self._lockstep.is_primary:
            new: List[_Request] = []
            while True:
                try:
                    new.append(self._ingress.get_nowait())
                except queue.Empty:
                    break
            with self._lock:
                cancels = self._pending_cancels
                self._pending_cancels = []
            stop = self._stop.is_set()
            # QoS: seat the new requests FIRST, then schedule, so the
            # broadcast order covers them. Safe — only this thread
            # consumes _waiting, and admission runs after the tick.
            qorder = None
            if self._qos_queue is not None:
                for r in new:
                    self._waiting.put(r)   # qos-admission (sanctioned)
                order, changed = self._qos_queue.reorder(time.time())
                if changed:
                    # Followers' deques already match ours except when
                    # this reorder rewrote it (puts and pops replicate
                    # tick-by-tick), so only changed orders broadcast.
                    qorder = order
                    self._last_qorder = order
            blob = None
            if new or cancels or stop or qorder is not None:
                blob = {'new': [(r.req_id, r.tokens, r.params)
                                for r in new],
                        'cancel': cancels, 'stop': stop}
                if qorder is not None:
                    blob['qorder'] = qorder
            self._lockstep.broadcast(blob)
            if self._qos_queue is None:
                for r in new:
                    self._waiting.put(r)   # qos-admission (sanctioned)
        else:
            blob = self._lockstep.broadcast(None)
            if blob is not None:
                from skypilot_tpu.infer import multihost
                for rid, toks, params in blob['new']:
                    self._waiting.put(_Request(  # qos-admission
                        req_id=rid, tokens=list(toks), params=params,
                        out_queue=multihost.DiscardQueue(),
                        rng=np.random.default_rng(params.seed + rid)))
                if self._qos_queue is not None and \
                        blob.get('qorder') is not None:
                    # Followers never reorder locally (their clocks
                    # must not influence admission order); they apply
                    # the primary's broadcast schedule verbatim.
                    self._qos_queue.apply_order(blob['qorder'])
        if blob is None:
            return False
        for rid in blob['cancel']:
            self._apply_cancel(rid)
        return bool(blob['stop'])

    def _finish_chunk(self, pending) -> None:
        """Pull a dispatched chunk's tokens and deliver them; release
        completed slots and advance the confirmed lengths. The sync
        point of the pipeline.

        Host work is VECTORIZED: the EOS / max-token / max-seq-len
        cutoff for every slot is computed with numpy over the whole
        [chunk, SLOTS] (spec: [chunk, SLOTS, k+1]) token array, and each
        slot's surviving run is delivered in ONE batched out_queue put
        (_put_many) — replacing the per-token Python loop + per-token
        queue lock that dominated steady-state host time at large
        chunk x slots. perf['host_finish_s'] accumulates the post-pull
        host time (cutoff math + delivery)."""
        kind, toks_dev, lps_dev, counts_dev, entries, chunk = pending
        toks_np = self._pull(toks_dev)        # sync point
        counts_np = self._pull(counts_dev) if counts_dev is not None \
            else None
        # Logprobs pulled only when some request in this chunk wants
        # them (an extra [chunk, SLOTS(, k+1)] f32 transfer otherwise).
        lps_np = self._pull(lps_dev) if any(
            req.params.logprobs for _, req in entries) else None
        now = time.perf_counter()
        delivered = 0
        trace_on = tracing.enabled()
        # Tick plane: the pull is this tick's measurement endpoint —
        # record the tick and accrue its attributed interference to
        # the chunk's requests BEFORE delivery, so a request that
        # completes (and releases) in this very chunk still reports
        # its ITL split in the 'done' trace event.
        if self._tickstats is not None and self._tick_t0 is not None:
            if kind == 'spec':
                pulled = int(counts_np[:, [i for i, _ in
                                           entries]].sum())
            else:
                pulled = chunk * len(entries)
            self._tick_record(now, entries, pulled, trace_on=trace_on)
        # Per-slot ACTUAL start position of this chunk's first token
        # (confirmed length is only advanced at chunk pulls, so it is
        # this chunk's true starting point).
        base = {i: int(self._conf_lengths[i]) for i, _ in entries}
        for i, req in entries:
            if self._slots[i] is not req:
                continue  # finished earlier / slot re-admitted
            if req.cancelled:
                # Cancelled mid-flight: free the slot at this delivery
                # boundary; tokens already computed for it in this
                # chunk are dropped.
                self._release(i)
                continue
            p = req.params
            if kind == 'spec':
                # [chunk, SLOTS, k+1]; the first counts[t, i] entries
                # of each verify step's row are valid. Flatten the
                # valid tokens in delivery order (t-major).
                c = counts_np[:, i]                          # [chunk]
                valid = np.arange(toks_np.shape[2])[None, :] \
                    < c[:, None]
                flat = toks_np[:, i, :][valid]
                flat_lps = lps_np[:, i, :][valid] \
                    if lps_np is not None else None
            else:
                flat = toks_np[:, i]                         # [chunk]
                flat_lps = lps_np[:, i] if lps_np is not None else None
            total = int(flat.shape[0])
            # Cutoffs: tokens up to AND INCLUDING the first EOS; at
            # most max_new_tokens total; position capped below
            # max_seq_len - 1. Each uses the token's own position (a
            # post-chunk check would drop valid tokens in final
            # chunks).
            if p.eos_token is not None:
                hits = np.flatnonzero(flat == p.eos_token)
                n_eos = int(hits[0]) + 1 if hits.size else total + 1
            else:
                n_eos = total + 1
            n_raw = min(n_eos, p.max_new_tokens - req.generated,
                        self.max_seq_len - 1 - base[i])
            n_del = min(total, n_raw)
            if n_del > 0:
                if p.logprobs:
                    items = list(zip((int(t) for t in flat[:n_del]),
                                     (float(v)
                                      for v in flat_lps[:n_del])))
                else:
                    items = flat[:n_del].tolist()
                _put_many(req.out_queue, items)
                req.generated += n_del
                delivered += n_del
                base[i] += n_del
                self.ledger.note(self._ledger_key(req), n_del)
                if trace_on:
                    # Pipelined-delivery boundary: n tokens of this
                    # request surfaced from a `chunk`-wide dispatch.
                    self._trace_span_event(req.req_id, 'decode_chunk',
                                           n=n_del, chunk=chunk)
            if kind == 'spec':
                # Acceptance accounting matches the sequential path: a
                # verify step whose run STARTED before the cutoff
                # counts in full (the cutoff may land mid-run).
                starts = np.cumsum(c) - c
                dmask = starts < max(n_del, 1)
                self.perf['spec_verify_steps'] += int(dmask.sum())
                self.perf['spec_accepted'] += int((c[dmask] - 1).sum())
            if n_raw <= total:
                self._release(i)
        for i, req in entries:
            if self._slots[i] is req:
                self._conf_lengths[i] = base[i]
        self.perf['decode_tokens'] += delivered
        self.perf['decode_chunks'] += 1
        self._m_decode_tokens.inc(delivered)
        if kind == 'spec':
            self.perf['spec_steps'] += chunk
            self.perf['spec_tokens'] += delivered
        # Steady-state rate: pull-to-pull intervals with no admission in
        # between (prefill and its sync excluded by construction).
        if self._last_pull_t is not None and not self._had_admission:
            self.perf['steady_tokens'] += delivered
            self.perf['steady_time_s'] += now - self._last_pull_t
            if delivered > 0:
                # Chunk-mean inter-token latency: tokens arrive in
                # pulled chunks, so the per-token time within a chunk
                # is unobservable — the pull interval divided by the
                # chunk's delivered count is the honest estimator.
                self._m_itl.observe((now - self._last_pull_t)
                                    / delivered)
        self._last_pull_t = now
        self._had_admission = False
        # Capacity ledger: the pull is the pipeline's sync point, so
        # mark -> now is a measured busy interval; split it across the
        # work noted since the last settle (admitted prompt tokens +
        # this chunk's delivered tokens).
        if self._busy_mark is not None:
            self.ledger.settle(now - self._busy_mark)
        self._busy_mark = now
        host_s = time.perf_counter() - now
        self.perf['host_finish_s'] += host_s
        self._m_host_finish.inc(host_s)
        if self._tickstats is not None:
            # Delivery host work postdates the record cut at the pull;
            # attach it to the tick it belongs to.
            self._tickstats.note_host(host_s)

    def _tick_record(self, end_t: float, entries, tokens: int, *,
                     trace_on: bool = False) -> None:
        """Fold one engine tick into the tick plane (only reachable
        with tickstats on; no-op if this tick's window was already
        recorded). Composition comes from the perf-counter deltas
        snapshotted at the tick top, so no admission path needed
        instrumenting; ``entries`` is the finished chunk's
        (slot, req) list — each of those requests accrues the tick's
        attributed interference before any release path can run."""
        ts = self._tickstats
        t0 = self._tick_t0
        if ts is None or t0 is None:
            return
        self._tick_t0 = None
        dur = max(end_t - t0, 0.0)
        a0, pt0, pd0 = self._tick_perf0
        prefill_reqs = int(self.perf['admitted_requests'] - a0)
        prefill_toks = int(self.perf['prefill_dispatch_tokens'] - pt0)
        dispatches = int(self.perf['prefill_dispatches'] - pd0)
        if prefill_reqs == 0 and prefill_toks > 0:
            # A chunked long-prompt prefill advanced (admission only
            # counts at completion) — still prefill co-residency.
            prefill_reqs = 1
        if not entries and prefill_reqs == 0 and prefill_toks == 0:
            return   # nothing measurable happened (deferred admission)
        # Per-dispatch width = the compiled bucket (B x bucket padded,
        # packed T ragged) — measured from the counters rather than
        # threaded through three admission paths.
        bucket = prefill_toks // dispatches if dispatches > 0 else 0
        if self.pool is not None:
            total = self.pool.cfg.n_pages - 1   # page 0 is the dummy
            kv_frac = ((total - self.pool.free_pages()) / total
                       if total > 0 else None)
        else:
            denom = self.num_slots * self.max_seq_len
            kv_frac = (float(self._conf_lengths.sum()) / denom
                       if denom > 0 else None)
        from skypilot_tpu.ops import dispatch as ops_dispatch
        _, baseline, excess = ts.on_tick(
            dur_s=dur,
            active_slots=len(entries),
            decode_reqs=len(entries),
            tokens=int(tokens),
            prefill_reqs=prefill_reqs,
            prefill_tokens=prefill_toks,
            prefill_bucket=bucket,
            kv_frac=kv_frac,
            kernel_paths=ops_dispatch.snapshot())
        if not entries:
            return
        # Every request decoding in a mixed tick pays the FULL excess:
        # ITL is per-request wall time, not a pool shared across the
        # batch.
        floor = max(dur - excess, 0.0)
        for _, req in entries:
            req.itl_decode_s += floor
            req.itl_interference_s += excess
            if trace_on and excess > 0.0:
                self._trace_span_event(
                    req.req_id, 'interference',
                    excess_ms=round(excess * 1e3, 3),
                    baseline_ms=round((baseline or 0.0) * 1e3, 3))
