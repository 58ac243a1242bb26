"""Benchmark: flagship-model training throughput on the local TPU.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "extra_metrics": [...]}

On the TPU (1 chip, v5e): Llama-1B-shaped bf16 train step; reports model
FLOPs utilization (MFU). Baseline = 0.45 MFU, the BASELINE.json north-star
target for Llama-3.1-8B SFT on v5e-16 (tokens/sec/chip is printed to stderr
as auxiliary context). extra_metrics carries the serving benchmark
(p50 TTFT + decode tok/s/chip on the continuous-batching engine,
BASELINE.md's serve row; baseline 500ms TTFT) and the debug-model
control-plane drills.

There is no CPU stand-in: with no TPU the script exits non-zero before
it measures anything, and a run in which any phase failed prints what
it has and exits non-zero. This process holds the chip, so every
replica a drill starts as a child is pinned to the CPU
(`_cpu_child_env`): a chip belongs to one process.
"""
import contextlib
import dataclasses
import json
import os
import signal
import sys
import time

import jax
import jax.numpy as jnp

BASELINE_MFU = 0.45
BASELINE_TTFT_MS = 500.0  # BASELINE.json: 70B serve p50 TTFT < 500ms

# Per-phase SIGALRM deadlines (seconds).
PHASE_DEADLINES = {
    'train bench': 1200,
    'serve bench': 900,
    'serve int8 bench': 600,
    'serve int4 bench': 600,
    'serve spec-decode bench': 1800,
    'serve 8b int8 bench': 900,
    'host overhead bench': 600,
    'tracing overhead bench': 420,
    'chaos recovery bench': 600,
    'overload bench': 420,
    'affinity bench': 600,
    'slo report bench': 420,
    'kv+ragged bench': 600,
    'kv tier bench': 600,
    'watchdog overhead bench': 300,
    'weight swap bench': 480,
    'adapter fleet bench': 720,
    'comms plane bench': 600,
    'capacity bench': 600,
    'interference bench': 600,
    'elastic bench': 600,
}

class PhaseTimeout(Exception):
    pass


def _cpu_child_env(**extra) -> dict:
    """Environment for a replica a drill starts as a child process.
    This process holds the chip and a chip belongs to one process, so
    the child is told to use the CPU (the drills run the `debug` model
    and measure the control plane, not the device)."""
    return dict(os.environ, JAX_PLATFORMS='cpu', **extra)


@contextlib.contextmanager
def phase_deadline(seconds: int, what: str):
    """A phase that overruns its deadline surfaces as a failed PHASE
    (and a non-zero exit), not a bench that never returns."""
    def _raise(signum, frame):
        raise PhaseTimeout(f'{what} exceeded {seconds}s')
    old = signal.signal(signal.SIGALRM, _raise)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)

# bf16 peak per chip is owned by utils/profiling.py so the bench, the
# trainer's published skyt_train_mfu, and the fleet cost report all
# divide by the same table.
from skypilot_tpu.utils import profiling as profiling_lib


def _reclaim_hbm(tag: str) -> None:
    """Drop every reclaimable device buffer between bench phases.

    Phases share one process; the 8B int8 phase needs ~10GB of the
    v5e's 16GB HBM, so a lingering train state (params + Adam moments
    of the 1.24B model ≈ 12GB) or an un-collected engine from an
    earlier phase starves it (observed: RESOURCE_EXHAUSTED on the 8B
    and spec phases after the 1B phases passed). gc drops cycles,
    clear_caches drops jit executables' tracing residue; the live-bytes
    print diagnoses what survives if the next phase still OOMs."""
    import gc
    gc.collect()
    jax.clear_caches()
    gc.collect()
    try:
        live = [b for b in jax.live_arrays() if b.size]
        tot = sum(b.size * b.dtype.itemsize for b in live)
        print(f'# hbm[{tag}]: {len(live)} live arrays, '
              f'{tot/1e9:.2f}GB retained', file=sys.stderr)
    except Exception:  # pylint: disable=broad-except
        pass


def _tpu_serve_cfg(**overrides):
    from skypilot_tpu.benchmark import serve_bench
    base = dict(model='llama3-1b', prompt_len=512, max_new_tokens=64,
                num_requests=16, num_slots=8, max_seq_len=1024,
                decode_chunk=32)
    base.update(overrides)
    return serve_bench.ServeBenchConfig(**base)


def _best_of_serve_runs(scfg, n: int = 2, **engine_kwargs) -> list:
    """Build one engine, run the serve bench n times on it, stop it.

    Best-of-n on one engine (compile paid once). prefix_caching stays
    OFF for every bench
    engine: pass 2 replays pass 1's prompts (same rng seed), so with
    the cache on its "prefill" would be a short suffix — measuring the
    cache, not the engine, against a baseline measured without it.
    """
    from skypilot_tpu.benchmark import serve_bench
    from skypilot_tpu.infer import server as server_lib

    engine = server_lib.build_engine(scfg.model, scfg.num_slots,
                                     scfg.max_seq_len, tp=scfg.tp,
                                     decode_chunk=scfg.decode_chunk,
                                     prefix_caching=False,
                                     spec_decode=scfg.spec_decode,
                                     **engine_kwargs)
    engine.start()
    try:
        return [serve_bench.run_serve_bench(scfg, engine=engine)
                for _ in range(n)]
    finally:
        engine.stop()


def serve_metrics() -> list:
    """Serving TTFT/throughput on the continuous-batching engine
    (BASELINE.md serve row). Random weights: latency is shape-bound."""
    runs = _best_of_serve_runs(_tpu_serve_cfg())
    r = min(runs, key=lambda x: x['p50_ttft_ms'])
    r['decode_tok_per_sec_steady'] = max(
        x['decode_tok_per_sec_steady'] for x in runs)
    r['decode_tok_per_sec'] = max(x['decode_tok_per_sec'] for x in runs)
    print(f'# serve: p50_ttft={r["p50_ttft_ms"]:.1f}ms '
          f'p99_ttft={r["p99_ttft_ms"]:.1f}ms '
          f'decode_wall={r["decode_tok_per_sec"]:,.0f} tok/s '
          f'decode_steady={r["decode_tok_per_sec_steady"]:,.0f} tok/s',
          file=sys.stderr)
    # best_of records the selection policy (p50/p99 from the min-TTFT
    # run, decode rates max'd across runs) so downstream comparisons to
    # a single-run BASELINE measurement know these are best-of-N.
    return [
        {'metric': 'serve_p50_ttft_ms_llama1b_1chip',
         'value': round(r['p50_ttft_ms'], 1), 'unit': 'ms',
         'vs_baseline': round(BASELINE_TTFT_MS / max(r['p50_ttft_ms'],
                                                     1e-3), 4),
         'best_of': len(runs)},
        {'metric': 'serve_decode_steady_tok_per_sec_per_chip',
         'value': round(r['decode_tok_per_sec_steady'], 1),
         'unit': 'tok/s/chip',
         'vs_baseline': round(r['decode_tok_per_sec_steady'] / 1000.0,
                              4),  # target: >=1,000 tok/s/chip (1B)
         'best_of': len(runs)},
        {'metric': 'serve_decode_wall_tok_per_sec_per_chip',
         'value': round(r['decode_tok_per_sec'], 1),
         'unit': 'tok/s/chip', 'vs_baseline': None,
         'best_of': len(runs)},
        # $/1M generated tokens at the catalog's v5e on-demand chip
        # price (BASELINE.md primary metric; the reference's whole
        # pitch is cost). Steady decode rate -> cost of pure
        # generation; spot would be ~2.3x cheaper.
        {'metric': 'serve_cost_per_mtok_usd',
         'value': _cost_per_mtok(r['decode_tok_per_sec_steady']),
         'unit': 'USD/1M-tok', 'vs_baseline': None,
         'best_of': len(runs)},
    ]


def _cost_per_mtok(tok_per_sec: float,
                   accelerator: str = 'tpu-v5e-1') -> 'float | None':
    """Generation cost from the engine's steady decode rate and the
    catalog's on-demand chip price."""
    if tok_per_sec <= 0:
        return None
    try:
        from skypilot_tpu import catalog
        offs = catalog.list_accelerators('gcp').get(accelerator) or []
        price = min(o.price for o in offs if o.price is not None)
    except Exception:  # pylint: disable=broad-except
        return None
    return round(price / (tok_per_sec * 3600.0) * 1e6, 4)


def serve_int8_metric(bf16_steady: float) -> list:
    """int8 weight-only pass (TPU workload shape): same serve workload
    on a quantized engine — decode is weight-HBM-bound, so this
    quantifies the --quantize int8 speedup. Runs as its OWN phase in
    main() so a slow/failed int8 pass can never cost the mandatory bf16
    metrics."""
    qruns = _best_of_serve_runs(_tpu_serve_cfg(), quantize='int8')
    int8_steady = max(x['decode_tok_per_sec_steady'] for x in qruns)
    print(f'# serve int8: decode_steady={int8_steady:,.0f} tok/s',
          file=sys.stderr)
    return [
        {'metric': 'serve_decode_steady_tok_per_sec_per_chip_int8',
         'value': round(int8_steady, 1), 'unit': 'tok/s/chip',
         # speedup vs the bf16 engine; None when the bf16 phase
         # produced no number (a ratio against a floor is nonsense)
         'vs_baseline': (round(int8_steady / bf16_steady, 4)
                         if bf16_steady > 0 else None),
         'best_of': len(qruns)},
    ]


def serve_int4_metric(bf16_steady: float) -> list:
    """int4 (w4a16, group-128) pass: quarter the weight bytes per
    decode step. Beyond the reference's stack — vLLM needs a
    pre-quantized AWQ/GPTQ checkpoint for w4; here any float model
    stream-quantizes at load (models/quant.py)."""
    qruns = _best_of_serve_runs(_tpu_serve_cfg(), quantize='int4')
    int4_steady = max(x['decode_tok_per_sec_steady'] for x in qruns)
    print(f'# serve int4: decode_steady={int4_steady:,.0f} tok/s',
          file=sys.stderr)
    return [
        {'metric': 'serve_decode_steady_tok_per_sec_per_chip_int4',
         'value': round(int4_steady, 1), 'unit': 'tok/s/chip',
         'vs_baseline': (round(int4_steady / bf16_steady, 4)
                         if bf16_steady > 0 else None),
         'best_of': len(qruns)},
    ]


def serve_spec_metric() -> list:
    """Speculative-decoding pass on the doc-grounded workload (internal
    n-gram repetition — the summarize/RAG shape prompt-lookup exists
    for; the random-token workload would measure ~0 acceptance by
    construction). Reports acceptance and the measured speedup (or
    honest slowdown) vs the same engine with spec off. Greedy-only:
    sampling slots fall back to plain decode."""
    wall = {}
    steady_spec = 0.0
    accept = 0.0
    draft_accept = 0.0
    for k in (0, 4):
        scfg = _tpu_serve_cfg(workload='doc', spec_decode=k)
        runs = _best_of_serve_runs(scfg)
        # Wall rate over the whole burst: well-defined for both engines
        # on the identical workload (the steady accumulator needs
        # admission-free pull intervals, which short spec runs may
        # never produce — every k+1-token step lands near an admission).
        wall[k] = max(x['decode_tok_per_sec'] for x in runs)
        if k > 0:
            accept = max(x['spec_accept_per_step'] for x in runs)
            steady_spec = max(x['decode_tok_per_sec_steady']
                              for x in runs)
    # Draft-MODEL proposer on the same workload, self-drafting (the
    # only honest draft available without a second real checkpoint:
    # random-init draft weights would measure chance acceptance).
    # Self-draft acceptance is the mechanism's ceiling (=k when the
    # draft cache stays position-aligned with the target — exactly
    # what this phase proves on-chip); the n-gram accept number above
    # is the production proposer's, a real draft checkpoint lands
    # between the two (engine --draft-checkpoint).
    scfg = _tpu_serve_cfg(workload='doc', spec_decode=4)
    runs = _best_of_serve_runs(scfg, draft_model_name='self')
    draft_accept = max(x['spec_accept_per_step'] for x in runs)
    print(f'# serve spec: wall spec={wall[4]:,.0f} '
          f'plain={wall[0]:,.0f} tok/s accept/step={accept:.2f} '
          f'draft(self) accept/step={draft_accept:.2f}',
          file=sys.stderr)
    return [
        {'metric': 'serve_spec_decode_tok_per_sec_doc',
         'value': round(wall[4], 1), 'unit': 'tok/s/chip',
         # measured speedup (or honest slowdown) vs the spec-off
         # engine on the SAME workload
         'vs_baseline': (round(wall[4] / wall[0], 4)
                         if wall[0] > 0 else None),
         'best_of': 2},
        {'metric': 'serve_spec_accept_per_step_doc',
         'value': round(accept, 3), 'unit': 'tokens/verify-step',
         'vs_baseline': None, 'best_of': 2},
        {'metric': 'serve_spec_decode_steady_tok_per_sec_doc',
         'value': round(steady_spec, 1), 'unit': 'tok/s/chip',
         'vs_baseline': None, 'best_of': 2},
        # Acceptance ceiling of the draft-model proposer (self-draft
        # = position-aligned by construction; k=4 expected).
        {'metric': 'serve_spec_draft_accept_per_step_doc',
         'value': round(draft_accept, 3), 'unit': 'tokens/verify-step',
         'vs_baseline': None, 'best_of': 2},
    ]


def serve_8b_int8_metric() -> list:
    """TRUE Llama-3.1-8B-shaped serving, int8 weight-only, ONE chip.

    8B int8 weights (~8.5GB) fit a single 16GB v5e — the first real
    step from the 1B proxy toward BASELINE.md's 70B serve row, runnable
    on the hardware that exists. Reduced slots (4 x 2048 paged) keep
    the KV pool ~1GB. Engine init fuses init+quantize in one jit so the
    bf16 tree is never fully resident (infer/server.py).
    """
    scfg = _tpu_serve_cfg(model='llama3-8b', num_slots=4,
                          max_seq_len=2048, prompt_len=512,
                          max_new_tokens=32, num_requests=8)
    runs = _best_of_serve_runs(scfg, quantize='int8')
    r = min(runs, key=lambda x: x['p50_ttft_ms'])
    steady = max(x['decode_tok_per_sec_steady'] for x in runs)
    print(f'# serve 8b int8: p50_ttft={r["p50_ttft_ms"]:.1f}ms '
          f'decode_steady={steady:,.0f} tok/s', file=sys.stderr)
    return [
        {'metric': 'serve_p50_ttft_ms_8b_int8_1chip',
         'value': round(r['p50_ttft_ms'], 1), 'unit': 'ms',
         # BASELINE.md 70B serve row: p50 TTFT < 500ms (here 8B/1chip)
         'vs_baseline': round(BASELINE_TTFT_MS /
                              max(r['p50_ttft_ms'], 1e-3), 4),
         'best_of': len(runs)},
        {'metric': 'serve_decode_steady_tok_per_sec_8b_int8_1chip',
         'value': round(steady, 1), 'unit': 'tok/s/chip',
         'vs_baseline': None, 'best_of': len(runs)},
    ]


def host_overhead_metrics() -> list:
    """Micro-bench of the host-device overlap layer (the debug model's
    device time is tiny, so these are HOST-side numbers).

    Reports, from the engine's own perf counters over a burst of
    same-bucket requests:
      * host_finish_s_per_token — steady-state host seconds of
        post-pull delivery work per generated token (the vectorized
        _finish_chunk's cost).
      * admission_dispatches_per_request — target prefill dispatches
        divided by admitted requests (< 1.0 proves batched admission
        amortized prefills across the burst).
    """
    from skypilot_tpu.infer import engine as engine_lib
    from skypilot_tpu.infer import server as server_lib

    n_requests, n_slots = 8, 4
    eng = server_lib.build_engine('debug', num_slots=n_slots,
                                  max_seq_len=64, decode_chunk=8,
                                  cache_mode='dense',
                                  prefix_caching=False)
    eng.start()
    try:
        prompts = [[(i * 7 + j) % 50 + 1 for j in range(24)]
                   for i in range(n_requests)]
        # Warm the compiles (prefill buckets + insert + decode chunk)
        # so the measured burst is steady-state, not tracing.
        eng.generate(prompts[0], engine_lib.SamplingParams(
            max_new_tokens=4))
        eng.reset_perf()
        queues = [eng.submit(p, engine_lib.SamplingParams(
            max_new_tokens=16))[1] for p in prompts]
        for q in queues:
            while q.get(timeout=120) is not None:
                pass
        perf = eng.perf_stats()
    finally:
        eng.stop()
    host_per_tok = (perf['host_finish_s']
                    / max(perf['decode_tokens'], 1))
    disp_per_req = (perf['prefill_dispatches']
                    / max(perf['admitted_requests'], 1))
    print(f'# host overhead: {host_per_tok*1e6:.1f}us host/token, '
          f'{perf["prefill_dispatches"]} prefill dispatches / '
          f'{perf["admitted_requests"]} requests '
          f'(max batch {perf["admission_batch_size"]})',
          file=sys.stderr)
    return [
        {'metric': 'host_finish_s_per_token',
         'value': round(host_per_tok, 9), 'unit': 's/tok',
         'vs_baseline': None},
        {'metric': 'admission_dispatches_per_request',
         'value': round(disp_per_req, 4), 'unit': 'dispatches/request',
         # 1.0 = the old one-prefill-per-request admission; < 1.0 is
         # the batched-admission win.
         'vs_baseline': (round(1.0 / disp_per_req, 4)
                         if disp_per_req > 0 else None)},
    ]


def tracing_overhead_metrics() -> list:
    """Tracing-plane overhead on the REAL serving surface (CPU-runnable,
    like the host-overhead phase): p50 wall latency of /generate
    requests through the full aiohttp middleware stack with tracing
    disabled (SKYT_TRACE=0 — the no-op singleton path) vs fully on
    (sample rate 1.0, so every request's spans are built, bridged from
    the engine phase trace, and retained). Acceptance
    (docs/observability.md): the enabled-vs-disabled p50 delta stays
    within ~2% — tracing must be cheap enough to leave on.

    Reported per-mode p50s use the better of 2 interleaved passes each
    (same co-tenant-noise rationale as _best_of_serve_runs)."""
    import socket
    import statistics
    import threading

    import requests
    from aiohttp import web

    from skypilot_tpu.infer import server as server_lib

    eng = server_lib.build_engine('debug', num_slots=2, max_seq_len=64,
                                  decode_chunk=8, cache_mode='dense',
                                  prefix_caching=False)
    eng.start()
    srv = server_lib.InferenceServer(eng)
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        port = s.getsockname()[1]
    threading.Thread(target=lambda: web.run_app(
        srv.make_app(), port=port, print=None, handle_signals=False),
        daemon=True).start()
    base = f'http://127.0.0.1:{port}'
    sess = requests.Session()
    deadline = time.time() + 60
    while time.time() < deadline:
        try:
            if sess.get(base + '/health', timeout=2).status_code == 200:
                break
        except requests.RequestException:
            pass
        time.sleep(0.2)

    payload = {'tokens': [7, 8, 9, 10], 'max_tokens': 8}

    def p50(n):
        lats = []
        for _ in range(n):
            t0 = time.perf_counter()
            r = sess.post(base + '/generate', json=payload, timeout=60)
            r.raise_for_status()
            lats.append(time.perf_counter() - t0)
        return statistics.median(lats) * 1e3

    keys = ('SKYT_TRACE', 'SKYT_TRACE_SAMPLE')
    saved = {k: os.environ.get(k) for k in keys}
    best = {'off': float('inf'), 'on': float('inf')}
    try:
        os.environ['SKYT_TRACE'] = '0'
        p50(8)   # warm compiles + connection before any timed pass
        # Interleave off/on passes so slow co-tenant phases hit both
        # modes alike instead of biasing whichever ran second.
        for _ in range(2):
            os.environ['SKYT_TRACE'] = '0'
            best['off'] = min(best['off'], p50(30))
            os.environ['SKYT_TRACE'] = '1'
            os.environ['SKYT_TRACE_SAMPLE'] = '1'
            best['on'] = min(best['on'], p50(30))
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        eng.stop()
    delta_pct = (best['on'] - best['off']) / best['off'] * 100.0
    print(f"# tracing overhead: p50 off={best['off']:.2f}ms "
          f"on={best['on']:.2f}ms delta={delta_pct:+.2f}%",
          file=sys.stderr)
    return [
        {'metric': 'serve_trace_p50_ms_tracing_off',
         'value': round(best['off'], 3), 'unit': 'ms',
         'vs_baseline': None, 'best_of': 2},
        {'metric': 'serve_trace_p50_ms_tracing_on',
         'value': round(best['on'], 3), 'unit': 'ms',
         'vs_baseline': None, 'best_of': 2},
        # Acceptance: <= ~2%. vs_baseline expresses the off/on ratio
        # (>= ~0.98 means tracing-on costs <= ~2%).
        {'metric': 'serve_trace_overhead_p50_delta_pct',
         'value': round(delta_pct, 3), 'unit': '%',
         'vs_baseline': round(best['off'] / best['on'], 4)
         if best['on'] > 0 else None, 'best_of': 2},
    ]


def overload_bench_metrics() -> list:
    """QoS overload phase (CPU-runnable, docs/qos.md): interactive p95
    TTFT with the replica unloaded vs under a batch-class flood, with
    SKYT_QOS=1 and aggressive shed thresholds. Acceptance: the flooded
    interactive p95 TTFT stays within ~25% of unloaded, zero
    interactive requests shed, batch sheds > 0 (read from /metrics).

    TTFT is measured end-to-end as time to the first streamed chunk of
    /generate (stream=true), through the real aiohttp stack.
    """
    import socket
    import statistics
    import threading

    import requests
    from aiohttp import web

    from skypilot_tpu.infer import server as server_lib

    env_keys = {
        'SKYT_QOS': '1',
        # Shed early so a small CPU flood trips the ladder. The flood
        # is deliberately small (3 pacing clients): every flooder
        # thread shares the GIL with the server + engine under test,
        # so a big flood measures interpreter contention, not QoS
        # scheduling.
        'SKYT_QOS_QUEUE_DEGRADE': '0.25',
        'SKYT_QOS_QUEUE_SHED': '0.5',
        'SKYT_QOS_DEGRADE_MAX_TOKENS': '4',
        # One of the two slots is reserved for interactive work: a
        # batch flood can never occupy the whole replica, so the
        # interactive p95 TTFT stays near its unloaded value.
        'SKYT_QOS_RESERVE_SLOTS': '1',
        'SKYT_QOS_REFRESH_S': '0.05',
        'SKYT_QOS_HOLD_S': '5',
        # Queue depth drives this phase; the debug model's TTFT jitter
        # must not escalate the ladder on its own.
        'SKYT_QOS_TTFT_SLO_MS': '0',
    }
    saved = {k: os.environ.get(k) for k in env_keys}
    os.environ.update(env_keys)
    eng = None
    try:
        # decode_chunk=2: the flooded-TTFT floor is waiting out the
        # in-flight batch decode chunk before the interactive prefill
        # can dispatch; on CPU a 4-step chunk alone busts the 25%
        # budget, while 1 doubles host dispatch overhead. 2 balances.
        eng = server_lib.build_engine('debug', num_slots=2,
                                      max_seq_len=64, decode_chunk=2,
                                      cache_mode='dense',
                                      prefix_caching=False)
        eng.start()
        srv = server_lib.InferenceServer(eng)
        with socket.socket() as s:
            s.bind(('127.0.0.1', 0))
            port = s.getsockname()[1]
        threading.Thread(target=lambda: web.run_app(
            srv.make_app(), port=port, print=None,
            handle_signals=False), daemon=True).start()
        base = f'http://127.0.0.1:{port}'
        sess = requests.Session()
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                if sess.get(base + '/health',
                            timeout=2).status_code == 200:
                    break
            except requests.RequestException:
                pass
            time.sleep(0.2)

        probe_sess = requests.Session()

        # A realistic interactive probe: a 48-token prompt, so TTFT
        # is dominated by the prefill the QoS plane schedules — with a
        # 3-token prompt the baseline is so small that fixed ~5ms GIL
        # jitter from the co-resident flood decides the ratio.
        probe_prompt = [(i % 50) + 2 for i in range(48)]

        def ttft_ms(cls: str) -> float:
            t0 = time.perf_counter()
            r = probe_sess.post(
                base + '/generate',
                json={'tokens': probe_prompt, 'max_tokens': 4,
                      'stream': True},
                headers={'X-Priority': cls}, stream=True, timeout=120)
            r.raise_for_status()
            next(r.iter_lines())
            dt = (time.perf_counter() - t0) * 1e3
            # Drain fully so the connection is reusable (keep-alive):
            # a fresh TCP connect per probe would measure accept()
            # latency under flood load, not QoS scheduling.
            for _ in r.iter_lines():
                pass
            r.close()
            return dt

        # 40 probes per round, lightly paced: with 20 samples the p95
        # IS the max sample, so one event-loop collision with a flood
        # request (tens of ms) decides the whole phase. Pacing mirrors
        # a real interactive client (they do not arrive back-to-back
        # on one connection).
        probes_per_round = 60

        def probe_round(samples=None, codes=None):
            samples = [] if samples is None else samples
            for _ in range(probes_per_round):
                try:
                    samples.append(ttft_ms('interactive'))
                    if codes is not None:
                        codes.append(200)
                except requests.HTTPError as e:
                    if codes is not None:
                        codes.append(e.response.status_code)
                time.sleep(0.02)
            return samples

        for _ in range(6):
            ttft_ms('interactive')      # warm compiles + connections
        unloaded = probe_round()

        stop = threading.Event()

        def flood():
            s2 = requests.Session()
            while not stop.is_set():
                try:
                    r = s2.post(base + '/generate',
                                json={'tokens': [3, 4, 5],
                                      'max_tokens': 48},
                                headers={'X-Priority': 'batch',
                                         'X-Tenant': 'flooder'},
                                timeout=120)
                    if r.status_code == 429:
                        # A well-behaved batch client honors
                        # Retry-After (capped so the flood persists);
                        # hammering 429s in a tight loop measures
                        # event-loop DoS, not QoS scheduling.
                        time.sleep(min(float(
                            r.headers.get('Retry-After', 1)), 0.5))
                except requests.RequestException:
                    pass

        def flood_round():
            """One flooded probe round: start the flood, let the
            backlog build, probe, stop."""
            stop.clear()
            flooders = [threading.Thread(target=flood, daemon=True)
                        for _ in range(3)]
            for th in flooders:
                th.start()
            time.sleep(1.0)             # let the backlog build
            samples = probe_round(codes=codes)
            stop.set()
            for th in flooders:
                th.join(timeout=30)
            return samples

        # Three interleaved (unloaded, flooded) rounds per condition.
        # This box's noise comes in multi-second windows, so each
        # condition's best (min) p95 across its rounds is the cleanest
        # measurement of that condition, and the acceptance ratio
        # compares those. Real queueing delay — what this phase
        # exists to catch — recurs in EVERY flood round including the
        # best one, so best-of suppresses machine noise without hiding
        # the effect under test.
        codes = []
        pairs = [(unloaded, flood_round())]
        for _ in range(2):
            pairs.append((probe_round(), flood_round()))
        text = sess.get(base + '/metrics', timeout=5).text

        def counter(cls: str) -> float:
            total = 0.0
            for line in text.splitlines():
                if line.startswith(
                        f'skyt_qos_shed_total{{class="{cls}"'):
                    total += float(line.rsplit(' ', 1)[1])
            return total

        shed_batch = counter('batch')
        shed_interactive = counter('interactive')
        def p95(samples):
            return statistics.quantiles(samples, n=20)[-1] \
                if len(samples) >= 2 else float('inf')

        p95_un = min(p95(u) for u, _ in pairs)
        p95_fl = min(p95(f) for _, f in pairs)
        ratio = p95_fl / p95_un if p95_un > 0 else float('inf')
        interactive_429 = sum(1 for c in codes if c == 429)
        print(f'# overload bench: interactive p95 TTFT unloaded='
              f'{p95_un:.1f}ms flood={p95_fl:.1f}ms '
              f'(ratio {ratio:.3f}), sheds batch={shed_batch:.0f} '
              f'interactive={shed_interactive:.0f}, '
              f'interactive 429s={interactive_429}', file=sys.stderr)
        return [
            {'metric': 'overload_interactive_p95_ttft_ms_unloaded',
             'value': round(p95_un, 3), 'unit': 'ms',
             'vs_baseline': None},
            {'metric': 'overload_interactive_p95_ttft_ms_flood',
             'value': round(p95_fl, 3), 'unit': 'ms',
             # Acceptance <= ~1.25: flood p95 within 25% of unloaded
             # (median of the per-pair ratios, see above).
             'vs_baseline': round(ratio, 4)},
            {'metric': 'overload_batch_sheds',
             'value': shed_batch, 'unit': 'requests',
             'vs_baseline': None},
            # Acceptance: exactly 0 (interactive is never shed).
            {'metric': 'overload_interactive_sheds',
             'value': shed_interactive + interactive_429,
             'unit': 'requests', 'vs_baseline': None},
        ]
    finally:
        if eng is not None:
            eng.stop()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def slo_report_metrics() -> list:
    """SLO report phase (CPU-runnable, docs/observability.md "Fleet
    plane"): a classed burst against a real server, scraped through
    FleetTelemetry (baseline scrape before, one after — counter
    windows need both edges), then the fleet SLO report:

      * slo_attainment_interactive — fraction of interactive requests
        within their TTFT/ITL objectives over the burst window;
      * slo_good_tokens_per_chip_second / slo_chip_seconds_per_good_
        token — the goodput cost report (replica count x accelerator
        spec; 1 CPU "chip" here, so the number is a mechanism check,
        not a perf claim);
      * slo_fleet_scrape_overhead_p50_delta_pct — p50 /generate with a
        background /metrics scraper at an aggressive 0.5 s cadence
        (20x the production SKYT_FLEET_SCRAPE_S default) vs without,
        interleaved best-of-2 — the tracing-overhead methodology.
        Acceptance: <= ~1%.
    """
    import socket
    import statistics
    import threading

    import requests
    from aiohttp import web

    from skypilot_tpu.infer import server as server_lib
    from skypilot_tpu.serve import fleet as fleet_lib
    from skypilot_tpu.utils import metrics as metrics_lib

    eng = server_lib.build_engine('debug', num_slots=2, max_seq_len=64,
                                  decode_chunk=8, cache_mode='dense',
                                  prefix_caching=False)
    eng.start()
    srv = server_lib.InferenceServer(eng)
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        port = s.getsockname()[1]
    threading.Thread(target=lambda: web.run_app(
        srv.make_app(), port=port, print=None, handle_signals=False),
        daemon=True).start()
    base = f'http://127.0.0.1:{port}'
    sess = requests.Session()
    deadline = time.time() + 60
    while time.time() < deadline:
        try:
            if sess.get(base + '/health', timeout=2).status_code == 200:
                break
        except requests.RequestException:
            pass
        time.sleep(0.2)

    def gen(cls, i, n_tok=8):
        r = sess.post(base + '/generate',
                      json={'tokens': [i % 50 + 2, 3, 4],
                            'max_tokens': n_tok},
                      headers={'X-Priority': cls,
                               'X-Tenant': 'bench'}, timeout=60)
        r.raise_for_status()

    try:
        # Warm compiles AND prime every (class, tenant) series so the
        # baseline scrape has a first edge for each counter window.
        for cls in ('interactive', 'standard', 'batch'):
            gen(cls, 0)
        fl = fleet_lib.FleetTelemetry(
            'bench', metrics_registry=metrics_lib.MetricsRegistry())
        assert fl.scrape('1', base)
        for i in range(12):
            gen('interactive', i)
        for i in range(6):
            gen('batch', i)
        time.sleep(0.05)
        assert fl.scrape('1', base)
        rep = fl.fleet_slo(window_s=300)
        att = rep['slo']['interactive']['windows']['5m']['attainment']
        goodput = rep['goodput']

        # Scrape-overhead half: p50 /generate with/without a live
        # scraper, interleaved best-of-2 (tracing-overhead recipe).
        payload = {'tokens': [7, 8, 9], 'max_tokens': 8}

        def p50(n=30):
            lats = []
            for _ in range(n):
                t0 = time.perf_counter()
                r = sess.post(base + '/generate', json=payload,
                              timeout=60)
                r.raise_for_status()
                lats.append(time.perf_counter() - t0)
            return statistics.median(lats) * 1e3

        stop = threading.Event()

        def scraper():
            s2 = requests.Session()
            while not stop.is_set():
                try:
                    s2.get(base + '/metrics', timeout=5)
                except requests.RequestException:
                    pass
                stop.wait(0.5)

        best = {'off': float('inf'), 'on': float('inf')}
        for _ in range(2):
            best['off'] = min(best['off'], p50())
            stop.clear()
            th = threading.Thread(target=scraper, daemon=True)
            th.start()
            best['on'] = min(best['on'], p50())
            stop.set()
            th.join(timeout=10)
        delta_pct = (best['on'] - best['off']) / best['off'] * 100.0
        gtps = goodput['good_tokens_per_chip_second']
        print(f'# slo report: interactive attainment={att} '
              f'good_tok/chip_s={gtps} scrape overhead p50 '
              f'off={best["off"]:.2f}ms on={best["on"]:.2f}ms '
              f'delta={delta_pct:+.2f}%', file=sys.stderr)
        return [
            {'metric': 'slo_attainment_interactive',
             'value': att, 'unit': 'fraction',
             # vs the default 0.99 target
             'vs_baseline': (round(att / 0.99, 4)
                             if att is not None else None)},
            {'metric': 'slo_good_tokens_per_chip_second',
             'value': gtps, 'unit': 'tok/chip-s',
             'vs_baseline': None},
            {'metric': 'slo_chip_seconds_per_good_token',
             'value': goodput['chip_seconds_per_good_token'],
             'unit': 'chip-s/tok', 'vs_baseline': None},
            # Acceptance <= ~1%; vs_baseline = off/on ratio.
            {'metric': 'slo_fleet_scrape_overhead_p50_delta_pct',
             'value': round(delta_pct, 3), 'unit': '%',
             'vs_baseline': round(best['off'] / best['on'], 4)
             if best['on'] > 0 else None, 'best_of': 2},
        ]
    finally:
        eng.stop()


def chaos_recovery_metrics() -> list:
    """Recovery-time phase (CPU-runnable, docs/robustness.md): two
    real replica server subprocesses behind the in-process LB; one is
    SIGKILLed and the phase measures seconds from the kill to restored
    service through the retry + circuit-breaker path:

      * serve_recovery_first_success_s — kill -> first 200 (includes
        the failed attempt, backoff, and retry on the survivor).
      * serve_recovery_full_throughput_s — kill -> 5 consecutive
        requests each completing within 2x the pre-kill p50 (the
        breaker has ejected the dead replica; no request still pays a
        connect-to-the-corpse penalty).
    """
    import socket
    import statistics
    import subprocess
    import threading

    import requests
    from aiohttp import web

    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.utils import metrics as metrics_lib

    def free_port():
        with socket.socket() as s:
            s.bind(('127.0.0.1', 0))
            return s.getsockname()[1]

    env_keys = {'SKYT_SERVE_LB_SYNC_INTERVAL': '3600',
                'SKYT_LB_RETRY_BACKOFF_S': '0.02',
                'SKYT_LB_BREAKER_THRESHOLD': '2',
                'SKYT_LB_BREAKER_COOLDOWN_S': '60'}
    # The sync-interval override is deliberately NOT restored: the
    # phase's daemon LB thread outlives the phase, and restoring the
    # default would wake its parked controller-sync loop into a 2s
    # failure-warning loop for the rest of the bench.
    saved = {k: os.environ.get(k) for k in env_keys
             if k != 'SKYT_SERVE_LB_SYNC_INTERVAL'}
    os.environ.update(env_keys)
    ports = [free_port(), free_port()]
    urls = [f'http://127.0.0.1:{p}' for p in ports]
    procs = [subprocess.Popen(
        [sys.executable, '-m', 'skypilot_tpu.infer.server',
         '--model', 'debug', '--port', str(p),
         '--num-slots', '2', '--max-seq-len', '64'],
        env=_cpu_child_env(),
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        for p in ports]
    sess = requests.Session()
    try:
        for proc, url in zip(procs, urls):
            deadline = time.time() + 240
            while time.time() < deadline:
                if proc.poll() is not None:
                    raise RuntimeError(
                        f'replica died rc={proc.returncode}')
                try:
                    if sess.get(url + '/health',
                                timeout=2).status_code == 200:
                        break
                except requests.RequestException:
                    pass
                time.sleep(0.5)
            else:
                raise RuntimeError('replica never became healthy')
        lb_port = free_port()
        lb = lb_lib.SkyServeLoadBalancer(
            'http://127.0.0.1:9', lb_port,
            metrics_registry=metrics_lib.MetricsRegistry())
        lb.policy.set_ready_replicas(urls)
        threading.Thread(target=lambda: web.run_app(
            lb.make_app(), port=lb_port, print=None,
            handle_signals=False), daemon=True).start()
        base = f'http://127.0.0.1:{lb_port}'
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                sess.get(base + '/metrics', timeout=2)
                break
            except requests.RequestException:
                time.sleep(0.2)
        payload = {'tokens': [7, 8, 9], 'max_tokens': 8}

        def one() -> float:
            t0 = time.perf_counter()
            r = sess.post(base + '/generate', json=payload, timeout=60)
            r.raise_for_status()
            return time.perf_counter() - t0

        for _ in range(4):
            one()                       # warm both replicas + compiles
        baseline_p50 = statistics.median(one() for _ in range(10))

        procs[0].kill()                 # the chaos event
        t_kill = time.perf_counter()
        first_success = None
        full_at = None
        streak = 0
        win_start = 0.0
        bar = max(2 * baseline_p50, 0.05)
        deadline = time.time() + 120
        while time.time() < deadline and full_at is None:
            try:
                lat = one()
            except requests.RequestException:
                streak = 0
                continue
            now = time.perf_counter()
            if first_success is None:
                first_success = now - t_kill
            if lat <= bar:
                if streak == 0:
                    # Restored-throughput instant = when the healthy
                    # window STARTED (this request's send time), not
                    # when its 5th probe finished.
                    win_start = now - lat - t_kill
                streak += 1
                if streak >= 5:
                    full_at = win_start
            else:
                streak = 0
        if first_success is None:
            raise RuntimeError('no request succeeded after the kill')
        print(f'# chaos recovery: baseline p50={baseline_p50*1e3:.1f}ms '
              f'first_success={first_success:.3f}s '
              f'full_throughput={full_at if full_at else -1:.3f}s',
              file=sys.stderr)
        out = [
            {'metric': 'serve_recovery_first_success_s',
             'value': round(first_success, 3), 'unit': 's',
             'vs_baseline': None},
        ]
        if full_at is not None:
            out.append(
                {'metric': 'serve_recovery_full_throughput_s',
                 'value': round(full_at, 3), 'unit': 's',
                 'vs_baseline': None})
        return out
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def affinity_ab_metrics() -> list:
    """Prefix-affinity A/B phase (CPU-runnable, docs/serving.md
    "N-active front door"): the same multi-turn / shared-prefix
    workload through the SAME two paged-cache replicas, once behind a
    round-robin LB (affinity off) and once behind a prefix_affinity
    LB (consistent-hash ring + sticky sessions). Emits each
    condition's prefix-cache hit rate (hit pages / (hit + miss), from
    the replicas' own counters), the requests-per-chip-second proxy,
    and the sticky re-hash count.

    Acceptance: hit rate strictly higher with affinity ON (multi-turn
    prompts re-land where their prefix KV pages live instead of
    alternating replicas), and affinity_sticky_rehashes == 0 (a
    session is never re-hashed while its replica stays ready).
    """
    import socket
    import threading

    import requests
    from aiohttp import web

    from skypilot_tpu.infer import server as server_lib
    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.utils import metrics as metrics_lib

    def free_port():
        with socket.socket() as s:
            s.bind(('127.0.0.1', 0))
            return s.getsockname()[1]

    # Parked controller sync (same rationale as the chaos phase: the
    # daemon LB threads outlive the phase).
    os.environ['SKYT_SERVE_LB_SYNC_INTERVAL'] = '3600'
    engines = []
    try:
        urls = []
        for _ in range(2):
            # Paged cache + prefix caching ON — the thing under test.
            # pool_tokens is sized so the workload's distinct prefixes
            # fit without eviction noise.
            # (the debug model caps max_seq_len at 128)
            eng = server_lib.build_engine(
                'debug', num_slots=2, max_seq_len=128,
                decode_chunk=2, cache_mode='paged',
                prefix_caching=True, pool_tokens=16384)
            eng.start()
            engines.append(eng)
            srv = server_lib.InferenceServer(eng)
            port = free_port()
            threading.Thread(target=lambda app=srv.make_app(),
                             p=port: web.run_app(
                                 app, port=p, print=None,
                                 handle_signals=False),
                             daemon=True).start()
            urls.append(f'http://127.0.0.1:{port}')
        sess = requests.Session()
        for url in urls:
            deadline = time.time() + 120
            while time.time() < deadline:
                try:
                    if sess.get(url + '/health',
                                timeout=2).status_code == 200:
                        break
                except requests.RequestException:
                    pass
                time.sleep(0.2)
            else:
                raise RuntimeError(f'replica {url} never healthy')

        def make_lb(policy):
            port = free_port()
            lb = lb_lib.SkyServeLoadBalancer(
                'http://127.0.0.1:9', port, policy=policy,
                metrics_registry=metrics_lib.MetricsRegistry())
            lb.policy.set_ready_replicas(urls)
            threading.Thread(target=lambda: web.run_app(
                lb.make_app(), port=port, print=None,
                handle_signals=False), daemon=True).start()
            base = f'http://127.0.0.1:{port}'
            deadline = time.time() + 30
            while time.time() < deadline:
                try:
                    sess.get(base + '/metrics', timeout=2)
                    break
                except requests.RequestException:
                    time.sleep(0.2)
            return base

        def cache_counters():
            # /stats exposes the pool's live hit/miss page counts
            # (the /metrics mirrors sync on engine-loop ticks — an
            # idle engine may lag a scrape taken right after the last
            # response).
            hits = misses = 0.0
            for url in urls:
                block = sess.get(url + '/stats', timeout=5).json() \
                    .get('prefix_cache', {})
                hits += float(block.get('hit_pages', 0))
                misses += float(block.get('miss_pages', 0))
            return hits, misses

        # page_size=64: a 64-token conversation base is one FULL page
        # of publishable prefix KV; each turn appends 8 tokens, so
        # every turn after the first re-reads that page — IF it lands
        # on the replica that cached it (the debug model caps
        # max_seq_len at 128, so the conversation stays under one
        # extra page). n_convs is ODD on purpose: with an even count,
        # strict round-robin accidentally parity-pins every
        # conversation to one replica and the OFF condition measures
        # affinity too.
        n_convs, n_turns = 7, 5

        # Warm every (replica, bucket) compile BEFORE either
        # condition: the first condition must not pay the pow2-bucket
        # prefill compiles the second then amortizes.
        for url in urls:
            for turn in range(n_turns):
                sess.post(url + '/generate',
                          json={'tokens': [(9000 + turn * 131 + j)
                                           % 30000
                                           for j in range(64 + turn * 8)],
                                'max_tokens': 2},
                          timeout=300).raise_for_status()

        def run_condition(base, cond):
            offset = 50 + cond * 7000
            convs = {
                i: [(offset + i * 997 + j) % 30000 for j in range(64)]
                for i in range(n_convs)}
            homes = {}
            rehashes = 0
            n_requests = 0
            h0, m0 = cache_counters()
            t0 = time.perf_counter()
            for turn in range(n_turns):
                for i in range(n_convs):
                    prompt = convs[i] + [
                        (offset + i * 997 + 64 + k) % 30000
                        for k in range(turn * 8)]
                    r = sess.post(
                        base + '/generate',
                        json={'tokens': prompt, 'max_tokens': 2},
                        headers={'X-Session-Id': f'conv-{cond}-{i}'},
                        timeout=120)
                    r.raise_for_status()
                    n_requests += 1
                    rep = r.headers.get('X-Replica-Id')
                    if i in homes and homes[i] != rep:
                        rehashes += 1
                    homes[i] = rep
            elapsed = time.perf_counter() - t0
            h1, m1 = cache_counters()
            dh, dm = h1 - h0, m1 - m0
            rate = dh / (dh + dm) if (dh + dm) > 0 else 0.0
            rps_chip = n_requests / elapsed / len(urls)
            return rate, rps_chip, rehashes

        base_off = make_lb('round_robin')
        rate_off, rps_off, _ = run_condition(base_off, 0)
        base_on = make_lb('prefix_affinity')
        rate_on, rps_on, rehashes_on = run_condition(base_on, 1)
        print(f'# affinity A/B: prefix hit rate off={rate_off:.3f} '
              f'on={rate_on:.3f}, req/chip/s off={rps_off:.2f} '
              f'on={rps_on:.2f}, sticky rehashes={rehashes_on}',
              file=sys.stderr)
        return [
            {'metric': 'affinity_prefix_hit_rate_off',
             'value': round(rate_off, 4), 'unit': 'fraction',
             'vs_baseline': None},
            # Acceptance: > 1.0 (strictly higher hit rate with
            # affinity on for the multi-turn/shared-prefix workload).
            {'metric': 'affinity_prefix_hit_rate_on',
             'value': round(rate_on, 4), 'unit': 'fraction',
             'vs_baseline': (round(rate_on / rate_off, 4)
                             if rate_off > 0 else None)},
            {'metric': 'affinity_requests_per_chip_s_off',
             'value': round(rps_off, 3), 'unit': 'req/chip/s',
             'vs_baseline': None},
            {'metric': 'affinity_requests_per_chip_s_on',
             'value': round(rps_on, 3), 'unit': 'req/chip/s',
             'vs_baseline': (round(rps_on / rps_off, 4)
                             if rps_off > 0 else None)},
            # Acceptance: exactly 0 — sticky sessions are never
            # re-hashed while their replica stays ready.
            {'metric': 'affinity_sticky_rehashes',
             'value': rehashes_on, 'unit': 'requests',
             'vs_baseline': None},
        ]
    finally:
        for eng in engines:
            eng.stop()


def kv_tier_metrics() -> list:
    """kv tier phase (CPU-runnable, docs/performance.md "Tiered
    prefix cache"): restart-warm vs cold TTFT through the real
    prefix-affinity LB. Two paged replicas serve 384-token shared
    prefixes, and every timed request routes (by the rendezvous
    ring) to a replica that has NEVER prefilled its prefix while the
    OTHER replica holds the pages — exactly the post-restart /
    failover-return shape the tier exists for. With SKYT_KV_TIER=off
    the owner recomputes the full ~400-token prefill (cold); with
    =fleet it fetches the six int-hash-chained pages from the peer
    the LB names in X-KV-Peer, splices them in, and prefills only
    the 16-token tail (warm).

    Acceptance: kv_tier_restart_hit_rate_on strictly higher than
    _off (off is structurally 0 — the owner never saw the prefix),
    and warm TTFT p50 below cold (vs_baseline < 1.0).
    """
    import dataclasses as _dc
    import hashlib
    import socket
    import statistics
    import threading

    import requests
    from aiohttp import web

    from skypilot_tpu.infer import engine as engine_lib
    from skypilot_tpu.infer import server as server_lib
    from skypilot_tpu.models import llama
    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.utils import metrics as metrics_lib

    def free_port():
        with socket.socket() as s:
            s.bind(('127.0.0.1', 0))
            return s.getsockname()[1]

    # Parked controller sync (daemon LB threads outlive the phase);
    # the /kv/prefix donor endpoint and the fetch worker share the
    # bearer token via env.
    os.environ['SKYT_SERVE_LB_SYNC_INTERVAL'] = '3600'
    saved_env = {k: os.environ.get(k)
                 for k in ('SKYT_KV_TIER', 'SKYT_ADMIN_TOKEN')}
    os.environ['SKYT_ADMIN_TOKEN'] = 'bench-kv'

    # 384 tokens = exactly 6 full 64-token pages of publishable
    # prefix KV (the build_engine debug preset caps max_seq_len at
    # 128, so the engines are built by hand at 512). Token ids are
    # >= 10000 so the LB affinity key's 1024-byte window covers only
    # prefix tokens — the 16-token tail never re-keys the request.
    def prefix_tokens(i):
        return [10000 + (i * 613 + j * 7) % 19000 for j in range(384)]

    def tail_tokens(i):
        return [3 + (i * 31 + k) % 97 for k in range(16)]

    def affinity_key(toks):
        text = ','.join(str(t) for t in toks)
        return hashlib.sha256(
            text.encode('utf-8')[:1024]).hexdigest()[:16]

    sess = requests.Session()

    def run_condition(tier):
        os.environ['SKYT_KV_TIER'] = tier
        engines, urls = [], []
        try:
            cfg = _dc.replace(llama.CONFIGS['debug'], remat=False,
                              max_seq_len=512)
            if cfg.param_dtype == 'float32' and cfg.dtype == 'bfloat16':
                cfg = _dc.replace(cfg, param_dtype='bfloat16')
            model = llama.LlamaModel(cfg)
            params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                         jnp.zeros((1, 8), jnp.int32))
            for _ in range(2):
                eng = engine_lib.InferenceEngine(
                    model, params, num_slots=2, max_seq_len=512,
                    decode_chunk=2, cache_mode='paged',
                    prefix_caching=True, pool_tokens=16384)
                eng.start()
                engines.append(eng)
                srv = server_lib.InferenceServer(eng)
                port = free_port()
                threading.Thread(target=lambda app=srv.make_app(),
                                 p=port: web.run_app(
                                     app, port=p, print=None,
                                     handle_signals=False),
                                 daemon=True).start()
                urls.append(f'http://127.0.0.1:{port}')
            for url in urls:
                deadline = time.time() + 120
                while time.time() < deadline:
                    try:
                        if sess.get(url + '/health',
                                    timeout=2).status_code == 200:
                            break
                    except requests.RequestException:
                        pass
                    time.sleep(0.2)
                else:
                    raise RuntimeError(f'replica {url} never healthy')
            lb_port = free_port()
            lb = lb_lib.SkyServeLoadBalancer(
                'http://127.0.0.1:9', lb_port, policy='prefix_affinity',
                metrics_registry=metrics_lib.MetricsRegistry())
            lb.policy.set_ready_replicas(urls)
            threading.Thread(target=lambda: web.run_app(
                lb.make_app(), port=lb_port, print=None,
                handle_signals=False), daemon=True).start()
            base = f'http://127.0.0.1:{lb_port}'
            deadline = time.time() + 30
            while time.time() < deadline:
                try:
                    sess.get(base + '/metrics', timeout=2)
                    break
                except requests.RequestException:
                    time.sleep(0.2)
            ring = getattr(lb.policy, 'ring', None)
            if ring is None:
                raise RuntimeError('prefix_affinity LB has no ring')

            def ranked(toks):
                return list(ring.ranked(affinity_key(toks)))

            # Warmup (untimed): pay every compile BOTH conditions
            # share — the 512-token prefill bucket and decode step on
            # each replica directly, then one full seeded fetch cycle
            # per replica THROUGH the LB so the fleet condition also
            # compiles its page-install dispatch (the off condition
            # just recomputes — same traffic, fair A/B). Warmup
            # prefixes are probed until each replica has been the
            # ring's first choice at least once.
            for url in urls:
                sess.post(url + '/generate',
                          json={'tokens': prefix_tokens(9001),
                                'max_tokens': 1},
                          timeout=600).raise_for_status()
                sess.post(url + '/generate',
                          json={'tokens': prefix_tokens(9002)
                                + tail_tokens(9002),
                                'max_tokens': 1},
                          timeout=600).raise_for_status()
            owners_warmed = set()
            i = 9100
            while len(owners_warmed) < len(urls) and i < 9200:
                toks = prefix_tokens(i)
                order = ranked(toks)
                if order[0] not in owners_warmed:
                    owners_warmed.add(order[0])
                    # Seed the donor (2nd-ranked = the X-KV-Peer the
                    # LB will hint), then route through the LB.
                    sess.post(order[1] + '/generate',
                              json={'tokens': toks, 'max_tokens': 1},
                              timeout=600).raise_for_status()
                    sess.post(base + '/generate',
                              json={'tokens': toks + tail_tokens(i),
                                    'max_tokens': 1},
                              timeout=600).raise_for_status()
                i += 1

            def cache_counters():
                hits = misses = 0.0
                for eng in engines:
                    block = eng.stats().get('prefix_cache', {})
                    hits += float(block.get('hit_pages', 0))
                    misses += float(block.get('miss_pages', 0))
                return hits, misses

            def fetched_pages():
                total = 0.0
                for eng in engines:
                    tier_block = eng.stats().get('kv_tier') or {}
                    total += float(tier_block.get('fetched_pages', 0))
                return total

            # Timed: R distinct prefixes, each seeded ONLY on its
            # donor, then requested once through the LB (lands on
            # the cold owner; client-side elapsed of a max_tokens=1
            # request is the TTFT proxy).
            n_prefixes = 6
            ttfts = []
            seeded = []
            for i in range(n_prefixes):
                toks = prefix_tokens(i)
                order = ranked(toks)
                sess.post(order[1] + '/generate',
                          json={'tokens': toks, 'max_tokens': 1},
                          timeout=600).raise_for_status()
                seeded.append(toks + tail_tokens(i))
            h0, m0 = cache_counters()
            f0 = fetched_pages()
            for body_tokens in seeded:
                t0 = time.perf_counter()
                r = sess.post(base + '/generate',
                              json={'tokens': body_tokens,
                                    'max_tokens': 1},
                              timeout=600)
                ttfts.append(time.perf_counter() - t0)
                r.raise_for_status()
            h1, m1 = cache_counters()
            dh, dm = h1 - h0, m1 - m0
            rate = dh / (dh + dm) if (dh + dm) > 0 else 0.0
            return (rate, statistics.median(ttfts),
                    fetched_pages() - f0)
        finally:
            for eng in engines:
                eng.stop()

    try:
        rate_off, ttft_cold, _ = run_condition('off')
        rate_on, ttft_warm, pages_on = run_condition('fleet')
        print(f'# kv tier: restart hit rate off={rate_off:.3f} '
              f'on={rate_on:.3f}, ttft p50 cold={ttft_cold * 1e3:.1f}ms '
              f'warm={ttft_warm * 1e3:.1f}ms '
              f'({ttft_warm / ttft_cold:.2f}x), fetched pages='
              f'{pages_on:.0f}', file=sys.stderr)
        return [
            {'metric': 'kv_tier_restart_hit_rate_off',
             'value': round(rate_off, 4), 'unit': 'fraction',
             'vs_baseline': None},
            # Acceptance: strictly higher than _off (whose value is
            # structurally 0 here — the ring owner never saw the
            # prefix, so without the tier every page is a miss).
            {'metric': 'kv_tier_restart_hit_rate_on',
             'value': round(rate_on, 4), 'unit': 'fraction',
             'vs_baseline': (round(rate_on / rate_off, 4)
                             if rate_off > 0 else None)},
            {'metric': 'kv_tier_restart_ttft_p50_cold_s',
             'value': round(ttft_cold, 4), 'unit': 's',
             'vs_baseline': None},
            # Acceptance: vs_baseline < 1.0 (fetch six pages from
            # the peer + tail prefill beats recomputing the full
            # prefix prefill).
            {'metric': 'kv_tier_restart_ttft_p50_warm_s',
             'value': round(ttft_warm, 4), 'unit': 's',
             'vs_baseline': (round(ttft_warm / ttft_cold, 4)
                             if ttft_cold > 0 else None)},
            {'metric': 'kv_tier_fetched_pages',
             'value': pages_on, 'unit': 'pages',
             'vs_baseline': None},
        ]
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def kv_ragged_metrics() -> list:
    """kv+ragged phase (CPU-runnable, docs/performance.md "raw-speed
    stack"): the three acceptance numbers of the int8-KV + ragged-
    prefill PR.

      * kv_pages_per_pool_ratio_int8 — pages a fixed HBM budget holds
        at int8 KV vs the fp pool, exact memory_plan arithmetic for
        the bf16 llama3-8b layout (acceptance >= 1.9; d=128 gives
        1.94) plus the f32 debug layout as the CPU cross-check.
      * prefill_padded_frac_{padded,ragged} — measured engine
        counters (prefill_padded_tokens / prefill_dispatch_tokens) on
        the SAME page-aligned mixed-length burst through the padded
        batch path vs the ragged packed path (acceptance: ragged ~0,
        padded ~0.5 — the pow2 row padding).
      * kv_ragged_good_tokens_per_chip_second (+ per-class) — the
        PR 8 SLO/goodput report over a classed burst against a real
        server running int8 KV + ragged prefill (1 CPU "chip": a
        mechanism check wiring the whole stack, not a perf claim).
    """
    import socket
    import threading

    import requests
    from aiohttp import web

    from skypilot_tpu.infer import memory_plan
    from skypilot_tpu.infer import server as server_lib
    from skypilot_tpu.models import llama as llama_lib
    from skypilot_tpu.serve import fleet as fleet_lib
    from skypilot_tpu.utils import metrics as metrics_lib

    # ---- 1. pages-per-pool arithmetic (the HBM story).
    ratio_8b = memory_plan.kv_pages_ratio(
        llama_lib.CONFIGS['llama3-8b'], 'int8')
    ratio_dbg = memory_plan.kv_pages_ratio(
        llama_lib.CONFIGS['debug'], 'int8')

    # ---- 2. padded-token fraction, padded vs ragged, same burst.
    # Page-aligned mixed lengths (32/64/16 tokens, page 16): the
    # ragged pack is exact while the padded path pads each row to the
    # 64 bucket AND the batch dim to pow2.
    prompts = [list(range(1, 33)), list(range(2, 66)),
               list(range(3, 19))]

    def run_burst(ragged: bool):
        import jax
        import jax.numpy as jnp
        from skypilot_tpu.infer import engine as engine_lib
        cfg = llama_lib.CONFIGS['debug']
        model = llama_lib.LlamaModel(cfg)
        params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                     jnp.zeros((1, 8), jnp.int32))
        eng = engine_lib.InferenceEngine(
            model, params, num_slots=4, max_seq_len=128,
            decode_chunk=4, cache_mode='paged', page_size=16,
            ragged_prefill=ragged)
        qs = [eng.submit(p, engine_lib.SamplingParams(
            max_new_tokens=4))[1] for p in prompts]
        eng.start()
        try:
            for q in qs:
                while q.get(timeout=120) is not None:
                    pass
        finally:
            eng.stop()
        perf = dict(eng.perf)
        return perf['prefill_padded_tokens'] / \
            max(1, perf['prefill_dispatch_tokens'])

    frac_padded = run_burst(ragged=False)
    frac_ragged = run_burst(ragged=True)

    # ---- 3. goodput through the full stack: int8 KV + ragged serve.
    os.environ['SKYT_KV_DTYPE'] = 'int8'
    try:
        eng = server_lib.build_engine('debug', num_slots=2,
                                      max_seq_len=64, decode_chunk=8,
                                      cache_mode='paged',
                                      prefix_caching=False)
    finally:
        os.environ.pop('SKYT_KV_DTYPE', None)
    assert eng.kv_quantized, 'int8 KV knob did not reach the engine'
    eng.start()
    srv = server_lib.InferenceServer(eng)
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        port = s.getsockname()[1]
    threading.Thread(target=lambda: web.run_app(
        srv.make_app(), port=port, print=None, handle_signals=False),
        daemon=True).start()
    base = f'http://127.0.0.1:{port}'
    sess = requests.Session()
    deadline = time.time() + 60
    while time.time() < deadline:
        try:
            if sess.get(base + '/health', timeout=2).status_code == 200:
                break
        except requests.RequestException:
            pass
        time.sleep(0.2)

    def gen(cls, i, n_tok=8):
        r = sess.post(base + '/generate',
                      json={'tokens': [i % 50 + 2, 3, 4],
                            'max_tokens': n_tok},
                      headers={'X-Priority': cls,
                               'X-Tenant': 'bench'}, timeout=60)
        r.raise_for_status()

    try:
        for cls in ('interactive', 'standard', 'batch'):
            gen(cls, 0)        # warm compiles + prime counter series
        fl = fleet_lib.FleetTelemetry(
            'bench', metrics_registry=metrics_lib.MetricsRegistry())
        assert fl.scrape('1', base)
        for i in range(10):
            gen('interactive', i)
        for i in range(5):
            gen('batch', i)
        time.sleep(0.05)
        assert fl.scrape('1', base)
        rep = fl.fleet_slo(window_s=300)
        goodput = rep['goodput']
        gtps = goodput['good_tokens_per_chip_second']
        chip_s = goodput['chips'] * goodput['window_s']
        per_class = {
            cls: round(blk['good_tokens'] / chip_s, 4)
            for cls, blk in goodput['classes'].items()
            if blk['tokens'] > 0 and chip_s > 0}
    finally:
        eng.stop()
    print(f'# kv+ragged: pages ratio 8b={ratio_8b:.3f} '
          f'debug={ratio_dbg:.3f}, padded frac '
          f'padded={frac_padded:.3f} ragged={frac_ragged:.3f}, '
          f'int8 good_tok/chip_s={gtps} per-class={per_class}',
          file=sys.stderr)
    out = [
        # Acceptance >= 1.9 at bf16 d=128.
        {'metric': 'kv_pages_per_pool_ratio_int8',
         'value': round(ratio_8b, 4), 'unit': 'x',
         'vs_baseline': round(ratio_8b, 4)},
        {'metric': 'kv_pages_per_pool_ratio_int8_debug_f32',
         'value': round(ratio_dbg, 4), 'unit': 'x',
         'vs_baseline': None},
        {'metric': 'prefill_padded_frac_padded',
         'value': round(frac_padded, 4), 'unit': 'fraction',
         'vs_baseline': None},
        # Acceptance ~0 on the page-aligned mixed burst.
        {'metric': 'prefill_padded_frac_ragged',
         'value': round(frac_ragged, 4), 'unit': 'fraction',
         'vs_baseline': (round(frac_ragged / frac_padded, 4)
                         if frac_padded > 0 else None)},
        {'metric': 'kv_ragged_good_tokens_per_chip_second',
         'value': gtps, 'unit': 'tok/chip-s', 'vs_baseline': None},
    ]
    for cls, v in sorted(per_class.items()):
        out.append({'metric': f'kv_ragged_good_tok_chip_s_{cls}',
                    'value': v, 'unit': 'tok/chip-s',
                    'vs_baseline': None})
    return out


def weight_swap_metrics() -> list:
    """Weight-swap phase (CPU-runnable, docs/robustness.md
    "Zero-downtime rollouts"): one real engine-server subprocess
    serving a streaming workload while ``POST /admin/weights`` hot-
    swaps its checkpoint in place. Reports:

      * weight_swap_itl_p95_ms — p95 inter-token latency over the
        swap window (stage + validate + drain + apply under load);
      * steady_itl_p95_ms — the same stream's p95 with no swap (the
        pause is the delta);
      * weight_swap_duration_s — end-to-end swap time from the admin
        response;
      * weight_swap_dropped_requests — MUST be 0: the drain holds
        queued work, it never drops it;
      * weight_swap_relaunches — MUST be 0: same server process (same
        pid) before and after the swap.
    """
    import dataclasses as _dc
    import shutil
    import socket
    import statistics
    import subprocess
    import tempfile
    import threading

    import jax
    import jax.numpy as jnp
    import requests

    from skypilot_tpu.models import llama
    from skypilot_tpu.models import weights as weights_lib

    def free_port():
        with socket.socket() as s:
            s.bind(('127.0.0.1', 0))
            return s.getsockname()[1]

    tmp = tempfile.mkdtemp(prefix='skyt-swapbench-')
    cfg = _dc.replace(llama.CONFIGS['debug'], max_seq_len=64,
                      param_dtype='float32', dtype='float32')
    model = llama.LlamaModel(cfg)
    zeros = jnp.zeros((1, 8), jnp.int32)
    ckpts = []
    for i, seed in enumerate((0, 7)):
        params = jax.jit(model.init)(jax.random.PRNGKey(seed), zeros)
        path = os.path.join(tmp, f'ckpt_{i}')
        weights_lib.save_hf_checkpoint(cfg, params, path)
        ckpts.append(path)
    port = free_port()
    url = f'http://127.0.0.1:{port}'
    env = _cpu_child_env(SKYT_ADMIN_TOKEN='bench-token')
    proc = subprocess.Popen(
        [sys.executable, '-m', 'skypilot_tpu.infer.server',
         '--checkpoint', ckpts[0], '--port', str(port),
         '--num-slots', '2', '--max-seq-len', '64'],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    sess = requests.Session()
    itls = {'steady': [], 'swap': []}
    lock = threading.Lock()
    window = {'mode': 'steady'}
    dropped = [0]
    stop = threading.Event()

    def worker(wid):
        i = 0
        while not stop.is_set():
            i += 1
            try:
                t_last = None
                with requests.post(
                        url + '/generate',
                        json={'tokens': [wid + 1, (i % 7) + 1, 3],
                              'max_tokens': 16, 'stream': True},
                        stream=True, timeout=120) as r:
                    if r.status_code != 200:
                        with lock:
                            dropped[0] += 1
                        continue
                    for line in r.iter_lines():
                        if not line:
                            continue
                        now = time.perf_counter()
                        if t_last is not None:
                            with lock:
                                itls[window['mode']].append(
                                    now - t_last)
                        t_last = now
            except requests.RequestException:
                with lock:
                    dropped[0] += 1

    try:
        deadline = time.time() + 300
        while time.time() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(f'replica died rc={proc.returncode}')
            try:
                if sess.get(url + '/health',
                            timeout=2).status_code == 200:
                    break
            except requests.RequestException:
                pass
            time.sleep(0.5)
        else:
            raise RuntimeError('replica never became healthy')
        pid_before = proc.pid
        threads = [threading.Thread(target=worker, args=(w,))
                   for w in range(2)]
        for th in threads:
            th.start()
        time.sleep(4.0)                        # steady window
        with lock:
            window['mode'] = 'swap'
        t0 = time.perf_counter()
        resp = sess.post(url + '/admin/weights',
                         json={'checkpoint': ckpts[1]},
                         headers={'Authorization':
                                  'Bearer bench-token'},
                         timeout=240)
        swap_wall = time.perf_counter() - t0
        if resp.status_code != 200:
            raise RuntimeError(f'swap failed: {resp.status_code} '
                               f'{resp.text[:200]}')
        swap_info = resp.json()
        time.sleep(1.0)                        # post-swap tail traffic
        with lock:
            window['mode'] = 'steady'
        time.sleep(1.0)
        stop.set()
        for th in threads:
            th.join(timeout=120)
        relaunches = 0 if (proc.poll() is None and
                           proc.pid == pid_before) else 1
        stats = sess.get(url + '/stats', timeout=10).json()
        if stats.get('weight_version') != swap_info['weight_version']:
            raise RuntimeError('swap did not land: /stats '
                               f'weight_version={stats.get("weight_version")}')

        def p95(xs):
            return (statistics.quantiles(xs, n=20)[-1]
                    if len(xs) >= 20 else max(xs)) if xs else None

        steady_p95 = p95(itls['steady'])
        swap_p95 = p95(itls['swap'])
        print(f'# weight swap: duration={swap_wall:.3f}s '
              f'(apply={swap_info.get("apply_s")}s) steady_itl_p95='
              f'{steady_p95 * 1e3 if steady_p95 else -1:.1f}ms '
              f'swap_itl_p95={swap_p95 * 1e3 if swap_p95 else -1:.1f}ms '
              f'dropped={dropped[0]} relaunches={relaunches}',
              file=sys.stderr)
        out = [
            {'metric': 'weight_swap_duration_s',
             'value': round(swap_wall, 3), 'unit': 's',
             'vs_baseline': None},
            {'metric': 'weight_swap_dropped_requests',
             'value': dropped[0], 'unit': 'requests',
             'vs_baseline': None},
            {'metric': 'weight_swap_relaunches',
             'value': relaunches, 'unit': 'relaunches',
             'vs_baseline': None},
        ]
        if steady_p95 is not None:
            out.append({'metric': 'steady_itl_p95_ms',
                        'value': round(steady_p95 * 1e3, 2),
                        'unit': 'ms', 'vs_baseline': None})
        if swap_p95 is not None:
            out.append({'metric': 'weight_swap_itl_p95_ms',
                        'value': round(swap_p95 * 1e3, 2),
                        'unit': 'ms', 'vs_baseline': None})
        return out
    finally:
        stop.set()
        if proc.poll() is None:
            proc.kill()
        shutil.rmtree(tmp, ignore_errors=True)


def adapter_fleet_metrics() -> list:
    """Adapter-fleet phase (CPU-runnable, docs/serving.md "Adapter
    fleet"): one real engine-server subprocess serving a streaming
    workload while ``POST /admin/adapters`` hot-loads a LoRA adapter
    into the live stack. Reports:

      * adapter_load_duration_s — end-to-end hot-load time from the
        admin response (stage + validate + graft under load);
      * adapter_load_itl_p95_ms — p95 inter-token latency over the
        load window;
      * adapter_steady_itl_p95_ms — the same stream's p95 with no
        load in flight (the hot-load pause is the delta);
      * adapter_load_dropped_requests — MUST be 0: a hot load grafts
        at a tick boundary, it never drops in-flight work;
      * adapter_routed_requests — lora-routed generations served by
        the freshly loaded adapter (must be > 0: the load is live,
        not just acknowledged);
      * adapter_{consolidated,dedicated}_req_per_chip_s and
        adapter_consolidation_gain — the SAME two-model workload
        through the real LB front door against ONE replica hosting
        both adapters vs one dedicated single-adapter replica per
        model (the tenants-per-chip claim), with per-model
        chip-seconds-per-good-token read from the replicas' own
        capacity-ledger counters.
    """
    import dataclasses as _dc
    import shutil
    import socket
    import statistics
    import subprocess
    import tempfile
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np
    import requests
    import flax.linen as nn

    from skypilot_tpu.models import llama
    from skypilot_tpu.models import weights as weights_lib
    from skypilot_tpu.train import checkpoint as ckpt_lib
    from skypilot_tpu.train import lora as tlora
    from skypilot_tpu.train import trainer

    def free_port():
        with socket.socket() as s:
            s.bind(('127.0.0.1', 0))
            return s.getsockname()[1]

    tmp = tempfile.mkdtemp(prefix='skyt-adapterbench-')
    cfg = _dc.replace(llama.CONFIGS['debug'], max_seq_len=64,
                      param_dtype='float32', dtype='float32')
    model = llama.LlamaModel(cfg)
    zeros = jnp.zeros((1, 8), jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), zeros)
    base_ckpt = os.path.join(tmp, 'base')
    weights_lib.save_hf_checkpoint(cfg, params, base_ckpt)
    # An adapter dir shaped exactly like an `sft --lora-rank` run
    # writes (TrainStateS), for the debug model the server serves.
    lcfg = tlora.LoRAConfig(rank=2, alpha=4.0)
    tx = trainer.make_optimizer(trainer.TrainerConfig())

    def save_adapter(subdir, seed):
        tree = tlora.init_lora_params(nn.meta.unbox(params['params']),
                                      lcfg, jax.random.PRNGKey(seed))
        rng = np.random.default_rng(seed)
        tree = jax.tree.map(
            lambda x: jnp.asarray(rng.normal(0, 0.1, x.shape),
                                  x.dtype), tree)
        state = trainer.TrainStateS(step=jnp.zeros((), jnp.int32),
                                    params=tree,
                                    opt_state=tx.init(tree))
        path = os.path.join(tmp, subdir)
        ck = ckpt_lib.Checkpointer(path, async_save=False)
        ck.save(0, state, force=True)
        ck.wait()
        ck.close()
        return path

    adapter_dir = save_adapter('adapter_fr', 9)
    adapter_de = save_adapter('adapter_de', 11)
    port = free_port()
    url = f'http://127.0.0.1:{port}'
    env = _cpu_child_env(SKYT_ADMIN_TOKEN='bench-token')
    proc = subprocess.Popen(
        [sys.executable, '-m', 'skypilot_tpu.infer.server',
         '--checkpoint', base_ckpt, '--port', str(port),
         '--num-slots', '2', '--max-seq-len', '64'],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    sess = requests.Session()
    itls = {'steady': [], 'load': []}
    lock = threading.Lock()
    window = {'mode': 'steady'}
    dropped = [0]
    routed = [0]
    stop = threading.Event()

    def worker(wid):
        i = 0
        while not stop.is_set():
            i += 1
            body = {'tokens': [wid + 1, (i % 7) + 1, 3],
                    'max_tokens': 16, 'stream': True}
            with lock:
                lora_live = window['mode'] == 'routed'
            if lora_live:
                body['lora'] = 'fr'
            try:
                t_last = None
                with requests.post(url + '/generate', json=body,
                                   stream=True, timeout=120) as r:
                    if r.status_code != 200:
                        with lock:
                            dropped[0] += 1
                        continue
                    for line in r.iter_lines():
                        if not line:
                            continue
                        now = time.perf_counter()
                        if t_last is not None:
                            with lock:
                                key = ('load'
                                       if window['mode'] == 'load'
                                       else 'steady')
                                itls[key].append(now - t_last)
                        t_last = now
                if lora_live:
                    with lock:
                        routed[0] += 1
            except requests.RequestException:
                with lock:
                    dropped[0] += 1

    try:
        deadline = time.time() + 300
        while time.time() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(f'replica died rc={proc.returncode}')
            try:
                if sess.get(url + '/health',
                            timeout=2).status_code == 200:
                    break
            except requests.RequestException:
                pass
            time.sleep(0.5)
        else:
            raise RuntimeError('replica never became healthy')
        threads = [threading.Thread(target=worker, args=(w,))
                   for w in range(2)]
        for th in threads:
            th.start()
        time.sleep(4.0)                        # steady window
        with lock:
            window['mode'] = 'load'
        t0 = time.perf_counter()
        resp = sess.post(url + '/admin/adapters',
                         json={'op': 'load', 'name': 'fr',
                               'checkpoint': adapter_dir,
                               'alpha': 4.0},
                         headers={'Authorization':
                                  'Bearer bench-token'},
                         timeout=240)
        load_wall = time.perf_counter() - t0
        if resp.status_code != 200:
            raise RuntimeError(f'adapter load failed: '
                               f'{resp.status_code} {resp.text[:200]}')
        time.sleep(1.0)                        # post-load tail traffic
        with lock:
            window['mode'] = 'routed'
        # The first post-load dispatch recompiles the decode step with
        # the grafted stack (~10s on a CPU host), so the routed window
        # is completion-gated, not a fixed sleep.
        deadline = time.time() + 120
        while time.time() < deadline:
            with lock:
                if routed[0] >= 4:
                    break
            time.sleep(0.2)
        stop.set()
        for th in threads:
            th.join(timeout=120)
        stats = sess.get(url + '/stats', timeout=10).json()
        hosted = (stats.get('adapters') or {}).get('adapters') or {}
        if 'fr' not in hosted:
            raise RuntimeError(f'load did not land: /stats '
                               f'adapters={hosted}')
        if routed[0] == 0:
            raise RuntimeError(f'no lora-routed generation completed '
                               f'(dropped={dropped[0]})')

        # -- Consolidation A/B (the tenants-per-chip claim): the SAME
        # two-model workload through the real LB front door against
        # (a) ONE replica hosting both adapters and (b) one dedicated
        # single-adapter replica per model. requests/chip/s, plus the
        # per-model chip-seconds-per-good-token ledger read from the
        # replicas' own capacity counters (what GET /fleet/adapters
        # rolls up fleet-wide).
        import re

        from aiohttp import web

        from skypilot_tpu.serve import load_balancer as lb_lib
        from skypilot_tpu.utils import metrics as metrics_lib

        # Park the LBs' controller-sync loops (no controller here);
        # deliberately not restored — the daemon LB threads outlive
        # the phase (same reasoning as the affinity phase).
        os.environ['SKYT_SERVE_LB_SYNC_INTERVAL'] = '3600'
        r = sess.post(url + '/admin/adapters',
                      json={'op': 'load', 'name': 'de',
                            'checkpoint': adapter_de, 'alpha': 4.0},
                      headers={'Authorization': 'Bearer bench-token'},
                      timeout=240)
        if r.status_code != 200:
            raise RuntimeError(f'de load failed: {r.status_code} '
                               f'{r.text[:200]}')

        line_re = re.compile(r'^(skyt_capacity_attributed_seconds_'
                             r'total|skyt_capacity_good_tokens_total)'
                             r'\{[^}]*model="([^"]*)"[^}]*\} '
                             r'([0-9.eE+-]+)$')

        def scrape(rep_url):
            attr, good = {}, {}
            for ln in sess.get(rep_url + '/metrics',
                               timeout=10).text.splitlines():
                m = line_re.match(ln)
                if not m:
                    continue
                fam, model, val = m.groups()
                dst = attr if fam.endswith('seconds_total') else good
                dst[model] = dst.get(model, 0.0) + float(val)
            return attr, good

        def start_lb(replica_urls, adapters_by_replica):
            lport = free_port()
            lb = lb_lib.SkyServeLoadBalancer(
                'http://127.0.0.1:9', lport,
                metrics_registry=metrics_lib.MetricsRegistry())
            lb.policy.set_ready_replicas(replica_urls)
            lb.state.replica_adapters.update(adapters_by_replica)
            threading.Thread(target=lambda: web.run_app(
                lb.make_app(), port=lport, print=None,
                handle_signals=False), daemon=True).start()
            lbase = f'http://127.0.0.1:{lport}'
            wait_deadline = time.time() + 30
            while time.time() < wait_deadline:
                try:
                    sess.get(lbase + '/metrics', timeout=2)
                    break
                except requests.RequestException:
                    time.sleep(0.2)
            return lb, lbase

        def run_fleet(lbase, chips, replica_urls):
            # Warm both model paths first: the post-load dispatch
            # recompiles the decode step with the grafted stack, and
            # a compile inside the timed window would charge XLA to
            # the serving numbers.
            for m in ('fr', 'de'):
                rw = requests.post(
                    lbase + '/generate',
                    json={'tokens': [1, 2, 3], 'max_tokens': 4,
                          'lora': m, 'model': m}, timeout=240)
                if rw.status_code != 200:
                    raise RuntimeError(f'warmup {m} failed: '
                                       f'{rw.status_code} '
                                       f'{rw.text[:200]}')
            before = {u: scrape(u) for u in replica_urls}
            served = {'fr': 0, 'de': 0}
            errors = [0]
            stop2 = threading.Event()

            def fleet_worker(model, wid):
                s2 = requests.Session()
                i = 0
                while not stop2.is_set():
                    i += 1
                    try:
                        r2 = s2.post(
                            lbase + '/generate',
                            json={'tokens': [wid + 1, (i % 7) + 1, 3],
                                  'max_tokens': 8, 'lora': model,
                                  'model': model}, timeout=120)
                        with lock:
                            if r2.status_code == 200:
                                served[model] += 1
                            else:
                                errors[0] += 1
                    except requests.RequestException:
                        with lock:
                            errors[0] += 1

            ths = [threading.Thread(target=fleet_worker,
                                    args=(m, wid))
                   for m in ('fr', 'de') for wid in range(2)]
            t0 = time.perf_counter()
            for th in ths:
                th.start()
            time.sleep(8.0)
            stop2.set()
            for th in ths:
                th.join(timeout=120)
            dur = time.perf_counter() - t0
            if errors[0]:
                raise RuntimeError(f'{errors[0]} routed requests '
                                   f'failed through the LB')
            after = {u: scrape(u) for u in replica_urls}
            per_model = {}
            for m in ('fr', 'de'):
                attr_d = sum(after[u][0].get(m, 0.0) -
                             before[u][0].get(m, 0.0)
                             for u in replica_urls)
                good_d = sum(after[u][1].get(m, 0.0) -
                             before[u][1].get(m, 0.0)
                             for u in replica_urls)
                per_model[m] = {
                    'attributed_chip_s': attr_d,
                    'good_tokens': good_d,
                    'chip_s_per_good_ktok':
                        (round(attr_d / good_d * 1e3, 4)
                         if good_d > 0 else None)}
            return {'req_per_chip_s':
                    round(sum(served.values()) / dur / chips, 3),
                    'served': dict(served), 'per_model': per_model}

        lb_a, lbase_a = start_lb(
            [url], {url: {'fr': 1, 'de': 1}})
        consolidated = run_fleet(lbase_a, 1, [url])

        dports = [free_port(), free_port()]
        dprocs = [subprocess.Popen(
            [sys.executable, '-m', 'skypilot_tpu.infer.server',
             '--checkpoint', base_ckpt, '--port', str(p),
             '--num-slots', '2', '--max-seq-len', '64'],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL) for p in dports]
        durls = [f'http://127.0.0.1:{p}' for p in dports]
        dedicated = None
        try:
            deadline = time.time() + 300
            pending = set(durls)
            while time.time() < deadline and pending:
                for du, dp in zip(durls, dprocs):
                    if dp.poll() is not None:
                        raise RuntimeError(
                            f'dedicated replica died '
                            f'rc={dp.returncode}')
                    if du in pending:
                        try:
                            if sess.get(du + '/health',
                                        timeout=2).status_code == 200:
                                pending.discard(du)
                        except requests.RequestException:
                            pass
                time.sleep(0.5)
            if pending:
                raise RuntimeError('dedicated replicas never became '
                                   'healthy')
            for du, (name, path) in zip(
                    durls, (('fr', adapter_dir), ('de', adapter_de))):
                r = sess.post(du + '/admin/adapters',
                              json={'op': 'load', 'name': name,
                                    'checkpoint': path, 'alpha': 4.0},
                              headers={'Authorization':
                                       'Bearer bench-token'},
                              timeout=240)
                if r.status_code != 200:
                    raise RuntimeError(
                        f'dedicated {name} load failed: '
                        f'{r.status_code} {r.text[:200]}')
            lb_b, lbase_b = start_lb(
                durls, {durls[0]: {'fr': 1}, durls[1]: {'de': 1}})
            dedicated = run_fleet(lbase_b, 2, durls)
            del lb_b
        finally:
            for dp in dprocs:
                if dp.poll() is None:
                    dp.kill()
        del lb_a
        gain = (consolidated['req_per_chip_s'] /
                dedicated['req_per_chip_s']
                if dedicated['req_per_chip_s'] else None)
        print(f'# adapter consolidation: 2-adapters-1-chip '
              f'{consolidated["req_per_chip_s"]} req/chip/s vs '
              f'dedicated {dedicated["req_per_chip_s"]} '
              f'(gain {gain and round(gain, 2)}x) '
              f'per_model={consolidated["per_model"]}',
              file=sys.stderr)

        def p95(xs):
            return (statistics.quantiles(xs, n=20)[-1]
                    if len(xs) >= 20 else max(xs)) if xs else None

        steady_p95 = p95(itls['steady'])
        load_p95 = p95(itls['load'])
        print(f'# adapter fleet: load={load_wall:.3f}s steady_itl_p95='
              f'{steady_p95 * 1e3 if steady_p95 else -1:.1f}ms '
              f'load_itl_p95={load_p95 * 1e3 if load_p95 else -1:.1f}ms '
              f'dropped={dropped[0]} routed={routed[0]}',
              file=sys.stderr)
        out = [
            {'metric': 'adapter_load_duration_s',
             'value': round(load_wall, 3), 'unit': 's',
             'vs_baseline': None},
            {'metric': 'adapter_load_dropped_requests',
             'value': dropped[0], 'unit': 'requests',
             'vs_baseline': None},
            {'metric': 'adapter_routed_requests',
             'value': routed[0], 'unit': 'requests',
             'vs_baseline': None},
        ]
        if steady_p95 is not None:
            out.append({'metric': 'adapter_steady_itl_p95_ms',
                        'value': round(steady_p95 * 1e3, 2),
                        'unit': 'ms', 'vs_baseline': None})
        if load_p95 is not None:
            out.append({'metric': 'adapter_load_itl_p95_ms',
                        'value': round(load_p95 * 1e3, 2),
                        'unit': 'ms', 'vs_baseline': None})
        out.append({'metric': 'adapter_consolidated_req_per_chip_s',
                    'value': consolidated['req_per_chip_s'],
                    'unit': 'req/chip/s', 'vs_baseline': None})
        out.append({'metric': 'adapter_dedicated_req_per_chip_s',
                    'value': dedicated['req_per_chip_s'],
                    'unit': 'req/chip/s', 'vs_baseline': None})
        if gain is not None:
            out.append({'metric': 'adapter_consolidation_gain',
                        'value': round(gain, 3), 'unit': 'x',
                        'vs_baseline': None})
        for fleet_name, fleet in (('consolidated', consolidated),
                                  ('dedicated', dedicated)):
            for m in ('fr', 'de'):
                cost = fleet['per_model'][m]['chip_s_per_good_ktok']
                if cost is not None:
                    out.append(
                        {'metric': f'adapter_{fleet_name}_chip_s_'
                                   f'per_good_ktok_{m}',
                         'value': cost, 'unit': 'chip-s/ktok',
                         'vs_baseline': None})
        return out
    finally:
        stop.set()
        if proc.poll() is None:
            proc.kill()
        shutil.rmtree(tmp, ignore_errors=True)


def watchdog_overhead_metrics() -> list:
    """Heartbeat hot-path cost (CPU-runnable): per-step wall delta of
    hb.on_step (file-backed, interval-throttled — the exact sft call)
    against a fixed synthetic step, interleaved best-of-2 per mode
    (same co-tenant-noise discipline as the tracing phase). Acceptance
    (docs/observability.md "Training plane"): <=1% of a ~ms-scale step."""
    import tempfile

    import numpy as np

    from skypilot_tpu.train import heartbeat as heartbeat_lib

    # ~ms-scale synthetic step: short enough to run hundreds of
    # iterations, long enough that the measured ratio means something
    # (a real TPU step is 10-1000x longer, so this is an upper bound).
    a = np.random.default_rng(0).standard_normal((640, 640))

    def run(hb, n=200) -> float:
        t0 = time.perf_counter()
        for i in range(n):
            (a @ a).sum()
            if hb is not None:
                hb.on_step(i)
        return time.perf_counter() - t0

    run(None, n=30)   # warm the BLAS path
    best_off = best_on = float('inf')
    per_step_us = None
    with tempfile.TemporaryDirectory() as d:
        for trial in range(3):
            best_off = min(best_off, run(None))
            hb = heartbeat_lib.HeartbeatWriter(
                os.path.join(d, f'hb-{trial}.json'), 0)
            best_on = min(best_on, run(hb))
        # Raw per-call cost, measured directly (no synthetic step).
        hb = heartbeat_lib.HeartbeatWriter(os.path.join(d, 'hb-raw.json'),
                                           0)
        n = 20000
        t0 = time.perf_counter()
        for i in range(n):
            hb.on_step(i)
        per_step_us = (time.perf_counter() - t0) / n * 1e6
    pct = (best_on - best_off) / best_off * 100.0
    print(f'# watchdog overhead: heartbeat on_step {per_step_us:.2f}us, '
          f'step-time delta {pct:+.2f}% (best-of-3 each mode)',
          file=sys.stderr)
    return [
        {'metric': 'heartbeat_step_overhead_pct',
         'value': round(pct, 2), 'unit': '%', 'vs_baseline': None},
        {'metric': 'heartbeat_on_step_us',
         'value': round(per_step_us, 2), 'unit': 'us',
         'vs_baseline': None},
    ]


# The comms-plane phase runs in a CPU subprocess with 8 forced host
# devices: the plane is CPU-runnable by design (emulated slices), an
# 8-way mesh exists regardless of the bench host's chip count, and the
# probe/census compiles stay out of this process. On-chip comms
# numbers are not measured here.
_COMMS_PHASE_SCRIPT = r'''
import json, sys, time

import jax, jax.numpy as jnp

from skypilot_tpu.models import llama
from skypilot_tpu.parallel import comms_census, comms_profile
from skypilot_tpu.parallel import mesh as mesh_lib
from skypilot_tpu.train import trainer

out = {}

def make_step(mesh, batch, seq):
    cfg = llama.CONFIGS['debug']
    model = llama.LlamaModel(cfg)
    tx = trainer.make_optimizer(trainer.TrainerConfig(
        warmup_steps=1, total_steps=1000))
    sample = jnp.zeros((batch, seq), jnp.int32)
    state, _ = trainer.create_sharded_state(model, tx, mesh, sample,
                                            jax.random.PRNGKey(0))
    step = trainer.make_train_step(model, tx, mesh, donate=False)
    data = {'tokens': sample, 'targets': sample}
    return step, state, data

def timed_steps(step, state, data, n):
    s = state
    t0 = time.perf_counter()
    for _ in range(n):
        s, metrics = step(s, data)
    jax.block_until_ready(metrics['loss'])
    return time.perf_counter() - t0

# --- probe + census one-shot costs + overhead A/B on the train loop
mesh = mesh_lib.build_hybrid_mesh(
    mesh_lib.MeshSpec(fsdp=2, tp=2), mesh_lib.MeshSpec(dp=2),
    num_slices=2)
step, state, data = make_step(mesh, 4, 64)
for _ in range(3):
    state, m = step(state, data)
jax.block_until_ready(m['loss'])

t0 = time.perf_counter()
profile, _src = comms_profile.load_or_probe(
    mesh, dcn_axes=('dp',), payloads_mb=[0.25], iters=2, force=True)
out['comms_probe_s'] = round(time.perf_counter() - t0, 3)
t0 = time.perf_counter()
entries, source = comms_census.census_step(step, state, data,
                                           mesh=mesh, mode='compiled')
rep = comms_census.report(
    entries, source, profile=profile, dcn_axes=('dp',),
    link_classes=comms_profile.axis_link_classes(mesh, ('dp',)))
out['comms_census_s'] = round(time.perf_counter() - t0, 3)
out['comms_census_sites'] = rep['sites']
out['comms_census_total_mib'] = round(rep['total_bytes'] / 2**20, 4)
if rep['total_seconds'] is not None:
    out['comms_predicted_step_comms_ms'] = round(
        rep['total_seconds'] * 1e3, 4)
summ = comms_profile.summary(profile)
ar = summ.get('ici.all_reduce') or {}
out['comms_probe_ici_allreduce_busbw_gbps'] = round(
    ar.get('busbw_gbps', 0.0), 4)

# Overhead: the plane adds no per-step work (census/probe are
# one-shot, metrics publish at log boundaries) — measure it anyway.
# Interleaved best-of-3 per mode, publish every 10 steps in ON mode.
N = 30
best_off = best_on = float('inf')
for _ in range(3):
    best_off = min(best_off, timed_steps(step, state, data, N))
    t0 = time.perf_counter()
    s = state
    for i in range(N):
        s, metrics = step(s, data)
        if (i + 1) % 10 == 0:
            comms_census.publish_metrics(rep, steps=10)
            comms_profile.publish_profile_metrics(profile)
    jax.block_until_ready(metrics['loss'])
    best_on = min(best_on, time.perf_counter() - t0)
out['comms_plane_overhead_pct'] = round(
    (best_on - best_off) / best_off * 100.0, 3)

# --- placement A/B: emulated heterogeneous 4-slice mesh. Injected
# per-pair DCN costs (slow links on (0,3) and (1,2)) make the
# advisor's win assertable on homogeneous CPU hardware: the predicted
# DCN ring cost is what differs; the real step-time A/B proves the
# permuted mesh trains (its links are equal here, so the times should
# match — the prediction is the measurement on this host).
HET = {'entries': profile.get('entries', {}), 'dcn_pairs': {
    '0,1': {'busbw_gbps': 10.0}, '0,2': {'busbw_gbps': 10.0},
    '0,3': {'busbw_gbps': 1.0}, '1,2': {'busbw_gbps': 1.0},
    '1,3': {'busbw_gbps': 10.0}, '2,3': {'busbw_gbps': 10.0}}}
dec = comms_profile.choose_dcn_permutation(4, HET)
out['comms_placement_perm'] = dec['perm']
out['comms_placement_ring_score_rowmajor'] = round(
    dec['rowmajor_score'], 4)
out['comms_placement_ring_score_measured'] = round(dec['score'], 4)
out['comms_placement_predicted_speedup'] = round(
    dec['rowmajor_score'] / max(dec['score'], 1e-12), 3)

ici, dcn = mesh_lib.MeshSpec(tp=2), mesh_lib.MeshSpec(dp=4)
times = {}
for name, kwargs in (('rowmajor', {'placement': 'rowmajor'}),
                     ('measured', {'placement': 'measured',
                                   'profile': HET})):
    m = mesh_lib.build_hybrid_mesh(ici, dcn, num_slices=4, **kwargs)
    st, s0, d0 = make_step(m, 8, 64)
    for _ in range(2):
        s0, mm = st(s0, d0)
    jax.block_until_ready(mm['loss'])
    times[name] = min(timed_steps(st, s0, d0, 10) for _ in range(2))
    out[f'comms_placement_steptime_{name}_ms'] = round(
        times[name] / 10 * 1e3, 3)

print('COMMS_PHASE_JSON ' + json.dumps(out))
'''


def comms_plane_metrics() -> list:
    """Comms-plane phase (docs/observability.md "Comms plane"),
    CPU-runnable: probe + census one-shot costs, the train-loop
    overhead with the plane on vs off (acceptance <=1% — the plane
    adds no per-step work), and the measured-vs-rowmajor placement
    A/B on the emulated heterogeneous 4-slice mesh."""
    import subprocess
    import tempfile

    env = _cpu_child_env()
    flags = env.get('XLA_FLAGS', '')
    if '--xla_force_host_platform_device_count' not in flags:
        env['XLA_FLAGS'] = (
            flags + ' --xla_force_host_platform_device_count=8').strip()
    with tempfile.TemporaryDirectory() as d:
        env['SKYT_COMMS_CACHE'] = os.path.join(d, 'comms_profile.json')
        proc = subprocess.run(
            [sys.executable, '-c', _COMMS_PHASE_SCRIPT],
            capture_output=True, text=True, env=env,
            timeout=PHASE_DEADLINES['comms plane bench'] - 60)
    line = next((l for l in proc.stdout.splitlines()
                 if l.startswith('COMMS_PHASE_JSON ')), None)
    if proc.returncode != 0 or line is None:
        tail = (proc.stderr or '').strip().splitlines()[-5:]
        raise RuntimeError(
            f'comms phase subprocess rc={proc.returncode}: '
            f'{" | ".join(tail)}')
    data = json.loads(line[len('COMMS_PHASE_JSON '):])
    print(f"# comms plane: probe {data.get('comms_probe_s')}s, census "
          f"{data.get('comms_census_s')}s "
          f"({data.get('comms_census_sites')} sites, "
          f"{data.get('comms_census_total_mib')}MiB/step), overhead "
          f"{data.get('comms_plane_overhead_pct')}%, placement "
          f"{data.get('comms_placement_perm')} predicted speedup "
          f"{data.get('comms_placement_predicted_speedup')}x",
          file=sys.stderr)
    unit = {'comms_probe_s': 's', 'comms_census_s': 's',
            'comms_census_total_mib': 'MiB',
            'comms_predicted_step_comms_ms': 'ms',
            'comms_plane_overhead_pct': '%',
            'comms_probe_ici_allreduce_busbw_gbps': 'GB/s',
            'comms_placement_predicted_speedup': 'x'}
    return [
        {'metric': k,
         'value': v, 'unit': unit.get(
             k, 'ms' if k.endswith('_ms') else ''),
         'vs_baseline': None}
        for k, v in data.items() if not isinstance(v, list)]


def capacity_bench_metrics() -> list:
    """Capacity-plane phase (CPU-runnable, docs/observability.md
    "Capacity plane"): the deterministic workload engine against a
    real debug replica behind the REAL in-process LB tier.

      * capacity_max_sustained_qps / capacity_slo_attainment — the
        capacity-search artifact: largest offered rate whose fraction
        of requests with client-observed TTFT within the phase
        objective still meets the target (SKYT_CAPACITY_TARGET; the
        phase floor is 0.9 — the CPU debug replica is too noisy for
        a 0.99 knee);
      * capacity_chip_seconds_per_good_token — the busy-ledger cost
        report through FleetTelemetry.capacity_report (1 CPU "chip":
        a mechanism check, not a perf claim);
      * capacity_flash_crowd_shed_fraction — batch-class shed
        fraction through a seeded 25x flash-crowd replay with
        SKYT_QOS=1 (the protected class's 5xx count rides along in
        the artifact and must be 0);
      * capacity_ledger_overhead_decode_pct — the ledger's measured
        per-chunk cost (microbenchmarked 2x note + settle) times the
        chunk rate of a measured saturated decode window. (An on/off
        throughput A/B cannot resolve this on a shared CPU host:
        adjacent windows swing +/-10% from machine noise, orders of
        magnitude above the ledger's real cost.) Acceptance: <= 1%.
    """
    import socket
    import threading

    import requests
    from aiohttp import web

    from skypilot_tpu.benchmark import capacity as capacity_lib
    from skypilot_tpu.benchmark import workload
    from skypilot_tpu.infer import server as server_lib
    from skypilot_tpu.serve import fleet as fleet_lib
    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.utils import env as env_lib
    from skypilot_tpu.utils import metrics as metrics_lib

    # QoS on with thresholds sized to the 2-slot debug replica (the
    # flash segment must shed batch), controller sync parked.
    phase_env = {
        'SKYT_QOS': '1',
        'SKYT_QOS_QUEUE_DEGRADE': '0.5',
        'SKYT_QOS_QUEUE_SHED': '1',
        'SKYT_QOS_RESERVE_SLOTS': '1',
        'SKYT_QOS_REFRESH_S': '0.05',
        'SKYT_QOS_HOLD_S': '1',
        'SKYT_QOS_TTFT_SLO_MS': '0',
        'SKYT_SERVE_LB_SYNC_INTERVAL': '3600',
    }
    saved = {k: os.environ.get(k) for k in phase_env}
    os.environ.update(phase_env)

    def _port():
        with socket.socket() as s:
            s.bind(('127.0.0.1', 0))
            return s.getsockname()[1]

    eng = server_lib.build_engine('debug', num_slots=2, max_seq_len=64,
                                  decode_chunk=8, cache_mode='dense',
                                  prefix_caching=False)
    eng.start()
    try:
        srv = server_lib.InferenceServer(eng)
        rport = _port()
        threading.Thread(target=lambda: web.run_app(
            srv.make_app(), port=rport, print=None,
            handle_signals=False), daemon=True).start()
        rbase = f'http://127.0.0.1:{rport}'
        sess = requests.Session()
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                if sess.get(rbase + '/health',
                            timeout=2).status_code == 200:
                    break
            except requests.RequestException:
                pass
            time.sleep(0.2)
        # The REAL LB tier in front: routing, retries, and observed
        # sheds are all inside the measurement.
        lport = _port()
        lb = lb_lib.SkyServeLoadBalancer(
            'http://127.0.0.1:9', lport,
            metrics_registry=metrics_lib.MetricsRegistry())
        lb.policy.set_ready_replicas([rbase])
        threading.Thread(target=lambda: web.run_app(
            lb.make_app(), port=lport, print=None,
            handle_signals=False), daemon=True).start()
        base = f'http://127.0.0.1:{lport}'
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                sess.get(base + '/metrics', timeout=2)
                break
            except requests.RequestException:
                time.sleep(0.1)
        # Warm compiles + prime the per-class series.
        for cls in ('interactive', 'batch'):
            sess.post(rbase + '/generate',
                      json={'tokens': [2, 3, 4], 'max_tokens': 8},
                      headers={'X-Priority': cls,
                               'X-Tenant': 'bench'},
                      timeout=60).raise_for_status()

        # -- Ledger overhead on steady decode. An on/off throughput
        # A/B cannot resolve this on a shared CPU host: adjacent
        # decode windows swing +/-10% from machine noise, while the
        # ledger's per-chunk cost is a lock + dict update + two
        # counter incs (~microseconds against a ~5ms chunk). So bound
        # it from the measured mechanism cost: microbenchmark the
        # exact per-chunk call pattern (2x note + settle) on a
        # private ledger, multiply by the chunk rate of a measured
        # saturated decode window.
        def decode_tps(n_threads=4, per=6, toks=40):
            def worker():
                s2 = requests.Session()
                for _ in range(per):
                    r = s2.post(rbase + '/generate',
                                json={'tokens': [5, 6, 7],
                                      'max_tokens': toks},
                                timeout=120)
                    r.raise_for_status()
            t0 = time.perf_counter()
            ths = [threading.Thread(target=worker)
                   for _ in range(n_threads)]
            for th in ths:
                th.start()
            for th in ths:
                th.join(timeout=300)
            return (n_threads * per * toks) / \
                (time.perf_counter() - t0)

        decode_tps(per=2)   # warm
        tps = max(decode_tps() for _ in range(2))
        from skypilot_tpu.infer import ledger as bench_ledger_lib
        bl = bench_ledger_lib.BusyLedger(
            metrics_lib.MetricsRegistry(), enabled=True)
        key = ('interactive', 'bench', 'debug')
        n_iter = 5000
        t0 = time.perf_counter()
        for _ in range(n_iter):
            bl.note(key, 8)
            bl.note(key, 8)
            bl.settle(1e-9)
        per_chunk_s = (time.perf_counter() - t0) / n_iter
        # One settle delivers decode_chunk tokens per active slot
        # (8 x 2 here): chunks/s at the measured throughput.
        chunks_per_s = tps / (8 * 2)
        delta_pct = per_chunk_s * chunks_per_s * 100.0

        # -- Capacity search: open-loop trials at increasing rates.
        seed = workload.default_seed()
        target = env_lib.get_float('SKYT_CAPACITY_TARGET', 0.0) or 0.9
        ttft_slo_s = 0.75

        def measure(rate):
            spec = workload.WorkloadSpec(
                seed=seed, duration_s=6.0, rate_rps=rate,
                arrival='poisson',
                tenants=(workload.TenantProfile(
                    tenant='bench', cls='interactive',
                    prompt_mean=4.0, prompt_sigma=0.4, prompt_cap=8,
                    output_mean=6.0, output_sigma=0.4, output_cap=8,
                    session_pool=4, session_reuse=0.4,
                    prefix_len=2),))
            runner = workload.OpenLoopRunner(
                workload.http_submitter(base, timeout_s=60.0),
                compression=3.0)
            outs = runner.run(workload.generate_schedule(spec))
            good = sum(1 for o in outs
                       if o.status == 200 and o.ttft_s is not None
                       and o.ttft_s <= ttft_slo_s)
            return good / len(outs) if outs else 0.0

        res = capacity_lib.capacity_search(
            measure, target=target, rate_lo=2.0, rate_hi=64.0,
            resolution=0.25, max_trials=6)

        # -- Flash crowd + cost ledger through the fleet plane.
        # Prime the flash mix's (class, tenant) series first so the
        # baseline scrape has a first edge for every counter window
        # (retry through any post-search shed hold).
        for cls, tenant in (('interactive', 'clicky'),
                            ('batch', 'cruncher')):
            deadline = time.time() + 30
            while time.time() < deadline:
                r = sess.post(rbase + '/generate',
                              json={'tokens': [2, 3, 4],
                                    'max_tokens': 8},
                              headers={'X-Priority': cls,
                                       'X-Tenant': tenant},
                              timeout=60)
                if r.status_code == 200:
                    break
                time.sleep(0.5)
        time.sleep(0.3)   # let the engine settle the primed work
        fl = fleet_lib.FleetTelemetry(
            'bench', metrics_registry=metrics_lib.MetricsRegistry())
        assert fl.scrape('1', rbase)
        # 25x step: the crowd must decisively outrun the debug
        # replica (whose CPU throughput varies run to run) so the
        # queue builds and the shed ladder actually engages.
        flash_spec = workload.WorkloadSpec(
            seed=seed + 1, duration_s=12.0, rate_rps=2.0,
            arrival='poisson', flash_at_s=4.0, flash_factor=25.0,
            flash_duration_s=4.0,
            tenants=(
                workload.TenantProfile(
                    tenant='clicky', cls='interactive', weight=1.0,
                    prompt_mean=3.0, prompt_sigma=0.3, prompt_cap=6,
                    output_mean=3.0, output_sigma=0.3, output_cap=4,
                    session_pool=2, session_reuse=0.5, prefix_len=2),
                workload.TenantProfile(
                    tenant='cruncher', cls='batch', weight=3.0,
                    prompt_mean=4.0, prompt_sigma=0.3, prompt_cap=8,
                    output_mean=40.0, output_sigma=0.5, output_cap=48,
                    session_pool=2, session_reuse=0.2,
                    prefix_len=2)))
        outs = workload.OpenLoopRunner(
            workload.http_submitter(base, timeout_s=60.0),
            compression=2.0).run(
                workload.generate_schedule(flash_spec))
        summary = workload.summarize(outs, compression=2.0)
        shed_fraction = summary['classes']['batch']['shed_fraction']
        protected_5xx = summary['classes']['interactive']['errors_5xx']
        time.sleep(0.3)   # let the engine settle the tail chunks
        assert fl.scrape('1', rbase)
        cap = fl.capacity_report(window_s=300)
        chip_s = sum(s['attributed_chip_seconds']
                     for s in cap['slices'].values())
        good_tok = sum(s['good_tokens']
                       for s in cap['slices'].values())
        cspgt = round(chip_s / good_tok, 9) if good_tok else None

        print(f'# capacity bench: max_sustained_qps='
              f'{res.max_sustained_qps} (attainment='
              f'{res.slo_attainment:.3f} target={target}, '
              f'{len(res.trials)} trials), chip_s/good_tok={cspgt} '
              f'({chip_s:.3f}s over {good_tok:.0f} good tok), flash '
              f'shed={shed_fraction:.3f} protected_5xx='
              f'{protected_5xx}, ledger overhead '
              f'{per_chunk_s * 1e6:.2f}us/chunk at {tps:.0f}tok/s '
              f'steady decode = {delta_pct:.4f}%', file=sys.stderr)
        return [
            {'metric': 'capacity_max_sustained_qps',
             'value': round(res.max_sustained_qps, 3), 'unit': 'rps',
             'vs_baseline': None, 'trials': len(res.trials),
             'bracket_hi': res.bracket_hi},
            {'metric': 'capacity_slo_attainment',
             'value': round(res.slo_attainment, 4),
             'unit': 'fraction',
             'vs_baseline': round(res.slo_attainment / target, 4)},
            {'metric': 'capacity_chip_seconds_per_good_token',
             'value': cspgt, 'unit': 'chip-s/tok',
             'vs_baseline': None},
            {'metric': 'capacity_flash_crowd_shed_fraction',
             'value': round(shed_fraction, 4), 'unit': 'fraction',
             'vs_baseline': None, 'protected_5xx': protected_5xx},
            # Acceptance <= 1% of steady decode.
            {'metric': 'capacity_ledger_overhead_decode_pct',
             'value': round(delta_pct, 4), 'unit': '%',
             'vs_baseline': None,
             'ledger_us_per_chunk': round(per_chunk_s * 1e6, 3),
             'steady_decode_tok_s': round(tps, 1)},
        ]
    finally:
        eng.stop()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def interference_bench_metrics() -> list:
    """Tick-plane interference phase (CPU-runnable,
    docs/observability.md "Tick plane"):

      * interference_itl_p99_inflation_pct — the headline: per-request
        ITL p99 of the same seeded workload-engine schedule through a
        mixed-admission replica vs one with prefill throttled to
        isolated ticks (SKYT_TICKSTATS_ISOLATE=1, the disaggregation
        counterfactual without the page transfer);
      * interference_attributed_frac + the advisor verdict — the tick
        plane's own attribution scraped through FleetTelemetry's
        /fleet/interference rollup, so the bench exercises the real
        read path (measured interference x PR 15 DCN busbw x PR 12
        KV page bytes -> disaggregate / keep_colocated);
      * tickstats_overhead_p50_delta_pct — SKYT_TICKSTATS=1 vs =0 on
        /generate p50 (interleaved best-of-2, the tracing-overhead
        methodology). Acceptance: <= ~1% — with it off the loop body
        contains no recording call at all, so this bounds the cost of
        leaving the plane on.
    """
    import socket
    import statistics
    import threading

    import requests
    from aiohttp import web

    from skypilot_tpu.benchmark import workload
    from skypilot_tpu.infer import server as server_lib
    from skypilot_tpu.serve import fleet as fleet_lib
    from skypilot_tpu.utils import metrics as metrics_lib

    keys = ('SKYT_TICKSTATS', 'SKYT_TICKSTATS_ISOLATE')
    saved = {k: os.environ.get(k) for k in keys}

    def _port():
        with socket.socket() as s:
            s.bind(('127.0.0.1', 0))
            return s.getsockname()[1]

    def _serve(eng):
        srv = server_lib.InferenceServer(eng)
        port = _port()
        threading.Thread(target=lambda: web.run_app(
            srv.make_app(), port=port, print=None,
            handle_signals=False), daemon=True).start()
        base = f'http://127.0.0.1:{port}'
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                if requests.get(base + '/health',
                                timeout=2).status_code == 200:
                    break
            except requests.RequestException:
                pass
            time.sleep(0.2)
        return base

    def _build(**env_over):
        # Tickstats is wired at engine construction, so the env must
        # be set before build_engine for each variant.
        os.environ.update(env_over)
        eng = server_lib.build_engine(
            'debug', num_slots=2, max_seq_len=64, decode_chunk=8,
            cache_mode='dense', prefix_caching=False)
        eng.start()
        return eng

    engines = []
    sess = requests.Session()
    try:
        # A: tick plane on, mixed admission (the production path).
        eng_a = _build(SKYT_TICKSTATS='1', SKYT_TICKSTATS_ISOLATE='0')
        engines.append(eng_a)
        abase = _serve(eng_a)
        # C: SKYT_TICKSTATS=0 — the loop contains no recording call.
        eng_c = _build(SKYT_TICKSTATS='0')
        engines.append(eng_c)
        cbase = _serve(eng_c)

        payload = {'tokens': [7, 8, 9, 10], 'max_tokens': 8}

        def timed(base):
            t0 = time.perf_counter()
            sess.post(base + '/generate', json=payload,
                      timeout=60).raise_for_status()
            return time.perf_counter() - t0

        for _ in range(8):   # warm compiles + connections on both
            timed(abase)
            timed(cbase)
        # Pair the modes per REQUEST (tighter than the tracing
        # bench's per-pass interleave — two servers exist here, so a
        # co-tenant noise window lands on both modes within the same
        # millisecond), then best-of-2 paired passes.
        best = {'on': float('inf'), 'off': float('inf')}
        for _ in range(2):
            on, off = [], []
            for _ in range(40):
                off.append(timed(cbase))
                on.append(timed(abase))
            best['off'] = min(best['off'],
                              statistics.median(off) * 1e3)
            best['on'] = min(best['on'], statistics.median(on) * 1e3)
        overhead_pct = (best['on'] - best['off']) / best['off'] * 100.0
        eng_c.stop()
        engines.remove(eng_c)

        # -- Same seeded schedule, mixed vs isolated admission. The
        # isolated replica admits prefill only from all-idle ticks:
        # the interference-free counterfactual a prefill->decode
        # split would buy, minus the page transfer the advisor costs.
        spec = workload.WorkloadSpec(
            seed=workload.default_seed(), duration_s=8.0,
            rate_rps=5.0, arrival='poisson',
            tenants=(workload.TenantProfile(
                tenant='bench', cls='interactive',
                prompt_mean=6.0, prompt_sigma=0.4, prompt_cap=12,
                output_mean=20.0, output_sigma=0.4, output_cap=32,
                session_pool=4, session_reuse=0.3, prefix_len=2),))

        def itl_p99_ms(base):
            outs = workload.OpenLoopRunner(
                workload.http_submitter(base, timeout_s=120.0),
                compression=3.0).run(workload.generate_schedule(spec))
            itls = sorted(
                (o.latency_s - o.ttft_s) / (o.tokens - 1)
                for o in outs
                if o.status == 200 and o.ttft_s is not None
                and o.tokens and o.tokens > 1)
            assert itls, 'no multi-token completions in the burst'
            return itls[min(len(itls) - 1,
                            int(0.99 * len(itls)))] * 1e3

        # Prime the schedule's class series so the baseline scrape
        # has a first edge for every counter window (capacity-bench
        # discipline). Multi-chunk decodes: the ITL histogram only
        # observes steady pull-to-pull intervals, and an unobserved
        # histogram exposes no bucket series to take an edge from.
        for _ in range(2):
            sess.post(abase + '/generate',
                      json={'tokens': [7, 8, 9, 10],
                            'max_tokens': 24},
                      headers={'X-Priority': 'interactive',
                               'X-Tenant': 'bench'},
                      timeout=60).raise_for_status()
        time.sleep(0.3)
        eng_b = _build(SKYT_TICKSTATS='1', SKYT_TICKSTATS_ISOLATE='1')
        engines.append(eng_b)
        bbase = _serve(eng_b)
        for _ in range(3):   # warm this replica's queue path too
            sess.post(bbase + '/generate', json=payload,
                      timeout=120).raise_for_status()
        fl = fleet_lib.FleetTelemetry(
            'bench', metrics_registry=metrics_lib.MetricsRegistry())
        assert fl.scrape('1', abase)
        # Interleaved best-of-2 per mode (same rationale as the
        # overhead passes): a p99 over one ~40-request replay is a
        # small-sample quantile, so take the quieter of two replays
        # for each admission mode with the modes alternating.
        mixed_p99 = iso_p99 = float('inf')
        for _ in range(2):
            mixed_p99 = min(mixed_p99, itl_p99_ms(abase))
            iso_p99 = min(iso_p99, itl_p99_ms(bbase))
        time.sleep(0.3)   # settle the tail chunks into the counters
        assert fl.scrape('1', abase)
        rep = fl.interference_report(window_s=300)
        adv = rep.get('advisor') or {}
        inflation_pct = (mixed_p99 - iso_p99) / iso_p99 * 100.0

        attributed = rep.get('interference_frac')
        print(f"# interference bench: itl_p99 mixed={mixed_p99:.2f}ms "
              f"isolated={iso_p99:.2f}ms "
              f"inflation={inflation_pct:+.1f}% "
              f"attributed_frac={attributed} "
              f"advisor={adv.get('recommendation')} "
              f"tickstats overhead p50 off={best['off']:.2f}ms "
              f"on={best['on']:.2f}ms delta={overhead_pct:+.2f}%",
              file=sys.stderr)
        return [
            {'metric': 'interference_itl_p99_ms_mixed',
             'value': round(mixed_p99, 3), 'unit': 'ms',
             'vs_baseline': None},
            {'metric': 'interference_itl_p99_ms_isolated',
             'value': round(iso_p99, 3), 'unit': 'ms',
             'vs_baseline': None},
            # Headline: measured prefill-induced ITL p99 inflation.
            {'metric': 'interference_itl_p99_inflation_pct',
             'value': round(inflation_pct, 3), 'unit': '%',
             'vs_baseline': None,
             'attributed_frac': (round(attributed, 4)
                                 if attributed is not None else None)},
            {'metric': 'interference_advisor_disaggregate',
             'value': 1.0 if adv.get('recommendation') ==
             'disaggregate' else 0.0, 'unit': 'bool',
             'vs_baseline': None,
             'recommendation': adv.get('recommendation'),
             'reason': adv.get('reason'),
             'dcn_source': (adv.get('transfer') or {}).get(
                 'dcn_source'),
             'benefit_s_per_request': (adv.get('tradeoff') or
                                       {}).get('benefit_s_per_request'),
             'cost_s_per_request': (adv.get('tradeoff') or
                                    {}).get('cost_s_per_request')},
            # Acceptance: <= ~1%. vs_baseline is the off/on ratio
            # (>= ~0.99 means tickstats-on costs <= ~1%).
            {'metric': 'tickstats_overhead_p50_delta_pct',
             'value': round(overhead_pct, 3), 'unit': '%',
             'vs_baseline': round(best['off'] / best['on'], 4)
             if best['on'] > 0 else None, 'best_of': 2},
        ]
    finally:
        for eng in engines:
            try:
                eng.stop()
            except Exception:  # pylint: disable=broad-except
                pass
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def elastic_bench_metrics() -> list:
    """Elastic-capacity phase (CPU-runnable, docs/serving.md
    "Elastic capacity"):

      * elastic_cold_start_ttft_s — client-observed latency through a
        scale-to-zero wake: a 4-wide arrival wave parks in the LB
        surge queue while the fleet "cold-starts" (a controlled wake
        delay), and every parked request must be served — zero 5xx
        for the parked class;
      * elastic_forecast_slo_attainment — a deterministic simulated-
        clock decision replay: the SAME periodic demand wave through
        the reactive autoscaler and the predictive wrapper, with a
        60 s provisioning lead. Attainment = fraction of measured
        steps where provisioned capacity covers offered demand; the
        predictive path must not be worse (it pre-scales before each
        wave instead of paying delay + lead after it);
      * elastic_reshard_qps_per_chip_delta_pct — the PR 16 capacity
        search before and after an in-place /admin/reshard layout
        flip on the live replica. On CPU the flip is an identity
        restage, so the honest claim is that resharding is ~free in
        throughput (mechanism check); on a real mesh the layouts
        genuinely differ.
    """
    import socket
    import threading
    import types

    import requests
    from aiohttp import web

    from skypilot_tpu.benchmark import capacity as capacity_lib
    from skypilot_tpu.benchmark import workload
    from skypilot_tpu.infer import server as server_lib
    from skypilot_tpu.serve import autoscalers as asc_lib
    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.serve import service_spec as spec_lib
    from skypilot_tpu.utils import env as env_lib
    from skypilot_tpu.utils import metrics as metrics_lib

    phase_env = {
        'SKYT_SERVE_LB_SYNC_INTERVAL': '3600',
        'SKYT_LB_NO_REPLICA_POLL_S': '0.05',
        'SKYT_LB_NO_REPLICA_TIMEOUT_S': '60',
        'SKYT_ADMIN_TOKEN': 'bench-elastic',
    }
    saved = {k: os.environ.get(k) for k in phase_env}
    os.environ.update(phase_env)

    def _port():
        with socket.socket() as s:
            s.bind(('127.0.0.1', 0))
            return s.getsockname()[1]

    eng = server_lib.build_engine('debug', num_slots=2, max_seq_len=64,
                                  decode_chunk=8, cache_mode='dense',
                                  prefix_caching=False)
    eng.start()
    try:
        srv = server_lib.InferenceServer(eng)
        rport = _port()
        threading.Thread(target=lambda: web.run_app(
            srv.make_app(), port=rport, print=None,
            handle_signals=False), daemon=True).start()
        rbase = f'http://127.0.0.1:{rport}'
        sess = requests.Session()
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                if sess.get(rbase + '/health',
                            timeout=2).status_code == 200:
                    break
            except requests.RequestException:
                pass
            time.sleep(0.2)
        # Warm the compile so the cold-start number measures the
        # surge-queue wake, not XLA.
        sess.post(rbase + '/generate',
                  json={'tokens': [2, 3, 4], 'max_tokens': 4},
                  timeout=120).raise_for_status()

        # The LB starts with an EMPTY ready set: scaled to zero.
        lport = _port()
        lb = lb_lib.SkyServeLoadBalancer(
            'http://127.0.0.1:9', lport,
            metrics_registry=metrics_lib.MetricsRegistry())
        threading.Thread(target=lambda: web.run_app(
            lb.make_app(), port=lport, print=None,
            handle_signals=False), daemon=True).start()
        base = f'http://127.0.0.1:{lport}'
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                sess.get(base + '/metrics', timeout=2)
                break
            except requests.RequestException:
                time.sleep(0.1)

        # -- Cold-start TTFT through the surge queue.
        wake_delay_s = 1.0
        lat, codes, lock = [], [], threading.Lock()

        def arrival():
            s2 = requests.Session()
            t0 = time.perf_counter()
            r = s2.post(base + '/generate',
                        json={'tokens': [3, 4, 5], 'max_tokens': 4},
                        timeout=120)
            with lock:
                lat.append(time.perf_counter() - t0)
                codes.append(r.status_code)

        threads = [threading.Thread(target=arrival) for _ in range(4)]
        for th in threads:
            th.start()
        time.sleep(wake_delay_s)     # the fleet cold-starts...
        lb.policy.set_ready_replicas([rbase])   # ...and wakes
        for th in threads:
            th.join(timeout=180)
        parked_5xx = sum(1 for c in codes if c >= 500)
        cold_ttft = sorted(lat)[len(lat) // 2] if lat else None

        # -- Forecast-vs-reactive attainment: simulated clock, same
        # wave, 60 s provisioning lead. Square wave, period 300 s =
        # the default season (30 x 10 s buckets).
        sim = {'t': 1_000_000.0}
        real_time_mod = asc_lib.time
        asc_lib.time = types.SimpleNamespace(time=lambda: sim['t'])
        try:
            # Downscale delay shorter than the low phase (200 s) so
            # the reactive path genuinely shrinks between waves and
            # pays upscale-delay + lead on every rise; 600 s would
            # let the first wave's capacity coast through the rest.
            spec = spec_lib.ServiceSpec(
                readiness_path='/', min_replicas=1, max_replicas=10,
                target_qps_per_replica=2.0,
                upscale_delay_seconds=30,
                downscale_delay_seconds=60)
            lead_s, dt = 60.0, 5.0
            period, high_s, low_q, high_q = 300.0, 100.0, 2.0, 18.0

            def demand(rel_t):
                return high_q if (rel_t % period) < high_s else low_q

            def replay(make_autoscaler):
                sim['t'] = 1_000_000.0
                t0 = sim['t']
                a = make_autoscaler()
                ready, pending = spec.min_replicas, []
                ok = n = 0
                # 3 seasons of warmup (the forecaster's trust gate),
                # 2 measured.
                while sim['t'] - t0 < 5 * period:
                    d = demand(sim['t'] - t0)
                    n_arr = int(d * dt)
                    a.collect_request_timestamps(
                        [sim['t'] + i * dt / n_arr
                         for i in range(n_arr)])
                    sim['t'] += dt
                    for item in list(pending):
                        if item[0] <= sim['t']:
                            ready += item[1]
                            pending.remove(item)
                    tgt = a.evaluate_scaling(
                        ready).target_num_replicas
                    inflight = sum(c for _, c in pending)
                    if tgt > ready + inflight:
                        pending.append((sim['t'] + lead_s,
                                        tgt - ready - inflight))
                    elif tgt < ready:
                        ready = tgt
                    if sim['t'] - t0 >= 3 * period:
                        n += 1
                        if ready * spec.target_qps_per_replica \
                                >= d - 1e-9:
                            ok += 1
                return ok / n if n else 0.0

            reactive_att = replay(
                lambda: asc_lib.RequestRateAutoscaler(
                    spec, metrics_registry=metrics_lib
                    .MetricsRegistry()))
            forecast_att = replay(
                lambda: asc_lib.PredictiveAutoscaler(
                    asc_lib.RequestRateAutoscaler(
                        spec, metrics_registry=metrics_lib
                        .MetricsRegistry()),
                    metrics_registry=metrics_lib.MetricsRegistry(),
                    clock=lambda: sim['t']))
        finally:
            asc_lib.time = real_time_mod

        # -- QPS-per-chip before/after an in-place reshard (the PR 16
        # capacity search, shortened: the A/B needs a stable knee,
        # not the full artifact).
        seed = workload.default_seed()
        target = env_lib.get_float('SKYT_CAPACITY_TARGET', 0.0) or 0.9

        def measure(rate):
            wspec = workload.WorkloadSpec(
                seed=seed, duration_s=4.0, rate_rps=rate,
                arrival='poisson',
                tenants=(workload.TenantProfile(
                    tenant='bench', cls='interactive',
                    prompt_mean=4.0, prompt_sigma=0.4, prompt_cap=8,
                    output_mean=6.0, output_sigma=0.4, output_cap=8,
                    session_pool=4, session_reuse=0.4,
                    prefix_len=2),))
            runner = workload.OpenLoopRunner(
                workload.http_submitter(base, timeout_s=60.0),
                compression=3.0)
            outs = runner.run(workload.generate_schedule(wspec))
            good = sum(1 for o in outs
                       if o.status == 200 and o.ttft_s is not None
                       and o.ttft_s <= 0.75)
            return good / len(outs) if outs else 0.0

        def search():
            return capacity_lib.capacity_search(
                measure, target=target, rate_lo=2.0, rate_hi=32.0,
                resolution=0.5, max_trials=4)

        before = search()
        resp = sess.post(
            rbase + '/admin/reshard', json={'virtual_nodes': 2},
            headers={'Authorization': 'Bearer bench-elastic'},
            timeout=120)
        resp.raise_for_status()
        stats = sess.get(rbase + '/stats', timeout=30).json()
        assert stats['virtual_nodes'] == 2, stats
        assert stats['weight_version'] == 1, stats
        # The layout flip recompiles prefill/decode for the new
        # sharding (~1.3 s on CPU); warm it so the second search
        # measures steady-state serving, not XLA.
        for _ in range(3):
            sess.post(rbase + '/generate',
                      json={'tokens': [2, 3, 4], 'max_tokens': 4},
                      timeout=120).raise_for_status()
        after = search()
        chips = 1.0   # CPU bench: one "chip"
        qpc_before = before.max_sustained_qps / chips
        qpc_after = after.max_sustained_qps / chips
        delta_pct = ((qpc_after - qpc_before) / qpc_before * 100.0
                     if qpc_before else None)

        print(f'# elastic bench: cold_start_ttft={cold_ttft:.3f}s '
              f'(parked_5xx={parked_5xx}), attainment '
              f'forecast={forecast_att:.3f} vs '
              f'reactive={reactive_att:.3f}, qps/chip '
              f'{qpc_before:.2f} -> {qpc_after:.2f} '
              f'({delta_pct:+.1f}% across reshard)',
              file=sys.stderr)
        return [
            {'metric': 'elastic_cold_start_ttft_s',
             'value': round(cold_ttft, 4) if cold_ttft else None,
             'unit': 's', 'vs_baseline': None,
             'parked_5xx': parked_5xx,
             'wake_delay_s': wake_delay_s},
            {'metric': 'elastic_forecast_slo_attainment',
             'value': round(forecast_att, 4), 'unit': 'fraction',
             'vs_baseline': (round(forecast_att / reactive_att, 4)
                             if reactive_att else None),
             'reactive_attainment': round(reactive_att, 4),
             'lead_s': 60.0},
            {'metric': 'elastic_reshard_qps_per_chip_delta_pct',
             'value': (round(delta_pct, 2)
                       if delta_pct is not None else None),
             'unit': '%', 'vs_baseline': None,
             'qps_per_chip_before': round(qpc_before, 3),
             'qps_per_chip_after': round(qpc_after, 3),
             'trials': len(before.trials) + len(after.trials)},
        ]
    finally:
        eng.stop()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def train_mfu(dev) -> 'tuple[float, str]':
    """Train-throughput phase; returns (MFU, metric name). Raises on
    failure — main() isolates it so one phase crashing never loses the
    other's number."""
    from skypilot_tpu.models import llama
    # What each block's checkpoint saves ('full' recompute vs 'dots'
    # save-matmuls) — an on-chip tuning knob, no code edit needed.
    remat_pol = os.environ.get('SKYT_BENCH_REMAT', 'full')
    ndev = jax.device_count()
    if ndev > 1:
        # Multi-chip: the 8B-shaped fsdp run (BASELINE.json's SFT
        # config is Llama-3.1-8B on v5e-16) — params + Adam state
        # shard over the slice, per-chip batch of 1x2048.
        from skypilot_tpu.parallel import mesh as mesh_lib
        cfg = dataclasses.replace(llama.CONFIGS['llama3-8b'],
                                  max_seq_len=2048,
                                  param_dtype='bfloat16',
                                  remat_policy=remat_pol)
        mfu = _run_train(cfg, ndev, 2048, 10, 3, dev, windows=4,
                         mesh_spec=mesh_lib.MeshSpec(fsdp=ndev))
        return mfu, f'train_mfu_llama8b_fsdp{ndev}'
    # The TRUE llama3-1b shape (128k vocab), with a bf16 train state
    # because a f32 Adam state (~17GB) cannot fit one 16GB v5e chip —
    # on a real slice fsdp shards it; single-chip MFU is a
    # pure-throughput measurement. If it does not fit, the phase fails
    # under its own name: no other model stands in for it.
    cfg = dataclasses.replace(
        llama.CONFIGS['llama3-1b'], max_seq_len=2048,
        param_dtype='bfloat16', remat_policy=remat_pol)
    return (_run_train(cfg, 4, 2048, 10, 3, dev, windows=4),
            'train_mfu_llama1b_1chip')


def _run_train(cfg, batch, seq, steps, warmup, dev, windows=1,
               mesh_spec=None) -> float:
    from skypilot_tpu.models import llama
    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.train import trainer

    model = llama.LlamaModel(cfg)
    mesh = mesh_lib.build_mesh(mesh_spec or mesh_lib.MeshSpec())
    tcfg = trainer.TrainerConfig(warmup_steps=10, total_steps=1000)
    tx = trainer.make_optimizer(tcfg)
    sample = jnp.zeros((batch, seq), jnp.int32)
    state, _ = trainer.create_sharded_state(model, tx, mesh, sample,
                                            jax.random.PRNGKey(0))
    step = trainer.make_train_step(model, tx, mesh, donate=False)

    # N train steps inside ONE lax.scan with per-step on-device random
    # data: a single dispatch (no per-call host overhead), and fresh
    # inputs each step so no layer of caching can elide work.
    def scan_steps(state, key, n):
        def body(carry, k):
            st = carry
            toks = jax.random.randint(k, (batch, seq + 1), 0,
                                      cfg.vocab_size, jnp.int32)
            data = {'tokens': toks[:, :-1], 'targets': toks[:, 1:]}
            st, metrics = trainer_step_inner(st, data)
            return st, metrics['loss']
        return jax.lax.scan(body, state, jax.random.split(key, n))

    # Reuse the uncompiled inner step (make_train_step's jit would nest).
    import flax.linen as nn
    from skypilot_tpu.parallel import sharding as sharding_lib

    def trainer_step_inner(st, data):
        def loss_fn(params):
            logits = model.apply({'params': params}, data['tokens'])
            loss, n_tok = trainer.cross_entropy_loss(logits,
                                                     data['targets'])
            return loss, n_tok
        (loss, _), grads = jax.value_and_grad(loss_fn,
                                              has_aux=True)(st.params)
        return st.apply_gradients(grads, tx), {'loss': loss}

    with mesh, nn.logical_axis_rules(list(sharding_lib.DEFAULT_RULES)):
        run = jax.jit(scan_steps, static_argnums=(2,), donate_argnums=(0,))
        state, warm_losses = run(state, jax.random.PRNGKey(1), warmup)
        jax.device_get(warm_losses)
        # Best-of-N windows (timeit-style min).
        #
        # The timed region ends with a VALUE FETCH: device_get cannot
        # return until the window's last loss — which depends on every
        # step — exists. The one fetch is amortized across the window's
        # steps.
        dt = float('inf')
        for w in range(max(1, windows)):
            t0 = time.perf_counter()
            state, losses = run(state, jax.random.PRNGKey(2 + w), steps)
            losses = jax.device_get(losses)
            dt = min(dt, time.perf_counter() - t0)

        tokens_per_step = batch * seq
        # FLOPs of the timed window from the program's own HLO cost
        # analysis at the lowered stage (utils/profiling.py — global
        # pre-partition count, matching the mesh-total peak below; no
        # backend compile), falling back to the analytic
        # 6ND + 12*L*D*S attention count the bench used historically.
        n_params = cfg.num_params()
        analytic_window = (6 * n_params +
                           12 * cfg.n_layers * cfg.dim * seq) * \
            tokens_per_step * steps
        window_flops, flops_src = profiling_lib.train_step_flops(
            run, state, jax.random.PRNGKey(2), steps,
            analytic=analytic_window)
    metrics = {'loss': losses[-1]}

    tokens_per_sec = tokens_per_step * steps / dt
    model_flops = (window_flops or analytic_window) / dt
    # tokens_per_sec is global; normalize by the mesh's total peak.
    mfu = model_flops / (profiling_lib.peak_flops(dev) * mesh.size)

    print(f'# device={dev.device_kind} x{mesh.size} '
          f'params={n_params/1e9:.2f}B '
          f'batch={batch} seq={seq} steps={steps} '
          f'tokens/sec/chip={tokens_per_sec/mesh.size:,.0f} '
          f'step_time={dt/steps*1000:.1f}ms '
          f'loss={float(metrics["loss"]):.3f} flops_src={flops_src}',
          file=sys.stderr)
    if mfu > 1.2:
        # A >120% MFU is physically impossible: the timer measured
        # dispatch, not execution. Fail loudly — a fake headline number
        # in the bench artifact is worse than an error.
        raise RuntimeError(
            f'non-physical MFU {mfu:.2f} — timing measured dispatch, '
            'not execution; refusing to report it')
    return mfu


def _phases(extra: list) -> list:
    """(deadline name, thunk) in run order, after the train phase; the
    chip phases come first. `extra` is the list main() accumulates
    metrics in: the int8/int4 passes compare against the serve phase's
    steady rate in it."""
    def bf16_steady() -> float:
        steady = next(
            (m['value'] for m in extra if m['metric'] ==
             'serve_decode_steady_tok_per_sec_per_chip'), None)
        if steady is None:
            raise RuntimeError('needs the serve phase, which failed')
        return steady

    return [
        ('serve bench', serve_metrics),
        ('serve int8 bench', lambda: serve_int8_metric(bf16_steady())),
        ('serve int4 bench', lambda: serve_int4_metric(bf16_steady())),
        ('serve 8b int8 bench', serve_8b_int8_metric),
        ('serve spec-decode bench', serve_spec_metric),
        ('host overhead bench', host_overhead_metrics),
        ('tracing overhead bench', tracing_overhead_metrics),
        ('chaos recovery bench', chaos_recovery_metrics),
        ('overload bench', overload_bench_metrics),
        ('affinity bench', affinity_ab_metrics),
        ('slo report bench', slo_report_metrics),
        ('kv+ragged bench', kv_ragged_metrics),
        ('kv tier bench', kv_tier_metrics),
        ('weight swap bench', weight_swap_metrics),
        ('adapter fleet bench', adapter_fleet_metrics),
        ('watchdog overhead bench', watchdog_overhead_metrics),
        ('comms plane bench', comms_plane_metrics),
        ('capacity bench', capacity_bench_metrics),
        ('interference bench', interference_bench_metrics),
        ('elastic bench', elastic_bench_metrics),
    ]


def main() -> int:
    from skypilot_tpu.ops import dispatch as ops_dispatch
    from skypilot_tpu.utils import compile_cache
    compile_cache.configure()
    device = ops_dispatch.device_info()
    if device['platform'] != 'tpu':
        print(f'bench.py measures the TPU; JAX selected {device}',
              file=sys.stderr)
        return 2
    dev = jax.devices()[0]

    mfu = None
    metric_name = 'train_mfu_llama1b_1chip'
    failed = {}
    try:
        with phase_deadline(PHASE_DEADLINES['train bench'], 'train bench'):
            mfu, metric_name = train_mfu(dev)
    except (Exception, PhaseTimeout) as e:  # pylint: disable=broad-except
        failed['train bench'] = repr(e)
        print(f'# train bench failed: {e!r}', file=sys.stderr)

    extra = []
    for name, phase in _phases(extra):
        # Phases share one process and one chip's HBM.
        _reclaim_hbm(f'pre-{name}')
        try:
            with phase_deadline(PHASE_DEADLINES[name], name):
                extra += phase()
        except (Exception, PhaseTimeout) as e:  # pylint: disable=broad-except
            failed[name] = repr(e)
            print(f'# {name} failed: {e!r}', file=sys.stderr)

    print(json.dumps({
        'metric': metric_name,
        'value': round(mfu, 4) if mfu is not None else None,
        'unit': 'MFU',
        'vs_baseline': (round(mfu / BASELINE_MFU, 4)
                        if mfu is not None else None),
        # selection policy: train MFU is the best of 4 timed windows
        'best_of': 4,
        'device': device,
        'failed_phases': failed,
        'extra_metrics': extra,
    }))
    return 1 if failed else 0


if __name__ == '__main__':
    sys.exit(main())
