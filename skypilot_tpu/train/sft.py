"""SFT training entrypoint: `python -m skypilot_tpu.train.sft`.

The workload behind examples/llama_finetune.yaml — the TPU-native rebuild
of the reference's llm/llama-3_1-finetuning/lora.yaml (torchtune launcher)
as a framework-owned pjit program: multi-host init from the gang env
contract, sharded Llama/Mixtral, async Orbax checkpoint/resume (the
preemption-recovery half the managed-jobs controller needs), JSONL or
synthetic data.
"""
import time

_T_FIRST_LINE = time.perf_counter()   # set-up's `imports` phase starts here

import argparse  # noqa: E402
import json  # noqa: E402
from typing import Dict, Iterator, Optional  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from skypilot_tpu.utils import compile_cache  # noqa: E402
from skypilot_tpu.utils import faults  # noqa: E402
from skypilot_tpu.utils import log_utils  # noqa: E402
from skypilot_tpu.utils import env  # noqa: E402

logger = log_utils.init_logger(__name__)

_IMPORTS_S = time.perf_counter() - _T_FIRST_LINE


class _SetupPhases:
    """Where a run's set-up went, measured where it happens: consecutive
    phases from main()'s start, each ended by `mark(<its name>)`, and
    the module's own imports before them. After the first log boundary
    sft prints them as one line, `setup phases: ...`, whose parts sum to
    its `total` (docs/observability.md "Device profiles"; a profile
    starts after set-up, so the line is the record)."""

    def __init__(self) -> None:
        self.seconds = {'imports': _IMPORTS_S}
        self._last = time.perf_counter()

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.seconds[name] = now - self._last
        self._last = now

    def line(self, before: Dict, after: Dict) -> str:
        """`before`, `after`: compile_cache.snapshot() around the first
        step_fn call: its tracing, lowering, and backend compile or
        cache read, in brackets behind `first_step`."""
        stages = ' '.join(
            f'{name}={after[key] - before[key]:.3f}' for name, key in (
                ('trace', 'trace_seconds'), ('lower', 'lower_seconds'),
                ('compile_or_read', 'compile_seconds')))
        parts = ' '.join(
            f'{name}={s:.3f}' + (f' ({stages})' if name == 'first_step'
                                 else '') for name, s in self.seconds.items())
        return f'{parts} total={sum(self.seconds.values()):.3f}'


def _log_kernel_plans(ops_dispatch) -> None:
    """After the first step traced and compiled the model: say which
    kernel ladder rung each op landed on, so a run silently degraded to
    the XLA reference (e.g. an un-lowerable shape) is visible in the job
    log, and the plans fixed at trace time."""
    paths = ops_dispatch.snapshot()
    if paths:
        # The flash backward is the Pallas kernels, always; the words
        # stay for the log's readers.
        logger.info('kernel dispatch paths: %s (pallas %s, flash backward '
                    'pallas)', paths, 'interpreted'
                    if ops_dispatch.interpret_mode() else 'compiled')
    plans = ops_dispatch.flash_plan_snapshot()
    if plans:
        # Per kernel: tile extents and, per head, tiles visited / masked
        # / skipped and the grid's steps (`bd_` plans add the tiles the
        # allowed pairs would fill).
        logger.info('flash tile plan: %s', ', '.join(
            '{} {block_q}x{block_k} {visited}/{masked}/'
            '{skipped} steps {steps}'.format(k, **p) +
            (f' needed {p["needed"]}' if 'needed' in p else '')
            for k, p in plans.items()))
    moe_plan = ops_dispatch.moe_plan_snapshot()
    if moe_plan:
        logger.info('moe routing plan: %s', ' '.join(
            f'{k}={v}' for k, v in moe_plan.items()))
    grouped = ops_dispatch.grouped_plan_line()
    if grouped:
        logger.info('grouped tile plan: %s', grouped)
    bd_plan = ops_dispatch.bd_plan_snapshot()
    if bd_plan:
        logger.info('block diffusion plan: %s', ' '.join(
            f'{k}={v}' for k, v in bd_plan.items()))


def parse_mesh(spec: Optional[str], n_devices: int):
    """'fsdp=8,tp=2' → MeshSpec; None → auto for the device count."""
    from skypilot_tpu.parallel import mesh as mesh_lib
    if not spec or spec == 'auto':
        return mesh_lib.auto_spec(n_devices)
    axes = {}
    for part in spec.split(','):
        k, v = part.split('=')
        axes[k.strip()] = int(v)
    unknown = set(axes) - set(mesh_lib.MESH_AXES)
    if unknown:
        raise ValueError(f'unknown mesh axes {unknown}')
    return mesh_lib.MeshSpec(**axes)


def _comms_report(step_fn, state, batch, mesh, dcn_axes, lowered,
                  dmetrics, live_state) -> Optional[Dict]:
    """Comms plane at the first log boundary (docs/observability.md
    "Comms plane"): census the step's collectives, multiply by the
    CACHED link profile (sft never probes — the probe runs in bench/
    validation or `python -m skypilot_tpu.parallel.collectives`), log
    the per-axis breakdown, attach it to train.steps spans
    and the postmortem live state. Never raises; returns the report
    dict or None when the plane is off."""
    from skypilot_tpu.parallel import comms_census
    from skypilot_tpu.parallel import comms_profile
    if comms_census.census_mode() == 'off':
        return None
    try:
        entries, source = comms_census.census_step(
            step_fn, state, batch, mesh=mesh, lowered=lowered)
        link_classes = comms_profile.axis_link_classes(mesh, dcn_axes)
        profile = comms_profile.load_cached(mesh, dcn_axes)
        rep = comms_census.report(entries, source, profile=profile,
                                  dcn_axes=dcn_axes,
                                  link_classes=link_classes)
        logger.info('comms census (%s%s): %s', source,
                    '' if profile else '; no cached link profile — '
                    'bytes only', comms_census.format_report(rep))
        if profile:
            comms_profile.publish_profile_metrics(profile)
        if rep['axes']:
            attrs = {'comm_bytes_per_step': rep['total_bytes'],
                     'comm_breakdown': comms_census.format_report(rep)}
            if rep['total_seconds'] is not None:
                attrs['comm_seconds_estimate'] = round(
                    rep['total_seconds'], 6)
            dmetrics.set_span_attrs(attrs)
        live_state['comms'] = rep
        return rep
    except Exception as e:  # pylint: disable=broad-except
        logger.warning('comms report failed (%r); continuing without',
                       e)
        return None


def _rows(arr: np.ndarray, shift: bool) -> Dict[str, np.ndarray]:
    """A batch of a [B, S + 1] array of ids for next-token training
    (targets one to the right of tokens), or of a [B, S] one as it is:
    rows of the data for an objective that makes its own inputs and
    targets of them (train/block_diffusion.py)."""
    if shift:
        return {'tokens': arr[:, :-1], 'targets': arr[:, 1:]}
    return {'tokens': arr}


def synthetic_batches(vocab_size: int, batch: int, seq: int,
                      seed: int = 0, shift: bool = True
                      ) -> Iterator[Dict[str, np.ndarray]]:
    rng = np.random.default_rng(seed)
    while True:
        yield _rows(rng.integers(0, vocab_size, (batch, seq + shift),
                                 dtype=np.int32), shift)


def jsonl_batches(path: str, vocab_size: int, batch: int, seq: int,
                  tokenizer=None, shift: bool = True
                  ) -> Iterator[Dict[str, np.ndarray]]:
    """Pack {'text' or 'tokens'} JSONL rows into fixed [B,S] batches
    (`_rows` has what `shift` says).

    tokenizer: optional infer.tokenizer instance (--data-tokenizer
    points at a checkpoint dir's tokenizer.json) used for 'text' rows —
    real-vocab finetunes. Without one, text falls back to byte-level
    (dependency-free; fine for smoke/debug runs); pre-tokenized
    'tokens' rows bypass both."""
    def _tokens():
        while True:
            n_rows = 0
            with open(path, 'r', encoding='utf-8') as f:
                for line in f:
                    if not line.strip():
                        continue
                    n_rows += 1
                    row = json.loads(line)
                    if 'tokens' in row:
                        yield from (int(t) % vocab_size
                                    for t in row['tokens'])
                    elif tokenizer is not None:
                        yield from (int(t) % vocab_size
                                    for t in tokenizer.encode(
                                        row['text']))
                    else:
                        yield from (b % vocab_size
                                    for b in row['text'].encode())
                    yield 0  # document separator
            if n_rows == 0:
                raise ValueError(f'no data rows in {path!r}')

    stream = _tokens()
    while True:
        flat = np.fromiter(stream, dtype=np.int32,
                           count=batch * (seq + shift))
        yield _rows(flat.reshape(batch, seq + shift), shift)


def main(argv=None) -> None:
    phases = _SetupPhases()
    compile_cache.configure()
    parser = argparse.ArgumentParser()
    parser.add_argument('--model', default='llama3-1b')
    parser.add_argument('--mesh', default='auto',
                        help="e.g. 'fsdp=8,tp=2' or 'auto'")
    parser.add_argument('--dcn-mesh', default=None,
                        help="multi-slice: the axes that cross the "
                             "slice boundary (DCN), e.g. 'dp=2'; "
                             "--mesh then describes ONE slice (ICI). "
                             "Slice count/assignment comes from the "
                             "platform (MEGASCALE env on TPU)")
    parser.add_argument('--steps', type=int, default=1000)
    parser.add_argument('--batch', type=int, default=8)
    parser.add_argument('--seq', type=int, default=2048)
    parser.add_argument('--attn', default='auto',
                        choices=['auto', 'flash', 'xla', 'ring'],
                        help="'ring' = ring attention over the cp mesh "
                             "axis (long-context sequence parallelism; "
                             "pair with --mesh cp=N)")
    parser.add_argument('--lr', type=float, default=3e-4)
    parser.add_argument('--data', default=None,
                        help='JSONL path; default synthetic')
    parser.add_argument('--data-tokenizer', default=None,
                        help='tokenizer dir/file (tokenizer.json) for '
                             "JSONL 'text' rows; default byte-level "
                             'fallback. Typically the base checkpoint '
                             'dir.')
    parser.add_argument('--lora-rank', type=int, default=0,
                        help='> 0 enables LoRA: only adapter params '
                             'train (reference: llm/llama-3_1-finetuning'
                             '/lora.yaml)')
    parser.add_argument('--lora-alpha', type=float, default=16.0)
    parser.add_argument('--base-checkpoint', default=None,
                        help='HF-format checkpoint dir: start from real '
                             'weights instead of random init (the '
                             'finetune case; required for meaningful '
                             'LoRA). Loaded mesh-sharded.')
    parser.add_argument('--checkpoint-dir', default=None)
    parser.add_argument('--checkpoint-every', type=int, default=100)
    parser.add_argument('--resume', default='auto',
                        choices=['auto', 'never'])
    parser.add_argument('--log-every', type=int, default=10)
    parser.add_argument('--prefetch', type=int, default=2,
                        help='input-pipeline prefetch depth: batches '
                             'assembled and device_put on a background '
                             'thread while the current step runs '
                             '(docs/performance.md). 0 disables.')
    args = parser.parse_args(argv)

    # Multi-host: join via the gang env contract (runtime/gang.py
    # exports the JAX coordinator triplet; this jax's argless
    # initialize would not read it).
    from skypilot_tpu.runtime import gang
    gang.initialize_jax_distributed()
    logger.info('process %d/%d, %d local / %d global devices',
                jax.process_index(), jax.process_count(),
                jax.local_device_count(), jax.device_count())
    phases.mark('runtime')      # the first jax.devices(): the TPU's start
    from skypilot_tpu.ops import dispatch as ops_dispatch
    logger.info('device: %s', json.dumps(ops_dispatch.device_info()))

    # Training-plane observability (docs/observability.md "Training
    # plane"): per-step heartbeats to SKYT_HEARTBEAT_FILE (relayed by
    # the per-host agent to the gang watchdog) plus a rank-local
    # sentinel that dumps a postmortem bundle if THIS rank stalls —
    # the path that still works when the main thread is wedged in a
    # device call. hb is None with SKYT_WATCHDOG=0: the step loop then
    # contains no heartbeat call at all.
    from skypilot_tpu.train import heartbeat as heartbeat_lib
    from skypilot_tpu.train import postmortem as postmortem_lib
    from skypilot_tpu.train import watchdog as watchdog_lib
    hb = heartbeat_lib.writer_from_env(
        device_kind=jax.devices()[0].device_kind)
    # Rank comes from the gang env regardless of SKYT_WATCHDOG: the
    # train.step fault point's `rank` attr (where=rank:R targeting)
    # must stay correct with the heartbeat plane disabled.
    rank = env.get_int('SKYT_NODE_RANK', 0)
    # Live step-loop cell for engine-free bundle state: plain dict
    # writes on the host, no device syncs.
    live_state = {'step': None, 'steps_total': args.steps,
                  'model': args.model}
    train_state_reader = postmortem_lib.make_train_state_reader(
        live_state)
    sentinel = None
    if hb is not None:
        hb.mark_phase('init')
        sentinel = watchdog_lib.RankSentinel(
            hb, lambda snap: postmortem_lib.dump_bundle(
                'hang', rank=rank, heartbeat=snap,
                train_state=train_state_reader())).start()

    from skypilot_tpu.models import llama
    from skypilot_tpu.models import moe
    from skypilot_tpu.models import registry
    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.train import block_diffusion
    from skypilot_tpu.train import trainer

    try:
        model, cfg = registry.build(args.model, args.attn)
    except KeyError:
        raise SystemExit(f'unknown model {args.model}; choose from '
                         f'{registry.names()}') from None
    moe_cfg = getattr(model, 'moe', None)   # MixtralModel's MoeConfig
    # The objective is the model's. Under block diffusion --seq is the
    # data's length L: the model reads 2L positions, a batch is rows of
    # L ids with no shift, drawn from the ids under the mask's.
    bd = block_diffusion.objective_of(model)
    if args.base_checkpoint and not isinstance(cfg, llama.LlamaConfig):
        raise SystemExit(f'--base-checkpoint: models/weights.py has no '
                         f'loader for --model {args.model!r}')

    dcn_axes = ()
    if args.dcn_mesh:
        # Hybrid mesh: --mesh shards within a slice (ICI), --dcn-mesh
        # crosses slices (DCN). Keep bandwidth-hungry axes (fsdp/tp)
        # intra-slice; dp tolerates DCN latency. Slice placement along
        # the DCN axis follows SKYT_COMMS_PLACEMENT (default rowmajor;
        # 'measured' reorders by the cached comms profile —
        # docs/observability.md "Comms plane").
        dcn_spec = parse_mesh(args.dcn_mesh, 0)
        per_slice = jax.device_count() // max(1, dcn_spec.num_devices)
        spec = parse_mesh(args.mesh, per_slice)
        mesh = mesh_lib.build_hybrid_mesh(spec, dcn_spec)
        dcn_axes = tuple(a for a, s in dcn_spec.axis_sizes().items()
                         if s > 1)
        logger.info('hybrid mesh: ici=%s dcn=%s', spec, dcn_spec)
    else:
        spec = parse_mesh(args.mesh, jax.device_count())
        mesh = mesh_lib.build_mesh(spec)
        logger.info('mesh: %s', spec)
    if args.attn == 'ring' and spec.cp <= 1:
        # Without a cp axis the model would silently fall back to full
        # per-device attention — at long-context shapes that is an OOM
        # or a run without the requested sequence parallelism.
        raise SystemExit(
            "--attn ring needs a context-parallel mesh axis: add cp=N "
            "to --mesh (e.g. --mesh cp=8,tp=2)")

    tcfg = trainer.TrainerConfig(learning_rate=args.lr,
                                 total_steps=args.steps)
    tx = trainer.make_optimizer(tcfg)
    phases.mark('build')
    sample = jnp.zeros((args.batch, args.seq * (2 if bd else 1)),
                       jnp.int32)
    state, _ = trainer.create_sharded_state(model, tx, mesh, sample,
                                            jax.random.PRNGKey(0))
    # The host's share: the init program traced, compiled or read, and
    # enqueued. The device fills the state while what follows runs.
    phases.mark('state_init')

    from skypilot_tpu.train import checkpoint as ckpt_lib
    # Preemption-safe exit: SIGTERM/SIGINT requests a checkpoint at
    # the next step boundary; the run then exits EXIT_CODE_PREEMPTED
    # so the managed-jobs controller resumes from step k instead of
    # relaunching from zero (docs/robustness.md). immediate=True:
    # during startup (weight stream, first compile) there is no step
    # boundary coming for minutes — exit with the preemption code NOW
    # instead of burning the whole grace window loading and dying to
    # SIGKILL as FAILED; the guard turns cooperative at the step loop.
    guard = ckpt_lib.PreemptionGuard(immediate=True)
    try:
        ckpt = None
        if args.checkpoint_dir:
            ckpt = ckpt_lib.Checkpointer(
                args.checkpoint_dir,
                save_interval_steps=args.checkpoint_every)
        will_resume = (ckpt is not None and args.resume == 'auto'
                       and ckpt.latest_step() is not None)

        if args.base_checkpoint and will_resume and args.lora_rank == 0:
            # Full-finetune restart: the resume checkpoint holds the whole
            # state, so streaming the HF base in first would only burn
            # restart latency and transiently double param memory.
            logger.info('resume checkpoint found; skipping base load')
        elif args.base_checkpoint:
            # Finetune from real weights: replace the randomly initialized
            # params with the checkpoint's, loaded straight into the same
            # sharded layout (models/weights.py device_puts per leaf).
            from skypilot_tpu.models import weights as weights_lib
            import flax.linen as nn_meta
            ckpt_type = weights_lib.checkpoint_model_type(
                args.base_checkpoint)
            is_moe_model = args.model in moe.MIXTRAL_CONFIGS
            if (ckpt_type in ('mixtral', 'qwen3_moe')) != is_moe_model:
                raise SystemExit(
                    f'--base-checkpoint is {ckpt_type!r} but --model '
                    f'{args.model!r} is {"MoE" if is_moe_model else "dense"}')
            # Fail fast on a wrong-SIZE checkpoint BEFORE the multi-minute
            # weight stream: the loaders take shapes from the checkpoint,
            # and a mismatch would otherwise surface as an opaque einsum
            # error at the first train step.
            ckpt_cfg = (weights_lib.load_mixtral_config(args.base_checkpoint)
                        [0] if is_moe_model
                        else weights_lib.load_config(args.base_checkpoint))
            for f in ('dim', 'n_layers', 'n_heads', 'n_kv_heads', 'mlp_dim',
                      'vocab_size'):
                if getattr(ckpt_cfg, f) != getattr(cfg, f):
                    raise SystemExit(
                        f'--base-checkpoint {f}={getattr(ckpt_cfg, f)} does '
                        f'not match --model {args.model!r} '
                        f'{f}={getattr(cfg, f)}')
            if is_moe_model:
                loaded = weights_lib.load_mixtral_params(
                    cfg, moe_cfg, args.base_checkpoint, mesh=mesh)['params']
            else:
                loaded = weights_lib.load_llama_params(
                    cfg, args.base_checkpoint, mesh=mesh)['params']
            boxed = jax.tree.map(
                lambda box, arr: box.replace_boxed(arr)
                if isinstance(box, nn_meta.meta.AxisMetadata) else arr,
                state.params, loaded,
                is_leaf=lambda x: isinstance(x, nn_meta.meta.AxisMetadata))
            state = state.replace(params=boxed)
            logger.info('loaded base checkpoint %s', args.base_checkpoint)

        lora_cfg = None
        if args.lora_rank > 0 and bd is not None:
            raise SystemExit(f'--lora-rank: --model {args.model!r} trains '
                             f'by block diffusion, which the LoRA step '
                             f'does not compute')
        if args.lora_rank > 0:
            from skypilot_tpu.train import lora as lora_lib
            lora_cfg = lora_lib.LoRAConfig(rank=args.lora_rank,
                                           alpha=args.lora_alpha)
            frozen = state.params
            state = lora_lib.create_lora_state(model, frozen, tx, lora_cfg,
                                               jax.random.PRNGKey(1))
            logger.info('LoRA: %d trainable params',
                        lora_lib.num_lora_params(state.params))

        start_step = 0
        if ckpt is not None and args.resume == 'auto':
            restored = ckpt.restore(state)
            if restored is not None:
                state = restored
                start_step = int(jax.device_get(state.step))
                logger.info('resumed from step %d', start_step)

        if lora_cfg is not None:
            from skypilot_tpu.train import lora as lora_lib
            step_fn = lora_lib.make_lora_train_step(model, frozen, tx, mesh,
                                                    lora_cfg)
        else:
            step_fn = trainer.make_train_step(model, tx, mesh)
        data_tok = None
        if args.data and args.data_tokenizer:
            from skypilot_tpu.infer import tokenizer as tokenizer_lib
            data_tok = tokenizer_lib.load_tokenizer(args.data_tokenizer)
        data_vocab = getattr(cfg, 'data_vocab_size', cfg.vocab_size)
        batches = (jsonl_batches(args.data, data_vocab, args.batch,
                                 args.seq, tokenizer=data_tok,
                                 shift=bd is None)
                   if args.data else
                   synthetic_batches(data_vocab, args.batch, args.seq,
                                     shift=bd is None))

        from skypilot_tpu.parallel import comms_census
        from skypilot_tpu.utils import profiling
        prof = profiling.StepProfiler()   # no-op unless SKYT_PROFILE_DIR set
        comms_rep = None        # resolved -> comms census report dict
        # Deferred metrics: publish() pulls step k-1's loss/grad-norm while
        # step k runs — the log boundary never syncs the step chain's head
        # (logged loss lags one step; see trainer.DeferredMetrics).
        dmetrics = trainer.DeferredMetrics()

        # Overlap layer: assemble + device_put the next batches on a
        # background thread while the current step runs (train/prefetch.py).
        prefetcher = None
        if args.prefetch > 0:
            from skypilot_tpu.train import prefetch as prefetch_lib
            prefetcher = prefetch_lib.Prefetcher(
                batches, depth=args.prefetch,
                place=prefetch_lib.make_sharded_placer(mesh))
            batches = prefetcher
            # Bundles should record the prefetch queue depth (a full
            # queue + no steps = the device stopped pulling). The
            # sentinel's lambda reads this name late-bound.
            train_state_reader = postmortem_lib.make_train_state_reader(
                live_state, prefetcher)

        # Step loop from here: checkpoint writes begin, so preemption
        # must wait for a step boundary instead of exiting mid-write.
        guard.cooperative()
        if hb is not None:
            # First loop iteration traces + compiles; the watchdog's
            # stall budget must not apply until real steps flow.
            hb.mark_phase('compile')
        phases.mark('load')
        t0 = time.perf_counter()
        last_t = t0
        tokens_seen = 0
        # The time the loop waited for its input, next(batches): with a
        # prefetcher a queue pop unless the device outran the host.
        wait_s = last_wait_s = 0.0
        # In a step-window profile (SKYT_PROFILE_DIR) each iteration is
        # a `train.step` span on the host's line, on the device trace's
        # clock, with the calls below as its children
        # (docs/observability.md "Device profiles").
        span = jax.profiler.TraceAnnotation
        try:
            for step in range(start_step, args.steps):
                prof.on_step(step - start_step)
                first = step == start_step
                with jax.profiler.StepTraceAnnotation('train.step',
                                                      step_num=step):
                    with span('train.input_wait'):
                        t_in = time.perf_counter()
                        batch = next(batches)
                        wait_s += time.perf_counter() - t_in
                    if first:
                        phases.mark('first_batch')
                        compiled_before = compile_cache.snapshot()
                    with span('train.dispatch'):
                        state, metrics = step_fn(state, batch)
                    dmetrics.on_step(metrics)   # device refs only — no sync
                    if first:
                        phases.mark('first_step')
                        compiled_after = compile_cache.snapshot()
                        _log_kernel_plans(ops_dispatch)
                    tokens_seen += args.batch * args.seq * \
                        jax.process_count()
                    if hb is not None:
                        live_state['step'] = step
                        hb.on_step(step + 1,
                                   tokens_per_sec=tokens_seen /
                                   max(time.perf_counter() - t0, 1e-9))
                    saved = ckpt.save(step + 1, state) \
                        if ckpt is not None else False
                    # Chaos hook: kind=preempt here SIGTERMs this process,
                    # so the guard path below runs deterministically in
                    # tests; kind=hang (rank-targetable via `where=rank:R`)
                    # wedges the step loop so the watchdog/postmortem plane
                    # can be drilled on CPU (docs/robustness.md fault
                    # catalog).
                    faults.inject('train.step', step=step, rank=rank)
                    if guard.requested:
                        if hb is not None:
                            # SIGTERM path of the bundle contract: the dump
                            # is cheap and the evidence free (the preempted
                            # run is one operators ask questions about).
                            postmortem_lib.dump_bundle(
                                'preempt', rank=rank,
                                heartbeat=hb.snapshot(),
                                train_state=train_state_reader())
                        if ckpt is not None:
                            if not saved:
                                ckpt.save(step + 1, state, force=True)
                            ckpt.wait()   # async write must land before exit
                            logger.info('preemption: checkpoint saved at '
                                        'step %d', step + 1)
                        logger.info(
                            'preemption requested (signal %s); exiting '
                            'with code %d for controller recovery',
                            guard.signum, guard.EXIT_CODE)
                        raise SystemExit(guard.EXIT_CODE)
                    if (step + 1) % args.log_every != 0:
                        continue
                    with span('train.log'):   # train.pull stands inside it
                        now = time.perf_counter()
                        dt = now - t0
                        # Step time averaged over the logging window; the
                        # only device pull here is DeferredMetrics'
                        # step-(k-1) read, which overlaps step k's device
                        # compute.
                        n_window = min(args.log_every,
                                       step + 1 - start_step)
                        step_time = (now - last_t) / max(1, n_window)
                        if 'first_boundary' not in phases.seconds and \
                                comms_census.census_mode() != 'off':
                            # The census reads the step's lowering (same
                            # stage, no backend compile —
                            # docs/observability.md "Comms plane").
                            lowered = None
                            try:
                                lowered = step_fn.lower(state, batch)
                            except Exception as e:  # pylint: disable=broad-except
                                logger.warning('step lowering failed (%r)',
                                               e)
                            comms_rep = _comms_report(
                                step_fn, state, batch, mesh, dcn_axes,
                                lowered, dmetrics, live_state)
                        if comms_rep and comms_rep.get('axes'):
                            # Per-window publication: the bytes counter
                            # grows with the steps the census covers, the
                            # per-step seconds gauge just refreshes.
                            comms_census.publish_metrics(comms_rep,
                                                         steps=n_window)
                        wait_ms = 1e3 * (wait_s - last_wait_s) / \
                            max(1, n_window)
                        last_wait_s = wait_s
                        host = dmetrics.publish(
                            step_time_s=step_time,
                            tokens_per_sec=tokens_seen / dt,
                            steps=n_window, input_wait_ms=wait_ms)
                        last_t = now
                        logger.info(
                            'step %d/%d loss=%.4f tokens/s=%.0f%s%s '
                            'grad_norm=%.4f input_wait_ms=%.3f',
                            step + 1, args.steps,
                            host.get('loss', float('nan')),
                            tokens_seen / dt,
                            trainer.format_moe_stats(host),
                            block_diffusion.format_stats(host),
                            host.get('grad_norm', float('nan')), wait_ms)
                    if 'first_boundary' not in phases.seconds:
                        phases.mark('first_boundary')
                        logger.info('setup phases: %s', phases.line(
                            compiled_before, compiled_after))
        except SystemExit:
            raise
        except Exception:
            # Crash path of the bundle contract: stacks + flight
            # recorder + train state, then re-raise — the bundle must
            # never mask the real traceback.
            if hb is not None:
                postmortem_lib.dump_bundle(
                    'crash', rank=rank, heartbeat=hb.snapshot(),
                    train_state=train_state_reader())
            raise
        finally:
            # A crash inside the profiled window must still flush the trace
            # — the failing run is the one most worth profiling.
            prof.stop()
            if sentinel is not None:
                sentinel.stop()
            if prefetcher is not None:
                prefetcher.close()
        if hb is not None:
            hb.mark_phase('done')
        if ckpt is not None:
            if ckpt.latest_step() != args.steps:
                ckpt.save(args.steps, state, force=True)
            ckpt.close()
        logger.info('device at exit: %s',
                    json.dumps(ops_dispatch.device_info()))
        logger.info('compile cache: %s',
                    json.dumps(compile_cache.snapshot()))
        logger.info('done: %d steps', args.steps - start_step)
    finally:
        # In-process callers (tests) outlive main(): give them
        # their SIGTERM/SIGINT handlers back however the run
        # ends (completion, preemption SystemExit, setup error) —
        # and stop the sentinel thread (idempotent), so a setup
        # failure can't leave it watching a stale heartbeat.
        if sentinel is not None:
            sentinel.stop()
        guard.restore()


if __name__ == '__main__':
    main()
