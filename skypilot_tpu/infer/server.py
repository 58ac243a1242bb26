"""HTTP serving front-end for the inference engine.

The in-replica server the serve layer probes and proxies to (reference
serves vLLM's OpenAI-compatible server in a container; llm/vllm/
service.yaml readiness-probes /v1/models). Endpoints:

  GET  /health            — 200 once the engine loop is live (readiness
                            probe target).
  POST /generate          — {"tokens": [...]} or {"text": "..."},
                            optional max_tokens/temperature/top_k/
                            stream. stream=true sends one JSON line per
                            token as soon as it is sampled (TTFT = first
                            chunk latency).
  GET  /stats             — engine slot/queue stats;
                            ?request_id=N returns that request's phase
                            trace (queued → prefill_start →
                            first_token → done timestamps).
  GET  /metrics           — Prometheus text exposition (TTFT/ITL
                            histograms, token counters, KV-cache and
                            queue gauges; utils/metrics.py).
  POST /debug/profile     — ?ms=N on-demand jax.profiler capture
                            (403 unless SKYT_PROFILE_REMOTE=1;
                            single-flight; proxied fleet-wide by the
                            controller's POST /fleet/profile).
  GET  /v1/models         — OpenAI-compatible model listing (the
                            reference's service.yaml readiness-probes
                            this exact path).
  POST /v1/completions    — OpenAI-compatible completions (prompt str or
                            list, max_tokens/temperature/top_k/seed,
                            stop sequences (request cancelled at match),
                            n completions per prompt,
                            stream=true -> SSE chunks + [DONE]).
  POST /v1/chat/completions — OpenAI-compatible chat: messages render
                            through the checkpoint's own HF jinja
                            chat template (tokenizer_config.json or
                            --chat-template file), falling back to a
                            generic role-tag format.

Run:
  # random-weight debug model, byte tokenizer:
  python -m skypilot_tpu.infer.server --model debug --port 8000
  # real checkpoint (HF dir: *.safetensors + config.json +
  # tokenizer.json), tp-sharded over 4 chips:
  python -m skypilot_tpu.infer.server --checkpoint /path/llama3-8b --tp 4

Reference parity: llm/vllm/serve.yaml:1-30 (vLLM --model ... behind a
readiness-probed service).
"""
import argparse
import asyncio
import functools
import json
import os
import queue as queue_lib
import time
from typing import Dict, List, Optional

from aiohttp import web

from skypilot_tpu.infer import engine as engine_lib
from skypilot_tpu.infer import tokenizer as tokenizer_lib
from skypilot_tpu.infer import weight_swap as weight_swap_lib
from skypilot_tpu.serve import qos as qos_lib
from skypilot_tpu.serve import slo as slo_lib
from skypilot_tpu.utils import faults
from skypilot_tpu.utils import log_utils
from skypilot_tpu.utils import metrics as metrics_lib
from skypilot_tpu.utils import tracing as tracing_lib
from skypilot_tpu.utils import env as env_lib

logger = log_utils.init_logger(__name__)

# Back-compat aliases (older callers/tests import these from here).
byte_encode = lambda text, vocab_size: \
    tokenizer_lib.ByteTokenizer(vocab_size).encode(text)  # noqa: E731
byte_decode = lambda tokens: \
    tokenizer_lib.ByteTokenizer().decode(tokens)  # noqa: E731


class _StopScanner:
    """Windowed incremental stop-sequence matcher, shared by the SSE
    and drain paths. A new match can only END inside the newest piece,
    so each feed() searches max(len(stop))-1 chars of history plus the
    piece — O(total), not O(total^2) of rescanning everything."""

    def __init__(self, stops: List[str]) -> None:
        self.stops = stops
        self.max_len = max((len(s) for s in stops), default=0)
        self.acc = ''
        self.cut: Optional[int] = None   # absolute earliest-match index

    def feed(self, piece: str) -> bool:
        """Append new text; True once a stop has matched."""
        if self.cut is not None:
            return True
        lo = max(0, len(self.acc) - (self.max_len - 1)) \
            if self.max_len else len(self.acc)
        self.acc += piece
        if not self.stops:
            return False
        window = self.acc[lo:]
        best = None
        for s in self.stops:
            i = window.find(s)
            if i != -1 and (best is None or i < best):
                best = i
        if best is not None:
            self.cut = lo + best
        return self.cut is not None

    @property
    def text(self) -> str:
        """Full text, truncated before the earliest stop match."""
        return self.acc if self.cut is None else self.acc[:self.cut]

    def safe_len(self, final: bool = False) -> int:
        """Chars emittable now: everything up to the match, else all
        but the max(len(stop))-1 holdback (a partial stop prefix can
        span pieces); `final` flushes the holdback."""
        if self.cut is not None:
            return self.cut
        if final or not self.max_len:
            return len(self.acc)
        return max(0, len(self.acc) - (self.max_len - 1))


class InferenceServer:
    def __init__(self, engine: 'engine_lib.InferenceEngine',
                 tokenizer=None, model_id: str = 'skypilot-tpu',
                 lora_names: Optional[Dict[str, int]] = None,
                 lora_specs=None,
                 chat_template: Optional[str] = None,
                 special_tokens: Optional[Dict[str, str]] = None,
                 tracer: Optional['tracing_lib.Tracer'] = None) -> None:
        self.engine = engine
        self.tokenizer = tokenizer or tokenizer_lib.ByteTokenizer(
            engine.cfg.vocab_size)
        self.model_id = model_id
        # Tracing plane: server spans per route (traceparent extracted
        # from the LB / client), engine phase traces bridged in as
        # child spans, /debug/traces as the query surface. The flight
        # recorder snapshots engine state onto slow traces.
        self._tracer = tracer or tracing_lib.Tracer(
            service='infer', registry=engine.metrics_registry)
        self._tracer.store.slow_snapshot = self._engine_state_snapshot
        # Postmortem enrichment: a crash/hang bundle dumped from this
        # process shows the engine loop's last tick records (what was
        # the loop doing — mixed prefill/decode? pure decode? idle?).
        if engine.tickstats is not None:
            from skypilot_tpu.train import postmortem
            postmortem.register_state_reader(
                'recent_ticks', lambda: engine.tickstats.last(16))
        # The checkpoint's HF chat template (jinja source), rendered
        # for /v1/chat/completions the way vLLM renders it; None falls
        # back to the generic role-tag format.
        self._chat_template = None
        self._special_tokens = dict(special_tokens or {})
        if chat_template:
            try:
                import jinja2
                import jinja2.sandbox
            except ImportError:
                logger.warning('jinja2 not installed; chat requests '
                               'use the generic role-tag format')
                chat_template = None
        if chat_template:
            def raise_exception(msg):
                raise jinja2.TemplateError(msg)
            env = jinja2.sandbox.ImmutableSandboxedEnvironment(
                trim_blocks=True, lstrip_blocks=True)
            env.globals['raise_exception'] = raise_exception
            # Llama-3.1's template calls strftime_now for the system
            # date line (same helper transformers injects).
            import datetime as _dt
            env.globals['strftime_now'] = (
                lambda fmt: _dt.datetime.now().strftime(fmt))
            try:
                self._chat_template = env.from_string(chat_template)
            except jinja2.TemplateError as e:
                # Third-party template from the checkpoint: a syntax
                # error must not make the checkpoint unservable.
                logger.warning('chat template failed to compile (%s); '
                               'using the generic format', e)
        # QoS admission control (docs/qos.md): per-tenant token
        # buckets + the overload shed/degrade ladder, fed by live
        # engine signals. None with SKYT_QOS=0 — the admission gate is
        # then a single attribute check per request. Header PARSING
        # (X-Priority / X-Tenant, 400 on malformed) stays on in both
        # modes: the header contract must not depend on the flag.
        self._qos = qos_lib.ServerQoS(
            engine.qos_signals,
            registry=engine.metrics_registry) \
            if qos_lib.enabled() else None
        # Client-disconnect accounting: each detected disconnect also
        # cancelled its engine request(s) (slot + KV pages freed).
        self._m_disconnects = engine.metrics_registry.counter(
            'skyt_server_client_disconnects_total',
            'Requests whose client disconnected mid-flight (engine '
            'request cancelled)')
        # SLO goodput accounting (serve/slo.py): every finished
        # request is classified against its class objective; the fleet
        # scraper aggregates the resulting counters across replicas.
        self._goodput = slo_lib.GoodputTracker(
            registry=engine.metrics_registry)
        # In-place weight swap (docs/robustness.md "Zero-downtime
        # rollouts"): POST /admin/weights stages+validates+applies a
        # new checkpoint at a decode-tick boundary with zero requests
        # dropped. Gated on SKYT_ADMIN_TOKEN (403 otherwise) and
        # single-flight (409 concurrent).
        self._swap_mgr = weight_swap_lib.WeightSwapManager(engine)
        # Multi-LoRA routing (vLLM's OpenAI convention): 'model' in a
        # request names either the base model or a loaded adapter.
        self.lora_names = dict(lora_names or {})
        # Capacity plane (docs/observability.md "Capacity plane"):
        # bounded model labels for the engine's busy-time ledger (the
        # served id + loaded adapter names — never request strings),
        # and the per-(class, tenant, model) good-token counters the
        # fleet capacity report joins against attributed chip-seconds.
        self.engine.model_labels = {
            0: model_id, **{lid: name for name, lid
                            in self.lora_names.items()}}
        self._m_cap_tokens = engine.metrics_registry.counter(
            'skyt_capacity_tokens_total',
            'Generated tokens by QoS class, tenant, and model',
            ('class', 'tenant', 'model'))
        self._m_cap_good_tokens = engine.metrics_registry.counter(
            'skyt_capacity_good_tokens_total',
            'Generated tokens of requests that met their class SLO, '
            'by QoS class, tenant, and model',
            ('class', 'tenant', 'model'))
        if model_id in self.lora_names:
            # _resolve_lora matches the base id first, so a colliding
            # adapter would be silently unreachable.
            raise ValueError(
                f'--lora adapter name {model_id!r} collides with the '
                f'served model id; rename the adapter')
        # Adapter fleet (docs/serving.md "Adapter fleet"): dynamic
        # hot-load/unload of LoRA adapters at decode-tick boundaries
        # via POST /admin/adapters. Shares the swap manager's
        # single-flight lock; every change resyncs the routing map
        # and the bounded capacity-plane model labels.
        self._adapters = weight_swap_lib.AdapterRegistry(
            engine, self._swap_mgr, reserved_names={model_id},
            on_change=self._adapters_changed)
        if lora_specs:
            # Boot adapters with retained host trees: future loads
            # whose rank outgrows the stack can rebuild in full.
            self._adapters.seed(lora_specs)
        elif self.lora_names:
            self._adapters.seed_names(self.lora_names)

    def _adapters_changed(self) -> None:
        """AdapterRegistry change hook: resync routing ('model' name ->
        stack id) and the engine's bounded model-label map. Runs under
        the registry's single-flight lock, after the tick-boundary
        apply commits."""
        self.lora_names = self._adapters.name_ids()
        self.engine.model_labels = {
            0: self.model_id, **{lid: name for name, lid
                                 in self.lora_names.items()}}

    def _resolve_lora(self, payload, request=None):
        """-> (lora_id, error response | None). The base model id (or
        an absent 'model' field) routes to id 0; a loaded adapter name
        routes to its stack id; anything else is the OpenAI
        model_not_found error. When ``request`` is passed, the
        RESOLVED model label (base id or adapter name — a bounded
        set, never the raw request string) is stashed for the
        capacity-plane counters and flight-recorder snapshot."""
        name = payload.get('model')
        if name is None or name == self.model_id:
            if request is not None:
                request['skyt_model'] = self.model_id
            return 0, None
        lid = self.lora_names.get(name)
        if lid is None:
            return 0, web.json_response(
                {'error': {'message': f'model {name!r} not found',
                           'type': 'invalid_request_error',
                           'code': 'model_not_found'}}, status=404)
        if request is not None:
            request['skyt_model'] = name
        return lid, None

    async def _q_get(self, request: web.Request, out_q,
                     rids=()) -> object:
        """Blocking out_queue.get, off the event loop, that aborts the
        moment the client disconnects: the engine request(s) are
        cancelled — the slot and its KV pages free at the next delivery
        boundary — instead of generating into a dead socket. The get is
        chopped into short slices so disconnects are noticed within
        ~0.5 s even between token chunks."""
        loop = asyncio.get_running_loop()
        deadline = time.monotonic() + 300
        while True:
            try:
                return await loop.run_in_executor(
                    None, functools.partial(out_q.get, timeout=0.5))
            except queue_lib.Empty:
                pass
            if time.monotonic() > deadline:
                raise TimeoutError(
                    'engine produced nothing for 300s')
            tr = request.transport
            if tr is None or tr.is_closing():
                # The middleware counts the disconnect and re-cancels
                # (idempotent) — it also sees write-path resets this
                # poll can't observe.
                for rid in rids:
                    self.engine.cancel(rid)
                raise ConnectionResetError(
                    'client disconnected mid-request')

    @staticmethod
    def _deadline_from(request: web.Request):
        """Per-request deadline (tentpole): `X-Request-Deadline` is a
        relative budget in seconds; returns (absolute time.time()
        deadline | None, error response | None). Enforced by the
        engine's decode loop via SamplingParams.deadline."""
        hdr = request.headers.get('X-Request-Deadline')
        if hdr is None:
            return None, None
        try:
            budget = float(hdr)
            if budget <= 0:
                raise ValueError
        except ValueError:
            return None, web.json_response(
                {'error': f'X-Request-Deadline must be a positive '
                          f'number of seconds, got {hdr!r}'},
                status=400)
        return time.time() + budget, None

    def _qos_admit(self, request: web.Request, payload=None,
                   openai: bool = False,
                   max_new: Optional[int] = None):
        """QoS header contract + admission gate for one request.

        -> (cls, tenant, decision | None, error response | None).
        Malformed X-Priority / X-Tenant (or an unknown OpenAI
        `service_tier`) is a 400 naming the offender; with QoS enabled
        a shed/throttle decision is a 429 carrying Retry-After derived
        from the live overload/token-bucket state. An explicit
        X-Priority header wins over the body's service_tier. A
        'degrade' decision is returned to the caller, which clamps
        max_tokens before building SamplingParams."""
        try:
            cls = qos_lib.parse_priority(
                request.headers.get('X-Priority'))
            tenant = qos_lib.parse_tenant(
                request.headers.get('X-Tenant'))
            if openai and payload is not None and \
                    'X-Priority' not in request.headers:
                tier_cls = qos_lib.map_service_tier(
                    payload.get('service_tier'))
                if tier_cls is not None:
                    cls = tier_cls
        except ValueError as e:
            return None, None, None, web.json_response(
                {'error': str(e)}, status=400)
        # Stash for the goodput middleware: SLO attribution needs the
        # class/tenant even when the request is later shed or errors.
        request['skyt_qos_cls'] = cls
        request['skyt_qos_tenant'] = tenant
        if self._qos is None:
            return cls, tenant, None, None
        # Bounded model label for QoS (docs/serving.md "Adapter
        # fleet"): only names that RESOLVE to a loaded adapter key a
        # bucket/counter; everything else (absent, base, unknown-404)
        # collapses to the base id, so cardinality is the adapter
        # count, never the request-string space.
        model = self.model_id
        if payload is not None:
            named = payload.get('model')
            if isinstance(named, str) and named in self.lora_names:
                model = named
        dec = self._qos.admit(cls, tenant, max_new_tokens=max_new,
                              model=model)
        if dec.action in ('shed', 'throttle'):
            verb = ('shed by overload control'
                    if dec.action == 'shed'
                    else 'throttled by the per-tenant rate limit')
            return cls, tenant, dec, web.json_response(
                {'error': f'request {verb} '
                          f'(class={cls}, tenant={tenant}, '
                          f'overload level {dec.level}); retry after '
                          f'the Retry-After header',
                 'qos': {'class': cls, 'tenant': tenant,
                         'action': dec.action, 'level': dec.level}},
                status=429,
                headers={'Retry-After':
                         qos_lib.retry_after_header(dec.retry_after)})
        return cls, tenant, dec, None

    def _engine_state_snapshot(self) -> Dict[str, object]:
        """Engine occupancy at slow-trace capture time (the flight
        recorder's context: WHY was this request slow — deep queue?
        full slots? cold prefix cache?). Reads the same sources the
        /metrics gauges read; cheap enough to run per retained trace."""
        eng = self.engine
        with eng._lock:  # pylint: disable=protected-access
            occupants = [
                eng._ledger_key(s)  # pylint: disable=protected-access
                for s in eng._slots  # pylint: disable=protected-access
                if s is not None]
        running = len(occupants)
        snap: Dict[str, object] = {
            'queue_depth': eng._waiting.qsize(),  # pylint: disable=protected-access
            'running_slots': running,
            'num_slots': eng.num_slots,
            # Mixed-version windows during rolling updates must be
            # visible on flight-recorded slow traces ("slow because
            # the swap was draining under it").
            'weight_version': eng.weight_version,
        }
        if eng.pool is not None:
            total = eng.pool.cfg.n_pages - 1
            if total > 0:
                snap['kv_cache_utilization'] = round(
                    (total - eng.pool.free_pages()) / total, 4)
            if eng.prefix_caching:
                snap['prefix_cache'] = dict(eng.pool.prefix_stats)
        # Per-class queue depths + overload level on flight-recorded
        # slow traces: "slow because 40 batch requests sat ahead of
        # it" is the QoS plane's headline diagnosis.
        # Capacity plane: WHO held the slots when a slow trace was
        # captured — per-(class, tenant, model) occupancy, so every
        # SLO-violating exemplar from a capacity run is attributable
        # ("slow while 6 of 8 slots ran batch/analytics/base").
        if occupants:
            by_key: Dict[str, int] = {}
            for key in occupants:
                k = '/'.join(key)
                by_key[k] = by_key.get(k, 0) + 1
            snap['slot_occupancy'] = by_key
        depths = eng.qos_depths()
        if depths is not None:
            snap['qos_queue'] = depths
        if self._qos is not None:
            snap['qos_level'] = self._qos.overload.level()
        # Kernel dispatch paths: a slow trace that coincides with the
        # attention ladder degrading to the XLA rung should say so.
        from skypilot_tpu.ops import dispatch as ops_dispatch
        paths = ops_dispatch.snapshot()
        if paths:
            snap['kernel_paths'] = paths
        # Tick plane: what the engine loop was actually doing when the
        # snapshot was cut — the last few tick records show whether
        # the slow window was mixed prefill/decode or pure decode.
        if eng.tickstats is not None:
            snap['recent_ticks'] = eng.tickstats.last(8)
        return snap

    def _bridge_engine_spans(self, span, rids) -> None:
        """Attach the engine's phase trace for each request id as
        child spans of the server span: queue wait, prefill (TTFT's
        two halves), and decode, with the engine's batched-admission /
        chunk-delivery span events split across them. This is what
        turns 'the request was slow' into 'the request sat 700ms in
        the replica queue'."""
        for rid in rids:
            tr = self.engine.request_trace(rid)
            if not tr:
                continue
            queued = tr.get('queued')
            prefill = tr.get('prefill_start')
            first = tr.get('first_token')
            done = tr.get('done')
            events = tr.get('events', [])
            attrs = {'engine_request_id': rid,
                     'status': tr.get('status')}
            if queued is not None and prefill is not None:
                self._tracer.record_span(
                    'engine.queue_wait', queued, prefill, parent=span,
                    attributes=dict(
                        attrs, prompt_tokens=tr.get('prompt_tokens')))
            elif queued is not None and done is not None:
                # Cancelled/failed while still queued (no prefill ever
                # ran): the whole engine residency WAS queue wait —
                # the flight recorder's headline case must not lose
                # its engine span.
                self._tracer.record_span(
                    'engine.queue_wait', queued, done, parent=span,
                    attributes=dict(
                        attrs, prompt_tokens=tr.get('prompt_tokens')))
            if prefill is not None and first is not None:
                self._tracer.record_span(
                    'engine.prefill', prefill, first, parent=span,
                    attributes=attrs,
                    events=[e for e in events if e['ts'] <= first])
            if first is not None and done is not None:
                self._tracer.record_span(
                    'engine.decode', first, done, parent=span,
                    attributes=dict(attrs,
                                    generated=tr.get('generated')),
                    events=[e for e in events if e['ts'] > first])

    def _record_slo(self, request: web.Request, status: int,
                    t0_wall: float) -> None:
        """Classify a finished generation request for the SLO goodput
        counters (serve/slo.py). TTFT is SERVER-side — request arrival
        to the engine's first token — so queueing, admission, and any
        injected server.request latency all count against the
        objective, exactly as the client experiences them. Non-
        generation routes (no engine work, no parsed class) are
        skipped; server-caused denials (429 shed, 5xx) burn budget,
        client-side 4xx do not."""
        rids = request.get('skyt_engine_rids', ())
        cls = request.get('skyt_qos_cls')
        if not rids and cls is None:
            return
        cls = cls or qos_lib.DEFAULT_CLASS
        tenant = request.get('skyt_qos_tenant') or \
            qos_lib.DEFAULT_TENANT
        try:
            if not rids:
                if status == 429 or status >= 500:
                    self._goodput.record(cls, tenant, ok=False)
                return
            ok = status < 400
            for rid in rids:
                tr = self.engine.request_trace(rid) or {}
                first = tr.get('first_token')
                done = tr.get('done')
                gen = int(tr.get('generated') or 0)
                ttft = (first - t0_wall if first is not None
                        else None)
                itl = ((done - first) / (gen - 1)
                       if done is not None and first is not None
                       and gen >= 2 else None)
                good = self._goodput.record(cls, tenant, ok=ok,
                                            ttft_s=ttft, itl_s=itl,
                                            tokens=gen)
                # Capacity plane: good-token counters per (class,
                # tenant, model) — the denominator the fleet capacity
                # report divides attributed chip-seconds by.
                if gen > 0:
                    model = request.get('skyt_model') or self.model_id
                    self._m_cap_tokens.labels(
                        cls, tenant, model).inc(gen)
                    if good:
                        self._m_cap_good_tokens.labels(
                            cls, tenant, model).inc(gen)
        except Exception:  # pylint: disable=broad-except
            # Accounting must never turn a served request into a 500.
            logger.exception('SLO goodput recording failed')

    async def _debug_profile(self, request: web.Request
                             ) -> web.Response:
        """On-demand device profile: ``POST /debug/profile?ms=N``
        captures a jax.profiler trace of whatever the replica is doing
        for N ms (docs/observability.md "Fleet plane"). Gated on
        SKYT_PROFILE_REMOTE=1 — a trace names every op and shape the
        model runs, so reachability alone must not expose it — and
        single-flight (409 while one is in progress). On CPU the host
        trace is degraded but real."""
        if env_lib.get('SKYT_PROFILE_REMOTE', '0') not in \
                ('1', 'true'):
            return web.json_response(
                {'error': 'remote profiling disabled; start the '
                          'replica with SKYT_PROFILE_REMOTE=1'},
                status=403)
        raw = request.query.get('ms', '1000')
        try:
            ms = float(raw)
            if not 1 <= ms <= 60000:
                raise ValueError
        except ValueError:
            return web.json_response(
                {'error': f'ms must be a number in [1, 60000] '
                          f'milliseconds, got {raw!r}'}, status=400)
        from skypilot_tpu.utils import profiling as profiling_lib
        loop = asyncio.get_running_loop()
        try:
            result = await loop.run_in_executor(
                None, functools.partial(profiling_lib.capture_trace,
                                        ms))
        except profiling_lib.ProfilerBusy as e:
            return web.json_response({'error': str(e)}, status=409)
        except Exception as e:  # pylint: disable=broad-except
            logger.exception('profile capture failed')
            return web.json_response(
                {'error': f'profile capture failed: {e!r}'},
                status=500)
        return web.json_response(result)

    async def _admin_weights(self, request: web.Request
                             ) -> web.Response:
        """``POST /admin/weights`` — in-place weight hot-swap
        (docs/robustness.md "Zero-downtime rollouts").

        Body: ``{"checkpoint": <dir>, "version": N?, "drain": bool?}``
        or ``{"swap_back": true}``. Auth: requires SKYT_ADMIN_TOKEN to
        be configured AND presented as a bearer (403 otherwise — a
        weight push is a code push; reachability alone must never be
        enough). Single-flight: 409 while a swap is in progress; 400
        on a malformed body or a swap that failed validation/loading
        (old weights intact in every error case)."""
        token = env_lib.get('SKYT_ADMIN_TOKEN')
        if not token:
            return web.json_response(
                {'error': 'admin API disabled: start the replica with '
                          'SKYT_ADMIN_TOKEN set (the serve controller '
                          'exports the per-service token)'},
                status=403)
        import hmac
        got = request.headers.get('Authorization', '')
        if not hmac.compare_digest(
                got.encode('utf-8', 'surrogateescape'),
                f'Bearer {token}'.encode('utf-8')):
            return web.json_response(
                {'error': 'unauthorized: missing or bad Authorization '
                          'bearer token'}, status=403)
        try:
            payload = await request.json()
        except ValueError:
            payload = None
        if not isinstance(payload, dict):
            return web.json_response(
                {'error': 'body must be a JSON object'}, status=400)
        drain = payload.get('drain')
        if drain is not None and not isinstance(drain, bool):
            return web.json_response(
                {'error': f'drain must be a boolean, got {drain!r}'},
                status=400)
        version = payload.get('version')
        if version is not None and (isinstance(version, bool) or
                                    not isinstance(version, int) or
                                    version < 1):
            return web.json_response(
                {'error': f'version must be an integer >= 1, got '
                          f'{version!r}'}, status=400)
        loop = asyncio.get_running_loop()
        if payload.get('swap_back'):
            op = functools.partial(self._swap_mgr.swap_back,
                                   drain=drain)
        else:
            ckpt = payload.get('checkpoint')
            if not isinstance(ckpt, str) or not ckpt:
                return web.json_response(
                    {'error': 'checkpoint must be a non-empty path '
                              '(or pass swap_back: true)'}, status=400)
            op = functools.partial(self._swap_mgr.swap,
                                   checkpoint=ckpt, version=version,
                                   drain=drain)
        try:
            result = await loop.run_in_executor(None, op)
        except weight_swap_lib.SwapInFlight as e:
            return web.json_response({'error': str(e)}, status=409)
        except weight_swap_lib.WeightSwapError as e:
            return web.json_response(
                {'error': str(e),
                 'weight_version': self.engine.weight_version},
                status=400)
        return web.json_response(result)

    @staticmethod
    def _kv_peer_from(request: web.Request) -> Optional[str]:
        """The LB's X-KV-Peer hint (base URL of the replica its
        rendezvous ring designates as this prefix's owner), validated
        against the known replica set — anything else is dropped,
        never an error (the hint is advisory; SKYT_KV_TIER=off engines
        ignore it entirely). The LB strips any client-supplied
        X-KV-Peer before proxying (_HOP_HEADERS), so this check is the
        direct-to-replica half of the defense: the engine fetches from
        the peer with its admin bearer token, so an arbitrary URL here
        would be an SSRF + credential-leak vector. Accepted peers:
        loopback (single-host fleets, tests), or a scheme://host:port
        listed in SKYT_KV_PEER_ALLOW (fleets spanning hosts)."""
        from urllib.parse import urlsplit
        peer = request.headers.get('X-KV-Peer', '').strip()
        if not peer or len(peer) > 512:
            return None
        try:
            u = urlsplit(peer)
            port = u.port   # raises on a malformed port
        except ValueError:
            return None
        if u.scheme not in ('http', 'https') or not u.hostname:
            return None
        for entry in (env_lib.get('SKYT_KV_PEER_ALLOW') or '').split(','):
            entry = entry.strip()
            if not entry:
                continue
            try:
                a = urlsplit(entry)
                if (a.scheme, a.hostname, a.port) == \
                        (u.scheme, u.hostname, port):
                    return peer
            except ValueError:
                continue
        if u.hostname in ('127.0.0.1', 'localhost', '::1'):
            return peer
        return None

    async def _kv_prefix(self, request: web.Request) -> web.Response:
        """``GET /kv/prefix?hashes=<hex16>,...`` — serve this replica's
        leading resident run of a prefix-page hash chain (HBM registry
        first, host-store continuation), encoded with the engine's
        weight_version (infer/kv_tier.py codec; docs/performance.md
        "Tiered prefix cache"). Peers fetch through this on a local
        miss. Auth mirrors /admin/weights: KV pages are model
        activations — reachability alone must never be enough. 404
        (not 5xx) when nothing is resident or tiering is off."""
        token = env_lib.get('SKYT_ADMIN_TOKEN')
        if not token:
            return web.json_response(
                {'error': 'kv transfer disabled: start the replica '
                          'with SKYT_ADMIN_TOKEN set'}, status=403)
        import hmac
        got = request.headers.get('Authorization', '')
        if not hmac.compare_digest(
                got.encode('utf-8', 'surrogateescape'),
                f'Bearer {token}'.encode('utf-8')):
            return web.json_response(
                {'error': 'unauthorized: missing or bad Authorization '
                          'bearer token'}, status=403)
        raw = request.query.get('hashes', '')
        hashes: List[bytes] = []
        for part in raw.split(','):
            part = part.strip()
            if not part:
                continue
            try:
                h = bytes.fromhex(part)
            except ValueError:
                h = b''
            if len(h) != 16:   # chained blake2b-16 page hashes
                return web.json_response(
                    {'error': f'hashes must be 32-hex-char page '
                              f'hashes, got {part[:40]!r}'}, status=400)
            hashes.append(h)
        if not hashes:
            return web.json_response(
                {'error': 'need ?hashes=<hex>,<hex>,...'}, status=400)
        max_pages = env_lib.get_int('SKYT_KV_FETCH_MAX_PAGES', 64)
        loop = asyncio.get_running_loop()
        try:
            body = await loop.run_in_executor(
                None, functools.partial(self.engine.kv_export_encoded,
                                        hashes, max_pages))
        except Exception:  # pylint: disable=broad-except
            # A failed export is a cache miss to the peer, never a 5xx
            # chain (it would recompute anyway).
            logger.exception('kv export failed')
            body = None
        if not body:
            return web.json_response(
                {'error': 'no resident pages for this hash run'},
                status=404)
        return web.Response(
            body=body,
            headers={'Content-Type': 'application/octet-stream',
                     'X-Weight-Version':
                         str(self.engine.weight_version)})

    @staticmethod
    def _require_admin(request: web.Request
                       ) -> Optional[web.Response]:
        """Shared bearer gate for the admin/KV-transfer surface:
        requires SKYT_ADMIN_TOKEN to be configured AND presented (403
        otherwise — reachability alone must never be enough)."""
        token = env_lib.get('SKYT_ADMIN_TOKEN')
        if not token:
            return web.json_response(
                {'error': 'admin API disabled: start the replica with '
                          'SKYT_ADMIN_TOKEN set (the serve controller '
                          'exports the per-service token)'},
                status=403)
        import hmac
        got = request.headers.get('Authorization', '')
        if not hmac.compare_digest(
                got.encode('utf-8', 'surrogateescape'),
                f'Bearer {token}'.encode('utf-8')):
            return web.json_response(
                {'error': 'unauthorized: missing or bad Authorization '
                          'bearer token'}, status=403)
        return None

    async def _admin_reshard(self, request: web.Request
                             ) -> web.Response:
        """``POST /admin/reshard`` — in-place elastic reshard
        (docs/robustness.md "Elastic capacity").

        Body: ``{"virtual_nodes": N, "drain": bool?}`` or
        ``{"reshard_back": true}``. Auth mirrors /admin/weights.
        Single-flight with weight swaps: 409 while either is in
        progress; 400 on a malformed body or a layout that cannot
        tile the mesh (old layout intact in every error case)."""
        denied = self._require_admin(request)
        if denied is not None:
            return denied
        try:
            payload = await request.json()
        except ValueError:
            payload = None
        if not isinstance(payload, dict):
            return web.json_response(
                {'error': 'body must be a JSON object'}, status=400)
        drain = payload.get('drain')
        if drain is not None and not isinstance(drain, bool):
            return web.json_response(
                {'error': f'drain must be a boolean, got {drain!r}'},
                status=400)
        loop = asyncio.get_running_loop()
        if payload.get('reshard_back'):
            op = functools.partial(self._swap_mgr.reshard_back,
                                   drain=drain)
        else:
            nodes = payload.get('virtual_nodes')
            if isinstance(nodes, bool) or not isinstance(nodes, int) \
                    or nodes < 1:
                return web.json_response(
                    {'error': f'virtual_nodes must be an integer >= 1 '
                              f'(or pass reshard_back: true), got '
                              f'{nodes!r}'}, status=400)
            op = functools.partial(self._swap_mgr.reshard, nodes,
                                   drain=drain)
        try:
            result = await loop.run_in_executor(None, op)
        except weight_swap_lib.SwapInFlight as e:
            return web.json_response({'error': str(e)}, status=409)
        except weight_swap_lib.WeightSwapError as e:
            return web.json_response(
                {'error': str(e),
                 'virtual_nodes': getattr(self.engine, 'virtual_nodes',
                                          None)},
                status=400)
        return web.json_response(result)

    async def _admin_adapters(self, request: web.Request
                              ) -> web.Response:
        """``POST /admin/adapters`` — the adapter fleet's replica
        surface (docs/serving.md "Adapter fleet").

        Body: ``{"op": "load", "name": n, "checkpoint": dir,
        "alpha": f?, "drain": bool?}`` |
        ``{"op": "unload", "name": n, "drain": bool?}`` |
        ``{"op": "list"}``. Auth and error mapping mirror
        /admin/weights: 403 unauthenticated, 409 while any weight
        swap / reshard / adapter update is in flight OR while an
        unload's adapter id is still referenced by live requests, 400
        on a malformed body or a failed load — the old adapter stack
        is live in every error case."""
        denied = self._require_admin(request)
        if denied is not None:
            return denied
        try:
            payload = await request.json()
        except ValueError:
            payload = None
        if not isinstance(payload, dict):
            return web.json_response(
                {'error': 'body must be a JSON object'}, status=400)
        op_name = payload.get('op', 'load')
        if op_name == 'list':
            snap = self._adapters.snapshot()
            snap['last'] = self._adapters.last
            return web.json_response(snap)
        if op_name not in ('load', 'unload'):
            return web.json_response(
                {'error': f"op must be 'load', 'unload', or 'list', "
                          f'got {op_name!r}'}, status=400)
        name = payload.get('name')
        if not isinstance(name, str) or not name:
            return web.json_response(
                {'error': f'name must be a non-empty string, got '
                          f'{name!r}'}, status=400)
        drain = payload.get('drain')
        if drain is not None and not isinstance(drain, bool):
            return web.json_response(
                {'error': f'drain must be a boolean, got {drain!r}'},
                status=400)
        if op_name == 'load':
            ckpt = payload.get('checkpoint')
            if not isinstance(ckpt, str) or not ckpt:
                return web.json_response(
                    {'error': f'checkpoint must be a non-empty '
                              f'string (an adapter dir an `sft '
                              f'--lora-rank` run wrote), got '
                              f'{ckpt!r}'}, status=400)
            alpha = payload.get('alpha', 16.0)
            if isinstance(alpha, bool) or \
                    not isinstance(alpha, (int, float)):
                return web.json_response(
                    {'error': f'alpha must be a number, got '
                              f'{alpha!r}'}, status=400)
            op = functools.partial(self._adapters.load, name,
                                   checkpoint=ckpt,
                                   alpha=float(alpha), drain=drain)
        else:
            op = functools.partial(self._adapters.unload, name,
                                   drain=drain)
        loop = asyncio.get_running_loop()
        try:
            result = await loop.run_in_executor(None, op)
        except weight_swap_lib.AdapterInUse as e:
            return web.json_response({'error': str(e)}, status=409)
        except weight_swap_lib.SwapInFlight as e:
            return web.json_response({'error': str(e)}, status=409)
        except weight_swap_lib.WeightSwapError as e:
            return web.json_response({'error': str(e)}, status=400)
        return web.json_response(result)

    async def _admin_kv_prewarm(self, request: web.Request
                                ) -> web.Response:
        """``POST /admin/kv_prewarm`` — pull this replica's rendezvous
        share of the fleet's resident prefix pages from its peers into
        the host KV store (docs/serving.md "Elastic capacity": scale-up
        pre-warm). Body: ``{"self": <url>, "peers": [<url>, ...]}``.
        Auth mirrors /admin/weights. Best-effort by contract: per-peer
        failures are counted, never raised — a failed pre-warm costs
        recomputes, not readiness."""
        denied = self._require_admin(request)
        if denied is not None:
            return denied
        try:
            payload = await request.json()
        except ValueError:
            payload = None
        if not isinstance(payload, dict):
            return web.json_response(
                {'error': 'body must be a JSON object'}, status=400)
        self_node = payload.get('self')
        peers = payload.get('peers')
        if not isinstance(self_node, str) or not self_node:
            return web.json_response(
                {'error': 'self must be this replica\'s base URL'},
                status=400)
        if not isinstance(peers, list) or \
                not all(isinstance(p, str) and p for p in peers):
            return web.json_response(
                {'error': 'peers must be a list of replica base URLs'},
                status=400)
        token = env_lib.get('SKYT_ADMIN_TOKEN')
        loop = asyncio.get_running_loop()
        try:
            result = await loop.run_in_executor(
                None, functools.partial(self.engine.kv_prewarm,
                                        self_node, peers, token))
        except Exception as e:  # pylint: disable=broad-except
            logger.exception('kv prewarm failed')
            return web.json_response(
                {'error': f'kv prewarm failed: {e!r}'}, status=500)
        return web.json_response(result)

    async def _kv_index(self, request: web.Request) -> web.Response:
        """``GET /kv/index`` — this replica's resident prefix-page
        inventory (HBM registry + host-store keys) at the current
        weight_version, snapshotted at a tick boundary. Peers use it
        to compute their rendezvous share during scale-up pre-warm.
        Auth mirrors /kv/prefix. 404 (not 5xx) when tiering is off or
        the engine loop is too busy to answer."""
        denied = self._require_admin(request)
        if denied is not None:
            return denied
        loop = asyncio.get_running_loop()
        try:
            data = await loop.run_in_executor(None,
                                              self.engine.kv_index)
        except Exception:  # pylint: disable=broad-except
            logger.exception('kv index failed')
            data = None
        if data is None:
            return web.json_response(
                {'error': 'no kv inventory (tiering off or engine '
                          'busy)'}, status=404)
        return web.json_response(data)

    async def _health(self, request: web.Request) -> web.Response:
        del request
        if self.engine.ready.is_set():
            return web.json_response({'status': 'ok'})
        return web.json_response({'status': 'starting'}, status=503)

    async def _stats(self, request: web.Request) -> web.Response:
        rid = request.query.get('request_id')
        if rid is not None:
            try:
                rid_int = int(rid)
            except ValueError:
                return web.json_response(
                    {'error': f'request_id must be an integer, '
                              f'got {rid!r}'}, status=400)
            trace = self.engine.request_trace(rid_int)
            if trace is None:
                return web.json_response(
                    {'error': f'no phase trace for request {rid_int} '
                              f'(unknown or evicted)',
                     'hint': 'phase traces are a bounded FIFO keyed '
                             'by the X-Request-Id response header; '
                             'end-to-end traces (incl. the LB hop) '
                             'live at /debug/traces?trace_id=<id>'},
                    status=404)
            return web.json_response(trace)
        data = self.engine.stats()
        # Adapter fleet: the per-adapter name/id/version map rides the
        # controller's stats probe to the LB, which routes
        # model-named requests only to replicas hosting the adapter.
        data['adapters'] = self._adapters.snapshot()
        if self._qos is not None:
            # Scraped by the serve controller's replica prober and
            # forwarded to the LB through the sync response — the
            # per-replica QoS pressure the LB consults when picking.
            data['qos'] = self._qos.snapshot(self.engine.qos_depths())
        return web.json_response(data)

    async def _debug_traces(self, request: web.Request) -> web.Response:
        """This replica's span store: recent + flight-recorded slow
        traces. `?trace_id=` for one trace's spans, `?format=chrome`
        for a chrome://tracing / Perfetto dump."""
        payload, status = tracing_lib.debug_traces_payload(
            self._tracer, request.query)
        return web.json_response(payload, status=status)

    async def _debug_ticks(self, request: web.Request) -> web.Response:
        """The tick plane's ring (docs/observability.md "Tick plane"):
        summary + the last-N per-tick records, `?format=chrome` for a
        chrome://tracing / Perfetto dump of the engine loop's tick
        slices, `?last=N` to size the record tail."""
        ts = self.engine.tickstats
        if ts is None:
            return web.json_response(
                {'error': 'tick plane is disabled on this replica',
                 'hint': 'start the server with SKYT_TICKSTATS=1 '
                         '(the default) to record per-tick anatomy'},
                status=404)
        if request.query.get('format') == 'chrome':
            return web.json_response(ts.chrome_trace())
        last = request.query.get('last', '32')
        try:
            n = int(last)
        except ValueError:
            return web.json_response(
                {'error': f'last must be an integer, got {last!r}'},
                status=400)
        return web.json_response({'summary': ts.summary(),
                                  'ticks': ts.last(n)})

    async def _metrics(self, request: web.Request) -> web.Response:
        del request
        return web.Response(
            body=self.engine.metrics_registry.expose().encode('utf-8'),
            headers={'Content-Type': metrics_lib.CONTENT_TYPE})

    async def _generate(self, request: web.Request) -> web.StreamResponse:
        payload = await request.json()
        if 'tokens' in payload:
            tokens = [int(t) for t in payload['tokens']]
        elif 'text' in payload:
            tokens = self.tokenizer.encode(payload['text'])
        else:
            return web.json_response(
                {'error': 'need "tokens" or "text"'}, status=400)
        if not tokens:
            return web.json_response({'error': 'empty prompt'},
                                     status=400)
        eos = payload.get('eos_token', self.tokenizer.eos_id)
        # 'max_tokens' is the OpenAI-convention name; accept the
        # engine-side 'max_new_tokens' as an alias (same meaning here:
        # /generate counts generated tokens only).
        max_new = payload.get('max_tokens',
                              payload.get('max_new_tokens', 128))
        # Optional 'lora': adapter name (same names the OpenAI routes
        # accept in 'model').
        lora_id, lora_err = self._resolve_lora(
            {'model': payload['lora']} if payload.get('lora') else {},
            request=request)
        if lora_err is not None:
            return lora_err
        try:
            bias = self._parse_logit_bias(payload)
        except ValueError as e:
            return web.json_response({'error': str(e)}, status=400)
        deadline, dl_err = self._deadline_from(request)
        if dl_err is not None:
            return dl_err
        try:
            max_new = int(max_new)
        except (TypeError, ValueError):
            return web.json_response(
                {'error': f'max_tokens must be an integer, got '
                          f'{max_new!r}'}, status=400)
        qcls, qtenant, qdec, qerr = self._qos_admit(
            request, payload, max_new=max_new)
        if qerr is not None:
            return qerr
        if qdec is not None and qdec.max_new_tokens is not None:
            max_new = min(max_new, qdec.max_new_tokens)
        params = engine_lib.SamplingParams(
            lora_id=lora_id,
            logit_bias=bias,
            deadline=deadline,
            priority=qcls,
            tenant=qtenant,
            max_new_tokens=int(max_new),
            temperature=float(payload.get('temperature', 0.0)),
            top_k=int(payload.get('top_k', 0)),
            top_p=float(payload.get('top_p', 1.0)),
            presence_penalty=float(payload.get('presence_penalty',
                                               0.0)),
            frequency_penalty=float(payload.get('frequency_penalty',
                                                0.0)),
            eos_token=eos)
        err = self._params_error(params)
        if err is not None:
            return web.json_response({'error': err}, status=400)
        req_id, out_q = self.engine.submit(
            tokens, params, kv_peer=self._kv_peer_from(request))
        # Seen by the tracing middleware after the handler returns:
        # the engine's phase trace for each id is bridged in as child
        # spans of this request's server span.
        request['skyt_engine_rids'] = [req_id]

        if payload.get('stream'):
            resp = web.StreamResponse(
                headers={'Content-Type': 'application/x-ndjson',
                         'X-Request-Id': str(req_id)})
            await resp.prepare(request)
            while True:
                tok = await self._q_get(request, out_q, (req_id,))
                if tok is None:
                    break
                await resp.write(
                    json.dumps({'token': tok}).encode() + b'\n')
            await resp.write_eof()
            return resp

        out, _lps = await self._drain(request, out_q, (req_id,))
        visible, _ = self._finish(out, params)
        return web.json_response({
            'request_id': req_id,
            'tokens': out,
            'text': self.tokenizer.decode(visible),
        }, headers={'X-Request-Id': str(req_id)})

    # ----------------------------------------------- OpenAI-compatible
    # The reference serves vLLM's OpenAI API (llm/vllm/serve.yaml probes
    # /v1/models); these endpoints make our replicas drop-in for OpenAI
    # SDK clients pointed at the service endpoint.

    @staticmethod
    def _parse_logit_bias(payload):
        """OpenAI logit_bias arrives with STRING token-id keys; a
        malformed entry raises ValueError naming the actual offender
        (handlers turn it into a 400)."""
        raw = payload.get('logit_bias')
        if not isinstance(raw, dict) or not raw:
            return None
        out = {}
        for k, v in raw.items():
            try:
                out[int(k)] = float(v)
            except (TypeError, ValueError):
                raise ValueError(
                    f'logit_bias entries must map integer token ids '
                    f'to numbers, got {k!r}: {v!r}') from None
        return out

    def _sampling_from_openai(self, payload,
                              lora_id: int = 0,
                              deadline: Optional[float] = None
                              ) -> 'engine_lib.SamplingParams':
        temp = float(payload.get('temperature', 0.0))
        return engine_lib.SamplingParams(
            lora_id=lora_id,
            deadline=deadline,
            logit_bias=self._parse_logit_bias(payload),
            max_new_tokens=int(payload.get('max_tokens', 128)),
            temperature=temp,
            top_k=int(payload.get('top_k', 0)),
            top_p=float(payload.get('top_p', 1.0)),
            eos_token=self.tokenizer.eos_id,
            seed=int(payload.get('seed', 0)),
            presence_penalty=float(payload.get('presence_penalty',
                                               0.0)),
            frequency_penalty=float(payload.get('frequency_penalty',
                                                0.0)),
            # OpenAI 'logprobs': completions uses int|null (0 is a
            # valid ON value: chosen-token only); chat uses bool.
            # False/null => off; 0/True/N => on. Only chosen-token
            # logprobs are computed regardless of N (documented).
            logprobs=(payload.get('logprobs') is not None and
                      payload.get('logprobs') is not False))

    def _params_error(self, params) -> Optional[str]:
        """Error message for sampling params the engine would reject
        (top_k > 64, out-of-range top_p/temperature, out-of-vocab
        logit_bias ids) — handlers return it as a 400 BEFORE
        submitting, so invalid work never occupies an engine slot and
        OpenAI clients get the standard invalid-parameter behavior
        instead of a 500."""
        try:
            params.validate()
        except ValueError as e:
            return str(e)
        bad = [t for t in (params.logit_bias or {})
               if t >= self.engine.cfg.vocab_size]
        if bad:
            return (f'logit_bias token ids out of vocab '
                    f'(V={self.engine.cfg.vocab_size}): {bad[:5]}')
        return None

    @staticmethod
    def _parse_n(payload) -> Optional[int]:
        """OpenAI 'n' (completions per prompt): int in [1, 128]
        (OpenAI's own cap). None => malformed (handlers return 400)."""
        n = payload.get('n', 1)
        if isinstance(n, bool) or not isinstance(n, int):
            return None
        if not 1 <= n <= 128:
            return None
        return n

    @staticmethod
    def _stops_from_openai(payload) -> Optional[List[str]]:
        """OpenAI 'stop': a string or list of strings. None => the
        field is malformed (handlers return 400)."""
        stop = payload.get('stop')
        if stop is None:
            return []
        if isinstance(stop, str):
            return [stop] if stop else []
        if isinstance(stop, list) and all(isinstance(s, str)
                                          for s in stop):
            return [s for s in stop if s]
        return None

    def _incremental_decoder(self):
        """Closure decoding a token stream piece-by-piece; holds
        tokens whose prefix decode ends in U+FFFD so multi-byte UTF-8
        sequences never surface as mojibake (pass None to flush)."""
        held: List[int] = []

        def decode_incremental(tok: Optional[int]) -> Optional[str]:
            if tok is not None:
                held.append(tok)
            if not held:
                return None
            text = self.tokenizer.decode(list(held))
            if tok is not None and text.endswith('\ufffd') and \
                    len(held) < 4:
                return None          # likely incomplete; keep holding
            held.clear()
            return text or None
        return decode_incremental

    @staticmethod
    def _apply_stops(text: str, stops: List[str]) -> 'tuple[str, bool]':
        """Truncate at the earliest stop-sequence occurrence (the stop
        itself is not included — OpenAI semantics)."""
        cut = None
        for s in stops:
            i = text.find(s)
            if i != -1 and (cut is None or i < cut):
                cut = i
        if cut is None:
            return text, False
        return text[:cut], True

    async def _drain_stopping(self, request, rid, out_q, params,
                              stops: List[str]):
        """Drain a request; with stop sequences, cancel the engine
        request as soon as one matches so the slot frees immediately
        instead of running to max_tokens. Returns
        (text, finish_reason, generated_token_count, logprobs) —
        the count is tokens the engine actually produced (the cost),
        which can exceed the truncated text's length; logprobs is
        None unless params.logprobs (then a {'tokens': [per-token
        text], 'token_logprobs': [...]} dict — chosen-token raw
        logprobs; top-N alternatives are not computed)."""
        if not stops:
            out, lps = await self._drain(request, out_q, (rid,))
            visible, reason = self._finish(out, params)
            lp_obj = None
            if lps is not None:
                # Per-token text via the incremental decoder (one O(n)
                # pass; a multi-byte UTF-8 sequence spanning tokens
                # yields '' for the held tokens and the full piece at
                # the completing token) — the pieces concatenate
                # EXACTLY to the response text.
                dec = self._incremental_decoder()
                pieces = [dec(t) or '' for t in visible]
                tail = dec(None)
                if tail and pieces:
                    pieces[-1] += tail
                lp_obj = {'tokens': pieces,
                          'token_logprobs': lps[:len(visible)]}
            return (self.tokenizer.decode(visible), reason, len(out),
                    lp_obj)

        async def drain_terminal():
            # Consume through the terminal None so the slot is really
            # done (released) before we return.
            while await self._q_get(request, out_q, (rid,)) is not None:
                pass

        decode_incremental = self._incremental_decoder()
        scan = _StopScanner(stops)
        generated = 0

        while True:
            tok = await self._q_get(request, out_q, (rid,))
            if tok is None:
                tail = decode_incremental(None)
                if tail and scan.feed(tail):
                    return scan.text, 'stop', generated, None
                return scan.text, 'length', generated, None
            generated += 1
            if params.eos_token is not None and \
                    tok == params.eos_token:
                await drain_terminal()
                tail = decode_incremental(None)
                if tail:
                    scan.feed(tail)
                return scan.text, 'stop', generated, None
            piece = decode_incremental(tok)
            if piece is None:
                continue
            if scan.feed(piece):
                self.engine.cancel(rid)
                await drain_terminal()
                return scan.text, 'stop', generated, None

    async def _drain(self, request, out_q, rids=()):
        """-> (tokens, logprobs_or_None); the queue yields bare ints,
        or (token, logprob) pairs when params.logprobs is set. Aborts
        (cancelling `rids` in the engine) if the client disconnects."""
        out: List[int] = []
        lps: List[float] = []
        saw_pairs = False
        while True:
            item = await self._q_get(request, out_q, rids)
            if item is None:
                return out, (lps if saw_pairs else None)
            if isinstance(item, tuple):
                saw_pairs = True
                out.append(item[0])
                lps.append(item[1])
            else:
                out.append(item)

    def _finish(self, out: List[int],
                params: 'engine_lib.SamplingParams'):
        """(visible_tokens, finish_reason) — eos is not surfaced.

        OpenAI semantics: 'stop' ONLY for an eos; anything else (hit
        max_tokens, or the engine truncated at its max_seq_len) is
        'length'."""
        if params.eos_token is not None and out and \
                out[-1] == params.eos_token:
            return out[:-1], 'stop'
        return out, 'length'

    async def _models(self, request: web.Request) -> web.Response:
        del request
        return web.json_response({
            'object': 'list',
            'data': [{'id': self.model_id, 'object': 'model',
                      'owned_by': 'skypilot-tpu'}] +
                    [{'id': name, 'object': 'model',
                      'owned_by': 'skypilot-tpu',
                      'parent': self.model_id}
                     for name in sorted(self.lora_names)],
        })

    async def _sse(self, request, make_chunk, out_q, params,
                   stops: Optional[List[str]] = None, rid=None):
        """Stream tokens as OpenAI SSE chunks; a final chunk carries the
        finish_reason (OpenAI protocol), then [DONE]. With stop
        sequences, emission halts at the earliest match (the stop text
        is never sent) and the engine request is cancelled."""
        rids = (rid,) if rid is not None else ()
        headers = {'Content-Type': 'text/event-stream',
                   'Cache-Control': 'no-cache'}
        if rid is not None:
            headers['X-Request-Id'] = str(rid)
        resp = web.StreamResponse(headers=headers)
        await resp.prepare(request)
        saw_eos = False
        stopped = False
        sent = 0     # chars of the scanner's text already emitted
        decode_incremental = self._incremental_decoder()
        scan = _StopScanner(stops or [])
        ended = False   # terminal None already consumed

        async def emit(piece: str, final: bool = False) -> bool:
            """Send new text, stop-truncated via the shared windowed
            scanner. A partial stop prefix can span token boundaries,
            so max(len(stop))-1 trailing chars are held back until
            `final` — the stop text (or any prefix of it) is never
            sent. True => halt stream."""
            nonlocal sent, stopped
            matched = scan.feed(piece)
            out = scan.text[sent:scan.safe_len(final or matched)]
            if out:
                await resp.write(b'data: ' +
                                 json.dumps(make_chunk(out)).encode() +
                                 b'\n\n')
                sent += len(out)
            if matched:
                stopped = True
                if rid is not None and not ended:
                    self.engine.cancel(rid)
                    while await self._q_get(request, out_q,
                                            rids) is not None:
                        pass
            return matched

        while True:
            tok = await self._q_get(request, out_q, rids)
            if tok is None:
                ended = True
                break
            if params.eos_token is not None and tok == params.eos_token:
                saw_eos = True
                continue   # eos hidden; the final chunk signals stop
            piece = decode_incremental(tok)
            if piece is None:
                continue
            if await emit(piece):
                break
        if not stopped:
            # Flush held tokens AND the stop-holdback window.
            tail = decode_incremental(None) or ''
            await emit(tail, final=True)
        reason = 'stop' if (saw_eos or stopped) else 'length'
        await resp.write(b'data: ' +
                         json.dumps(make_chunk(None, reason)).encode() +
                         b'\n\n')
        await resp.write(b'data: [DONE]\n\n')
        await resp.write_eof()
        return resp

    def _prompt_token_lists(self, prompt):
        """OpenAI prompt forms: str | [str] | [int] | [[int]] ->
        list of token lists (None on malformed input)."""
        if isinstance(prompt, str):
            return [self.tokenizer.encode(prompt)]
        if isinstance(prompt, list) and prompt:
            if all(isinstance(x, int) for x in prompt):
                return [list(prompt)]
            if all(isinstance(x, str) for x in prompt):
                return [self.tokenizer.encode(x) for x in prompt]
            if all(isinstance(x, list) and
                   all(isinstance(t, int) for t in x) for x in prompt):
                return [list(x) for x in prompt]
        return None

    async def _completions(self, request: web.Request):
        payload = await request.json()
        prompt = payload.get('prompt')
        if prompt is None:
            return web.json_response({'error': 'prompt required'},
                                     status=400)
        token_lists = self._prompt_token_lists(prompt)
        if token_lists is None or any(not t for t in token_lists):
            return web.json_response(
                {'error': 'prompt must be a non-empty string, token '
                          'array, or list of either'}, status=400)
        # Validate BEFORE submitting: rejected work must not occupy
        # engine slots.
        n = self._parse_n(payload)
        if n is None:
            return web.json_response(
                {'error': 'n must be an integer in [1, 128]'},
                status=400)
        if payload.get('stream') and (len(token_lists) != 1 or n != 1):
            return web.json_response(
                {'error': 'stream supports a single prompt with n=1'},
                status=400)
        # Honest bounds: parameters we do not implement are rejected,
        # never silently ignored (a client asking for best_of sampling
        # or suffix insertion must not get plain completions back
        # unawares). echo is supported on the non-streaming path.
        if payload.get('suffix'):
            return web.json_response(
                {'error': 'suffix (insertion) is not supported'},
                status=400)
        best_of = payload.get('best_of')
        if best_of not in (None, 1, n):
            return web.json_response(
                {'error': f'best_of={best_of!r} is not supported '
                          f'(only best_of == n == {n}, i.e. plain '
                          'n-sampling, is implemented)'}, status=400)
        echo = bool(payload.get('echo'))
        if echo and payload.get('stream'):
            return web.json_response(
                {'error': 'echo cannot combine with stream'},
                status=400)
        lora_id, lora_err = self._resolve_lora(payload,
                                               request=request)
        if lora_err is not None:
            return lora_err
        deadline, dl_err = self._deadline_from(request)
        if dl_err is not None:
            return dl_err
        try:
            params = self._sampling_from_openai(payload, lora_id,
                                                deadline)
        except (TypeError, ValueError) as e:
            return web.json_response({'error': str(e)}, status=400)
        # Echo the requested model (adapter name for multi-LoRA
        # requests) back in responses, the vLLM convention.
        model_name = payload.get('model') or self.model_id
        err = self._params_error(params)
        if err is not None:
            return web.json_response({'error': err}, status=400)
        qcls, qtenant, qdec, qerr = self._qos_admit(
            request, payload, openai=True,
            max_new=params.max_new_tokens)
        if qerr is not None:
            return qerr
        params.priority = qcls
        params.tenant = qtenant
        if qdec is not None and qdec.max_new_tokens is not None:
            params.max_new_tokens = min(params.max_new_tokens,
                                        qdec.max_new_tokens)
        stops = self._stops_from_openai(payload)
        if stops is None:
            return web.json_response(
                {'error': 'stop must be a string or list of strings'},
                status=400)
        if params.logprobs and (stops or payload.get('stream')):
            return web.json_response(
                {'error': 'logprobs cannot combine with stop or '
                          'stream'}, status=400)
        echo_texts = None
        if echo:
            if params.logprobs:
                # The logprobs pieces are documented to concatenate
                # exactly to the response text; echoing the prompt
                # would silently misalign them (prompt logprobs are
                # not computed).
                return web.json_response(
                    {'error': 'echo cannot combine with logprobs '
                              '(prompt logprobs are not computed)'},
                    status=400)
            # Echo the LITERAL prompt strings (OpenAI semantics) —
            # decode only token-array prompts, where no original
            # string exists. Once per prompt, not per choice.
            items = prompt if isinstance(prompt, list) and \
                not isinstance(prompt[0], int) else [prompt]
            echo_texts = [
                item if isinstance(item, str)
                else self.tokenizer.decode(toks)
                for item, toks in zip(items, token_lists)]
        # n completions per prompt, choices prompt-major (OpenAI
        # layout). Distinct req_ids already decorrelate the sampling
        # streams (device keys seed with seed + req_id).
        kv_peer = self._kv_peer_from(request)
        subs = [self.engine.submit(t, params, kv_peer=kv_peer)
                for t in token_lists for _ in range(n)]
        request['skyt_engine_rids'] = [r for r, _ in subs]

        if payload.get('stream'):
            rid, out_q = subs[0]

            def chunk(piece, reason=None):
                return {'id': f'cmpl-{rid}', 'object': 'text_completion',
                        'model': model_name,
                        'choices': [{'index': 0,
                                     'text': piece or '',
                                     'finish_reason': reason}]}
            return await self._sse(request, chunk, out_q, params,
                                   stops=stops, rid=rid)

        # Concurrent drains: a stop match in ANY completion cancels
        # its engine request immediately (sequential drains would hold
        # later completions' slots until earlier ones finish).
        results = await asyncio.gather(*[
            self._drain_stopping(request, rid, out_q, params, stops)
            for rid, out_q in subs])
        choices = []
        total_out = 0
        for i, (text, reason, n_gen, lp_obj) in enumerate(results):
            total_out += n_gen
            if echo_texts is not None:
                # Prompt-major choice layout: completion i belongs to
                # prompt i // n.
                text = echo_texts[i // n] + text
            choice = {'index': i, 'text': text,
                      'finish_reason': reason}
            if lp_obj is not None:
                choice['logprobs'] = lp_obj
            choices.append(choice)
        n_in = sum(len(t) for t in token_lists)
        return web.json_response({
            'id': f'cmpl-{subs[0][0]}', 'object': 'text_completion',
            'model': model_name, 'choices': choices,
            'usage': {'prompt_tokens': n_in,
                      'completion_tokens': total_out,
                      'total_tokens': n_in + total_out},
        }, headers={'X-Request-Id': str(subs[0][0])})

    def _apply_chat_template(self, messages) -> str:
        """The checkpoint's HF chat template when the tokenizer dir
        carries one (jinja, rendered with add_generation_prompt=True —
        what vLLM does for the reference); a minimal generic role-tag
        format otherwise. A template render error falls back to the
        generic format with a warning rather than 500ing the request
        (templates are third-party code from the checkpoint)."""
        if self._chat_template is not None:
            try:
                return self._chat_template.render(
                    messages=messages, add_generation_prompt=True,
                    **self._special_tokens)
            except Exception as e:  # pylint: disable=broad-except
                logger.warning('chat template render failed (%s); '
                               'using the generic format', e)
        parts = []
        for m in messages:
            parts.append(f"<|{m.get('role', 'user')}|>\n"
                         f"{m.get('content', '')}")
        parts.append('<|assistant|>\n')
        return '\n'.join(parts)

    async def _chat_completions(self, request: web.Request):
        payload = await request.json()
        messages = payload.get('messages')
        if not messages or not isinstance(messages, list) or \
                not all(isinstance(m, dict) for m in messages):
            return web.json_response(
                {'error': 'messages must be a non-empty list of '
                          '{role, content} objects'}, status=400)
        n = self._parse_n(payload)
        if n is None:
            return web.json_response(
                {'error': 'n must be an integer in [1, 128]'},
                status=400)
        if payload.get('stream') and n != 1:
            return web.json_response(
                {'error': 'stream supports n=1'}, status=400)
        lora_id, lora_err = self._resolve_lora(payload,
                                               request=request)
        if lora_err is not None:
            return lora_err
        deadline, dl_err = self._deadline_from(request)
        if dl_err is not None:
            return dl_err
        try:
            params = self._sampling_from_openai(payload, lora_id,
                                                deadline)
        except (TypeError, ValueError) as e:
            return web.json_response({'error': str(e)}, status=400)
        # Echo the requested model (adapter name for multi-LoRA
        # requests) back in responses, the vLLM convention.
        model_name = payload.get('model') or self.model_id
        err = self._params_error(params)
        if err is not None:
            return web.json_response({'error': err}, status=400)
        qcls, qtenant, qdec, qerr = self._qos_admit(
            request, payload, openai=True,
            max_new=params.max_new_tokens)
        if qerr is not None:
            return qerr
        params.priority = qcls
        params.tenant = qtenant
        if qdec is not None and qdec.max_new_tokens is not None:
            params.max_new_tokens = min(params.max_new_tokens,
                                        qdec.max_new_tokens)
        if params.logprobs:
            # Chat logprobs use a different response schema (content
            # arrays); reject loudly rather than degrade silently.
            return web.json_response(
                {'error': 'logprobs is not supported on chat '
                          'completions'}, status=400)
        stops = self._stops_from_openai(payload)
        if stops is None:
            return web.json_response(
                {'error': 'stop must be a string or list of strings'},
                status=400)
        tokens = self.tokenizer.encode(
            self._apply_chat_template(messages))
        kv_peer = self._kv_peer_from(request)
        subs = [self.engine.submit(tokens, params, kv_peer=kv_peer)
                for _ in range(n)]
        request['skyt_engine_rids'] = [r for r, _ in subs]
        rid = subs[0][0]

        if payload.get('stream'):
            out_q = subs[0][1]
            first = {'sent': False}

            def chunk(piece, reason=None):
                delta = {}
                if not first['sent']:
                    # OpenAI protocol: the first delta carries the role.
                    delta['role'] = 'assistant'
                    first['sent'] = True
                if piece is not None:
                    delta['content'] = piece
                return {'id': f'chatcmpl-{rid}',
                        'object': 'chat.completion.chunk',
                        'model': model_name,
                        'choices': [{'index': 0, 'delta': delta,
                                     'finish_reason': reason}]}
            return await self._sse(request, chunk, out_q, params,
                                   stops=stops, rid=rid)

        results = await asyncio.gather(*[
            self._drain_stopping(request, crid, out_q, params, stops)
            for crid, out_q in subs])
        choices = []
        total_out = 0
        for i, (text, reason, n_gen, _lp) in enumerate(results):
            total_out += n_gen
            choices.append({'index': i,
                            'message': {'role': 'assistant',
                                        'content': text},
                            'finish_reason': reason})
        return web.json_response({
            'id': f'chatcmpl-{rid}', 'object': 'chat.completion',
            'model': model_name,
            'choices': choices,
            'usage': {'prompt_tokens': len(tokens),
                      'completion_tokens': total_out,
                      'total_tokens': len(tokens) + total_out},
        }, headers={'X-Request-Id': str(rid)})

    def make_app(self) -> web.Application:
        m_http = self.engine.metrics_registry.counter(
            'skyt_http_requests_total', 'HTTP requests served',
            ('path', 'code'))
        m_lat = self.engine.metrics_registry.histogram(
            'skyt_http_request_seconds',
            'HTTP request wall latency by route (streaming routes '
            'count the full stream)', ('path',))

        @web.middleware
        async def count_requests(request: web.Request, handler):
            # Label with the matched route's canonical path (a fixed,
            # bounded set) — never the raw request path, whose
            # cardinality is attacker-controlled.
            resource = request.match_info.route.resource
            path = resource.canonical if resource is not None \
                else 'unmatched'
            # Wall-clock arrival: the goodput tracker's server-side
            # TTFT reference point (engine phase traces use time.time).
            t0_wall = time.time()
            try:
                # Histogram.time() observes on the exception path too:
                # error latency is latency.
                with m_lat.labels(path).time():
                    resp = await handler(request)
            except web.HTTPException as e:
                m_http.labels(path, str(e.status)).inc()
                self._record_slo(request, e.status, t0_wall)
                raise
            except faults.FaultDisconnect:
                # Injected connection drop: actually sever the socket
                # so the peer sees a transport failure, not a tidy
                # HTTP 500 (what a crashing replica looks like).
                m_http.labels(path, '499').inc()
                if request.transport is not None:
                    request.transport.close()
                raise
            except ConnectionResetError:
                # Client went away mid-request — queue-wait polls raise
                # from _q_get, and writes into a closed transport raise
                # aiohttp's ClientConnectionResetError (a subclass).
                # Either way: cancel the engine request(s) so the slot
                # and KV pages free, and count it (nginx's 499).
                m_http.labels(path, '499').inc()
                self._m_disconnects.inc()
                for rid in request.get('skyt_engine_rids', ()):
                    self.engine.cancel(rid)
                raise
            except Exception:
                # aiohttp turns unhandled handler exceptions into 500s
                # — the error-rate signal this counter exists for.
                m_http.labels(path, '500').inc()
                self._record_slo(request, 500, t0_wall)
                raise
            m_http.labels(path, str(resp.status)).inc()
            self._record_slo(request, resp.status, t0_wall)
            return resp

        @web.middleware
        async def trace_requests(request: web.Request, handler):
            # Server span per request, parented under the LB's proxy
            # span when a traceparent arrived (streaming included: the
            # handler returns only after write_eof, so the span covers
            # the full stream). With SKYT_TRACE=0 start_span returns
            # the shared no-op singleton and this middleware adds two
            # dict lookups.
            resource = request.match_info.route.resource
            path = resource.canonical if resource is not None \
                else 'unmatched'
            ctx = self._tracer.extract(request.headers)
            span = self._tracer.start_span(
                'server ' + path, parent=ctx,
                attributes={'http.method': request.method,
                            'http.path': path})
            lb_rid = request.headers.get('X-Request-Id')
            if lb_rid:
                span.set_attribute('lb_request_id', lb_rid)
            with span:
                # Chaos hook (dormant unless SKYT_FAULTS arms it):
                # error/latency/hang/disconnect/preempt on the
                # replica's whole HTTP surface. Inside the span so the
                # fired fault's `fault.<kind>` event lands on THIS
                # request's trace (count_requests, outermost, would
                # run before the span exists); its exception handling
                # still applies — faults raise through this middleware.
                await faults.ainject('server.request', path=path)
                resp = await handler(request)
                span.set_attribute('http.status', resp.status)
                if span is not tracing_lib.NOOP_SPAN:
                    self._bridge_engine_spans(
                        span, request.get('skyt_engine_rids', ()))
                return resp

        app = web.Application(middlewares=[count_requests,
                                           trace_requests])
        app.router.add_get('/health', self._health)
        app.router.add_get('/stats', self._stats)
        app.router.add_get('/metrics', self._metrics)
        app.router.add_get('/debug/traces', self._debug_traces)
        app.router.add_get('/debug/ticks', self._debug_ticks)
        app.router.add_post('/debug/profile', self._debug_profile)
        app.router.add_post('/admin/weights', self._admin_weights)
        app.router.add_post('/admin/reshard', self._admin_reshard)
        app.router.add_post('/admin/adapters', self._admin_adapters)
        app.router.add_post('/admin/kv_prewarm', self._admin_kv_prewarm)
        app.router.add_get('/kv/prefix', self._kv_prefix)
        app.router.add_get('/kv/index', self._kv_index)
        app.router.add_post('/generate', self._generate)
        app.router.add_get('/v1/models', self._models)
        app.router.add_post('/v1/completions', self._completions)
        app.router.add_post('/v1/chat/completions',
                            self._chat_completions)
        return app


def build_engine(model_name: Optional[str] = None,
                 num_slots: int = 8,
                 max_seq_len: int = 2048,
                 checkpoint: Optional[str] = None,
                 tp: int = 1,
                 decode_chunk: int = 16,
                 cache_mode: str = 'auto',
                 pool_tokens: Optional[int] = None,
                 dtype: str = 'bfloat16',
                 prefix_caching: bool = True,
                 spec_decode: int = 0,
                 quantize: str = 'none',
                 kv_dtype: str = 'auto',
                 prefill_chunk: int = 0,
                 lockstep=None,
                 draft_model_name: Optional[str] = None,
                 draft_checkpoint: Optional[str] = None,
                 lora_stack=None
                 ) -> 'engine_lib.InferenceEngine':
    """Engine factory.

    checkpoint: HF-format dir (config.json + *.safetensors) — real
    weights, tp-sharded over the first `tp` local devices. Without a
    checkpoint, a randomly initialized `model_name` config (debug use).

    lockstep: infer.multihost.LockstepSync for a replica spanning
    multiple hosts — tp then counts GLOBAL devices (the mesh builder
    uses jax.devices(), which is already global after
    jax.distributed.initialize()).

    draft_model_name / draft_checkpoint (with spec_decode > 0): a
    small DRAFT MODEL replaces the n-gram proposer. draft_checkpoint
    loads HF weights; draft_model_name picks a config preset; the
    special name 'self' reuses the target model+params (acceptance is
    then 1.0 by construction — a mechanism check / upper bound, not a
    speedup, since the draft costs as much as the target). Draft runs
    replicated (it is small by construction), llama-family only.

    cache_mode: 'auto' (= paged; MoE shares the llama attention layer so
    paged decode covers both families), 'paged', or 'dense'.
    pool_tokens: paged-pool HBM budget in tokens (default: the dense
    equivalent, num_slots * max_seq_len — same HBM, more headroom; pass
    less to actually shrink the cache).
    """
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    from skypilot_tpu.models import llama

    mesh = None
    if tp > 1:
        from skypilot_tpu.parallel import mesh as mesh_lib
        mesh = mesh_lib.build_mesh(mesh_lib.MeshSpec(tp=tp))

    moe_cfg = None   # set by the MoE branches; routes the swap loader
    already_quantized = False
    if checkpoint:
        from skypilot_tpu.models import weights as weights_lib
        qmode = quantize if quantize in ('int8', 'int4') else 'none'
        # int8/int4: stream-quantize each tensor on host during load so the
        # bf16 tree is never resident in HBM (8B fits one 16GB chip).
        if weights_lib.checkpoint_model_type(checkpoint) in (
                'mixtral', 'qwen3_moe'):
            from skypilot_tpu.models import moe
            cfg, moe_cfg = weights_lib.load_mixtral_config(
                checkpoint, remat=False, param_dtype=dtype, dtype=dtype)
            cfg = _dc.replace(
                cfg, max_seq_len=min(cfg.max_seq_len, max_seq_len))
            # Dropless routing for serving (same rationale as the
            # named-config MoE branch below).
            moe_cfg = _dc.replace(moe_cfg, capacity_factor=8.0)
            make_model = lambda c: moe.MixtralModel(c, moe_cfg)  # noqa: E731
            model = make_model(cfg)
            params = weights_lib.load_mixtral_params(
                cfg, moe_cfg, checkpoint, mesh=mesh, quantize=qmode)
        else:
            cfg = weights_lib.load_config(
                checkpoint, remat=False, param_dtype=dtype, dtype=dtype)
            cfg = _dc.replace(
                cfg, max_seq_len=min(cfg.max_seq_len, max_seq_len))
            make_model = llama.LlamaModel
            model = make_model(cfg)
            params = weights_lib.load_llama_params(
                cfg, checkpoint, mesh=mesh, quantize=qmode)
        already_quantized = qmode != 'none'
    else:
        from skypilot_tpu.models import moe
        name = model_name or 'debug'
        if name in moe.MIXTRAL_CONFIGS:
            cfg, moe_cfg = moe.MIXTRAL_CONFIGS[name]
            # Dropless routing for serving: finite capacity drops tokens
            # as a function of batch shape, making outputs depend on
            # which requests happen to be batched together.
            moe_cfg = _dc.replace(moe_cfg, capacity_factor=8.0)
            make_model = lambda c: moe.MixtralModel(c, moe_cfg)  # noqa: E731
        else:
            cfg = llama.CONFIGS[name]
            make_model = llama.LlamaModel
        if cfg.param_dtype == 'float32' and cfg.dtype == 'bfloat16':
            # Inference wants bf16-resident weights: a f32 master copy
            # doubles HBM traffic per decode step for no benefit.
            cfg = _dc.replace(cfg, param_dtype='bfloat16')
        cfg = _dc.replace(cfg, remat=False,
                          max_seq_len=min(cfg.max_seq_len, max_seq_len))
        model = make_model(cfg)
        sample = jnp.zeros((1, 8), jnp.int32)
        if quantize in ('int8', 'int4') and mesh is None:
            # Fused init+quantize inside ONE jit: XLA frees each bf16
            # kernel right after its int8 copy is formed, so the full
            # bf16 tree (2x the int8 bytes) is never resident at once —
            # this is what lets an ~8B model initialize on a single
            # 16GB v5e chip (weights ~8.5GB int8 vs ~16GB bf16).
            from skypilot_tpu.models import quant as quant_lib
            params = jax.jit(lambda k: quant_lib.quantize_params(
                model.init(k, sample),
                mode=quantize))(jax.random.PRNGKey(0))
            already_quantized = True
        elif mesh is not None:
            # Initialise straight into the sharded layout: a preset
            # that only fits spread over the mesh must never be whole
            # on device 0 first.
            from skypilot_tpu.models import weights as weights_lib
            params = weights_lib.init_sharded_params(
                model, cfg, mesh, jax.random.PRNGKey(0), sample)
        else:
            params = jax.jit(model.init)(jax.random.PRNGKey(0), sample)
    if quantize in ('int8', 'int4'):
        # Weight-only quantization: halve (int8) or quarter (int4) the
        # HBM bytes every decode step streams (models/quant.py). int8
        # covers llama projections AND MoE expert weights (routers stay
        # float); int4 is llama-family only (quantize_params raises on
        # a MoE tree).
        from skypilot_tpu.models import quant as quant_lib
        if not already_quantized:
            params = quant_lib.quantize_params(params, mode=quantize)
        cfg = _dc.replace(cfg, quant=quantize)
        model = make_model(cfg)
    elif quantize != 'none':
        raise ValueError(f'unknown quantize mode {quantize!r}')
    if cache_mode == 'auto':
        # Paged for all families: MoE shares the llama attention layer,
        # so the paged decode path covers it too (tested against dense).
        cache_mode = 'paged'
    draft_model = draft_params = None
    if spec_decode > 0 and (draft_model_name or draft_checkpoint):
        if draft_model_name == 'self':
            draft_model, draft_params = model, params
        elif draft_checkpoint:
            from skypilot_tpu.models import weights as weights_lib
            dcfg = weights_lib.load_config(
                draft_checkpoint, remat=False, param_dtype=dtype,
                dtype=dtype)
            dcfg = _dc.replace(
                dcfg, max_seq_len=min(dcfg.max_seq_len, max_seq_len))
            draft_model = llama.LlamaModel(dcfg)
            draft_params = weights_lib.load_llama_params(
                dcfg, draft_checkpoint)
        else:
            dcfg = _dc.replace(
                llama.CONFIGS[draft_model_name], remat=False,
                max_seq_len=max_seq_len)
            if dcfg.param_dtype == 'float32' and dcfg.dtype == 'bfloat16':
                dcfg = _dc.replace(dcfg, param_dtype='bfloat16')
            draft_model = llama.LlamaModel(dcfg)
            draft_params = jax.jit(draft_model.init)(
                jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))
            logger.warning(
                'draft model %r is RANDOMLY INITIALIZED (no '
                '--draft-checkpoint): acceptance will be chance-level, '
                'making decode strictly SLOWER than --spec-decode 0. '
                'Debug use only — point --draft-checkpoint at real '
                'small-model weights for a speedup.', draft_model_name)
    engine = engine_lib.InferenceEngine(model, params,
                                        num_slots=num_slots,
                                        max_seq_len=cfg.max_seq_len,
                                        decode_chunk=decode_chunk,
                                        mesh=mesh,
                                        cache_mode=cache_mode,
                                        pool_tokens=pool_tokens,
                                        prefix_caching=prefix_caching,
                                        kv_dtype=kv_dtype,
                                        spec_decode=spec_decode,
                                        prefill_chunk=prefill_chunk,
                                        lockstep=lockstep,
                                        draft_model=draft_model,
                                        draft_params=draft_params,
                                        lora_stack=lora_stack)
    # In-place weight swap staging hooks (infer/weight_swap.py): a
    # loader that reads ANOTHER checkpoint of the same architecture
    # into a tree matching this engine's params — same config, same
    # mesh placement, same stream-quantize mode as the boot load, so
    # the swap validation compares like with like.
    engine.checkpoint_path = checkpoint
    qmode = quantize if quantize in ('int8', 'int4') else 'none'

    def _param_loader(path: str):
        from skypilot_tpu.models import weights as weights_lib
        if moe_cfg is not None:
            return weights_lib.load_mixtral_params(
                cfg, moe_cfg, path, mesh=mesh, quantize=qmode)
        return weights_lib.load_llama_params(
            cfg, path, mesh=mesh, quantize=qmode)

    engine.param_loader = _param_loader
    return engine


def main(argv=None) -> None:
    from skypilot_tpu.utils import compile_cache
    compile_cache.configure()

    parser = argparse.ArgumentParser()
    parser.add_argument('--model', default='debug',
                        help='config preset (ignored with --checkpoint)')
    parser.add_argument('--checkpoint', default=None,
                        help='HF-format checkpoint dir')
    parser.add_argument('--tokenizer', default=None,
                        help='tokenizer.json path/dir (defaults to the '
                             'checkpoint dir)')
    parser.add_argument('--tp', type=int, default=1,
                        help='tensor-parallel degree (local devices)')
    parser.add_argument('--port', type=int, default=8000)
    parser.add_argument('--num-slots', type=int, default=8)
    parser.add_argument('--max-seq-len', type=int, default=2048)
    parser.add_argument('--dtype', default='bfloat16',
                        help='compute/weight dtype (bfloat16|float32); '
                             'float32 reproduces transformers greedy '
                             'outputs bit-for-bit in parity checks')
    parser.add_argument('--cache-mode', default='auto',
                        choices=['auto', 'paged', 'dense'],
                        help='KV cache layout (auto: paged for llama)')
    parser.add_argument('--no-prefix-caching', action='store_true',
                        help='disable KV prefix caching (paged mode)')
    parser.add_argument('--spec-decode', type=int, default=0,
                        help='speculative decoding draft length k '
                             '(0 = off). Default proposer: n-gram '
                             'prompt-lookup; see --draft-checkpoint.')
    parser.add_argument('--draft-checkpoint', default=None,
                        help='HF checkpoint of a small draft model: '
                             'replaces the n-gram proposer with real '
                             'draft-model speculative decoding '
                             '(requires --spec-decode > 0)')
    parser.add_argument('--draft-model', default=None,
                        help="draft config preset, or 'self' to "
                             'self-draft with the target (mechanism '
                             'check; no speedup)')
    parser.add_argument('--quantize', default='none',
                        choices=['none', 'int8', 'int4'],
                        help='weight-only quantization (int8 = w8a16 '
                             'halves decode HBM traffic; int4 = w4a16 '
                             'group-128 scales, quarters it — '
                             'llama-family only)')
    parser.add_argument('--kv-dtype', default='auto',
                        choices=['auto', 'int8'],
                        help='KV-cache dtype (paged mode): int8 stores '
                             'the k/v pools quantized with per-token '
                             'scales — ~2x pages (concurrent users) '
                             'per HBM byte. auto defers to '
                             'SKYT_KV_DTYPE, then the model dtype')
    parser.add_argument('--prefill-chunk', type=int, default=0,
                        help='chunked prefill: long prompts prefill in '
                             'chunks of this many tokens, interleaved '
                             'with decode (0 = off)')
    parser.add_argument('--chat-template', default=None,
                        help='path to a jinja chat template file, '
                             'overriding the checkpoint tokenizer '
                             "dir's tokenizer_config.json template "
                             '(a missing file fails startup loudly)')
    parser.add_argument('--lora', action='append', default=None,
                        metavar='NAME=PATH[:ALPHA]',
                        help='serve a LoRA adapter alongside the base '
                             'model (repeatable). PATH is the Orbax '
                             'dir an `sft --lora-rank R` run wrote; '
                             'requests select the adapter by NAME in '
                             "the OpenAI 'model' field (vLLM "
                             'convention) or /generate "lora". '
                             'ALPHA defaults to 16.')
    parser.add_argument('--multihost', default='auto',
                        choices=['auto', 'on', 'off'],
                        help='multi-host replica over jax.distributed '
                             '(gang env contract). auto: on when the '
                             'gang reports >1 node (SKYT_NUM_NODES). '
                             'Host 0 serves HTTP; other hosts run the '
                             'engine in lockstep.')
    args = parser.parse_args(argv)

    # Rolling-update composition (docs/robustness.md "Zero-downtime
    # rollouts"): the serve controller exports the service spec's
    # current `weights:` checkpoint, so a replica launched mid- or
    # post-rollout boots on the weights the fleet is SERVING rather
    # than the task's original --checkpoint.
    env_ckpt = env_lib.get('SKYT_WEIGHTS_CHECKPOINT')
    if env_ckpt:
        logger.info('SKYT_WEIGHTS_CHECKPOINT overrides the startup '
                    'checkpoint: %s', env_ckpt)
        args.checkpoint = env_ckpt

    lockstep = None
    if args.multihost == 'on' or (
            args.multihost == 'auto' and
            env_lib.get_int('SKYT_NUM_NODES', 1) > 1):
        # Same bootstrap as a training gang (runtime/gang.py env
        # triplet): the replica's hosts form one jax.distributed
        # runtime; jax.devices() is global from here on, so --tp counts
        # devices across the whole slice.
        from skypilot_tpu.infer import multihost as multihost_lib
        lockstep = multihost_lib.initialize_from_env()

    lora_stack, lora_names, lora_specs = None, {}, None
    if args.lora:
        from skypilot_tpu.infer import lora as lora_lib
        lora_specs = lora_lib.parse_lora_flag(args.lora)
        lora_stack, lora_names = lora_lib.build_stack_from_specs(
            lora_specs, dtype=args.dtype)

    engine = build_engine(args.model, args.num_slots, args.max_seq_len,
                          checkpoint=args.checkpoint, tp=args.tp,
                          cache_mode=args.cache_mode, dtype=args.dtype,
                          prefix_caching=not args.no_prefix_caching,
                          spec_decode=args.spec_decode,
                          quantize=args.quantize,
                          kv_dtype=args.kv_dtype,
                          prefill_chunk=args.prefill_chunk,
                          lockstep=lockstep,
                          draft_model_name=args.draft_model,
                          draft_checkpoint=args.draft_checkpoint,
                          lora_stack=lora_stack)
    if lockstep is not None and not lockstep.is_primary:
        # Follower host: no HTTP, no local requests — run the engine
        # loop (driven by the primary's tick broadcasts) until the
        # primary's stop.
        engine.start()
        logger.info('multihost follower %d: engine loop running',
                    lockstep.process_index)
        engine.join()
        return
    tok_path = args.tokenizer or args.checkpoint
    tokenizer = None
    chat_template = None
    special_tokens = {}
    if args.chat_template:
        # Explicit override: a missing/unreadable file fails loudly.
        try:
            with open(args.chat_template, encoding='utf-8') as f:
                chat_template = f.read()
        except OSError as e:
            raise SystemExit(
                f'--chat-template {args.chat_template}: {e}')
    if tok_path:
        try:
            tokenizer = tokenizer_lib.load_tokenizer(tok_path)
        except FileNotFoundError:
            logger.warning('no tokenizer.json at %s; using byte '
                           'fallback', tok_path)
        if chat_template is None:
            chat_template = tokenizer_lib.load_chat_template(tok_path)
        special_tokens = tokenizer_lib.special_token_strings(tok_path)
    if chat_template:
        logger.info('chat template loaded (%d chars)%s',
                    len(chat_template),
                    ' from --chat-template' if args.chat_template
                    else '')
    engine.start()
    logger.info('warming up (compiling prefill buckets + decode)...')
    engine.warmup()
    model_id = (os.path.basename(args.checkpoint.rstrip('/'))
                if args.checkpoint else args.model)
    server = InferenceServer(engine, tokenizer, model_id=model_id,
                             lora_names=lora_names,
                             lora_specs=lora_specs,
                             chat_template=chat_template,
                             special_tokens=special_tokens)
    logger.info('inference server: model=%s ckpt=%s tp=%d port=%d '
                'slots=%d', args.model, args.checkpoint, args.tp,
                args.port, args.num_slots)
    try:
        web.run_app(server.make_app(), port=args.port, print=None)
    finally:
        # The loop thread is a daemon: caught inside a device call
        # when the interpreter tears the TPU client down, it aborts
        # the process (SIGABRT and a core dump on every SIGTERM).
        engine.stop()


if __name__ == '__main__':
    main()
