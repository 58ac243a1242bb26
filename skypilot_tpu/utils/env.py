"""Typed central registry of every ``SKYT_*`` environment variable.

Before this module, 120+ ``SKYT_*`` knobs were read ad hoc across ~30
files (``os.environ.get`` with inline defaults, five private copies of
``_env_float``), with no single place stating what exists, what type it
is, or what it defaults to. This registry is that place:

  * every variable is declared ONCE below with (name, type, default,
    one-line doc) — ``docs/env_vars.md`` is generated from this table
    (``python tools/lint.py --write-env-docs``) and the ``env-registry``
    analysis pass (tools/analysis) fails CI when the generated file
    drifts, when framework code reads ``os.environ`` for a ``SKYT_``
    name directly, or when a read names an unregistered variable;
  * reads go through the accessors here. ``get`` keeps exact
    ``os.environ.get`` semantics (string-or-default, no coercion) for
    call sites with bespoke parsing; ``get_int`` / ``get_float`` /
    ``get_bool`` add coercion with a logged-warning fallback on
    malformed values (the PR 1 StepProfiler precedent: a typo in a
    launch YAML degrades to the default, it does not crash the job).

This module must stay stdlib-only and leaf-level (log_utils itself
reads SKYT_DEBUG through it), so it logs through a plain stdlib logger
parented under the framework root.

Names containing ``<`` are patterns: ``SKYT_SLO_TTFT_MS_<CLASS>``
matches any concrete name sharing the prefix before ``<`` (the serve
SLO plane mints one variable per QoS class).
"""
import dataclasses
import logging
import os
from typing import Dict, Optional, Union

# Parented under 'skypilot_tpu' so the log_utils root handler applies
# once configured; never imports log_utils (that would be circular).
logger = logging.getLogger('skypilot_tpu.utils.env')

Default = Union[None, bool, int, float, str]


@dataclasses.dataclass(frozen=True)
class EnvVar:
    """One registered variable. ``exported`` marks variables the
    framework SETS for user jobs (gang env, service templates) rather
    than reads itself — they appear in docs but are not required to
    have an in-repo accessor read."""
    name: str
    type: str                 # 'str' | 'int' | 'float' | 'bool'
    default: Default
    doc: str
    exported: bool = False


_REGISTRY: Dict[str, EnvVar] = {}


def _var(name: str, type: str, default: Default, doc: str,
         exported: bool = False) -> None:
    assert name not in _REGISTRY, f'duplicate env var {name}'
    _REGISTRY[name] = EnvVar(name, type, default, doc, exported)


# --------------------------------------------------------------- core
_var('SKYT_DEBUG', 'bool', False,
     'Debug-level logging for the whole framework (log_utils root).')
_var('SKYT_MINIMIZE_LOGGING', 'bool', False,
     'Quiet info-level chatter (warnings and errors only).')
_var('SKYT_SHOW_DEBUG_INFO', 'bool', False,
     'Show extra debug detail on CLI error surfaces.')
_var('SKYT_DISABLE_USAGE_COLLECTION', 'bool', True,
     'Disable the opt-in usage telemetry plane entirely.')
_var('SKYT_USAGE_COLLECTION', 'bool', False,
     'Opt IN to usage telemetry (off unless exactly "1").')
_var('SKYT_CONFIG', 'str', '~/.skypilot_tpu/config.yaml',
     'Path of the user config YAML (skyt_config.py).')
_var('SKYT_STATE_DIR', 'str', '~/.skypilot_tpu',
     'Client-side state root: cluster/job DBs, serve state.')
_var('SKYT_AGENT_HOME', 'str', '~',
     'Home of the per-host runtime agent ($HOME on job hosts): '
     'jobs.db, agent.json, logs live under <home>/.skyt.')
_var('SKYT_CLUSTER_NAME', 'str', None,
     'Cluster name stamped into gang env and postmortem bundles.')
_var('SKYT_JOB_ID', 'str', None,
     'Numeric job id of the running gang job (set by the agent).')
_var('SKYT_TASK_ID', 'str', None,
     'Task id (job+cluster+task triple) of the running gang job.')
_var('SKYT_BENCHMARK_DIR', 'str', '~/.skyt/benchmarks',
     'Where benchmark callbacks write their summary JSON.')
_var('SKYT_TIMELINE_FILE', 'str',
     '~/.skypilot_tpu/timeline-<pid>.json',
     'Output path of the client-side Chrome timeline.')

# ------------------------------------------------------------ runtime
_var('SKYT_NUM_NODES', 'int', 1,
     'Gang size; >1 turns on multi-host paths (jax.distributed).')
_var('SKYT_NODE_RANK', 'int', 0,
     'This host\'s rank within the gang (0 = head).')
_var('SKYT_NODE_IPS', 'str', None,
     'Newline-separated gang host IPs.', exported=True)
_var('SKYT_NUM_ACCELERATORS_PER_NODE', 'str', None,
     'Accelerator count per host.', exported=True)
_var('SKYT_COORDINATOR_ADDRESS', 'str', None,
     'jax.distributed coordinator address (head host:port).',
     exported=True)
_var('SKYT_WORKDIR', 'str', None,
     'Synced workdir a job\'s run script cds into.', exported=True)
_var('SKYT_WATCHDOG_INTERVAL_S', 'float', 2.0,
     'Agent-side liveness poll interval for job processes.')
_var('SKYT_JOBS_CHECK_GAP', 'float', 20.0,
     'Managed-jobs controller poll interval (seconds).')
_var('SKYT_JOBS_PREEMPTION_GRACE', 'float', 30.0,
     'Grace window before an unreachable cluster counts as preempted.')
_var('SKYT_JOBS_CONTROLLER', 'str', None,
     'Managed-jobs controller placement: "process" or "cluster" '
     '(falls back to config key jobs.controller.mode).')

# ------------------------------------------------------- provisioning
_var('SKYT_GCP_TOKEN', 'str', None,
     'Static OAuth token overriding gcloud auth for the GCP API.')
_var('SKYT_GCP_PROJECT', 'str', None,
     'GCP project id override for the TPU provisioner.')
_var('SKYT_LOCAL_ROOT', 'str', '~/.skyt_local',
     'Root of the local (offline) provider: fake clusters, job dirs.')

# ------------------------------------------------------------ storage
_var('SKYT_LOCAL_STORAGE_ROOT', 'str', '<SKYT_LOCAL_ROOT>/_storage',
     'Directory backing local:// buckets (offline store).')
_var('SKYT_DEFAULT_STORE', 'str', None,
     'Store used when a spec names none: gcs|s3|azure|r2|cos|local '
     '(falls back to config key storage.default_store, then gcs).')
_var('SKYT_AZURE_STORAGE_ACCOUNT', 'str', '',
     'Azure storage account name for az:// buckets.')
_var('SKYT_R2_ENDPOINT', 'str',
     'https://<account>.r2.cloudflarestorage.com',
     'Cloudflare R2 S3-compatible endpoint.')
_var('SKYT_COS_ENDPOINT', 'str',
     'https://s3.<region>.cloud-object-storage.appdomain.cloud',
     'IBM COS S3-compatible endpoint.')

# ------------------------------------------------------------- kernels
_var('SKYT_PAGED_ATTN', 'str', 'pallas',
     'Paged decode attention impl: "pallas" or "xla".')
_var('SKYT_SPEC_PAGED_ATTN', 'str', 'pallas',
     'Speculative-verify paged attention impl: "pallas" or "xla".')
_var('SKYT_KV_DTYPE', 'str', 'auto',
     'Paged KV-cache dtype: "int8" quantizes the k/v pools (per-token '
     'per-head scales, ~2x pages per HBM byte); "auto" = model dtype. '
     'An explicit engine kv_dtype="int8" / --kv-dtype int8 forces it; '
     'the default "auto" defers to this env var.')
_var('SKYT_RAGGED_PREFILL', 'bool', True,
     'Ragged (packed variable-length) batched prefill: mixed-length '
     'bursts pack into one segment-masked dispatch instead of padding '
     'every row to the pow2 bucket. "0" restores the padded batch '
     'path.')
_var('SKYT_RAGGED_MAX_TOKENS', 'int', 0,
     'Packed-token cap per ragged prefill dispatch (0 = the largest '
     'prefill bucket).')
_var('SKYT_RING_IMPL', 'str', None,
     'Ring-attention impl override ("xla" forces the XLA path).')

# ------------------------------------------------- tiered prefix cache
_var('SKYT_KV_TIER', 'str', 'off',
     'Prefix-KV cache tiering: "off" (HBM only, the byte-for-byte '
     'hot path), "host" (spill evicted pages to a host-RAM LRU and '
     'promote on miss), or "fleet" (host tier + cross-replica page '
     'fetch over GET /kv/prefix). Requires paged cache + prefix '
     'caching; ignored (with a warning) under lockstep.')
_var('SKYT_KV_HOST_BYTES', 'int', 256 * 1024 * 1024,
     'Byte budget of the host-RAM prefix-page LRU (L2). Evicted '
     'int8 pages + scale rows (or model-dtype pages) spill here.')
_var('SKYT_KV_FETCH_MAX_PAGES', 'int', 64,
     'Cap on pages per cross-replica /kv/prefix transfer, enforced '
     'on both the requesting engine and the serving endpoint.')
_var('SKYT_KV_FETCH_TIMEOUT_S', 'float', 2.0,
     'HTTP timeout of one cross-replica KV fetch; the engine '
     'abandons the fetch (and recomputes) at 1.5x this deadline.')
_var('SKYT_KV_PEER_ALLOW', 'str', '',
     'Comma-separated replica base URLs (scheme://host:port) a '
     'replica accepts in the X-KV-Peer fetch hint, matched on '
     'scheme+host+port. Loopback peers are always accepted; any '
     'other unlisted peer is dropped — the engine fetches with its '
     'admin bearer token, so fleets spanning hosts must list their '
     'replica URLs here.')

# -------------------------------------------------------- comms plane
_var('SKYT_COMMS_PROBE_MB', 'str', '1,16',
     'Comma-separated per-device payload sweep (MiB) of the comms '
     'link probe (parallel/comms_profile.py).')
_var('SKYT_COMMS_PROBE_ITERS', 'int', 5,
     'Timed iterations per comms probe measurement.')
_var('SKYT_COMMS_PROBE_TIMEOUT_S', 'float', 120.0,
     'Soft wall-clock budget of one comms probe sweep (checked '
     'between measurements).')
_var('SKYT_COMMS_CACHE', 'str',
     '~/.cache/skypilot_tpu/comms_profile.json',
     'Persistent comms-profile cache path (probe results + placement '
     'advisor winners; atomic tmp+rename writes).')
_var('SKYT_COMMS_PLACEMENT', 'str', 'rowmajor',
     'DCN slice placement of build_hybrid_mesh: "rowmajor" (today\'s '
     'layout) or "measured" (cheapest ring permutation under the '
     'cached comms profile; ICI layout untouched).')
_var('SKYT_COMMS_CENSUS', 'str', 'lowered',
     'HLO communication census mode: "lowered" (explicit shard_map '
     'collectives, no backend compile), "compiled" (post-SPMD module '
     '— one extra AOT compile), or "off".')

# ------------------------------------------------------------ tracing
_var('SKYT_TRACE', 'bool', True,
     'Master switch for the request-tracing plane (off iff "0").')
_var('SKYT_TRACE_SAMPLE', 'float', 0.0,
     'Head-sampling ratio for non-forced traces (0..1).')
_var('SKYT_TRACE_SLOW_MS', 'float', 500.0,
     'Tail-sampling threshold: traces slower than this are kept.')
_var('SKYT_PROFILE', 'bool', False,
     'Ask the agent to profile this job (sets SKYT_PROFILE_DIR).',
     exported=True)
_var('SKYT_PROFILE_DIR', 'str', None,
     'Where the on-demand device profiler writes traces.')
_var('SKYT_PROFILE_START_STEP', 'int', 2,
     'First train step the StepProfiler captures.')
_var('SKYT_PROFILE_NUM_STEPS', 'int', 3,
     'How many consecutive steps the StepProfiler captures.')
_var('SKYT_PROFILE_REMOTE', 'bool', False,
     'Enable the replica /profile remote-profiling endpoint.')
_var('SKYT_METRICS_MAX_SERIES', 'int', 1000,
     'Per-family label-set cap in the metrics registry.')
_var('SKYT_TS_MAX_SERIES', 'int', 4096,
     'Fleet time-series store: max distinct series.')
_var('SKYT_TS_MAX_POINTS', 'int', 360,
     'Fleet time-series store: max points per series.')

# ------------------------------------------------------------- faults
_var('SKYT_FAULTS', 'str', '',
     'Fault-injection plan, e.g. "engine.loop=error,p=0.5".')
_var('SKYT_FAULTS_SEED', 'int', 0,
     'Deterministic seed for probabilistic fault plans.')

# -------------------------------------------------------------- serve
_var('SKYT_SERVE_CONTROLLER', 'str', None,
     'Serve controller placement: "process" or "cluster" (falls '
     'back to config key serve.controller.mode).')
_var('SKYT_SERVE_CONTROLLER_INTERVAL', 'float', 2.0,
     'Serve controller reconcile-loop interval (seconds).')
_var('SKYT_SERVE_STATE_PRUNE_S', 'float', 600.0,
     'How often the controller prunes terminal serve-state rows.')
_var('SKYT_SERVE_STATE_TTL_S', 'float', 3600.0,
     'Age before a terminal serve-state row is pruned.')
_var('SKYT_SERVE_DRAIN_GRACE_S', 'float', 10.0,
     'Drain grace before a replica teardown turns forceful.')
_var('SKYT_SERVE_RELAUNCH_BACKOFF_S', 'float', 5.0,
     'Initial backoff between replica relaunch attempts.')
_var('SKYT_SERVE_RELAUNCH_BACKOFF_MAX_S', 'float', 120.0,
     'Backoff ceiling between replica relaunch attempts.')
_var('SKYT_SERVE_ADOPT_PROBE_RETRIES', 'int', 3,
     'Readiness probes a restarted controller grants each adopted '
     'replica before reaping it.')
_var('SKYT_SERVE_LB_SYNC_INTERVAL', 'float', 2.0,
     'LB -> controller sync interval (seconds).')
_var('SKYT_REPLICA_PORT', 'str', None,
     'Port a serve replica must bind (set in replica task env).',
     exported=True)
_var('SKYT_AUTOSCALER_MAX_TIMESTAMPS', 'int', 16384,
     'Cap on buffered request timestamps feeding autoscaling.')
_var('SKYT_FLEET', 'bool', True,
     'Master switch for the controller\'s fleet-telemetry scraper.')
_var('SKYT_FLEET_SCRAPE_S', 'float', 10.0,
     'Fleet scrape interval (seconds).')
_var('SKYT_FLEET_SCRAPE_TIMEOUT_S', 'float', 2.0,
     'Per-target fleet scrape timeout.')
_var('SKYT_FLEET_STALE_S', 'float', 60.0,
     'Age before a fleet target\'s series are considered stale.')
_var('SKYT_FLEET_ACCELERATOR', 'str', '',
     'Accelerator kind stamped on the SLO cost report.')
_var('SKYT_FLEET_CHIPS_PER_REPLICA', 'float', 1.0,
     'Chips per replica for good-tokens-per-chip-second accounting.')

# ----------------------------------------------------- load balancer
_var('SKYT_LB_BREAKER_THRESHOLD', 'int', 3,
     'Consecutive transport failures before a replica breaker opens.')
_var('SKYT_LB_BREAKER_COOLDOWN_S', 'float', 2.0,
     'Open-state cooldown before a half-open trial request.')
_var('SKYT_LB_RETRY_BUDGET_S', 'float', 60.0,
     'Wall-clock budget for cross-replica retries of one request.')
_var('SKYT_LB_RETRY_BACKOFF_S', 'float', 0.05,
     'Base backoff between upstream retry attempts.')
_var('SKYT_LB_NO_REPLICA_POLL_S', 'float', 1.0,
     'Poll interval while a request waits for a ready replica.')
_var('SKYT_LB_NO_REPLICA_TIMEOUT_S', 'float', 30.0,
     'How long a request may wait for a ready replica before 503.')
_var('SKYT_LB_UPSTREAM_TOTAL_S', 'float', 0.0,
     'Total per-attempt upstream timeout (0 = unbounded streaming).')
_var('SKYT_LB_UPSTREAM_CONNECT_S', 'float', 10.0,
     'Upstream TCP connect timeout.')
_var('SKYT_LB_MAX_PENDING_TIMESTAMPS', 'int', 16384,
     'Cap on unsent controller-sync timestamps (drop-oldest).')
_var('SKYT_LB_STALE_TTL_S', 'float', 300.0,
     'Max age of a stale LBState snapshot before the LB drains.')
_var('SKYT_LB_STALE_PROBE_PATH', 'str', None,
     'Override readiness path for LB-side stale-mode probes.')
_var('SKYT_LB_STALE_PROBE_TIMEOUT_S', 'float', 2.0,
     'Timeout of LB-side stale-mode health probes.')
_var('SKYT_LB_STALE_PROBE_THRESHOLD', 'int', 3,
     'Consecutive probe failures before stale-mode prunes a replica.')
_var('SKYT_LB_LEASE_INTERVAL_S', 'float', 1.0,
     'Leader-lease heartbeat/poll interval for hot-standby LBs.')
_var('SKYT_LB_TAKEOVER_BIND_TIMEOUT_S', 'float', 30.0,
     'How long a promoted standby retries binding the serve port.')
_var('SKYT_LB_ID', 'str', None,
     'Instance id of this LB process (metrics `lb` label, gossip '
     'identity, fleet scrape target); default lb-<port>.')
_var('SKYT_LB_PEER_URLS', 'str', '',
     'Comma-separated peer LB base URLs for the N-active tier '
     '(enables the gossip loop; own advertise URL is filtered out).')
_var('SKYT_LB_ADVERTISE_URL', 'str', None,
     'URL peers and the controller reach this LB at '
     '(default http://127.0.0.1:<port>; override on multi-host tiers).')
_var('SKYT_LB_PEER_SYNC_S', 'float', 2.0,
     'LB <-> LB gossip exchange interval (seconds).')
_var('SKYT_LB_PEER_STALE_S', 'float', 10.0,
     'Exchange age past which a peer view leaves the aggregates '
     '(per-peer stale-mode discipline).')
_var('SKYT_LB_AFFINITY_PREFIX_BYTES', 'int', 1024,
     'Bytes of normalized prompt prefix hashed into the affinity key.')
_var('SKYT_LB_RING_WEIGHT_OCCUPANCY', 'float', 1.0,
     'Ring weight gain per unit of prefix-cache occupancy '
     '(weight = 1 + gain * occupancy).')
_var('SKYT_LB_RING_SESSIONS_MAX', 'int', 8192,
     'Sticky-session LRU capacity of the prefix_affinity policy.')

# ------------------------------------------- weight swap / rollouts
_var('SKYT_SWAP_DRAIN', 'bool', True,
     'In-place weight swap: drain in-flight requests to the decode-'
     'tick boundary (finish on the OLD weights) before applying; '
     '"0" applies at the next boundary and in-flight requests '
     'continue on the new weights.')
_var('SKYT_SWAP_TIMEOUT_S', 'float', 120.0,
     'How long a weight swap waits for the engine to reach an '
     'applicable tick boundary before aborting (old weights stay '
     'live).')
_var('SKYT_ADMIN_TOKEN', 'str', None,
     'Bearer token guarding the replica admin API (POST '
     '/admin/weights). Unset disables the route (403); the serve '
     'controller exports the per-service token to its replicas.',
     exported=True)
_var('SKYT_WEIGHTS_CHECKPOINT', 'str', None,
     'Weights checkpoint override applied at replica startup '
     '(exported from the service spec\'s `weights:` field, so '
     'replicas launched mid/post-rollout boot on the current '
     'weights instead of the task\'s original --checkpoint).',
     exported=True)
_var('SKYT_ROLLOUT_BAKE_S', 'float', 30.0,
     'Canary bake window of a rolling weight update: seconds the '
     'canary serves the new weights (watched against SLO burn-rate '
     'alerts and replica health) before the fleet follows.')
_var('SKYT_ROLLOUT_SWAP_TIMEOUT_S', 'float', 180.0,
     'Per-replica HTTP timeout of the controller\'s POST '
     '/admin/weights calls during a rolling update.')
_var('SKYT_ROLLOUT_RETRIES', 'int', 3,
     'Consecutive per-replica swap/rollback failures a rolling '
     'update tolerates before escalating (rollback, then drain+'
     'relaunch of the stuck replica). The elastic reshard '
     'orchestrator shares this budget.')

# ------------------------------------------------------ adapter fleet
_var('SKYT_ADAPTER_TIMEOUT_S', 'float', 120.0,
     'How long an adapter hot-load/unload waits for the engine to '
     'reach an applicable decode-tick boundary before aborting (the '
     'old adapter stack stays live).')
_var('SKYT_ADAPTER_MAX', 'int', 32,
     'Max adapters loadable on one replica via POST /admin/adapters '
     '(bounds stack HBM growth and per-model metric cardinality).')
_var('SKYT_ADAPTER_ROLLOUT_TIMEOUT_S', 'float', 120.0,
     'Per-replica HTTP timeout of the controller\'s POST '
     '/admin/adapters calls during a fleet-wide adapter update.')

# ------------------------------------------------- elastic capacity
_var('SKYT_AUTOSCALE_PREDICT', 'bool', False,
     'Wrap the reactive autoscaler in the predictive one '
     '(serve/forecast.py): scale BEFORE a forecast demand wave, '
     'degrade to reactive when the error bound blows. Off = '
     'behavior unchanged.')
_var('SKYT_FORECAST_BUCKET_S', 'float', 10.0,
     'Width of one demand-forecast bucket (seconds).')
_var('SKYT_FORECAST_SEASON_BUCKETS', 'int', 30,
     'Buckets per season of the Holt-Winters seasonal component.')
_var('SKYT_FORECAST_LEAD_S', 'float', 60.0,
     'Provisioning lead time: how far ahead the predictive '
     'autoscaler scales (must cover launch + cold start).')
_var('SKYT_FORECAST_ALPHA', 'float', 0.5,
     'Holt-Winters level smoothing factor.')
_var('SKYT_FORECAST_BETA', 'float', 0.1,
     'Holt-Winters trend smoothing factor.')
_var('SKYT_FORECAST_GAMMA', 'float', 0.3,
     'Holt-Winters seasonal smoothing factor.')
_var('SKYT_FORECAST_ERR_BOUND', 'float', 0.5,
     'Relative one-step-ahead error (EWMA) above which the forecast '
     'is not acted on (predictive degrades to reactive).')
_var('SKYT_FORECAST_MIN_BUCKETS', 'int', 8,
     'Fitted buckets required before a forecast is trusted.')
_var('SKYT_FORECAST_MAX_POINTS', 'int', 16384,
     'Cap on buffered raw observations per demand curve '
     '(drop-oldest, counted).')
_var('SKYT_LB_SURGE_QUEUE_MAX', 'int', 256,
     'Requests the LB parks awaiting a cold-starting replica while '
     'the ready set is empty; beyond it, immediate 503+Retry-After.')
_var('SKYT_SERVE_PREWARM', 'bool', False,
     'Push a KV pre-warm to each newly READY replica: it pulls its '
     'rendezvous share of fleet-resident prefix pages from peers.')
_var('SKYT_PREWARM_TIMEOUT_S', 'float', 10.0,
     'HTTP timeout of the controller\'s POST /admin/kv_prewarm push.')

# ---------------------------------------------------------------- qos
_var('SKYT_QOS', 'bool', False,
     'Master switch for the QoS plane (admission, DRR, shedding).')
_var('SKYT_QOS_WEIGHTS', 'str', '',
     'DRR class weights, e.g. "interactive:8,standard:4,batch:1".')
_var('SKYT_QOS_QUANTUM', 'float', 256.0,
     'DRR quantum (token credits per round).')
_var('SKYT_QOS_AGING_S', 'float', 30.0,
     'Anti-starvation aging horizon for queued requests.')
_var('SKYT_QOS_DEBT_HALFLIFE_S', 'float', 30.0,
     'Half-life of accumulated DRR debt.')
_var('SKYT_QOS_RESERVE_SLOTS', 'int', 0,
     'Engine slots reserved for interactive-class admission.')
_var('SKYT_QOS_QUEUE_DEGRADE', 'float', 4.0,
     'Queue-depth-per-slot level that triggers degrade mode.')
_var('SKYT_QOS_QUEUE_SHED', 'float', 8.0,
     'Queue-depth-per-slot level that triggers shedding.')
_var('SKYT_QOS_KV_DEGRADE', 'float', 0.90,
     'KV-cache utilization that triggers degrade mode.')
_var('SKYT_QOS_KV_SHED', 'float', 0.97,
     'KV-cache utilization that triggers shedding.')
_var('SKYT_QOS_TTFT_SLO_MS', 'float', 500.0,
     'Interactive TTFT objective the overload ladder protects.')
_var('SKYT_QOS_HOLD_S', 'float', 2.0,
     'Hysteresis hold before the overload level steps down.')
_var('SKYT_QOS_REFRESH_S', 'float', 0.25,
     'Overload-level recompute cadence.')
_var('SKYT_QOS_RETRY_AFTER_S', 'float', 1.0,
     'Base Retry-After seconds on shed (429) responses.')
_var('SKYT_QOS_DEGRADE_MAX_TOKENS', 'float', 32.0,
     'max_tokens clamp applied to batch requests in degrade mode.')
_var('SKYT_QOS_TENANT_RPS', 'float', 0.0,
     'Per-tenant request-rate limit (0 = off).')
_var('SKYT_QOS_TENANT_BURST', 'float', 0.0,
     'Per-tenant burst allowance (0 = 2x the rate).')
_var('SKYT_QOS_AUTOSCALE_WEIGHTS', 'str', '',
     'Class weights for QoS-aware autoscaling demand.')
_var('SKYT_QOS_MODEL_WEIGHTS', 'str', '',
     'Per-model DRR quantum multipliers for the fair queue, e.g. '
     '"summarize:4,translate:1" (multiplied with the class weight; '
     'unlisted models weigh 1.0).')

# ----------------------------------------------------------------- slo
_var('SKYT_SLO_TARGET', 'float', 0.99,
     'Global SLO attainment target (per-class override below).')
_var('SKYT_SLO_TTFT_MS_<CLASS>', 'float', None,
     'Per-class p95 TTFT bound in ms (pattern; class upper-cased).')
_var('SKYT_SLO_ITL_MS_<CLASS>', 'float', None,
     'Per-class p95 inter-token-latency bound in ms (pattern).')
_var('SKYT_SLO_TARGET_<CLASS>', 'float', None,
     'Per-class attainment target override (pattern).')
_var('SKYT_SLO_FAST_SHORT_S', 'float', 300.0,
     'Fast burn-rate alert: short window (seconds).')
_var('SKYT_SLO_FAST_LONG_S', 'float', 3600.0,
     'Fast burn-rate alert: long window (seconds).')
_var('SKYT_SLO_FAST_BURN', 'float', 14.4,
     'Fast burn-rate alert threshold (multiples of budget burn).')
_var('SKYT_SLO_SLOW_SHORT_S', 'float', 21600.0,
     'Slow burn-rate alert: short window (seconds).')
_var('SKYT_SLO_SLOW_LONG_S', 'float', 259200.0,
     'Slow burn-rate alert: long window (seconds).')
_var('SKYT_SLO_SLOW_BURN', 'float', 6.0,
     'Slow burn-rate alert threshold.')

# ------------------------------------------------- capacity / traffic
_var('SKYT_CAPACITY_LEDGER', 'bool', True,
     'Engine busy-time ledger: chip-seconds attributed per (class, '
     'tenant, model) slice (infer/ledger.py).')
_var('SKYT_CAPACITY_TARGET', 'float', None,
     'Capacity-search SLO attainment target (defaults to '
     'SKYT_SLO_TARGET).')
_var('SKYT_CAPACITY_WINDOW_S', 'float', 300.0,
     'Default window of the /fleet/capacity report (seconds).')
_var('SKYT_TRAFFIC_COMPRESSION', 'float', 1.0,
     'Open-loop traffic engine virtual-time compression: N replays '
     'the schedule N times faster than spec time.')
_var('SKYT_TRAFFIC_MAX_INFLIGHT', 'int', 256,
     'Generator-health backstop on concurrently in-flight open-loop '
     'requests (hitting it shows up as arrival lateness, not as '
     'closed-loop throttling).')
_var('SKYT_TRAFFIC_SEED', 'int', 0,
     'Default seed of the deterministic workload schedule.')

# ---------------------------------------- tick plane / interference
_var('SKYT_TICKSTATS', 'bool', True,
     'Tick plane (infer/tickstats.py): per-tick records at '
     '/debug/ticks + prefill<->decode interference attribution. 0 '
     'removes the recording call from the engine loop entirely.')
_var('SKYT_TICKSTATS_RING', 'int', 512,
     'Tick records retained in the /debug/ticks ring (drop-oldest).')
_var('SKYT_TICKSTATS_EWMA', 'float', 0.2,
     'EWMA weight of the pure-decode tick-time baseline per '
     'active-slot bucket.')
_var('SKYT_TICKSTATS_ISOLATE', 'bool', False,
     'Isolated-prefill schedule: admit prefill only from ticks with '
     'no active decode slots (the disaggregation counterfactual).')
_var('SKYT_INTERFERENCE_MIN_SAMPLES', 'int', 4,
     'Pure-decode ticks a slot bucket needs before its baseline is '
     'warm enough to attribute mixed-tick excess.')
_var('SKYT_INTERFERENCE_MIN_INFLATION', 'float', 0.1,
     'Disaggregation advisor floor: measured interference below this '
     'fraction of ITL is treated as noise, not a reason to split '
     'prefill off-replica.')
_var('SKYT_INTERFERENCE_DCN_GBPS', 'float', 10.0,
     'Fallback DCN bandwidth (GB/s) for the advisor\'s KV transfer '
     'cost when no measured comms profile covers a DCN pair '
     '(verdicts mark it "assumed").')

# -------------------------------------------------------------- train
_var('SKYT_WATCHDOG', 'bool', True,
     'Master switch for heartbeats + rank sentinel + gang watchdog.')
_var('SKYT_HEARTBEAT_FILE', 'str', None,
     'Per-rank heartbeat file path (set by the agent for gang jobs).')
_var('SKYT_HEARTBEAT_INTERVAL_S', 'float', 1.0,
     'Heartbeat write cadence.')
_var('SKYT_WATCHDOG_POLL_S', 'float', 1.0,
     'Gang-watchdog poll interval.')
_var('SKYT_WATCHDOG_FACTOR', 'float', 10.0,
     'Hang verdict at factor x the learned step-time baseline.')
_var('SKYT_WATCHDOG_MIN_S', 'float', 60.0,
     'Floor on the hang stall budget (seconds).')
_var('SKYT_WATCHDOG_STRAGGLER_K', 'float', 3.0,
     'Straggler verdict at K x the gang-median step lag.')
_var('SKYT_WATCHDOG_PIPELINE_DEPTH', 'int', 2,
     'Allowed in-flight step skew between ranks before desync.')
_var('SKYT_WATCHDOG_CONFIRM', 'int', 2,
     'Consecutive confirming polls before a verdict escalates.')
_var('SKYT_POSTMORTEM_DIR', 'str', '~/.skyt/postmortems',
     'Where crash bundles (py-stacks, env, verdicts) are written.')


# ---------------------------------------------------------- accessors
_FALSEY = ('', '0', 'false', 'no', 'off')


def lookup(name: str) -> EnvVar:
    """Registry entry for a concrete name (pattern-aware): the exact
    entry if one exists, else the pattern entry whose prefix before
    ``<`` matches. Unregistered names raise — reads must resolve
    through the registry (the env-registry analysis pass enforces the
    same statically)."""
    ev = _REGISTRY.get(name)
    if ev is not None:
        return ev
    for pat, pev in _REGISTRY.items():
        cut = pat.find('<')
        if cut > 0 and name.startswith(pat[:cut]):
            return pev
    raise KeyError(
        f'{name} is not in the SKYT_* env registry '
        f'(declare it in skypilot_tpu/utils/env.py)')


def get(name: str, default: Optional[str] = None) -> Optional[str]:
    """Raw read with exact ``os.environ.get`` semantics (no coercion,
    no empty-string handling) for call sites with bespoke parsing.
    The name must still be registered."""
    lookup(name)
    return os.environ.get(name, default)


def get_bool(name: str, default: Optional[bool] = None) -> bool:
    """Flag read: unset uses the default (registry default when the
    call site passes none); set counts as true unless the lowered
    value is one of '', '0', 'false', 'no', 'off'."""
    ev = lookup(name)
    if default is None:
        default = bool(ev.default)
    raw = os.environ.get(name)
    if raw is None:
        return default
    return raw.lower() not in _FALSEY


def get_int(name: str, default: Optional[int] = None,
            minimum: Optional[int] = None) -> int:
    """Int read with warning fallback: unset/empty uses the default,
    malformed or below-``minimum`` values log a warning and use the
    default (a typo in a launch YAML must degrade, not crash)."""
    ev = lookup(name)
    if default is None:
        default = int(ev.default or 0)
    raw = os.environ.get(name)
    if raw is None or raw == '':
        return default
    try:
        val = int(raw)
    except ValueError:
        logger.warning('%s=%r is not an integer; using default %d',
                       name, raw, default)
        return default
    if minimum is not None and val < minimum:
        logger.warning('%s=%d is below the minimum %d; using default '
                       '%d', name, val, minimum, default)
        return default
    return val


def get_float(name: str, default: Optional[float] = None) -> float:
    """Float read with warning fallback (see get_int)."""
    ev = lookup(name)
    if default is None:
        default = float(ev.default or 0.0)
    raw = os.environ.get(name)
    if raw is None or raw == '':
        return default
    try:
        return float(raw)
    except ValueError:
        logger.warning('%s=%r is not a number; using default %s',
                       name, raw, default)
        return default


# ------------------------------------------------------ docs generator
def registry() -> Dict[str, EnvVar]:
    """Read-only copy of the registry (analysis + tests)."""
    return dict(_REGISTRY)


def _fmt_default(ev: EnvVar) -> str:
    if ev.default is None:
        return '(unset)'
    if ev.type == 'bool':
        return '1' if ev.default else '0'
    return f'`{ev.default}`'


def generate_docs() -> str:
    """docs/env_vars.md content, generated from the registry. The
    env-registry analysis pass fails when the checked-in file differs
    from this output (regenerate with
    ``python tools/lint.py --write-env-docs``)."""
    lines = [
        '# Environment variables',
        '',
        '<!-- GENERATED from skypilot_tpu/utils/env.py; do not edit.',
        '     Regenerate: python tools/lint.py --write-env-docs',
        '     (the env-registry analysis pass gates drift). -->',
        '',
        'Every `SKYT_*` variable the framework reads, generated from',
        'the typed registry in `skypilot_tpu/utils/env.py`. Names',
        'containing `<...>` are patterns (one concrete variable per',
        'QoS class). Variables marked *exported* are set BY the',
        'framework for user jobs rather than read by it.',
        '',
        '| variable | type | default | description |',
        '|---|---|---|---|',
    ]
    for name in sorted(_REGISTRY):
        ev = _REGISTRY[name]
        typ = ev.type + (' (exported)' if ev.exported else '')
        lines.append(f'| `{ev.name}` | {typ} | {_fmt_default(ev)} | '
                     f'{ev.doc} |')
    return '\n'.join(lines) + '\n'
