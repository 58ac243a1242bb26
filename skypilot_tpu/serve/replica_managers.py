"""Replica lifecycle management for serving.

Reference: sky/serve/replica_managers.py (1,233 LoC) — `ReplicaInfo`
(:382), `ReplicaManager` (:560), `SkyPilotReplicaManager` (:604) with
three daemon threads (process-pool refresher :940, job-status fetcher
:1003, readiness prober :1019), spot-preemption detection + recovery,
versioned rolling updates.

TPU-native deltas: replicas are launched in daemon threads (no
subprocess pool — `execution.launch` is importable, the reference forks
`sky.launch` subprocesses because Ray state is process-bound), and
preemption detection leans on the provider query (a preempted TPU
queued-resource is *deleted*, so a missing cluster record == preempted).
"""
import dataclasses
import threading
import time
from typing import Dict, List, Optional

import requests

from skypilot_tpu import exceptions
from skypilot_tpu import state as cluster_state
from skypilot_tpu.serve import serve_state
from skypilot_tpu.serve import service_spec as spec_lib
from skypilot_tpu.utils import faults
from skypilot_tpu.utils import log_utils
from skypilot_tpu.utils import metrics as metrics_lib
from skypilot_tpu.utils import env

logger = log_utils.init_logger(__name__)

# Consecutive probe failures before READY -> NOT_READY (reference
# _consecutive_failure_threshold ~ 180s / probe interval).
NOT_READY_THRESHOLD = 3
# Consecutive failures while NOT_READY before giving up -> FAILED.
FAILED_THRESHOLD = 10


def _drain_grace_seconds() -> float:
    """Grace period a deliberately retired READY replica gets between
    leaving the ready set (the LB stops routing to it at the next
    sync) and the actual teardown, so in-flight requests finish."""
    return env.get_float('SKYT_SERVE_DRAIN_GRACE_S', 10)


def _relaunch_backoff_bounds() -> 'tuple[float, float]':
    return (env.get_float('SKYT_SERVE_RELAUNCH_BACKOFF_S', 5),
            env.get_float('SKYT_SERVE_RELAUNCH_BACKOFF_MAX_S', 120))


def _rollout_bake_s() -> float:
    return env.get_float('SKYT_ROLLOUT_BAKE_S', 30.0)


def _rollout_retries() -> int:
    return env.get_int('SKYT_ROLLOUT_RETRIES', 3, minimum=1)


# Rolling-update phases (docs/robustness.md "Zero-downtime rollouts").
# Active phases are ticked by the control loop; terminal ones are kept
# (persisted) for status surfaces only.
ROLLOUT_ACTIVE_PHASES = ('canary', 'bake', 'rollout', 'rollback')
ROLLOUT_PHASES = ROLLOUT_ACTIVE_PHASES + ('done', 'rolled_back')


@dataclasses.dataclass
class RolloutState:
    """One rolling in-place weight update, JSON-persisted on the
    service row (serve_state.set_rollout) after every transition so a
    controller crash mid-rollout resumes (phase 'rollout'/'rollback')
    or conservatively rolls back (phase 'canary'/'bake' — the bake
    observations died with the old process)."""
    phase: str
    target_version: int            # spec version being rolled TO
    baseline_version: int          # spec version rolled FROM
    checkpoint: str                # target weights (spec.weights)
    baseline_checkpoint: Optional[str]
    spec_config: dict              # new spec yaml config (commit input)
    task_yaml: str
    started_at: float
    canary: Optional[int] = None   # replica id
    updated: List[int] = dataclasses.field(default_factory=list)
    bake_until: float = 0.0
    fails: int = 0                 # consecutive per-replica failures
    error: Optional[str] = None

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> 'RolloutState':
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})

    @property
    def active(self) -> bool:
        return self.phase in ROLLOUT_ACTIVE_PHASES


RESHARD_ACTIVE_PHASES = ('reshard', 'rollback')
RESHARD_PHASES = RESHARD_ACTIVE_PHASES + ('done', 'rolled_back')


@dataclasses.dataclass
class ReshardState:
    """One in-place elastic reshard (docs/robustness.md "Elastic
    capacity"): flip every READY replica's virtual-node layout through
    POST /admin/reshard, one replica per control tick, rolling back the
    already-resharded set (newest first) after repeated failures.

    Deliberately IN-MEMORY, unlike RolloutState: the layout is a
    performance knob, not a correctness hazard — a controller restart
    mid-reshard leaves each replica serving on whatever layout it
    holds, and the operator re-issues the reshard. Persisting it would
    buy crash-resume for an operation that is cheap to re-request."""
    target_nodes: int
    phase: str = 'reshard'
    started_at: float = dataclasses.field(default_factory=time.time)
    updated: List[int] = dataclasses.field(default_factory=list)
    fails: int = 0                 # consecutive per-replica failures
    error: Optional[str] = None

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @property
    def active(self) -> bool:
        return self.phase in RESHARD_ACTIVE_PHASES


ADAPTER_ACTIVE_PHASES = ('update', 'rollback')
ADAPTER_PHASES = ADAPTER_ACTIVE_PHASES + ('done', 'rolled_back')


@dataclasses.dataclass
class AdapterState:
    """One fleet-wide adapter convergence (docs/serving.md "Adapter
    fleet"): push one load/unload through every READY replica's
    POST /admin/adapters, one replica per control tick, rolling the
    already-updated set back (newest first) after repeated failures —
    a load rolls back by unloading, an unload by reloading from the
    recorded checkpoint.

    IN-MEMORY like ReshardState and for the same reason: each
    replica's adapter set is re-readable from its /stats, and the
    operator re-issues a half-applied convergence after a controller
    restart — persisting it would buy crash-resume for an operation
    that is cheap to re-request."""
    op: str                        # 'load' | 'unload'
    name: str
    checkpoint: Optional[str] = None
    alpha: float = 16.0
    drain: Optional[bool] = None
    phase: str = 'update'
    started_at: float = dataclasses.field(default_factory=time.time)
    updated: List[int] = dataclasses.field(default_factory=list)
    fails: int = 0                 # consecutive per-replica failures
    error: Optional[str] = None

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def payload(self) -> dict:
        """The /admin/adapters body this convergence applies."""
        body = {'op': self.op, 'name': self.name}
        if self.op == 'load':
            body['checkpoint'] = self.checkpoint
            body['alpha'] = self.alpha
        if self.drain is not None:
            body['drain'] = self.drain
        return body

    @property
    def active(self) -> bool:
        return self.phase in ADAPTER_ACTIVE_PHASES


@dataclasses.dataclass
class ReplicaInfo:
    """Reference: sky/serve/replica_managers.py:382."""
    replica_id: int
    cluster_name: str
    version: int
    status: serve_state.ReplicaStatus
    endpoint: Optional[str] = None
    use_spot: bool = False
    launched_at: float = 0.0
    first_ready_at: Optional[float] = None
    consecutive_failures: int = 0
    failure_reason: Optional[str] = None
    # Last /stats snapshot from an inference-server replica (TTFT
    # percentiles, steady decode rate, slot occupancy) — best-effort:
    # None for replicas that don't expose /stats.
    stats: Optional[dict] = None
    # Liveness identity (docs/robustness.md "Control plane"): enough
    # persisted state that a RESTARTING controller can tell an
    # adoptable live replica from a dead orphan without relaunching.
    # pid is the replica's head-agent pid where the provider exposes
    # one (local provider; None for cloud replicas, whose cluster
    # record + probe are the identity); pid_start is the kernel
    # starttime token guarding against pid reuse.
    pid: Optional[int] = None
    pid_start: Optional[int] = None
    # Set when a restart re-adopted this replica (observability).
    adopted_at: Optional[float] = None
    # Set when the replica reached a terminal/preempted state; the
    # serve_state.prune_terminal_replicas sweep keys on it.
    terminal_at: Optional[float] = None
    # Weight version the replica is serving (in-place swaps bump it
    # without touching `version`, the SPEC version — mixed-version
    # windows during a rollout are visible here, in /controller/status,
    # and through the LB sync as skyt_lb_replica_weight_version).
    weight_version: int = 1

    @property
    def is_alive(self) -> bool:
        return self.status in (serve_state.ReplicaStatus.PENDING,
                               serve_state.ReplicaStatus.PROVISIONING,
                               serve_state.ReplicaStatus.STARTING,
                               serve_state.ReplicaStatus.READY,
                               serve_state.ReplicaStatus.NOT_READY)


# Fields added after the first pickled rows shipped: a dataclass
# unpickles by restoring __dict__ directly, so rows written by an
# older build come back WITHOUT the newer attributes. Backfill them so
# adoption logic never needs getattr() guards.
_PICKLE_BACKFILL = {'stats': None, 'pid': None, 'pid_start': None,
                    'adopted_at': None, 'terminal_at': None,
                    'weight_version': 1}


def backfill(info: 'ReplicaInfo') -> 'ReplicaInfo':
    """THE one old-pickle upgrade point — every consumer of persisted
    ReplicaInfo rows (manager adoption, serve status) routes through
    this instead of scattering per-field getattr guards."""
    for field, default in _PICKLE_BACKFILL.items():
        if not hasattr(info, field):
            setattr(info, field, default)
    return info


_backfill = backfill


class ReplicaManager:
    """Reference: sky/serve/replica_managers.py:560."""

    def __init__(self, service_name: str, spec: 'spec_lib.ServiceSpec',
                 task_yaml: str, version: int = 1,
                 metrics_registry: Optional[
                     'metrics_lib.MetricsRegistry'] = None,
                 telemetry=None) -> None:
        self.service_name = service_name
        self.spec = spec
        self.task_yaml = task_yaml
        self.version = version
        # Fleet telemetry plane (serve/fleet.py): the prober's READY
        # visits double as throttled /metrics scrapes. Optional — the
        # manager works identically without it.
        self._telemetry = telemetry
        reg = metrics_registry or metrics_lib.REGISTRY
        self._m_launches = reg.counter(
            'skyt_serve_replica_launches_total', 'Replica launches',
            ('service',))
        # Per-service only: replica ids grow monotonically over churn
        # and counter children are never evicted, so a replica_id label
        # would leak memory on long-lived spot services. Per-replica
        # detail lives in replica status / logs.
        self._m_probe_failures = reg.counter(
            'skyt_serve_probe_failures_total',
            'Failed readiness probes', ('service',))
        self._m_replicas = reg.gauge(
            'skyt_serve_replicas', 'Replicas by lifecycle status',
            ('service', 'status'))
        self._m_drains = reg.counter(
            'skyt_serve_replica_drains_total',
            'READY replicas retired through the drain grace period',
            ('service',))
        self._m_adoptions = reg.counter(
            'skyt_serve_replica_adoptions_total',
            'Persisted replicas re-adopted (not relaunched) by a '
            'restarting controller', ('service',))
        self._m_reaps = reg.counter(
            'skyt_serve_replica_reaps_total',
            'Persisted replicas reaped as orphans by a restarting '
            'controller', ('service', 'reason'))
        # Rolling in-place weight updates (docs/robustness.md
        # "Zero-downtime rollouts").
        self._m_rollout_state = reg.gauge(
            'skyt_serve_rollout_state',
            'Rolling weight update state (1 on the current phase, 0 '
            'elsewhere)', ('service', 'phase'))
        self._m_rollout_swaps = reg.counter(
            'skyt_serve_rollout_swaps_total',
            'Per-replica /admin/weights calls made by the rollout '
            'orchestrator, by result', ('service', 'result'))
        self._m_rollouts = reg.counter(
            'skyt_serve_rollouts_total',
            'Rolling weight updates finished, by outcome',
            ('service', 'outcome'))
        # Elastic capacity plane (docs/serving.md "Elastic capacity"):
        # cold-start attribution (scale-to-zero wakes vs ordinary
        # scale-ups), KV pre-warm pushes, and reshard orchestration.
        self._m_cold_starts = reg.counter(
            'skyt_serve_cold_starts_total',
            'Replicas that reached first-READY, by cold-start kind '
            '(wake_from_zero = no other replica was READY)',
            ('service', 'kind'))
        self._m_cold_start_s = reg.counter(
            'skyt_serve_cold_start_seconds_total',
            'Total launch->first-READY seconds, the chip-seconds '
            'ledger\'s cold-start attribution input', ('service',))
        self._m_prewarms = reg.counter(
            'skyt_serve_prewarms_total',
            'KV pre-warm pushes to newly READY replicas, by result',
            ('service', 'result'))
        self._m_reshard_calls = reg.counter(
            'skyt_serve_reshard_calls_total',
            'Per-replica /admin/reshard calls made by the reshard '
            'orchestrator, by result', ('service', 'result'))
        self._m_reshards = reg.counter(
            'skyt_serve_reshards_total',
            'Elastic reshards finished, by outcome',
            ('service', 'outcome'))
        self._m_reshard_state = reg.gauge(
            'skyt_serve_reshard_state',
            'Elastic reshard state (1 on the current phase, 0 '
            'elsewhere)', ('service', 'phase'))
        # Adapter fleet (docs/serving.md "Adapter fleet"): fleet-wide
        # adapter load/unload convergence, one replica per tick.
        self._m_adapter_calls = reg.counter(
            'skyt_serve_adapter_calls_total',
            'Per-replica /admin/adapters calls made by the adapter '
            'fleet orchestrator, by result', ('service', 'result'))
        self._m_adapter_updates = reg.counter(
            'skyt_serve_adapter_updates_total',
            'Fleet-wide adapter convergences finished, by outcome',
            ('service', 'outcome'))
        self._m_adapter_state = reg.gauge(
            'skyt_serve_adapter_state',
            'Fleet-wide adapter convergence state (1 on the current '
            'phase, 0 elsewhere)', ('service', 'phase'))
        # Relaunch backoff: repeated replica failures (probe-failure ->
        # FAILED -> reconcile relaunch) back off exponentially instead
        # of tight-looping launches against a broken image/config; any
        # replica reaching READY resets it.
        self._relaunch_backoff = 0.0
        self._next_launch_ok = 0.0
        self._probe_passes = -1
        # replica_id -> probe pass of the last /stats ATTEMPT: the
        # throttle must key on attempts, not on stats being None —
        # replicas without a /stats endpoint stay None forever and
        # would otherwise be re-fetched every pass.
        self._stats_attempt: Dict[int, int] = {}
        self.replicas: Dict[int, ReplicaInfo] = {
            info.replica_id: _backfill(info)
            for info in serve_state.get_replicas(service_name)}
        self._next_id = max(self.replicas, default=0) + 1
        self._threads: Dict[int, threading.Thread] = {}
        self._lock = threading.RLock()
        # Per-service bearer token: the replica admin API credential
        # (exported to replicas as SKYT_ADMIN_TOKEN at launch, carried
        # on the orchestrator's /admin/weights calls).
        svc = serve_state.get_service(service_name)
        self._admin_token: Optional[str] = \
            svc.get('auth_token') if svc else None
        # Injectable for tests: (info, payload) -> (ok, error | None).
        self._swap_fn = self._swap_replica_http
        self._reshard_fn = self._reshard_replica_http
        self._adapter_fn = self._adapter_replica_http
        # Injectable prewarm push: (info, peers) -> (ok, error | None).
        self._prewarm_fn = self._prewarm_replica_http
        # In-memory by design — see ReshardState.
        self._reshard: Optional[ReshardState] = None
        # In-memory by design — see AdapterState.
        self._adapter_update: Optional[AdapterState] = None
        # Restart-safe rollout state: loaded BEFORE restart adoption so
        # the orphan check can recognize versions a crashed rollout
        # legitimately left behind (composes with PR 7 adoption).
        self._rollout: Optional[RolloutState] = None
        raw = serve_state.get_rollout(service_name)
        if raw is not None:
            try:
                self._rollout = RolloutState.from_dict(raw)
            except TypeError:
                logger.warning('persisted rollout state unreadable; '
                               'ignoring: %r', raw)
        self._reconcile_restart()
        self._resume_rollout()

    # ------------------------------------------------- restart adoption
    def _reconcile_restart(self) -> None:
        """Reconcile persisted replicas after a controller restart —
        ADOPT, don't relaunch (docs/robustness.md "Control plane").

        Mid-launch rows (PROVISIONING/STARTING/SHUTTING_DOWN) follow
        the orphaned-launch-intent rules: a cluster that materialized
        is kept for the prober, one that never did is torn down so
        reconcile() relaunches the delta. Rows that were SERVING
        (READY/NOT_READY) get the full liveness check — recorded pid
        still the same process (runtime/reaper.pid_start_token guards
        reuse), spec version current, readiness probe answering — and
        are re-adopted into the manager with ZERO relaunches when it
        passes; true orphans (dead pid, failed probe, stale version,
        vanished cluster) are reaped, never adopted. Reference: the
        supervised process pool in sky/serve/replica_managers.py:
        940-1019 rediscovers launch processes the same way.
        """
        from skypilot_tpu import state as cluster_state
        serving = [info for info in self.replicas.values()
                   if info.status in (serve_state.ReplicaStatus.READY,
                                      serve_state.ReplicaStatus.NOT_READY)]
        if serving:
            # Concurrent adoption checks: each unreachable replica
            # costs up to retries × probe_timeout, and this runs
            # BEFORE the controller binds its sync port — serial
            # probing of N hung replicas would hold the whole control
            # plane down long enough to blow the LB's stale TTL.
            import concurrent.futures as futures
            with futures.ThreadPoolExecutor(
                    max_workers=min(8, len(serving))) as pool:
                list(pool.map(self._adopt_or_reap, serving))
        handled = {info.replica_id for info in serving}
        for info in list(self.replicas.values()):
            if info.replica_id in handled:
                continue  # adopted or already reaping (SHUTTING_DOWN)
            if info.status is serve_state.ReplicaStatus.PREEMPTED:
                # Detected-preempted row whose teardown thread died
                # with the old controller: finish the teardown.
                self._reap(info, 'preempted_pre_restart')
                continue
            if info.status is serve_state.ReplicaStatus.FAILED:
                # FAILED row still in the DB means the old controller
                # died between _save(FAILED) and the teardown finishing
                # — without this, the replica's cluster leaks forever
                # (and the prune sweep would later erase the only
                # record pointing at it).
                self._reap(info, 'failed_pre_restart')
                continue
            if info.status not in (serve_state.ReplicaStatus.PENDING,
                                   serve_state.ReplicaStatus.PROVISIONING,
                                   serve_state.ReplicaStatus.STARTING,
                                   serve_state.ReplicaStatus.SHUTTING_DOWN):
                continue
            record = cluster_state.get_cluster(info.cluster_name)
            if info.status is serve_state.ReplicaStatus.SHUTTING_DOWN or \
                    record is None:
                logger.info('recovering orphaned replica %d (%s, '
                            'cluster %s): terminating',
                            info.replica_id, info.status.value,
                            'present' if record else 'absent')
                info.status = serve_state.ReplicaStatus.SHUTTING_DOWN
                self._save(info)
                threading.Thread(target=self._terminate_thread,
                                 args=(info,), daemon=True).start()
            else:
                # Cluster exists: recompute the endpoint and let the
                # prober drive it to READY.
                try:
                    handle = record['handle']
                    head = handle.cluster_info.ordered()[0]
                    if info.endpoint is None:
                        info.endpoint = f'http://{head.get_feasible_ip()}:80'
                    info.status = serve_state.ReplicaStatus.STARTING
                    self._save(info)
                    logger.info('recovered replica %d (cluster alive)',
                                info.replica_id)
                except Exception:  # pylint: disable=broad-except
                    logger.warning('replica %d unrecoverable; dropping',
                                   info.replica_id)
                    threading.Thread(target=self._terminate_thread,
                                     args=(info,), daemon=True).start()

    def _orphan_reason(self, info: ReplicaInfo) -> Optional[str]:
        """Why a persisted serving replica canNOT be adopted (None =
        adoptable). Ordered cheapest-first; the HTTP probe runs last."""
        from skypilot_tpu import state as cluster_state
        from skypilot_tpu.runtime import reaper
        try:
            # Chaos hook: an injected error forces this row down the
            # reap path (tests/test_chaos_*.py, SKYT_FAULTS
            # replica.orphan=error[,where=replica:<id>]).
            faults.inject('replica.orphan', replica=info.replica_id)
        except faults.FaultError:
            return 'fault_injected'
        if info.version != self.version:
            # Mid-rollout crash windows legitimately leave replicas
            # one version AHEAD of the committed spec (the commit
            # orders replica rows before the spec row): a replica
            # whose version matches the recorded rollout's baseline
            # or target is part of that rollout, not an orphan —
            # reaping it would relaunch a healthy replica the resume
            # logic is about to reconcile.
            with self._lock:
                ro = self._rollout
            if not (ro is not None and
                    info.version in (ro.baseline_version,
                                     ro.target_version)):
                return 'stale_spec_version'
        if cluster_state.get_cluster(info.cluster_name) is None:
            return 'cluster_gone'
        if info.pid is not None:
            if not reaper.pid_alive(info.pid):
                return 'dead_pid'
            if info.pid_start is not None and \
                    reaper.pid_start_token(info.pid) != info.pid_start:
                return 'pid_reused'
        if info.endpoint is None:
            return 'probe_failed'
        # Retry the probe: a reap here tears down and relaunches, and
        # controller restarts correlate with replicas being under load
        # — a single timed-out probe must not cost a healthy replica
        # (the steady-state prober tolerates FAILED_THRESHOLD=10
        # consecutive failures for the same condition).
        attempts = env.get_int('SKYT_SERVE_ADOPT_PROBE_RETRIES', 3,
                               minimum=1)
        for i in range(attempts):
            if self._probe_one(info):
                return None
            if i + 1 < attempts:
                time.sleep(0.5)
        return 'probe_failed'

    def _adopt_or_reap(self, info: ReplicaInfo) -> None:
        reason = self._orphan_reason(info)
        if reason is None:
            info.status = serve_state.ReplicaStatus.READY
            info.consecutive_failures = 0
            info.adopted_at = time.time()
            self._save(info)
            self._m_adoptions.labels(self.service_name).inc()
            logger.info('adopted replica %d at %s (pid %s): READY, '
                        'no relaunch', info.replica_id, info.endpoint,
                        info.pid)
        else:
            self._reap(info, reason)

    def _reap(self, info: ReplicaInfo, reason: str) -> None:
        """Terminate + drop a persisted replica a restart could not
        adopt; reconcile() then launches the delta. Counted per reason
        so a chaos run can assert 'reaped, not adopted'."""
        logger.warning('reaping orphaned replica %d (%s): %s',
                       info.replica_id, info.status.value, reason)
        self._m_reaps.labels(self.service_name, reason).inc()
        info.status = serve_state.ReplicaStatus.SHUTTING_DOWN
        info.failure_reason = f'reaped on controller restart: {reason}'
        info.terminal_at = time.time()
        self._save(info)
        threading.Thread(target=self._terminate_thread,
                         args=(info,), daemon=True).start()

    # ------------------------------------------------------------ persist
    def _save(self, info: ReplicaInfo) -> None:
        serve_state.upsert_replica(self.service_name, info.replica_id,
                                   info)

    def _drop(self, info: ReplicaInfo) -> None:
        with self._lock:
            self.replicas.pop(info.replica_id, None)
        serve_state.remove_replica(self.service_name, info.replica_id)
        if self._telemetry is not None:
            # A torn-down replica leaves the fleet aggregates NOW
            # (the stale TTL would get it eventually; this is tidier).
            self._telemetry.drop_target(str(info.replica_id))

    # ------------------------------------------------------------- launch
    def _load_task(self):
        from skypilot_tpu import task as task_lib
        return task_lib.Task.from_yaml(self.task_yaml)

    def launch_replica(self, use_spot: Optional[bool] = None) -> int:
        with self._lock:
            rid = self._next_id
            self._next_id += 1
            info = ReplicaInfo(
                replica_id=rid,
                cluster_name=f'{self.service_name}-{rid}',
                version=self.version,
                status=serve_state.ReplicaStatus.PROVISIONING,
                use_spot=bool(use_spot),
                launched_at=time.time(),
                # The launch env exports the spec's CURRENT weights
                # (SKYT_WEIGHTS_CHECKPOINT), so the replica boots on
                # the committed version — not the task's original
                # checkpoint from version 1.
                weight_version=self.version)
            self.replicas[rid] = info
            self._save(info)
            self._m_launches.labels(self.service_name).inc()
            th = threading.Thread(target=self._launch_thread,
                                  args=(info,), daemon=True)
            self._threads[rid] = th
            th.start()
            return rid

    def _launch_thread(self, info: ReplicaInfo) -> None:
        from skypilot_tpu import execution
        try:
            task = self._load_task()
            port = self._replica_port(task)
            task.envs['SKYT_REPLICA_PORT'] = str(port)
            # Weight-rollout plumbing (docs/robustness.md "Zero-
            # downtime rollouts"): the service token doubles as the
            # replica admin-API credential, and the spec's CURRENT
            # weights checkpoint rides along so replicas launched
            # mid/post-rollout boot on what the fleet is serving.
            if self._admin_token and \
                    'SKYT_ADMIN_TOKEN' not in task.envs:
                task.envs['SKYT_ADMIN_TOKEN'] = self._admin_token
            weights = getattr(self.spec, 'weights', None)
            if weights and 'SKYT_WEIGHTS_CHECKPOINT' not in task.envs:
                task.envs['SKYT_WEIGHTS_CHECKPOINT'] = weights
            if info.use_spot:
                for res in task.resources:
                    res.use_spot = True  # spot overflow replicas
            # Chaos hook (docs/robustness.md fault catalog): 'latency'
            # stalls provisioning in THIS launch thread — the surge-
            # queue honesty drill's lever (parked requests must get a
            # bounded 503, not a hang); 'error' fails the launch into
            # the ordinary FAILED + relaunch-backoff path.
            faults.inject('scale.provision',
                          replica=info.replica_id,
                          service=self.service_name)
            execution.launch(task, cluster_name=info.cluster_name,
                             detach_run=True, stream_logs=False)
            record = cluster_state.get_cluster(info.cluster_name)
            assert record is not None
            handle = record['handle']
            head = handle.cluster_info.ordered()[0]
            ip = head.get_feasible_ip()
            info.endpoint = f'http://{ip}:{port}'
            info.pid, info.pid_start = self._liveness_identity(handle,
                                                               info)
            info.status = serve_state.ReplicaStatus.STARTING
            self._save(info)
            logger.info('replica %d up at %s', info.replica_id,
                        info.endpoint)
        except (exceptions.SkyTpuError, faults.FaultError) as e:
            logger.warning('replica %d launch failed: %s',
                           info.replica_id, e)
            info.status = serve_state.ReplicaStatus.FAILED
            info.failure_reason = str(e)
            info.terminal_at = time.time()
            self._save(info)
            self._note_replica_failed()

    def _liveness_identity(self, handle, info: ReplicaInfo
                           ) -> 'tuple[Optional[int], Optional[int]]':
        """(pid, start-token) of the replica's head process where the
        provider exposes one — the local provider's head agent. Cloud
        replicas return (None, None): their cluster record + readiness
        probe are the restart-adoption identity."""
        from skypilot_tpu.runtime import reaper
        try:
            if handle.provider_name == 'local':
                from skypilot_tpu.provision.local import instance as \
                    local_instance
                pid = local_instance.head_agent_pid(info.cluster_name)
                if pid is not None:
                    return pid, reaper.pid_start_token(pid)
        except Exception:  # pylint: disable=broad-except
            logger.warning('liveness identity unavailable for replica '
                           '%d', info.replica_id, exc_info=True)
        return None, None

    def _note_replica_failed(self) -> None:
        """Gate the next reconcile launch behind an exponential backoff
        (reset when any replica reaches READY): without it a replica
        that fails fast — bad image, bad checkpoint path — relaunches
        in a tight provision/fail loop."""
        base, cap = _relaunch_backoff_bounds()
        self._relaunch_backoff = min(
            max(self._relaunch_backoff * 2, base), cap)
        self._next_launch_ok = time.time() + self._relaunch_backoff
        logger.info('replica failure: relaunches gated for %.1fs',
                    self._relaunch_backoff)

    def _note_first_ready(self, info: ReplicaInfo) -> None:
        """Cold-start attribution + pre-warm push, fired exactly once
        per replica (its launch->first-READY transition). The seconds
        feed the chip-seconds ledger: capacity burned before the
        replica served its first token. kind='wake_from_zero' when no
        OTHER replica was READY at the moment this one arrived — the
        scale-to-zero wake the surge queue was bridging."""
        seconds = max(0.0, (info.first_ready_at or 0.0) -
                      info.launched_at)
        with self._lock:
            others = [r for r in self.replicas.values()
                      if r.replica_id != info.replica_id and
                      r.status is serve_state.ReplicaStatus.READY]
        kind = 'scale_up' if others else 'wake_from_zero'
        self._m_cold_starts.labels(self.service_name, kind).inc()
        self._m_cold_start_s.labels(self.service_name).inc(seconds)
        if self._telemetry is not None:
            try:
                self._telemetry.note_cold_start(kind, seconds)
            except AttributeError:
                pass   # older telemetry object (tests with stubs)
        logger.info('replica %d cold start: %.1fs (%s)',
                    info.replica_id, seconds, kind)
        # Proactive KV pre-warm (opt-in; docs/serving.md "Elastic
        # capacity"): ask the new replica to pull its rendezvous share
        # of the fleet's resident prefix pages from its peers, in a
        # daemon thread so the probe loop never blocks on it.
        # Best-effort by contract: a failed pre-warm costs prefix
        # recomputes, never readiness.
        if not env.get_bool('SKYT_SERVE_PREWARM', False):
            return
        peers = [r.endpoint for r in others if r.endpoint]
        if not peers or not info.endpoint:
            return

        def _push() -> None:
            ok, err = self._prewarm_fn(info, peers)
            self._m_prewarms.labels(self.service_name,
                                    'ok' if ok else 'error').inc()
            if not ok:
                logger.warning('replica %d kv prewarm failed: %s',
                               info.replica_id, err)

        threading.Thread(target=_push, daemon=True,
                         name=f'prewarm-{info.replica_id}').start()

    def _prewarm_replica_http(self, info: ReplicaInfo,
                              peers: List[str]
                              ) -> 'tuple[bool, Optional[str]]':
        """One POST /admin/kv_prewarm against a newly READY replica
        (the injectable default of self._prewarm_fn)."""
        if not info.endpoint:
            return False, 'replica has no endpoint'
        headers = {}
        if self._admin_token:
            headers['Authorization'] = f'Bearer {self._admin_token}'
        try:
            resp = requests.post(
                info.endpoint + '/admin/kv_prewarm',
                json={'self': info.endpoint, 'peers': peers},
                headers=headers,
                timeout=env.get_float('SKYT_PREWARM_TIMEOUT_S', 10.0))
            if resp.status_code == 200:
                return True, None
            try:
                msg = resp.json().get('error', '')
            except ValueError:
                msg = resp.text[:200]
            return False, f'HTTP {resp.status_code}: {msg}'
        except requests.RequestException as e:
            return False, str(e)

    def _replica_port(self, task) -> int:
        """Replica serving port: first task resources port, else (local
        clouds, where every replica shares 127.0.0.1) a fresh free one."""
        for res in task.resources:
            if res.ports:
                if res.cloud != 'local':
                    return int(res.ports[0])
        import socket
        with socket.socket() as s:
            s.bind(('127.0.0.1', 0))
            return s.getsockname()[1]

    # ---------------------------------------------------------- teardown
    def terminate_replica(self, rid: int, sync: bool = False,
                          drain: bool = False) -> None:
        """drain=True (deliberate retirement of a serving replica:
        scale-down, rolling update): the replica leaves the ready set
        NOW — the LB stops routing to it at its next controller sync —
        but teardown waits SKYT_SERVE_DRAIN_GRACE_S so in-flight
        requests finish instead of dying mid-stream. Failed/preempted
        replicas skip the grace (nothing useful is in flight)."""
        with self._lock:
            info = self.replicas.get(rid)
            if info is None:
                return
            drain = drain and \
                info.status is serve_state.ReplicaStatus.READY
            info.status = serve_state.ReplicaStatus.SHUTTING_DOWN
            self._save(info)
        if drain:
            self._m_drains.labels(self.service_name).inc()
        th = threading.Thread(target=self._terminate_thread,
                              args=(info, drain), daemon=True)
        th.start()
        if sync:
            th.join(timeout=60)

    def _terminate_thread(self, info: ReplicaInfo,
                          drain: bool = False) -> None:
        from skypilot_tpu import core
        if drain:
            grace = _drain_grace_seconds()
            logger.info('replica %d draining for %.1fs before teardown',
                        info.replica_id, grace)
            time.sleep(grace)
        try:
            core.down(info.cluster_name, purge=True)
        except exceptions.ClusterDoesNotExist:
            pass
        except exceptions.SkyTpuError as e:
            logger.warning('teardown of replica %d failed: %s',
                           info.replica_id, e)
        self._drop(info)

    def terminate_all(self) -> None:
        with self._lock:
            rids = [r for r in self.replicas]
        threads = []
        for rid in rids:
            info = self.replicas.get(rid)
            if info is None:
                continue
            info.status = serve_state.ReplicaStatus.SHUTTING_DOWN
            self._save(info)
            th = threading.Thread(target=self._terminate_thread,
                                  args=(info,), daemon=True)
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=120)

    # ------------------------------------------------------------- probe
    def _probe_one(self, info: ReplicaInfo) -> bool:
        url = info.endpoint + self.spec.readiness_path
        try:
            # Chaos hook: an injected error here is a failed probe
            # (drives NOT_READY/FAILED transitions deterministically).
            faults.inject('serve.probe', replica=info.replica_id)
        except faults.FaultError:
            return False
        try:
            if self.spec.post_data is not None:
                resp = requests.post(
                    url, json=self.spec.post_data,
                    timeout=self.spec.probe_timeout_seconds)
            else:
                resp = requests.get(
                    url, timeout=self.spec.probe_timeout_seconds)
            return resp.status_code == 200
        except requests.RequestException:
            return False

    # 'qos' is the replica's QoS pressure block (overload level,
    # per-class queue depths) — forwarded to the LB via the sync
    # response so replica picking can steer shed-prone classes away.
    # 'prefix_cache' carries the replica's prefix-cache occupancy —
    # the LB surfaces it as skyt_lb_replica_prefix_cache{replica},
    # groundwork for cache-affinity routing (ROADMAP item 2).
    # 'adapters' is the replica's loaded-adapter map (name -> id/
    # version) — synced to the LB so model-named requests route only
    # to replicas hosting the adapter.
    _STATS_KEYS = ('ttft_ms', 'steady_decode_tok_per_sec',
                   'active_slots', 'num_slots', 'waiting', 'qos',
                   'prefix_cache', 'adapters')
    # Scrape /stats only every Kth probe pass: the scrape is a serial
    # blocking GET per READY replica inside the controller's one
    # control thread, and the data is only read by `serve status` and
    # the LB's QoS pressure steering (best-effort, staleness-tolerant).
    _STATS_EVERY = 5

    def _fetch_stats(self, info: ReplicaInfo) -> Optional[dict]:
        """Best-effort /stats scrape from a READY replica (the engine
        server exposes it; arbitrary user services 404 -> None or may
        answer with any shape -> consumers must not trust types)."""
        try:
            resp = requests.get(info.endpoint + '/stats', timeout=2)
            if resp.status_code != 200:
                return None
            data = resp.json()
            if not isinstance(data, dict):
                return None
            return {k: data[k] for k in self._STATS_KEYS if k in data}
        except (requests.RequestException, ValueError):
            return None

    def _update_replica_gauges(self) -> None:
        """Per-status replica gauge — set EVERY known status each pass
        so counts drop back to 0 when replicas leave a state (a labeled
        gauge never forgets a child on its own)."""
        with self._lock:
            counts = {s: 0 for s in serve_state.ReplicaStatus}
            for info in self.replicas.values():
                counts[info.status] += 1
        for status, n in counts.items():
            self._m_replicas.labels(self.service_name,
                                    status.value).set(n)

    def probe_all(self) -> None:
        """One probe pass (reference: _replica_prober :1019 + parallel
        probes :497-543)."""
        self._probe_passes += 1
        for info in list(self.replicas.values()):
            if info.status not in (serve_state.ReplicaStatus.STARTING,
                                   serve_state.ReplicaStatus.READY,
                                   serve_state.ReplicaStatus.NOT_READY):
                continue
            # Preemption first: a deleted cluster can still answer DNS.
            if cluster_state.get_cluster(info.cluster_name) is None:
                logger.info('replica %d cluster gone -> PREEMPTED',
                            info.replica_id)
                info.status = serve_state.ReplicaStatus.PREEMPTED
                info.terminal_at = time.time()
                self._save(info)
                self.terminate_replica(info.replica_id)
                continue
            ok = self._probe_one(info)
            if ok:
                if info.first_ready_at is None:
                    info.first_ready_at = time.time()
                    self._note_first_ready(info)
                info.consecutive_failures = 0
                # A healthy replica proves the config launches: clear
                # the relaunch backoff gate.
                self._relaunch_backoff = 0.0
                self._next_launch_ok = 0.0
                if info.status is not serve_state.ReplicaStatus.READY:
                    logger.info('replica %d READY', info.replica_id)
                info.status = serve_state.ReplicaStatus.READY
                last = self._stats_attempt.get(info.replica_id,
                                               -self._STATS_EVERY)
                if self._probe_passes - last >= self._STATS_EVERY:
                    self._stats_attempt[info.replica_id] = \
                        self._probe_passes
                    info.stats = self._fetch_stats(info)
                if self._telemetry is not None and info.endpoint:
                    # Fleet scrape rides the probe visit: throttled
                    # (SKYT_FLEET_SCRAPE_S) and no-raise by contract —
                    # a failing scrape counts an error and ages out,
                    # never blocks this loop (telemetry.scrape fault
                    # point; docs/observability.md "Fleet plane").
                    self._telemetry.maybe_scrape(
                        str(info.replica_id), info.endpoint)
                self._save(info)
                continue
            info.consecutive_failures += 1
            self._m_probe_failures.labels(self.service_name).inc()
            # Stale perf numbers beside a failing replica mislead
            # incident triage.
            info.stats = None
            if info.status is serve_state.ReplicaStatus.STARTING:
                if time.time() - info.launched_at > \
                        self.spec.initial_delay_seconds:
                    info.status = serve_state.ReplicaStatus.FAILED
                    info.failure_reason = (
                        f'not ready within initial_delay_seconds='
                        f'{self.spec.initial_delay_seconds}')
                    info.terminal_at = time.time()
                    self._save(info)
                    self.terminate_replica(info.replica_id)
                    self._note_replica_failed()
            elif info.consecutive_failures >= FAILED_THRESHOLD:
                info.status = serve_state.ReplicaStatus.FAILED
                info.failure_reason = 'readiness probe kept failing'
                info.terminal_at = time.time()
                self._save(info)
                self.terminate_replica(info.replica_id)
                self._note_replica_failed()
            elif info.consecutive_failures >= NOT_READY_THRESHOLD:
                info.status = serve_state.ReplicaStatus.NOT_READY
                self._save(info)
            else:
                self._save(info)
        self._update_replica_gauges()

    # ---------------------------------------------------------- reconcile
    def reconcile(self, target: int, ondemand_base: int = 0) -> None:
        """Drive alive-replica count to `target`; retire old versions once
        enough new-version replicas are READY (rolling update,
        reference: versioned updates in SkyPilotReplicaManager)."""
        with self._lock:
            alive = [r for r in self.replicas.values() if r.is_alive]
            cur_version = [r for r in alive if r.version == self.version]
            old_version = [r for r in alive if r.version != self.version]

            # Rolling update: bring up new-version replicas to `target`,
            # and keep enough old replicas alive that READY(new) + old
            # never drops below target — retire only the surplus.
            # Repeated-failure backoff gate: skip this pass's launches
            # (reconcile runs again shortly) instead of relaunching a
            # failing config in a tight loop.
            may_launch = time.time() >= self._next_launch_ok
            if old_version:
                new_ready = sum(
                    1 for r in cur_version
                    if r.status is serve_state.ReplicaStatus.READY)
                if len(cur_version) < target and may_launch:
                    for _ in range(target - len(cur_version)):
                        self.launch_replica()
                n_keep_old = max(0, target - new_ready)
                # Keep READY old replicas (serving capacity) and retire
                # NOT_READY/STARTING ones first.
                old_version.sort(
                    key=lambda r: r.status is not
                    serve_state.ReplicaStatus.READY)
                for info in old_version[n_keep_old:]:
                    # Rolling-update retirement is deliberate: drain.
                    self.terminate_replica(info.replica_id, drain=True)
                return

            n_alive = len(cur_version)
            if n_alive < target and may_launch:
                # ondemand base first, spot for overflow (fallback
                # autoscaler semantics).
                n_ondemand = sum(1 for r in cur_version if not r.use_spot)
                for _ in range(target - n_alive):
                    use_spot = (ondemand_base > 0 and
                                n_ondemand >= ondemand_base)
                    self.launch_replica(use_spot=use_spot)
                    if not use_spot:
                        n_ondemand += 1
            elif len(cur_version) > target:
                # Scale down: prefer NOT_READY/STARTING, then newest.
                order = sorted(
                    cur_version,
                    key=lambda r: (r.status is
                                   serve_state.ReplicaStatus.READY,
                                   -r.replica_id))
                for info in order[:len(cur_version) - target]:
                    # Scale-down retirement is deliberate: drain.
                    self.terminate_replica(info.replica_id, drain=True)

    def update_version(self, spec: 'spec_lib.ServiceSpec',
                       task_yaml: str, version: int) -> None:
        self.spec = spec
        self.task_yaml = task_yaml
        self.version = version

    # ------------------------------------- rolling in-place weight update
    def start_rolling_update(self, spec: 'spec_lib.ServiceSpec',
                             task_yaml: str, version: int) -> dict:
        """Begin a canaried in-place weight rollout to `spec.weights`
        (docs/robustness.md "Zero-downtime rollouts"). The spec/
        version commit is DEFERRED to rollout completion — until then
        every replica keeps its baseline spec version, so a controller
        crash at any point restarts into a consistent adoption view.
        Raises if a rollout is already active."""
        assert spec.weights, 'rolling update requires spec.weights'
        with self._lock:
            if self._rollout is not None and self._rollout.active:
                raise exceptions.SkyTpuError(
                    f'a rolling update to version '
                    f'{self._rollout.target_version} is already in '
                    f'progress (phase {self._rollout.phase})')
            if self._reshard is not None and self._reshard.active:
                raise exceptions.SkyTpuError(
                    f'an elastic reshard is in progress (phase '
                    f'{self._reshard.phase}); roll out after it '
                    f'finishes')
            if self._adapter_update is not None and \
                    self._adapter_update.active:
                raise exceptions.SkyTpuError(
                    f'an adapter fleet update is in progress (phase '
                    f'{self._adapter_update.phase}); roll out after '
                    f'it finishes')
            self._rollout = RolloutState(
                phase='canary',
                target_version=int(version),
                baseline_version=self.version,
                checkpoint=spec.weights,
                baseline_checkpoint=getattr(self.spec, 'weights',
                                            None),
                spec_config=spec.to_yaml_config(),
                task_yaml=task_yaml,
                started_at=time.time())
        self._save_rollout()
        logger.info('rolling update started: v%d -> v%d (weights %s)',
                    self.version, version, spec.weights)
        return self.rollout_status()

    def _resume_rollout(self) -> None:
        """Recover a rollout a dead controller left behind: 'rollout'
        and 'rollback' phases resume exactly where they stopped (the
        updated-set is persisted per transition); 'canary'/'bake'
        conservatively roll back — the bake-window observations died
        with the old process, and re-baking a canary nobody watched is
        how bad weights reach a fleet."""
        with self._lock:
            ro = self._rollout
        if ro is None or not ro.active:
            return
        if ro.phase in ('canary', 'bake'):
            ro.error = (f'controller restarted during {ro.phase}; '
                        f'rolling back')
            ro.phase = 'rollback'
            logger.warning('resumed rollout v%d: %s',
                           ro.target_version, ro.error)
        else:
            logger.info('resumed rollout v%d in phase %s '
                        '(%d replica(s) updated)', ro.target_version,
                        ro.phase, len(ro.updated))
        self._save_rollout()

    def _save_rollout(self) -> None:
        with self._lock:
            ro = self._rollout
        serve_state.set_rollout(self.service_name,
                                ro.to_dict() if ro is not None
                                else None)
        for phase in ROLLOUT_PHASES:
            self._m_rollout_state.labels(self.service_name, phase).set(
                1 if (ro is not None and ro.phase == phase) else 0)

    def rollout_status(self) -> Optional[dict]:
        with self._lock:
            ro = self._rollout
        if ro is None:
            return None
        out = ro.to_dict()
        out.pop('spec_config', None)   # bulky; not a status surface
        return out

    def _swap_replica_http(self, info: ReplicaInfo,
                           payload: dict) -> 'tuple[bool, Optional[str]]':
        """One POST /admin/weights against a replica (the injectable
        default of self._swap_fn)."""
        if not info.endpoint:
            return False, 'replica has no endpoint'
        headers = {}
        if self._admin_token:
            headers['Authorization'] = f'Bearer {self._admin_token}'
        try:
            resp = requests.post(
                info.endpoint + '/admin/weights', json=payload,
                headers=headers,
                timeout=env.get_float('SKYT_ROLLOUT_SWAP_TIMEOUT_S',
                                      180.0))
            if resp.status_code == 200:
                return True, None
            try:
                msg = resp.json().get('error', '')
            except ValueError:
                msg = resp.text[:200]
            return False, f'HTTP {resp.status_code}: {msg}'
        except requests.RequestException as e:
            return False, str(e)

    def _rollout_candidates(self, ro: RolloutState) -> List[ReplicaInfo]:
        """READY replicas not yet swapped, lowest id first (stable
        canary choice)."""
        with self._lock:
            return sorted(
                (r for r in self.replicas.values()
                 if r.status is serve_state.ReplicaStatus.READY and
                 r.endpoint and r.replica_id not in ro.updated),
                key=lambda r: r.replica_id)

    def _rollout_unhealthy(self, ro: RolloutState) -> Optional[str]:
        """Why the bake looks bad (None = healthy): the canary must
        still be READY, and the PR 8 SLO plane must not be burning
        error budget anywhere in the fleet."""
        if ro.canary is not None:
            info = self.replicas.get(ro.canary)
            if info is None or \
                    info.status is not serve_state.ReplicaStatus.READY:
                return (f'canary replica {ro.canary} left READY '
                        f'({info.status.value if info else "gone"})')
        if self._telemetry is not None:
            firing = self._telemetry.alerts_firing()
            if firing:
                return ('SLO burn-rate alert firing for class(es) '
                        + ', '.join(firing))
        return None

    def _swap_one(self, ro: RolloutState, info: ReplicaInfo) -> bool:
        """Swap one replica to the target weights; True on success."""
        ok, err = self._swap_fn(info, {'checkpoint': ro.checkpoint,
                                       'version': ro.target_version})
        if ok:
            self._m_rollout_swaps.labels(self.service_name, 'ok').inc()
            ro.updated.append(info.replica_id)
            ro.fails = 0
            info.weight_version = ro.target_version
            self._save(info)
            logger.info('rollout v%d: replica %d swapped in place',
                        ro.target_version, info.replica_id)
            return True
        self._m_rollout_swaps.labels(self.service_name, 'error').inc()
        ro.fails += 1
        ro.error = f'replica {info.replica_id} swap failed: {err}'
        logger.warning('rollout v%d: %s (consecutive fails: %d)',
                       ro.target_version, ro.error, ro.fails)
        return False

    def rollout_tick(self) -> None:
        """One state-machine step of the active rollout — called from
        the controller's control loop each pass, persisted after every
        transition (restart-safe). Phases: canary (swap one replica)
        -> bake (watch SLO burn + canary health for
        SKYT_ROLLOUT_BAKE_S) -> rollout (one replica per tick) ->
        done; any failure or unhealthy bake -> rollback (swap back
        every updated replica, newest first) -> rolled_back."""
        with self._lock:
            ro = self._rollout
        if ro is None or not ro.active:
            return
        before = (ro.phase, list(ro.updated), ro.fails, ro.error)
        if ro.phase == 'canary':
            self._tick_canary(ro)
        elif ro.phase == 'bake':
            self._tick_bake(ro)
        elif ro.phase == 'rollout':
            self._tick_rollout(ro)
        elif ro.phase == 'rollback':
            self._tick_rollback(ro)
        # Persist on ANY field delta — fails/error included, so a
        # controller crash mid-retry resumes with the true
        # consecutive-failure count instead of re-granting the full
        # SKYT_ROLLOUT_RETRIES budget to a wedged replica.
        if (ro.phase, ro.updated, ro.fails, ro.error) != before:
            self._save_rollout()

    def _tick_canary(self, ro: RolloutState) -> None:
        cand = self._rollout_candidates(ro)
        if not cand:
            return          # nothing READY yet; try next tick
        info = cand[0]
        ro.canary = info.replica_id
        if self._swap_one(ro, info):
            ro.bake_until = time.time() + _rollout_bake_s()
            ro.phase = 'bake'
            logger.info('rollout v%d: canary %d baking for %.0fs',
                        ro.target_version, info.replica_id,
                        _rollout_bake_s())
        else:
            # The canary is THE blast-radius bound: any failure —
            # validation reject, injected weights.swap fault, timeout
            # — aborts the whole rollout before a second replica is
            # touched.
            ro.phase = 'rollback'

    def _tick_bake(self, ro: RolloutState) -> None:
        bad = self._rollout_unhealthy(ro)
        if bad is not None:
            ro.error = f'bake failed: {bad}'
            logger.warning('rollout v%d: %s -> rolling back',
                           ro.target_version, ro.error)
            ro.phase = 'rollback'
            return
        if time.time() >= ro.bake_until:
            ro.phase = 'rollout'
            logger.info('rollout v%d: bake clean; proceeding '
                        'fleet-wide', ro.target_version)

    def _tick_rollout(self, ro: RolloutState) -> None:
        bad = self._rollout_unhealthy(ro)
        if bad is not None:
            ro.error = f'rollout halted: {bad}'
            logger.warning('rollout v%d: %s -> rolling back',
                           ro.target_version, ro.error)
            ro.phase = 'rollback'
            return
        cand = self._rollout_candidates(ro)
        if cand:
            # One replica per tick: capacity dips by at most one
            # swap's drain at a time, and every tick re-reads health.
            if not self._swap_one(ro, cand[0]) and \
                    ro.fails >= _rollout_retries():
                ro.phase = 'rollback'
            return
        # No READY stragglers: wait for any replica still coming up
        # (it will boot on the baseline weights and get swapped here),
        # commit once the whole alive fleet is on the target.
        with self._lock:
            pending = [r for r in self.replicas.values()
                       if r.is_alive and
                       r.replica_id not in ro.updated]
        if pending:
            return
        self._commit_rollout(ro)

    def _commit_rollout(self, ro: RolloutState) -> None:
        """Every alive replica serves the target weights: make the new
        spec/version durable. Ordering matters for crash windows:
        replica rows first, then the spec row, then the terminal
        rollout phase — at every intermediate point a restarting
        controller adopts (the orphan check recognizes the rollout's
        baseline/target versions) and the resumed 'rollout' phase
        re-runs this commit idempotently."""
        new_spec = spec_lib.ServiceSpec.from_yaml_config(
            dict(ro.spec_config))
        with self._lock:
            for info in self.replicas.values():
                if info.is_alive:
                    info.version = ro.target_version
                    info.weight_version = ro.target_version
                    self._save(info)
        serve_state.set_service_spec(self.service_name, new_spec,
                                     ro.task_yaml, ro.target_version)
        self.update_version(new_spec, ro.task_yaml, ro.target_version)
        ro.phase = 'done'
        self._m_rollouts.labels(self.service_name, 'done').inc()
        logger.info('rollout v%d: committed — fleet on %s with zero '
                    'relaunches', ro.target_version, ro.checkpoint)

    def _tick_rollback(self, ro: RolloutState) -> None:
        """Swap every updated replica back to the baseline weights,
        newest first (the canary — most likely already degraded — goes
        last-in-first-out). A replica that refuses to swap back after
        SKYT_ROLLOUT_RETRIES attempts is drained and relaunched: the
        spec was never committed, so reconcile brings it back on the
        baseline."""
        while ro.updated:
            rid = ro.updated[-1]
            info = self.replicas.get(rid)
            if info is None or not info.is_alive:
                ro.updated.pop()   # gone; nothing to roll back
                continue
            ok, err = self._swap_fn(info, {'swap_back': True})
            if ok:
                self._m_rollout_swaps.labels(self.service_name,
                                             'rollback_ok').inc()
                ro.updated.pop()
                ro.fails = 0
                info.weight_version = ro.baseline_version
                self._save(info)
                logger.info('rollout v%d: replica %d rolled back',
                            ro.target_version, rid)
                continue
            self._m_rollout_swaps.labels(self.service_name,
                                         'rollback_error').inc()
            ro.fails += 1
            logger.warning('rollout v%d: replica %d swap-back failed '
                           '(%d/%d): %s', ro.target_version, rid,
                           ro.fails, _rollout_retries(), err)
            if ro.fails >= _rollout_retries():
                # Last resort: relaunch puts it back on the baseline
                # (spec never committed). Still zero impact on the
                # replicas that rolled back in place.
                logger.warning('rollout v%d: draining replica %d for '
                               'relaunch on the baseline', ro.target_version, rid)
                self.terminate_replica(rid, drain=True)
                ro.updated.pop()
                ro.fails = 0
            return   # failed attempt: retry/escalate next tick
        ro.phase = 'rolled_back'
        self._m_rollouts.labels(self.service_name,
                                'rolled_back').inc()
        logger.warning('rollout v%d: rolled back fleet-wide (%s); '
                       'serving baseline v%d', ro.target_version,
                       ro.error or 'unspecified failure',
                       ro.baseline_version)

    # ---------------------------------------- in-place elastic reshard
    def start_reshard(self, virtual_nodes: int) -> dict:
        """Begin flipping every READY replica's virtual-node layout to
        `virtual_nodes`, one replica per control tick (docs/
        robustness.md "Elastic capacity"). Refuses while a rollout OR
        another reshard is active — both ride the replicas' single-
        flight swap slot, and interleaving them would make 409s
        ambiguous. Raises SkyTpuError on conflict or a bad target."""
        try:
            target = int(virtual_nodes)
        except (TypeError, ValueError):
            raise exceptions.SkyTpuError(
                f'virtual_nodes must be an integer, got '
                f'{virtual_nodes!r}')
        if target < 1:
            raise exceptions.SkyTpuError(
                f'virtual_nodes must be >= 1, got {target}')
        with self._lock:
            if self._rollout is not None and self._rollout.active:
                raise exceptions.SkyTpuError(
                    f'a rolling update is in progress (phase '
                    f'{self._rollout.phase}); reshard after it '
                    f'finishes')
            if self._reshard is not None and self._reshard.active:
                raise exceptions.SkyTpuError(
                    f'a reshard to {self._reshard.target_nodes} '
                    f'virtual nodes is already in progress (phase '
                    f'{self._reshard.phase})')
            if self._adapter_update is not None and \
                    self._adapter_update.active:
                raise exceptions.SkyTpuError(
                    f'an adapter fleet update is in progress (phase '
                    f'{self._adapter_update.phase}); reshard after '
                    f'it finishes')
            self._reshard = ReshardState(target_nodes=target)
        self._update_reshard_gauge()
        logger.info('reshard started: -> %d virtual nodes', target)
        return self.reshard_status()

    def reshard_status(self) -> Optional[dict]:
        with self._lock:
            rs = self._reshard
        return rs.to_dict() if rs is not None else None

    def _update_reshard_gauge(self) -> None:
        with self._lock:
            rs = self._reshard
        for phase in RESHARD_PHASES:
            self._m_reshard_state.labels(self.service_name, phase).set(
                1 if (rs is not None and rs.phase == phase) else 0)

    def _reshard_replica_http(self, info: ReplicaInfo,
                              payload: dict
                              ) -> 'tuple[bool, Optional[str]]':
        """One POST /admin/reshard against a replica (the injectable
        default of self._reshard_fn)."""
        if not info.endpoint:
            return False, 'replica has no endpoint'
        headers = {}
        if self._admin_token:
            headers['Authorization'] = f'Bearer {self._admin_token}'
        try:
            resp = requests.post(
                info.endpoint + '/admin/reshard', json=payload,
                headers=headers,
                timeout=env.get_float('SKYT_ROLLOUT_SWAP_TIMEOUT_S',
                                      180.0))
            if resp.status_code == 200:
                return True, None
            try:
                msg = resp.json().get('error', '')
            except ValueError:
                msg = resp.text[:200]
            return False, f'HTTP {resp.status_code}: {msg}'
        except requests.RequestException as e:
            return False, str(e)

    def _reshard_candidates(self, rs: ReshardState) -> List[ReplicaInfo]:
        with self._lock:
            return sorted(
                (r for r in self.replicas.values()
                 if r.status is serve_state.ReplicaStatus.READY and
                 r.endpoint and r.replica_id not in rs.updated),
                key=lambda r: r.replica_id)

    def reshard_tick(self) -> None:
        """One state-machine step of the active reshard — called from
        the control loop beside rollout_tick. One replica per tick so
        capacity dips by at most one tick-boundary apply at a time;
        repeated failures roll the already-resharded set back (newest
        first). Covers the replicas READY during the window: a replica
        still STARTING boots on the default layout — the layout is a
        performance knob, so a partially-covered fleet is degraded
        throughput, never an outage."""
        with self._lock:
            rs = self._reshard
        if rs is None or not rs.active:
            return
        before = rs.phase
        if rs.phase == 'reshard':
            self._tick_reshard(rs)
        elif rs.phase == 'rollback':
            self._tick_reshard_rollback(rs)
        if rs.phase != before:
            self._update_reshard_gauge()

    def _tick_reshard(self, rs: ReshardState) -> None:
        cand = self._reshard_candidates(rs)
        if not cand:
            rs.phase = 'done'
            self._m_reshards.labels(self.service_name, 'done').inc()
            logger.info('reshard done: %d replica(s) on %d virtual '
                        'nodes', len(rs.updated), rs.target_nodes)
            return
        info = cand[0]
        ok, err = self._reshard_fn(
            info, {'virtual_nodes': rs.target_nodes})
        if ok:
            self._m_reshard_calls.labels(self.service_name,
                                         'ok').inc()
            rs.updated.append(info.replica_id)
            rs.fails = 0
            logger.info('reshard: replica %d on %d virtual nodes',
                        info.replica_id, rs.target_nodes)
            return
        self._m_reshard_calls.labels(self.service_name, 'error').inc()
        rs.fails += 1
        rs.error = f'replica {info.replica_id} reshard failed: {err}'
        logger.warning('reshard: %s (consecutive fails: %d)',
                       rs.error, rs.fails)
        if rs.fails >= _rollout_retries():
            rs.phase = 'rollback'

    def _tick_reshard_rollback(self, rs: ReshardState) -> None:
        """Reshard every updated replica back to its previous layout,
        newest first. A replica that refuses after the retry budget is
        SKIPPED, not drained — a wrong layout is degraded throughput,
        and relaunching a serving replica over it would turn a perf
        hiccup into a capacity dip."""
        while rs.updated:
            rid = rs.updated[-1]
            info = self.replicas.get(rid)
            if info is None or not info.is_alive:
                rs.updated.pop()   # gone; nothing to roll back
                continue
            ok, err = self._reshard_fn(info, {'reshard_back': True})
            if ok:
                self._m_reshard_calls.labels(self.service_name,
                                             'rollback_ok').inc()
                rs.updated.pop()
                rs.fails = 0
                logger.info('reshard: replica %d rolled back', rid)
                continue
            self._m_reshard_calls.labels(self.service_name,
                                         'rollback_error').inc()
            rs.fails += 1
            logger.warning('reshard: replica %d rollback failed '
                           '(%d/%d): %s', rid, rs.fails,
                           _rollout_retries(), err)
            if rs.fails >= _rollout_retries():
                logger.warning('reshard: skipping replica %d (layout '
                               'left as-is)', rid)
                rs.updated.pop()
                rs.fails = 0
            return   # failed attempt: retry/escalate next tick
        rs.phase = 'rolled_back'
        self._m_reshards.labels(self.service_name,
                                'rolled_back').inc()
        logger.warning('reshard to %d virtual nodes rolled back (%s)',
                       rs.target_nodes, rs.error or
                       'unspecified failure')

    # ------------------------------------- fleet-wide adapter updates
    def start_adapter_update(self, op: str, name: str,
                             checkpoint: Optional[str] = None,
                             alpha: float = 16.0,
                             drain: Optional[bool] = None) -> dict:
        """Begin converging one adapter load/unload across every READY
        replica, one per control tick (docs/serving.md "Adapter
        fleet"). Refuses while a rollout, reshard, or another adapter
        update is active — all three ride the replicas' single-flight
        swap slot. Raises SkyTpuError on conflict or a bad request."""
        if op not in ('load', 'unload'):
            raise exceptions.SkyTpuError(
                f"op must be 'load' or 'unload', got {op!r}")
        if not isinstance(name, str) or not name:
            raise exceptions.SkyTpuError(
                f'name must be a non-empty string, got {name!r}')
        if op == 'load' and (not isinstance(checkpoint, str)
                             or not checkpoint):
            raise exceptions.SkyTpuError(
                f'load requires a checkpoint dir, got {checkpoint!r}')
        with self._lock:
            if self._rollout is not None and self._rollout.active:
                raise exceptions.SkyTpuError(
                    f'a rolling update is in progress (phase '
                    f'{self._rollout.phase}); update adapters after '
                    f'it finishes')
            if self._reshard is not None and self._reshard.active:
                raise exceptions.SkyTpuError(
                    f'an elastic reshard is in progress (phase '
                    f'{self._reshard.phase}); update adapters after '
                    f'it finishes')
            if self._adapter_update is not None and \
                    self._adapter_update.active:
                au = self._adapter_update
                raise exceptions.SkyTpuError(
                    f'an adapter fleet update ({au.op} {au.name!r}) '
                    f'is already in progress (phase {au.phase})')
            if op == 'unload' and checkpoint is None:
                # Best-effort rollback recipe: the checkpoint recorded
                # in any READY replica's /stats adapters block.
                for r in self.replicas.values():
                    block = self._replica_adapter_block(r)
                    meta = (block or {}).get('adapters', {}).get(name)
                    if isinstance(meta, dict) and meta.get('path'):
                        checkpoint = meta['path']
                        if meta.get('alpha') is not None:
                            alpha = float(meta['alpha'])
                        break
            self._adapter_update = AdapterState(
                op=op, name=name, checkpoint=checkpoint,
                alpha=float(alpha), drain=drain)
        self._update_adapter_gauge()
        logger.info('adapter fleet update started: %s %r%s', op, name,
                    f' from {checkpoint}' if op == 'load' else '')
        return self.adapter_update_status()

    def adapter_update_status(self) -> Optional[dict]:
        with self._lock:
            au = self._adapter_update
        return au.to_dict() if au is not None else None

    def _update_adapter_gauge(self) -> None:
        with self._lock:
            au = self._adapter_update
        for phase in ADAPTER_PHASES:
            self._m_adapter_state.labels(self.service_name, phase).set(
                1 if (au is not None and au.phase == phase) else 0)

    @staticmethod
    def _replica_adapter_block(info: ReplicaInfo) -> Optional[dict]:
        """The replica's /stats 'adapters' block, shape-checked."""
        if isinstance(info.stats, dict) and \
                isinstance(info.stats.get('adapters'), dict):
            return info.stats['adapters']
        return None

    def _adapter_replica_http(self, info: ReplicaInfo, payload: dict
                              ) -> 'tuple[bool, Optional[str]]':
        """One POST /admin/adapters against a replica (the injectable
        default of self._adapter_fn)."""
        if not info.endpoint:
            return False, 'replica has no endpoint'
        headers = {}
        if self._admin_token:
            headers['Authorization'] = f'Bearer {self._admin_token}'
        try:
            resp = requests.post(
                info.endpoint + '/admin/adapters', json=payload,
                headers=headers,
                timeout=env.get_float('SKYT_ADAPTER_ROLLOUT_TIMEOUT_S',
                                      120.0))
            if resp.status_code == 200:
                return True, None
            try:
                msg = resp.json().get('error', '')
            except ValueError:
                msg = resp.text[:200]
            return False, f'HTTP {resp.status_code}: {msg}'
        except requests.RequestException as e:
            return False, str(e)

    def _adapter_candidates(self, au: AdapterState) -> List[ReplicaInfo]:
        with self._lock:
            return sorted(
                (r for r in self.replicas.values()
                 if r.status is serve_state.ReplicaStatus.READY and
                 r.endpoint and r.replica_id not in au.updated),
                key=lambda r: r.replica_id)

    def adapter_tick(self) -> None:
        """One state-machine step of the active adapter convergence —
        called from the control loop beside reshard_tick. One replica
        per tick: at most one replica is ever mid-apply, so the
        routable set for the adapter shrinks/grows one replica at a
        time and the LB's model-aware routing always has somewhere to
        send in-flight traffic. Covers the replicas READY during the
        window; one that boots later converges on the NEXT issued
        update (its /stats adapter set makes the gap visible)."""
        with self._lock:
            au = self._adapter_update
        if au is None or not au.active:
            return
        before = au.phase
        if au.phase == 'update':
            self._tick_adapter(au)
        elif au.phase == 'rollback':
            self._tick_adapter_rollback(au)
        if au.phase != before:
            self._update_adapter_gauge()

    def _tick_adapter(self, au: AdapterState) -> None:
        cand = self._adapter_candidates(au)
        if not cand:
            au.phase = 'done'
            self._m_adapter_updates.labels(self.service_name,
                                           'done').inc()
            logger.info('adapter fleet update done: %s %r on %d '
                        'replica(s)', au.op, au.name, len(au.updated))
            return
        info = cand[0]
        ok, err = self._adapter_fn(info, au.payload())
        if ok:
            self._m_adapter_calls.labels(self.service_name,
                                         'ok').inc()
            au.updated.append(info.replica_id)
            au.fails = 0
            logger.info('adapter fleet update: replica %d %sed %r',
                        info.replica_id, au.op, au.name)
            return
        self._m_adapter_calls.labels(self.service_name, 'error').inc()
        au.fails += 1
        au.error = (f'replica {info.replica_id} adapter {au.op} '
                    f'failed: {err}')
        logger.warning('adapter fleet update: %s (consecutive fails: '
                       '%d)', au.error, au.fails)
        if au.fails >= _rollout_retries():
            au.phase = 'rollback'

    def _tick_adapter_rollback(self, au: AdapterState) -> None:
        """Reverse the already-updated replicas, newest first: a load
        rolls back by unloading the name, an unload by reloading from
        the recorded checkpoint. A replica that refuses after the
        retry budget — or an unload with no recorded checkpoint — is
        SKIPPED, not drained: a divergent adapter set is degraded
        routing (the LB sees it in /stats and steers around it),
        and relaunching a serving replica over it would turn that
        into a capacity dip."""
        if au.op == 'unload' and not au.checkpoint:
            logger.warning('adapter fleet update: cannot roll back '
                           'unload of %r (no recorded checkpoint); '
                           'leaving %d replica(s) without it',
                           au.name, len(au.updated))
            au.updated.clear()
        while au.updated:
            rid = au.updated[-1]
            info = self.replicas.get(rid)
            if info is None or not info.is_alive:
                au.updated.pop()   # gone; nothing to roll back
                continue
            if au.op == 'load':
                payload = {'op': 'unload', 'name': au.name}
            else:
                payload = {'op': 'load', 'name': au.name,
                           'checkpoint': au.checkpoint,
                           'alpha': au.alpha}
            ok, err = self._adapter_fn(info, payload)
            if ok:
                self._m_adapter_calls.labels(self.service_name,
                                             'rollback_ok').inc()
                au.updated.pop()
                au.fails = 0
                logger.info('adapter fleet update: replica %d rolled '
                            'back', rid)
                continue
            self._m_adapter_calls.labels(self.service_name,
                                         'rollback_error').inc()
            au.fails += 1
            logger.warning('adapter fleet update: replica %d rollback '
                           'failed (%d/%d): %s', rid, au.fails,
                           _rollout_retries(), err)
            if au.fails >= _rollout_retries():
                logger.warning('adapter fleet update: skipping '
                               'replica %d (adapter set left '
                               'divergent)', rid)
                au.updated.pop()
                au.fails = 0
            return   # failed attempt: retry/escalate next tick
        au.phase = 'rolled_back'
        self._m_adapter_updates.labels(self.service_name,
                                       'rolled_back').inc()
        logger.warning('adapter fleet update %s %r rolled back (%s)',
                       au.op, au.name,
                       au.error or 'unspecified failure')

    # ------------------------------------------------------------- views
    def ready_urls(self) -> List[str]:
        with self._lock:
            return [r.endpoint for r in self.replicas.values()
                    if r.status is serve_state.ReplicaStatus.READY and
                    r.endpoint]

    def ready_qos(self) -> dict:
        """endpoint -> QoS pressure block for READY replicas whose
        last /stats scrape carried one (engine servers with SKYT_QOS=1;
        arbitrary user services simply never appear here)."""
        with self._lock:
            out = {}
            for r in self.replicas.values():
                if r.status is serve_state.ReplicaStatus.READY and \
                        r.endpoint and isinstance(r.stats, dict) and \
                        isinstance(r.stats.get('qos'), dict):
                    out[r.endpoint] = r.stats['qos']
            return out

    def ready_weight_versions(self) -> dict:
        """endpoint -> serving weight version for READY replicas —
        synced to the LB (skyt_lb_replica_weight_version) so mixed-
        version windows during a rollout are visible at the front
        door."""
        with self._lock:
            return {r.endpoint: int(getattr(r, 'weight_version', 1)
                                    or 1)
                    for r in self.replicas.values()
                    if r.status is serve_state.ReplicaStatus.READY and
                    r.endpoint}

    def ready_prefix_cache(self) -> dict:
        """endpoint -> prefix-cache stats block (occupancy, hit/miss
        pages) for READY replicas whose last /stats scrape carried one
        (engine servers with paged prefix caching; other services
        never appear)."""
        with self._lock:
            out = {}
            for r in self.replicas.values():
                if r.status is serve_state.ReplicaStatus.READY and \
                        r.endpoint and isinstance(r.stats, dict) and \
                        isinstance(r.stats.get('prefix_cache'), dict):
                    out[r.endpoint] = r.stats['prefix_cache']
            return out

    def ready_adapters(self) -> dict:
        """endpoint -> {adapter name: version} for READY replicas
        whose last /stats scrape carried an adapters block — the
        model-aware routing map synced to the LB. Versions ride along
        so a mid-replacement fleet (same name, mixed versions) is
        visible at the front door."""
        with self._lock:
            out = {}
            for r in self.replicas.values():
                if r.status is not serve_state.ReplicaStatus.READY \
                        or not r.endpoint:
                    continue
                block = self._replica_adapter_block(r)
                if block is None:
                    continue
                named = block.get('adapters')
                if not isinstance(named, dict):
                    continue
                out[r.endpoint] = {
                    str(n): int(meta.get('version', 1) or 1)
                    for n, meta in named.items()
                    if isinstance(meta, dict)}
            return out

    def num_alive(self) -> int:
        with self._lock:
            return sum(1 for r in self.replicas.values() if r.is_alive)
