"""Chaos suite, rollouts through the real controller: rolling weight
update with canary rollback, adapter hot-load, reshard with rollback
(docs/robustness.md).

The drills run the REAL LB -> server -> engine HTTP stack on the CPU;
a death is a SIGKILLed subprocess, not a mock. Shared helpers:
tests/chaos_helpers.py.
"""
import threading
import time

import pytest
import requests

from skypilot_tpu.utils import metrics as metrics_lib

from chaos_helpers import (
    _free_port, _run_app_bg, _spawn_service, _wait_replicas_ready,
    _wait_rollout_phase,
)
# Fixtures, used by name:
from chaos_helpers import _reset_faults  # noqa: unused-import
from chaos_helpers import control_plane_env  # noqa: unused-import

pytestmark = pytest.mark.heavy


# ===================================== zero-downtime rolling updates
def _save_debug_checkpoints(tmp_path, seeds=(0, 7, 11)):
    """HF-format debug-model checkpoints (one per seed) the engine
    server's swap loader can read."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    from skypilot_tpu.models import llama
    from skypilot_tpu.models import weights as weights_lib
    cfg = _dc.replace(llama.CONFIGS['debug'], max_seq_len=64,
                      param_dtype='float32', dtype='float32')
    model = llama.LlamaModel(cfg)
    zeros = jnp.zeros((1, 8), jnp.int32)
    out = []
    for i, seed in enumerate(seeds):
        params = jax.jit(model.init)(jax.random.PRNGKey(seed), zeros)
        path = str(tmp_path / f'ckpt_{chr(ord("a") + i)}')
        weights_lib.save_hf_checkpoint(cfg, params, path)
        out.append(path)
    return out


_ENGINE_REPLICA = (
    'python -m skypilot_tpu.infer.server --model debug '
    '--port "$SKYT_REPLICA_PORT" --num-slots 2 --max-seq-len 64')


# 17 s here: three weight roll-outs over two engine replicas.
# Measured on an idle 8-core box; the driver's is some three times slower.
@pytest.mark.time_limit(300)
@pytest.mark.integration
@pytest.mark.usefixtures('one_device_children')
def test_chaos_rolling_update_canary_rollback(control_plane_env,
                                              monkeypatch):
    """THE zero-downtime-rollout drill (docs/robustness.md
    "Zero-downtime rollouts", validation step 15): 2 REAL engine
    replicas behind the real controller + an in-process LB.

    Run 1 (unfaulted): a mid-burst rolling update to checkpoint B
    lands the new weight version fleet-wide — zero client-visible
    5xx, zero relaunches (the launch counter never ticks past the
    initial 2), every replica at weight_version 2.

    Run 2 (faulted): `weights.swap=error` armed on checkpoint C — the
    canary's swap aborts with its old weights intact, the rollout
    auto-rolls-back, the mid-burst traffic still sees zero 5xx, and
    the fleet ends on the OLD version with the spec uncommitted."""
    import yaml as yaml_lib

    import skypilot_tpu as sky
    from skypilot_tpu import resources as resources_lib
    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve import service_spec as spec_lib
    from skypilot_tpu.train import push_weights

    tmp_path = control_plane_env
    ckpt_a, ckpt_b, ckpt_c = _save_debug_checkpoints(tmp_path)
    # Arm the canary-kill for run 2 ONLY: the where= filter keys on
    # the pushed checkpoint, so run 1 (ckpt_b) is untouched. The env
    # is inherited by the replica processes at launch.
    monkeypatch.setenv('SKYT_FAULTS',
                       f'weights.swap=error,where=checkpoint:{ckpt_c}')
    monkeypatch.setenv('SKYT_ROLLOUT_BAKE_S', '0.5')
    task = sky.Task(name='rsvc', run=_ENGINE_REPLICA)
    task.set_resources(resources_lib.Resources(cloud='local'))
    spec = spec_lib.ServiceSpec(
        readiness_path='/health', min_replicas=2,
        initial_delay_seconds=600, probe_timeout_seconds=5,
        weights=ckpt_a)
    task.service = spec
    task_yaml = str(tmp_path / 'rsvc.task.yaml')
    with open(task_yaml, 'w', encoding='utf-8') as f:
        yaml_lib.safe_dump(task.to_yaml_config(), f)
    cport, lport = _free_port(), _free_port()
    assert serve_state.add_service('rsvc', spec, task_yaml, cport,
                                   lport)
    token = serve_state.get_service('rsvc')['auth_token']
    headers = {'Authorization': f'Bearer {token}'}
    curl = f'http://127.0.0.1:{cport}'

    ctrl = _spawn_service('rsvc', 'controller')
    lb = None
    try:
        _wait_replicas_ready('rsvc', 2, timeout=420)
        reg = metrics_lib.MetricsRegistry()
        lb_port = _free_port()
        lb = lb_lib.SkyServeLoadBalancer(
            curl, lb_port, controller_auth=token,
            metrics_registry=reg)
        _run_app_bg(lb.make_app(), lb_port)
        base = f'http://127.0.0.1:{lb_port}'
        deadline = time.time() + 120
        while time.time() < deadline and \
                len(lb.policy.ready_replicas) < 2:
            time.sleep(0.2)
        assert len(lb.policy.ready_replicas) == 2

        results = []
        stop_burst = threading.Event()
        lock = threading.Lock()

        def burst():
            i = 0
            while not stop_burst.is_set():
                i += 1
                try:
                    r = requests.post(
                        base + '/generate',
                        json={'tokens': [1 + (i % 5), 2, 3],
                              'max_tokens': 6},
                        timeout=120)
                    code = r.status_code
                except requests.RequestException as e:
                    code = f'EXC:{e!r}'
                with lock:
                    results.append(code)

        threads = [threading.Thread(target=burst) for _ in range(3)]
        for th in threads:
            th.start()
        try:
            # ---- run 1: clean rolling update, driven through the
            # real weight-push client (train/push_weights.py).
            state = push_weights.push(curl, ckpt_b, token=token,
                                      wait=True, timeout_s=300)
            assert state['phase'] == 'done'
        finally:
            time.sleep(1.0)     # a little post-rollout traffic
            stop_burst.set()
            for th in threads:
                th.join(timeout=120)
        with lock:
            run1 = list(results)
        assert run1 and all(c == 200 for c in run1), run1[:20]
        status = requests.get(curl + '/controller/status',
                              headers=headers, timeout=10).json()
        assert all(r['weight_version'] == 2 and r['version'] == 2
                   for r in status['replicas']), status['replicas']
        # Zero relaunches: the launch counter holds at the initial 2.
        mtext = requests.get(curl + '/controller/metrics',
                             headers=headers, timeout=10).text
        assert 'skyt_serve_replica_launches_total{service="rsvc"} 2' \
            in mtext, mtext
        # The LB saw the new version through the sync.
        deadline = time.time() + 30
        while time.time() < deadline and \
                set(lb.state.replica_weight_version.values()) != {2}:
            time.sleep(0.3)
        assert set(lb.state.replica_weight_version.values()) == {2}

        # ---- run 2: the armed fault kills the canary's swap.
        results.clear()
        stop_burst.clear()
        threads = [threading.Thread(target=burst) for _ in range(3)]
        for th in threads:
            th.start()
        try:
            resp = requests.post(curl + '/controller/rolling_update',
                                 json={'checkpoint': ckpt_c},
                                 headers=headers, timeout=30)
            assert resp.status_code == 200, resp.text
            status = _wait_rollout_phase(cport, token,
                                         ('rolled_back',),
                                         timeout=240)
        finally:
            time.sleep(1.0)
            stop_burst.set()
            for th in threads:
                th.join(timeout=120)
        with lock:
            run2 = list(results)
        assert run2 and all(c == 200 for c in run2), run2[:20]
        ro = status['rollout']
        assert ro['phase'] == 'rolled_back'
        assert 'swap failed' in (ro['error'] or '')
        # Fleet ends on the OLD version; spec never committed.
        assert all(r['weight_version'] == 2 and r['version'] == 2
                   for r in status['replicas']), status['replicas']
        assert serve_state.get_service('rsvc')['version'] == 2
        # Still zero relaunches across BOTH runs.
        mtext = requests.get(curl + '/controller/metrics',
                             headers=headers, timeout=10).text
        assert 'skyt_serve_replica_launches_total{service="rsvc"} 2' \
            in mtext, mtext
        assert ('skyt_serve_rollouts_total{service="rsvc",'
                'outcome="done"} 1') in mtext
        assert ('skyt_serve_rollouts_total{service="rsvc",'
                'outcome="rolled_back"} 1') in mtext
    finally:
        if ctrl.poll() is None:
            try:
                requests.post(curl + '/controller/terminate', json={},
                              headers=headers, timeout=60)
            except requests.RequestException:
                pass
            ctrl.kill()
        del lb


def _wait_adapter_phase(cport, token, phases, timeout=240):
    headers = {'Authorization': f'Bearer {token}'}
    deadline = time.time() + timeout
    last = None
    while time.time() < deadline:
        try:
            last = requests.get(
                f'http://127.0.0.1:{cport}/controller/status',
                headers=headers, timeout=10).json()
            au = last.get('adapter_update') or {}
            if au.get('phase') in phases:
                return last
        except requests.RequestException:
            pass
        time.sleep(0.3)
    raise AssertionError(
        f'adapter update never reached {phases}: '
        f'{(last or {}).get("adapter_update")}')


def _save_debug_adapter(tmp_path, rank=2, alpha=4.0, seed=9):
    """An Orbax adapter dir shaped exactly like an `sft --lora-rank`
    run writes (TrainStateS), for the debug model the drill's
    replicas serve."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp
    import numpy as np
    import flax.linen as nn

    from skypilot_tpu.models import llama
    from skypilot_tpu.train import checkpoint as ckpt_lib
    from skypilot_tpu.train import lora as tlora
    from skypilot_tpu.train import trainer

    cfg = _dc.replace(llama.CONFIGS['debug'], max_seq_len=64)
    model = llama.LlamaModel(cfg)
    params = nn.meta.unbox(
        jax.jit(model.init)(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))['params'])
    lcfg = tlora.LoRAConfig(rank=rank, alpha=alpha)
    tree = tlora.init_lora_params(params, lcfg,
                                  jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    tree = jax.tree.map(
        lambda x: jnp.asarray(rng.normal(0, 0.1, x.shape), x.dtype),
        tree)
    tx = trainer.make_optimizer(trainer.TrainerConfig())
    state = trainer.TrainStateS(step=jnp.zeros((), jnp.int32),
                                params=tree, opt_state=tx.init(tree))
    path = str(tmp_path / 'adapter_fr')
    ck = ckpt_lib.Checkpointer(path, async_save=False)
    ck.save(0, state, force=True)
    ck.wait()
    ck.close()
    return path


# 23 s here: a controller, two engine replicas and four adapter loads.
# Measured on an idle 8-core box; the driver's is some three times slower.
@pytest.mark.time_limit(300)
@pytest.mark.integration
@pytest.mark.usefixtures('one_device_children')
def test_chaos_adapter_hot_load_drill(control_plane_env):
    """THE adapter hot-load drill (docs/serving.md "Adapter fleet",
    validation step 21): 2 REAL engine replicas behind the real
    controller + an in-process LB. A fleet-wide adapter load lands
    mid-burst through POST /controller/adapters — zero client-visible
    5xx, zero relaunches — then the front door routes by model name
    (aggregated /v1/models, honest 404), a direct unload is REFUSED
    while requests reference the adapter, and the fleet-wide unload
    converges clean."""
    import yaml as yaml_lib

    import skypilot_tpu as sky
    from skypilot_tpu import resources as resources_lib
    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve import service_spec as spec_lib

    tmp_path = control_plane_env
    adapter_dir = _save_debug_adapter(tmp_path)
    task = sky.Task(name='asvc', run=_ENGINE_REPLICA)
    task.set_resources(resources_lib.Resources(cloud='local'))
    spec = spec_lib.ServiceSpec(
        readiness_path='/health', min_replicas=2,
        initial_delay_seconds=600, probe_timeout_seconds=5)
    task.service = spec
    task_yaml = str(tmp_path / 'asvc.task.yaml')
    with open(task_yaml, 'w', encoding='utf-8') as f:
        yaml_lib.safe_dump(task.to_yaml_config(), f)
    cport, lport = _free_port(), _free_port()
    assert serve_state.add_service('asvc', spec, task_yaml, cport,
                                   lport)
    token = serve_state.get_service('asvc')['auth_token']
    headers = {'Authorization': f'Bearer {token}'}
    curl = f'http://127.0.0.1:{cport}'

    ctrl = _spawn_service('asvc', 'controller')
    lb = None
    try:
        _wait_replicas_ready('asvc', 2, timeout=420)
        reg = metrics_lib.MetricsRegistry()
        lb_port = _free_port()
        lb = lb_lib.SkyServeLoadBalancer(
            curl, lb_port, controller_auth=token,
            metrics_registry=reg)
        _run_app_bg(lb.make_app(), lb_port)
        base = f'http://127.0.0.1:{lb_port}'
        deadline = time.time() + 120
        while time.time() < deadline and \
                len(lb.policy.ready_replicas) < 2:
            time.sleep(0.2)
        assert len(lb.policy.ready_replicas) == 2

        results = []
        stop_burst = threading.Event()
        lock = threading.Lock()

        def burst(lora=None):
            i = 0
            while not stop_burst.is_set():
                i += 1
                body = {'tokens': [1 + (i % 5), 2, 3],
                        'max_tokens': 6}
                if lora:
                    body['lora'] = lora
                try:
                    r = requests.post(base + '/generate', json=body,
                                      timeout=120)
                    code = r.status_code
                except requests.RequestException as e:
                    code = f'EXC:{e!r}'
                with lock:
                    results.append(code)

        threads = [threading.Thread(target=burst) for _ in range(3)]
        for th in threads:
            th.start()
        try:
            # ---- fleet-wide hot load, mid-burst.
            resp = requests.post(
                curl + '/controller/adapters',
                json={'op': 'load', 'name': 'fr',
                      'checkpoint': adapter_dir, 'alpha': 4.0},
                headers=headers, timeout=30)
            assert resp.status_code == 200, resp.text
            # A second update while one is active: 409, not a queue.
            resp2 = requests.post(
                curl + '/controller/adapters',
                json={'op': 'load', 'name': 'de',
                      'checkpoint': adapter_dir},
                headers=headers, timeout=30)
            assert resp2.status_code == 409, resp2.text
            status = _wait_adapter_phase(cport, token, ('done',))
        finally:
            time.sleep(1.0)     # a little post-load traffic
            stop_burst.set()
            for th in threads:
                th.join(timeout=120)
        with lock:
            run1 = list(results)
        assert run1 and all(c == 200 for c in run1), run1[:20]
        au = status['adapter_update']
        assert au['op'] == 'load' and au['name'] == 'fr'
        assert len(au['updated']) == 2, au
        # Zero relaunches: hot load never restarted a replica.
        mtext = requests.get(curl + '/controller/metrics',
                             headers=headers, timeout=10).text
        assert 'skyt_serve_replica_launches_total{service="asvc"} 2' \
            in mtext, mtext
        # The adapter set rides the sync into the LB's world view.
        deadline = time.time() + 60
        while time.time() < deadline and not (
                len(lb.state.replica_adapters) == 2 and
                all('fr' in named for named in
                    lb.state.replica_adapters.values())):
            time.sleep(0.3)
        assert all('fr' in named for named in
                   lb.state.replica_adapters.values()), \
            lb.state.replica_adapters

        # Front door model surface: aggregated /v1/models lists the
        # adapter fleet-wide (and teaches the LB the base id).
        models = requests.get(base + '/v1/models', timeout=30).json()
        by_id = {e['id']: e for e in models['data']}
        assert 'fr' in by_id and by_id['fr'].get('parent') == 'debug'
        assert by_id['fr'].get('replicas') == 2
        # Model-named request serves through the adapter...
        r = requests.post(base + '/v1/completions',
                          json={'model': 'fr', 'prompt': 'hi',
                                'max_tokens': 4}, timeout=120)
        assert r.status_code == 200, r.text
        # ...and a model NOBODY hosts is an honest front-door 404.
        r = requests.post(base + '/v1/completions',
                          json={'model': 'ghost', 'prompt': 'hi',
                                'max_tokens': 4}, timeout=120)
        assert r.status_code == 404, r.text
        assert r.json()['error']['code'] == 'model_not_found'

        # ---- unload-while-referenced: long adapter generations hold
        # the id in flight on a specific replica; its direct unload
        # must 409 with the stack untouched.
        cstat = requests.get(curl + '/controller/status',
                             headers=headers, timeout=10).json()
        endpoint = cstat['replicas'][0]['endpoint']
        long_results = []

        def long_gen():
            r = requests.post(
                endpoint + '/generate',
                json={'tokens': [1, 2, 3], 'max_tokens': 60,
                      'lora': 'fr'}, timeout=120)
            long_results.append(r.status_code)

        lthreads = [threading.Thread(target=long_gen)
                    for _ in range(6)]
        for th in lthreads:
            th.start()
        time.sleep(0.05)
        r = requests.post(endpoint + '/admin/adapters',
                          json={'op': 'unload', 'name': 'fr'},
                          headers=headers, timeout=30)
        assert r.status_code == 409, (r.status_code, r.text)
        assert 'referenced' in r.json()['error']
        for th in lthreads:
            th.join(timeout=120)
        assert long_results == [200] * 6, long_results

        # ---- fleet-wide unload converges clean once drained.
        resp = requests.post(curl + '/controller/adapters',
                             json={'op': 'unload', 'name': 'fr'},
                             headers=headers, timeout=30)
        assert resp.status_code == 200, resp.text
        _wait_adapter_phase(cport, token, ('done',))
        deadline = time.time() + 60
        while time.time() < deadline and any(
                'fr' in named for named in
                lb.state.replica_adapters.values()):
            time.sleep(0.3)
        assert not any('fr' in named for named in
                       lb.state.replica_adapters.values())
        # Both converges visible in the orchestrator counter; still
        # zero relaunches across the whole drill.
        mtext = requests.get(curl + '/controller/metrics',
                             headers=headers, timeout=10).text
        assert ('skyt_serve_adapter_updates_total{service="asvc",'
                'outcome="done"} 2') in mtext, mtext
        assert 'skyt_serve_replica_launches_total{service="asvc"} 2' \
            in mtext, mtext
    finally:
        if ctrl.poll() is None:
            try:
                requests.post(curl + '/controller/terminate', json={},
                              headers=headers, timeout=60)
            except requests.RequestException:
                pass
            ctrl.kill()
        del lb


def _wait_reshard_phase(cport, token, phases, timeout=180):
    headers = {'Authorization': f'Bearer {token}'}
    deadline = time.time() + timeout
    last = None
    while time.time() < deadline:
        try:
            last = requests.get(
                f'http://127.0.0.1:{cport}/controller/status',
                headers=headers, timeout=10).json()
            rs = last.get('reshard') or {}
            if rs.get('phase') in phases:
                return last
        except requests.RequestException:
            pass
        time.sleep(0.3)
    raise AssertionError(
        f'reshard never reached {phases}: '
        f'{(last or {}).get("reshard")}')


# 19 s here: three reshards of two engine replicas and a controller
# restart. Measured on an idle 8-core box;
# the driver's is some three times slower.
@pytest.mark.time_limit(300)
@pytest.mark.integration
def test_chaos_reshard_rollback_and_controller_sigkill(
        control_plane_env, monkeypatch):
    """THE mid-reshard chaos drill (docs/robustness.md "Elastic
    capacity"): 2 REAL engine replicas behind the real controller +
    an in-process LB.

    Run 1 (clean): an in-place reshard 1 -> 2 virtual nodes lands
    fleet-wide mid-burst — zero client-visible 5xx, zero relaunches,
    weight_version untouched.

    Run 2 (faulted): `reshard=error` armed on target 4 — every
    replica refuses, the orchestrator rolls back automatically, the
    mid-burst traffic still sees zero 5xx and the fleet keeps the
    old layout.

    Run 3 (SIGKILL mid-reshard): the controller is SIGKILLed while a
    replica's reshard POST is in flight. Reshard state is in-memory
    BY DESIGN: the restarted controller adopts both replicas (zero
    relaunches), reports no reshard, the mixed-layout fleet keeps
    serving 200s, and re-issuing the reshard converges — the
    already-flipped replica no-ops (idempotent re-assert)."""
    import yaml as yaml_lib

    import skypilot_tpu as sky
    from skypilot_tpu import resources as resources_lib
    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.serve import serve_state
    from skypilot_tpu.serve import service_spec as spec_lib

    tmp_path = control_plane_env
    # where= keys on the reshard target, so each run picks its fault:
    # target 4 errors (run 2); target 1 stalls 2.5s (run 3's kill
    # window + the idempotent re-assert). Inherited by the replica
    # processes at launch.
    monkeypatch.setenv('SKYT_FAULTS',
                       'reshard=error,where=virtual_nodes:4;'
                       'reshard=latency,arg=2.5,where=virtual_nodes:1')
    monkeypatch.setenv('SKYT_ROLLOUT_RETRIES', '2')
    task = sky.Task(name='esvc', run=_ENGINE_REPLICA)
    task.set_resources(resources_lib.Resources(cloud='local'))
    spec = spec_lib.ServiceSpec(
        readiness_path='/health', min_replicas=2,
        initial_delay_seconds=600, probe_timeout_seconds=5)
    task.service = spec
    task_yaml = str(tmp_path / 'esvc.task.yaml')
    with open(task_yaml, 'w', encoding='utf-8') as f:
        yaml_lib.safe_dump(task.to_yaml_config(), f)
    cport, lport = _free_port(), _free_port()
    assert serve_state.add_service('esvc', spec, task_yaml, cport,
                                   lport)
    token = serve_state.get_service('esvc')['auth_token']
    headers = {'Authorization': f'Bearer {token}'}
    curl = f'http://127.0.0.1:{cport}'

    ctrl = _spawn_service('esvc', 'controller')
    lb = None
    try:
        _wait_replicas_ready('esvc', 2, timeout=420)
        reg = metrics_lib.MetricsRegistry()
        lb_port = _free_port()
        lb = lb_lib.SkyServeLoadBalancer(
            curl, lb_port, controller_auth=token,
            metrics_registry=reg)
        _run_app_bg(lb.make_app(), lb_port)
        base = f'http://127.0.0.1:{lb_port}'
        deadline = time.time() + 120
        while time.time() < deadline and \
                len(lb.policy.ready_replicas) < 2:
            time.sleep(0.2)
        assert len(lb.policy.ready_replicas) == 2

        def replica_stats():
            status = requests.get(curl + '/controller/status',
                                  headers=headers, timeout=10).json()
            out = {}
            for rep in status['replicas']:
                stats = requests.get(rep['endpoint'] + '/stats',
                                     timeout=30).json()
                out[rep['replica_id']] = (stats['virtual_nodes'],
                                          stats['weight_version'])
            return out

        assert set(replica_stats().values()) == {(1, 1)}

        results = []
        stop_burst = threading.Event()
        lock = threading.Lock()

        def burst():
            i = 0
            while not stop_burst.is_set():
                i += 1
                try:
                    r = requests.post(
                        base + '/generate',
                        json={'tokens': [1 + (i % 5), 2, 3],
                              'max_tokens': 6},
                        timeout=120)
                    code = r.status_code
                except requests.RequestException as e:
                    code = f'EXC:{e!r}'
                with lock:
                    results.append(code)

        def run_burst_during(fn):
            results.clear()
            stop_burst.clear()
            threads = [threading.Thread(target=burst)
                       for _ in range(2)]
            for th in threads:
                th.start()
            try:
                out = fn()
            finally:
                time.sleep(0.5)
                stop_burst.set()
                for th in threads:
                    th.join(timeout=120)
            with lock:
                codes = list(results)
            assert codes and all(c == 200 for c in codes), codes[:20]
            return out

        # ---- run 1: clean elastic flip 1 -> 2, mid-burst.
        def clean_flip():
            resp = requests.post(curl + '/controller/reshard',
                                 json={'virtual_nodes': 2},
                                 headers=headers, timeout=30)
            assert resp.status_code == 200, resp.text
            return _wait_reshard_phase(cport, token, ('done',),
                                       timeout=120)

        status = run_burst_during(clean_flip)
        assert status['reshard']['phase'] == 'done'
        # Layout flipped fleet-wide; the weights plane untouched.
        assert set(replica_stats().values()) == {(2, 1)}

        # ---- run 2: the armed fault refuses target 4 -> rollback.
        def faulted_flip():
            resp = requests.post(curl + '/controller/reshard',
                                 json={'virtual_nodes': 4},
                                 headers=headers, timeout=30)
            assert resp.status_code == 200, resp.text
            return _wait_reshard_phase(cport, token, ('rolled_back',),
                                       timeout=120)

        status = run_burst_during(faulted_flip)
        rs = status['reshard']
        assert rs['phase'] == 'rolled_back'
        assert 'replica' in (rs['error'] or '')
        # Old layout intact everywhere; still zero relaunches.
        assert set(replica_stats().values()) == {(2, 1)}
        mtext = requests.get(curl + '/controller/metrics',
                             headers=headers, timeout=10).text
        assert 'skyt_serve_replica_launches_total{service="esvc"} 2' \
            in mtext, mtext
        assert ('skyt_serve_reshards_total{service="esvc",'
                'outcome="done"} 1') in mtext
        assert ('skyt_serve_reshards_total{service="esvc",'
                'outcome="rolled_back"} 1') in mtext

        # ---- run 3: SIGKILL mid-reshard (target 1 stalls 2.5s per
        # replica call — the kill lands inside the first POST).
        resp = requests.post(curl + '/controller/reshard',
                             json={'virtual_nodes': 1},
                             headers=headers, timeout=30)
        assert resp.status_code == 200, resp.text
        _wait_reshard_phase(cport, token, ('reshard',), timeout=30)
        time.sleep(1.0)
        ctrl.kill()
        ctrl.wait(timeout=30)

        ctrl = _spawn_service('esvc', 'controller')
        _wait_replicas_ready('esvc', 2, timeout=120)
        deadline = time.time() + 60
        status = None
        while time.time() < deadline:
            try:
                status = requests.get(curl + '/controller/status',
                                      headers=headers,
                                      timeout=10).json()
                break
            except requests.RequestException:
                time.sleep(0.3)
        assert status is not None
        # In-memory by design: the restarted controller has no
        # reshard; the replicas were adopted, not relaunched.
        assert status['reshard'] is None
        mtext = requests.get(curl + '/controller/metrics',
                             headers=headers, timeout=10).text
        assert ('skyt_serve_replica_adoptions_total{service="esvc"} '
                '2') in mtext, mtext
        assert 'skyt_serve_replica_launches_total{service="esvc"}' \
            not in mtext, mtext
        # Mixed layouts are fine to serve: zero 5xx either way.
        for i in range(4):
            r = requests.post(base + '/generate',
                              json={'tokens': [2 + i, 3, 4],
                                    'max_tokens': 4},
                              timeout=120)
            assert r.status_code == 200, r.text
        # The killed controller's POST is still running inside its
        # replica (the 2.5 s stall), and a replica answers a second
        # reshard with 409 until the first is done. Wait for that flip,
        # not on how long a controller takes to start.
        deadline = time.time() + 30
        while time.time() < deadline and not any(
                nodes == 1 for nodes, _ in replica_stats().values()):
            time.sleep(0.1)
        # Re-issue: the operator's recovery lever. The already-
        # flipped replica no-ops; the straggler flips.
        resp = requests.post(curl + '/controller/reshard',
                             json={'virtual_nodes': 1},
                             headers=headers, timeout=30)
        assert resp.status_code == 200, resp.text
        _wait_reshard_phase(cport, token, ('done',), timeout=120)
        assert set(replica_stats().values()) == {(1, 1)}
    finally:
        if ctrl.poll() is None:
            try:
                requests.post(curl + '/controller/terminate', json={},
                              headers=headers, timeout=60)
            except requests.RequestException:
                pass
            ctrl.kill()
        del lb
