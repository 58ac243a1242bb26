"""End-to-end on the local provider, the examples that really train:
`skyt launch` of examples/*.yaml with real agents and real training
processes. The quick launch / queue / logs / stop cycle is in
tests/test_e2e_local.py, whose fixture and helpers these use.
"""
import pytest

import skypilot_tpu as sky
from skypilot_tpu import core
from skypilot_tpu import execution
from skypilot_tpu import resources as resources_lib

from test_e2e_local import _wait_terminal
from test_e2e_local import local_env  # noqa: unused-import (fixture)

pytestmark = pytest.mark.heavy


def _examples_dir():
    import os
    return os.path.join(os.path.dirname(__file__), '..', 'examples')


# slow: 16 s in a six-worker run: a real example trained data-parallel
# by two processes under one jax.distributed runtime.
@pytest.mark.slow
@pytest.mark.integration
def test_cnn_distributed_yaml_two_nodes(local_env, capsys):
    """examples/cnn_distributed.yaml (the resnet_distributed_torch
    analog) runs 2-node data-parallel under skyt launch on the local
    provider: both nodes join one jax.distributed runtime via the gang
    env contract and the loss is finite at the end."""
    import os
    t = sky.Task.from_yaml(
        os.path.join(_examples_dir(), 'cnn_distributed.yaml'),
        env_overrides={'STEPS': '8', 'GLOBAL_BATCH': '8'})
    t.envs['JAX_PLATFORMS'] = 'cpu'
    t.envs['XLA_FLAGS'] = '--xla_force_host_platform_device_count=1'
    t.set_resources(resources_lib.Resources(cloud='local'))
    assert t.num_nodes == 2
    jid = execution.launch(t, cluster_name='c-cnn', detach_run=True)
    job = _wait_terminal('c-cnn', jid, timeout=420)
    assert job['status'] == 'SUCCEEDED', job
    core.tail_logs('c-cnn', jid, follow=False)
    out = capsys.readouterr().out
    assert 'nodes=2' in out, out          # really ran 2-process DP
    assert 'FINAL loss=' in out, out


@pytest.mark.integration
@pytest.mark.usefixtures('one_device_children')
def test_text_classify_yaml(local_env, capsys):
    """examples/text_classify_finetune.yaml (the huggingface GLUE/IMDB
    analog) runs under skyt launch on the local provider and actually
    learns (eval accuracy printed; >0.9 at these settings)."""
    import os
    import re
    t = sky.Task.from_yaml(
        os.path.join(_examples_dir(), 'text_classify_finetune.yaml'),
        env_overrides={'STEPS': '40', 'BATCH': '16'})
    t.envs['JAX_PLATFORMS'] = 'cpu'
    t.set_resources(resources_lib.Resources(cloud='local'))
    jid = execution.launch(t, cluster_name='c-imdb', detach_run=True)
    job = _wait_terminal('c-imdb', jid, timeout=420)
    assert job['status'] == 'SUCCEEDED', job
    core.tail_logs('c-imdb', jid, follow=False)
    out = capsys.readouterr().out
    m = re.search(r'eval_acc=([0-9.]+)', out)
    assert m, out
    assert float(m.group(1)) > 0.9, out
