"""Serve a real HF-format checkpoint END-TO-END and verify greedy
continuations through the HTTP path match transformers.

The reference's serving story is `--model <hf id>` into vLLM
(llm/vllm/serve.yaml); ours is `--checkpoint <dir>` into the TPU-native
engine. This test drives the full served path — safetensors from disk →
server subprocess → HTTP /generate — not just the loader. The checkpoint
is written by save_hf_checkpoint (HF layout:
config.json + model.safetensors), the same format released Llama weights
ship in; swap the dir for a downloaded snapshot and nothing changes.
"""
import dataclasses
import json
import os
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.integration


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


@pytest.fixture(scope='module')
def ckpt_dir(tmp_path_factory):
    from skypilot_tpu.models import llama, weights
    cfg = dataclasses.replace(llama.CONFIGS['debug'], max_seq_len=64)
    model = llama.LlamaModel(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(7),
                                 jnp.zeros((1, 8), jnp.int32))
    out = tmp_path_factory.mktemp('served_ckpt')
    weights.save_hf_checkpoint(cfg, params, str(out))
    return str(out)


def _post(url, payload, timeout=300):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={'Content-Type': 'application/json'})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


@pytest.mark.usefixtures('one_device_children')
def test_served_checkpoint_matches_transformers(ckpt_dir):
    transformers = pytest.importorskip('transformers')
    torch = pytest.importorskip('torch')

    port = _free_port()
    env = {**os.environ, 'PYTHONPATH': REPO, 'JAX_PLATFORMS': 'cpu'}
    proc = subprocess.Popen(
        [sys.executable, '-m', 'skypilot_tpu.infer.server',
         '--checkpoint', ckpt_dir, '--port', str(port),
         '--num-slots', '2', '--max-seq-len', '64',
         # f32 for exact greedy parity with transformers: the debug
         # model's random weights leave logits nearly tied, so bf16
         # rounding flips argmax (real trained weights serve in bf16).
         '--dtype', 'float32'],
        env=env, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    base = f'http://127.0.0.1:{port}'
    try:
        deadline = time.time() + 180
        ready = False
        while time.time() < deadline and proc.poll() is None:
            try:
                with urllib.request.urlopen(f'{base}/health', timeout=2):
                    ready = True
                    break
            except OSError:
                time.sleep(0.5)
        assert ready, ('server never became healthy: '
                       + (proc.stdout.read() if proc.poll() is not None
                          else 'still starting'))

        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, 250, n).tolist() for n in (5, 12, 21)]
        served = []
        for p in prompts:
            r = _post(f'{base}/generate',
                      {'tokens': p, 'max_tokens': 8, 'temperature': 0})
            served.append(r['tokens'])

        hf = transformers.LlamaForCausalLM.from_pretrained(ckpt_dir)
        hf.eval()
        for p, got in zip(prompts, served):
            with torch.no_grad():
                full = hf.generate(
                    torch.tensor([p]), max_new_tokens=8,
                    do_sample=False).numpy()[0].tolist()
            assert full[len(p):] == got, (p, full[len(p):], got)
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
