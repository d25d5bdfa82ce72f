"""End-to-end system tests: train -> crash -> resume; quantized serving;
multi-device sharding consistency (subprocess with forced host devices);
dry-run machinery on a small arch."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow          # subprocess end-to-end runs (minutes)

ROOT = Path(__file__).resolve().parents[1]
ENV = dict(os.environ, PYTHONPATH=str(ROOT / "src"))


def _run(args, env=ENV, timeout=480):
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, env=env, timeout=timeout, cwd=ROOT)


def test_train_crash_resume(tmp_path):
    base = ["-m", "repro.launch.train", "--arch", "tinyllama-1.1b", "--smoke",
            "--steps", "8", "--batch", "2", "--seq", "16",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "3", "--log-every", "2"]
    r1 = _run(base + ["--fail-at-step", "7"])
    assert r1.returncode == 42, r1.stderr[-2000:]
    assert "SIMULATED FAILURE" in r1.stdout
    r2 = _run(base)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed from checkpoint step 6" in r2.stdout
    assert "done: 8 steps" in r2.stdout


def test_serve_quantized_end_to_end():
    r = _run(["-m", "repro.launch.serve", "--arch", "tinyllama-1.1b", "--smoke",
              "--quantize", "serve", "--requests", "2",
              "--prompt-len", "4", "--gen", "4"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "Tensorizer W8A8" in r.stdout
    assert "decode steps" in r.stdout


def test_multi_device_sharded_training_consistent():
    """Forward/train on a (2,4) mesh must produce the same loss as 1 device —
    run in a subprocess with 8 forced host devices."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.distributed import sharding as shd
from repro.distributed.sharding import make_mesh
from repro.models import init_model, steps
from repro.optim import adamw_init

cfg = get_config("deepseek_moe_16b").smoke().replace(n_experts=4, topk=2)
batch = {"tokens": jnp.arange(8*16, dtype=jnp.int32).reshape(8,16) % cfg.vocab,
         "labels": (jnp.arange(8*16, dtype=jnp.int32).reshape(8,16)+1) % cfg.vocab}
losses = []
for shape, names in [((1,), ("data",)), ((2, 4), ("data", "model"))]:
    mesh = make_mesh(shape, names)
    with shd.use_mesh(mesh):
        params = init_model(cfg, jax.random.PRNGKey(0))
        opt = adamw_init(params)
        ts = jax.jit(steps.make_train_step(cfg))
        _, _, m = ts(params, opt, batch, jnp.zeros((), jnp.int32))
        losses.append(float(m["loss"]))
print("LOSSES", losses)
assert abs(losses[0] - losses[1]) < 0.05, losses
print("SHARDING_CONSISTENT")
"""
    r = _run(["-c", code])
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    assert "SHARDING_CONSISTENT" in r.stdout


def test_dryrun_cell_small_arch():
    """The dry-run machinery end-to-end on the smallest cell (subprocess —
    it forces 512 devices). Proves lower+compile+cost+collectives pipeline."""
    r = _run(["-m", "repro.launch.dryrun", "--arch", "xlstm-125m",
              "--shape", "decode_32k"], timeout=560)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "[dryrun] OK" in r.stdout
    rec = json.loads((ROOT / "reports" / "dryrun" /
                      "xlstm_125m_decode_32k_pod_16x16.json").read_text())
    assert rec["status"] == "ok"
    assert rec["n_devices"] == 256
    assert rec["flops"] > 0
