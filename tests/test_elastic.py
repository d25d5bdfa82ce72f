"""Elastic restart end-to-end: checkpoint on one mesh, reload + resume on a
DIFFERENT device count (the node-failure recovery path, DESIGN.md §7)."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow          # 8-device subprocess restart (minutes)

ROOT = Path(__file__).resolve().parents[1]
ENV = dict(os.environ, PYTHONPATH=str(ROOT / "src"))

_WORKER = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import load_checkpoint, save_checkpoint, latest_step
from repro.configs import get_config
from repro.distributed import sharding as shd
from repro.distributed.sharding import make_mesh
from repro.ft import plan_elastic_mesh
from repro.models import init_model, steps, param_specs
from repro.optim import adamw_init

ckpt = sys.argv[1]
cfg = get_config("tinyllama_1_1b").smoke()
batch = {"tokens": jnp.arange(8*16, dtype=jnp.int32).reshape(8,16) % cfg.vocab,
         "labels": (jnp.arange(8*16, dtype=jnp.int32).reshape(8,16)+1) % cfg.vocab}

# ---- phase 1: train 2 steps on a (4, 2) mesh, checkpoint ----
mesh1 = make_mesh((4, 2), ("data", "model"))
with shd.use_mesh(mesh1):
    params = init_model(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    ts = jax.jit(steps.make_train_step(cfg))
    for s in range(2):
        params, opt, m = ts(params, opt, batch, jnp.asarray(s + 5, jnp.int32))
    save_checkpoint(ckpt, 2, {"params": params, "opt": opt})
    loss_before = float(m["loss"])

# ---- phase 2: "2 hosts died" -> elastic plan -> resume on (2, 2) mesh ----
plan = plan_elastic_mesh(n_surviving_hosts=1, chips_per_host=4,
                         model_parallel=2, old_data_parallel=4, global_batch=8)
assert plan["mesh_shape"] == (2, 2), plan
mesh2 = make_mesh(plan["mesh_shape"], plan["axis_names"])
with shd.use_mesh(mesh2):
    like_p = jax.eval_shape(lambda k: init_model(cfg, k), jax.random.PRNGKey(0))
    like_o = jax.eval_shape(adamw_init, like_p)
    state = load_checkpoint(ckpt, 2, {"params": like_p, "opt": like_o})
    params2, opt2 = state["params"], state["opt"]
    ts2 = jax.jit(steps.make_train_step(cfg))
    params2, opt2, m2 = ts2(params2, opt2, batch, jnp.asarray(7, jnp.int32))
    print("RESUMED_LOSS", float(m2["loss"]))
    assert np.isfinite(float(m2["loss"]))
print("ELASTIC_OK grad_accum=%d" % plan["grad_accum"])
"""


def test_elastic_restart_across_meshes(tmp_path):
    r = subprocess.run([sys.executable, "-c", _WORKER, str(tmp_path / "ck")],
                       capture_output=True, text=True, env=ENV, timeout=480,
                       cwd=ROOT)
    assert r.returncode == 0, (r.stdout[-800:], r.stderr[-2500:])
    assert "ELASTIC_OK" in r.stdout
