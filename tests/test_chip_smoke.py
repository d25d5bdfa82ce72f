"""chip_smoke.py's phases at smoke width on the CPU, its refusal to run
anywhere but on a TPU, and the serving entry points on four virtual CPU
devices (each Engine on one device, Router host i on device i)."""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_config
from repro.models import init_model

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model(smoke):
    cfg = get_config(smoke.ARCH).smoke()
    return cfg, init_model(cfg, jax.random.PRNGKey(smoke.SEED))


def test_phase_http_matches_one_at_a_time(smoke, model):
    t = smoke.TRAFFIC
    facts = smoke.phase_http(*model)
    assert facts["requests"] == t.n_requests
    assert facts["tokens"] == t.n_requests * t.gen


def test_phase_kernel_int8_weights(smoke, model):
    # the kernel runs in interpret mode here: a shorter table and generation
    # keep its grid loop to seconds
    traffic = smoke.Traffic(prompt_lens=(32, 128), gen=8, max_seq_len=256)
    cfg, params = smoke.quantize(*model)
    facts = smoke.phase_kernel(cfg, params, traffic)
    assert facts["kernel_max_abs_diff"] <= smoke.KERNEL_TOL
    assert facts["flags"]["decode"] > 0


def test_main_refuses_without_a_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


ROUTER_REHEARSAL = """
import jax
import chip_smoke as cs
from repro.models import init_model
cfg = cs.get_config(cs.ARCH).smoke()
facts = cs.phase_router(cfg, init_model(cfg, jax.random.PRNGKey(cs.SEED)),
                        jax.devices())
assert facts["handoffs"], facts
print("router rehearsal", facts)
"""


@pytest.mark.parametrize("argv,expect", [
    (["scripts/http_smoke.py"], "http_smoke: ALL PASS"),
    (["-m", "repro.launch.serve", "--arch", "tinyllama-1.1b", "--smoke",
      "--requests", "4", "--prompt-len", "8", "--gen", "4", "--slots", "2",
      "--hosts", "4"], "host 3: 1 done"),
    (["-c", ROUTER_REHEARSAL], "router rehearsal"),
], ids=["http_smoke", "serve_cli_hosts4", "chip_smoke_router"])
def test_entry_points_on_four_devices(argv, expect):
    """With four devices visible, the entry points still build one-device
    engines (a mesh over every device would be refused by the Engine)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), str(ROOT)]
                   + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    out = subprocess.run([sys.executable, *argv], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-4000:]
    assert expect in out.stdout
