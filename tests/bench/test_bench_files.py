"""BENCHMARK.json against the benchmark's contract, and every file it names
found by name; the command refuses to run without a TPU, and outside a
checkout that holds the program."""
import json
import os
import re
import shutil
import subprocess
import sys

import _bench_path  # noqa: F401
from bench import common

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_benchmark_json_keys_and_names():
    bm = common.benchmark()
    assert list(bm) == ["command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"]
    assert bm["command"] == ["python3", "bench/run.py"]
    assert 1 <= bm["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bm[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"] == f"bench/configs/{c['name']}.json"
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
    e2e = {m["name"] for m in bm["end_to_end"]}
    assert "setup_s" in e2e
    for m in bm["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
        assert m["source"] in {"host_clock", "device_trace"}
    for m in bm["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and m["source"] in SOURCES
        assert UNIT.match(m["unit"]) and m["better"] in {"lower", "higher"}


def test_every_named_file_is_found():
    bm = common.benchmark()
    for c in bm["configs"]:
        cfg = common.config(c["name"])
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert (common.BENCH / "references" / f"{cfg['family']}.py").exists()
        assert (common.BENCH / "families" / f"{cfg['family']}.py").exists()
        fam = common.family(cfg["family"])
        for f in ("leaves", "arch", "token_matmul_flops", "attention_flops"):
            assert callable(getattr(fam, f, None)), (cfg["family"], f)
    for w in bm["workloads"]:
        common.config(w["config"])
        assert common.mix(w["traffic"])["arrivals"] == "poisson"
        assert common.cell(w["name"])["rate_per_s"] > 0
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert callable(common.metric_reader(m["name"]).read)


def test_peaks_are_keyed_by_device_kind():
    peaks = common.load_json(common.BENCH / "peaks.json")
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bfloat16_flops"] == 197e12 and v5e["int8_ops"] == 393e12


def _cmd(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sc2-chat", "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_command_refuses_without_a_tpu():
    out = _cmd(common.ROOT)
    assert out.returncode != 0
    assert "TPU" in out.stderr and out.stdout.strip() == ""


def test_command_refuses_in_a_bare_checkout(tmp_path):
    bm = common.benchmark()
    shutil.copy(common.ROOT / "BENCHMARK.json", tmp_path)
    for p in bm["paths"]:
        shutil.copytree(common.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _cmd(tmp_path)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert json.loads((tmp_path / "BENCHMARK.json").read_text()) == bm


WIDTH = re.compile(r"(_dim|_rank|_size|_factor)$|^num_experts_per_tok$")


def test_reduced_cuts_no_width_and_departures_stand_apart():
    for c in common.benchmark()["configs"]:
        cfg = common.config(c["name"])
        assert not [k for k in cfg["reduced"] if WIDTH.search(k)], c["name"]
        # what the program cannot run as published is not a cut
        assert not set(cfg.get("departs", {})) & set(cfg["reduced"])
