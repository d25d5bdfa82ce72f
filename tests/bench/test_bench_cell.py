"""A CPU rehearsal of a cell at smoke widths through the harness's internal
entry: the open loop, the metric readers, the check and the result's
schema. The check's limits here are for these smoke sizes on the CPU
(smoke_sizes.SMOKE_LIMITS); the cells' own limits are in their
configuration files."""
import json
import time

import pytest

import _bench_path  # noqa: F401
from smoke_sizes import SMOKE_CELL, SMOKE_LIMITS, SMOKE_MIX, smoke_config
from bench import common, harness
from bench import run as bench_run



def smoke_run(family, monkeypatch, seed=2 ** 33 + 5, metrics=None):
    return harness.run_cell(
        smoke_config(family, SMOKE_LIMITS[family]), SMOKE_MIX, SMOKE_CELL,
        seed, 2.0, False,
        metrics if metrics is not None else common.benchmark()["end_to_end"],
        t_process=time.monotonic(), log=lambda s: None)


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_smoke_cell_is_correct_and_reports_every_metric(family, monkeypatch):
    res = smoke_run(family, monkeypatch)
    assert res["correct"], res["checks"]
    assert set(res["readings"]) == {
        "lost", "short", "max_logit_gap", "mean_logit_gap",
        "first_token_max_gap"} | ({"untied_mean_gap"} if family == "moe"
                                  else set())
    assert res["failed"] == 0 and res["attempted"] == 8
    names = [m["name"] for m in common.benchmark()["end_to_end"]]
    assert list(res["metrics"]) == names
    for m in common.benchmark()["end_to_end"]:
        v = res["metrics"][m["name"]]
        assert v == {"value": v["value"], "unit": m["unit"]}
        assert v["value"] > 0


def test_result_line_schema(monkeypatch):
    res = bench_run.finite(smoke_run("dense", monkeypatch))
    line = json.loads(json.dumps(res))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "readings", "checks"]
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"
    assert set(line["readings"]) == {"lost", "short", "max_logit_gap",
                                     "mean_logit_gap", "first_token_max_gap"}
    for name, v in line["checks"].items():
        assert set(v) == {"value", "limit"}, name
        assert v["value"] == line["readings"][name]
