"""The check comes out false when the timed path is broken underneath: the
run is driven as the command drives it, past the look for a chip, with a
fault planted in the program's decode or prefill step, and judged on the
numbers the family's configuration file compares."""
import time

import pytest

import _bench_path  # noqa: F401
from faults import plant
from smoke_sizes import SMOKE_CELL, SMOKE_LIMITS, SMOKE_MIX, smoke_config
from bench import harness


@pytest.mark.parametrize("family", ["dense", "moe"])
@pytest.mark.parametrize("fault", ["token", "state", "prefill"])
def test_fault_makes_the_run_incorrect(family, fault, monkeypatch):
    plant(monkeypatch, fault)
    res = harness.run_cell(
        smoke_config(family, SMOKE_LIMITS[family]), SMOKE_MIX, SMOKE_CELL,
        2 ** 33 + 5, 2.0, False, [], t_process=time.monotonic(),
        log=lambda s: None)
    assert not res["correct"]
    assert set(res["checks"]) == {"lost", "short"} | set(SMOKE_LIMITS[family])
    assert any(res["checks"][k]["value"] > lim
               for k, lim in SMOKE_LIMITS[family].items()), res["checks"]
