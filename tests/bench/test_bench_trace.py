"""The trace reducer on a small trace recorded on a TPU v5e: three requests
served through the Router at small widths, three prefill and seven decode
calls (data/tiny_serving.xplane.pb.gz, made by the chip's JAX profiler)."""
import gzip
import shutil
from pathlib import Path

import pytest

import _bench_path  # noqa: F401
from bench import program, trace

DATA = Path(__file__).parent / "data" / "tiny_serving.xplane.pb.gz"


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with gzip.open(DATA) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace.reduce(str(path), 1.0)


def test_modules_are_found_by_step_name(tiny):
    assert tiny.n_devices == 1 and len(tiny.modules) == 85
    assert len(tiny.module_events(program.PREFILL_MODULE)) == 3
    assert len(tiny.module_events(program.DECODE_MODULE)) == 7
    assert sum(d for _, d in tiny.module_events(program.DECODE_MODULE)) \
        == pytest.approx(0.000215584, rel=1e-9)
    assert sum(d for _, d in tiny.module_events(program.PREFILL_MODULE)) \
        == pytest.approx(0.000101621, rel=1e-9)


def test_busy_time_is_the_union_of_operations(tiny):
    assert tiny.busy_s == pytest.approx(0.0003475769999998074, rel=1e-9)
    assert all(a[1] < b[0] for a, b in zip(tiny.busy, tiny.busy[1:]))
    assert tiny.busy_s < sum(tiny.ops.values())    # ops nest and overlap


def test_breakdown(tiny):
    ops = trace.top_ops(tiny, 3)
    assert [n for n, _ in ops] == ["%copy-start", "%copy-start.20",
                                   "%while.4"]
    gaps = trace.idle_gaps(tiny, 3)
    assert gaps[0][0] == "bench.step"
    assert gaps[0][1] == pytest.approx(0.139057577, rel=1e-9)
    assert [g for _, g in gaps] == sorted((g for _, g in gaps), reverse=True)


def test_merge():
    assert trace.merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == \
        [(0, 2.5), (3, 4)]
