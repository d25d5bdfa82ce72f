"""The span and scope reduction (bench/spans.py) and the metrics that read it.

Two traces recorded on a TPU v5e: ``data/tiny_serving.xplane.pb.gz``, taken
before the program had spans or named scopes (three requests through the
Router, a dense model at small widths), and ``data/tiny_spans.xplane.pb.gz``
(``make_span_trace.py``: a DeepSeek-MoE stage at small widths serving six
requests, with the program's spans and scopes and a ``bench.step`` span
around each ``Router.step``)."""
import gzip
import shutil
import types
from pathlib import Path

import pytest

import _bench_path  # noqa: F401
from bench import common, program, spans, trace

DATA = Path(__file__).parent / "data"
METRICS = ("decode_attn_ms", "prefill_moe_ms", "decode_host_ms")
# tiny_spans: 14 decode calls of 0.040 ms and 6 prefill calls of 0.069 ms
PINNED = {"decode_attn_ms": 0.009321499999987477,
          "prefill_moe_ms": 0.03474599999992468,
          "decode_host_ms": 6.832094285714316}


def _unpack(tmp_path_factory, name):
    d = tmp_path_factory.mktemp(name) / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    path = d / f"{name}.xplane.pb"
    with gzip.open(DATA / f"{name}.xplane.pb.gz") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


@pytest.fixture(scope="module")
def before(tmp_path_factory):
    return _unpack(tmp_path_factory, "tiny_serving")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    return _unpack(tmp_path_factory, "tiny_spans")


def _read(path, monkeypatch):
    """Each new metric as the harness reads it after a traced run whose
    trace is ``path``."""
    monkeypatch.setattr(spans, "TRACES", path.parents[2])
    run = types.SimpleNamespace(trace=trace.reduce(str(path), 1.0))
    return {m: common.metric_reader(m).read(run) for m in METRICS}


def test_wire_reader_finds_scope_paths(before):
    found = set(spans.tf_ops(str(before)).values())
    assert "jit(decode_step)/while/body/closed_call/dot_general" in found


def test_a_trace_without_spans_or_scopes_reads_nothing(before, monkeypatch):
    assert _read(before, monkeypatch) == dict.fromkeys(METRICS)


def test_another_runs_trace_is_not_read(traced, monkeypatch):
    monkeypatch.setattr(spans, "TRACES", traced.parents[2])
    t = trace.reduce(str(traced), 1.0)
    t.busy = t.busy[1:]
    assert spans.load(types.SimpleNamespace(trace=t)) is None
    assert spans.load(types.SimpleNamespace(trace=None)) is None


def test_metrics_are_pinned(traced, monkeypatch):
    got = _read(traced, monkeypatch)
    assert got == pytest.approx(PINNED, rel=1e-9)


def test_scopes_fit_in_their_step(traced):
    s = spans.reduce(str(traced))
    for module, scopes in ((program.DECODE_MODULE,
                            ("attn", "moe", "logits", "sample")),
                           (program.PREFILL_MODULE,
                            ("attn", "moe", "moe/router", "moe/experts",
                             "moe/shared", "logits", "sample"))):
        jit = spans.jit_name(module)
        runs = s.executions(jit)
        step_ms = sum(e - a for a, e in runs) / len(runs) * 1e3
        for scope in scopes:
            assert 0 < s.scope_ms(jit, scope) <= step_ms, (jit, scope)
        parts = [s.scope_s(jit, x) for x in ("moe/router", "moe/experts",
                                              "moe/shared")]
        if jit == "prefill_step":
            assert sum(parts) <= s.scope_s(jit, "moe") * (1 + 1e-9)
    assert s.scope_ms("decode_step", "no_such_scope") is None


def test_idle_attribution_sums_to_idle_time(traced):
    s = spans.reduce(str(traced))
    per, gaps = spans.attribute(s)
    window = s.window[1] - s.window[0]
    idle = window - s.busy_within(*s.window)
    assert sum(per.values()) == pytest.approx(idle, rel=1e-9)
    assert sum(g for _, g, _ in gaps) == pytest.approx(idle, rel=1e-9)
    assert set(per) >= {"engine.decode.wait", "opq.block", "python.gc",
                        "bench.step"}
    report = spans.report(s)
    assert "idle under program spans" in report


def test_busy_within_idle_and_attribution():
    s = spans.Spans(window=(0, 10), busy=[(1, 2), (4, 6)], busy_s=3,
                    modules=[], ops={}, host=[("engine.decode", 1.5, 5.0)])
    assert s.busy_within(1.5, 5.0) == pytest.approx(1.5)
    assert s.host_ms("engine.decode") == pytest.approx(2000.0)
    assert s.idle() == [(0, 1), (2, 4), (6, 10)]
    per, _ = spans.attribute(s)
    assert per == pytest.approx({None: 5.0, "engine.decode": 2.0})
