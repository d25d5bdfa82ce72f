"""The program's own spans and named scopes in a traced run's profile.

    python3 -m bench.spans <trace_dir>

prints where the device's idle time went: each long gap with the innermost
host span over it, the share of idle time under each span name, and the
share under no span. Take the trace of a server with ``jax.profiler``
(``start_trace(dir)`` ... ``stop_trace()``); a benchmark run with
``--trace 1`` leaves its trace in ``.bench_trace/<cell>``.

Two readings, both on the trace's one clock:

- host spans: ``jax.profiler.TraceAnnotation`` events on the host planes.
  The program's are named for its layers (``router.*``, ``engine.*``,
  ``opq.*``, ``python.gc``: ``serving/metrics.py`` ``span``), the
  harness's start with ``bench.``. They are read through ``ProfileData``.
- named scopes: each XLA op's ``tf_op`` stat holds its scope path, e.g.
  ``jit(decode_step)/while/body/closed_call/attn/dot_general:``. The stat
  sits in the device plane's ``event_metadata`` map, which ``ProfileData``
  does not expose, so :func:`tf_ops` reads it with a small reader of the
  protobuf wire format (``XPlane`` fields 4 and 5; the bytes of ``lines``
  are skipped). An op's metadata is found by its name and the program id of
  the module execution it runs in.

A scope's device time is the union of the intervals of the ops on the
``XLA Ops`` line whose ``tf_op`` starts with the step's jit name and holds
the scope as consecutive path segments. Ops on ``Async XLA Ops`` are left
out: copies there can span a whole loop. The device is busy where an op of
either line runs, as ``bench/trace.py`` counts it. The idle attribution
covers the stretch from the first host span to the last (program or
harness), which leaves out the profiler's own start and stop. This module
imports nothing from the program.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import os
import sys
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

from bench.trace import Interval, merge

PROGRAM = ("router.", "engine.", "opq.", "python.")
HARNESS = "bench."
TRACES = Path(__file__).resolve().parents[1] / ".bench_trace"


# ---------------------------------------------------------------- wire format

def _varint(b: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b: bytes, i: int = 0,
            end: Optional[int] = None) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    (start, end) slice for a length-delimited field, bytes for fixed ones."""
    end = len(b) if end is None else end
    while i < end:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
            yield field, v
        elif wire == 2:
            n, i = _varint(b, i)
            yield field, (i, i + n)
            i += n
        elif wire == 1:
            yield field, b[i:i + 8]
            i += 8
        elif wire == 5:
            yield field, b[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")


def tf_ops(path: str) -> Dict[Tuple[str, int], str]:
    """(op name, program id) -> scope path (``tf_op`` without its trailing
    ``:``) for every op of the trace's TPU planes that carries one."""
    with open(path, "rb") as f:
        b = f.read()
    out: Dict[Tuple[str, int], str] = {}
    for field, plane in _fields(b):
        if field != 1:                                   # XSpace.planes
            continue
        name, metas, stat_names = "", [], {}
        for pf, v in _fields(b, *plane):
            if pf == 2:
                name = b[v[0]:v[1]].decode()
            elif pf == 4:                                # event_metadata
                metas.append(v)
            elif pf == 5:                                # stat_metadata
                for ef, ev in _fields(b, *v):
                    if ef == 2:
                        sid, sname = 0, ""
                        for mf, mv in _fields(b, *ev):
                            if mf == 1:
                                sid = mv
                            elif mf == 2:
                                sname = b[mv[0]:mv[1]].decode()
                        stat_names[sid] = sname
        if not name.startswith("/device:TPU:"):
            continue
        for entry in metas:
            for ef, ev in _fields(b, *entry):
                if ef != 2:
                    continue
                op, tf_op, program = "", None, None
                for mf, mv in _fields(b, *ev):
                    if mf == 2:
                        op = b[mv[0]:mv[1]].decode()
                    elif mf == 5:                        # XEventMetadata.stats
                        sid, val = 0, None
                        for sf, sv in _fields(b, *mv):
                            if sf == 1:
                                sid = sv
                            elif sf == 5:                # str_value
                                val = b[sv[0]:sv[1]].decode()
                            elif sf in (3, 4):           # uint64 / int64
                                val = sv
                            elif sf == 7:                # ref_value
                                val = stat_names.get(sv)
                        stat = stat_names.get(sid)
                        if stat == "tf_op":
                            tf_op = val
                        elif stat == "program_id":
                            program = val
                if tf_op and program is not None:
                    out[(op, int(program))] = tf_op.rstrip(":")
    return out


# ---------------------------------------------------------------- reduction

def jit_name(module: str) -> str:
    """An XLA module's jit name: ``jit_decode_step(123)`` -> ``decode_step``."""
    name = module.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


@dataclasses.dataclass
class Spans:
    window: Interval                                 # first to last span
    busy: List[Interval]                             # merged, every chip
    busy_s: float                                    # per chip, trace.py's
    modules: List[Tuple[str, float, float]]          # (jit name, start, end)
    ops: Dict[str, List[Interval]]                   # tf_op -> XLA Ops events
    host: List[Tuple[str, float, float]]             # (span, start, end)

    def executions(self, jit: str) -> List[Interval]:
        return sorted((s, e) for n, s, e in self.modules if n == jit)

    def scope_s(self, jit: str, scope: str) -> Optional[float]:
        """Device seconds under ``scope`` (``attn``, ``moe/experts``) in the
        programs jitted as ``jit``; None where no op carries the scope."""
        want = scope.split("/")
        found: List[Interval] = []
        for tf_op, ivs in self.ops.items():
            path = tf_op.split("/")
            if path[0] != f"jit({jit})":
                continue
            if any(path[i:i + len(want)] == want
                   for i in range(1, len(path) - len(want) + 1)):
                found += ivs
        return sum(e - s for s, e in merge(found)) if found else None

    def scope_ms(self, jit: str, scope: str) -> Optional[float]:
        """Mean device ms under ``scope`` per execution of ``jit``."""
        n = len(self.executions(jit))
        t = self.scope_s(jit, scope)
        return None if t is None or not n else t / n * 1e3

    def busy_within(self, s: float, e: float) -> float:
        starts = [a for a, _ in self.busy]
        i = max(0, bisect.bisect_right(starts, s) - 1)
        out = 0.0
        for a, b in self.busy[i:]:
            if a >= e:
                break
            out += max(0.0, min(b, e) - max(a, s))
        return out

    def host_ms(self, name: str) -> Optional[float]:
        """Mean over the ``name`` spans of the span's length less the device
        busy time inside it, in ms; None where the trace holds none."""
        own = [(s, e) for n, s, e in self.host if n == name]
        if not own:
            return None
        return sum((e - s) - self.busy_within(s, e)
                   for s, e in own) / len(own) * 1e3

    def idle(self) -> List[Interval]:
        """The stretches of the window in which no op runs on a chip."""
        lo, hi = self.window
        out, t = [], lo
        for s, e in self.busy:
            if s >= hi:
                break
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if hi > t:
            out.append((t, hi))
        return out


def reduce(path: str) -> Optional[Spans]:
    """The trace at ``path`` reduced; None where it holds no TPU plane."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    meta = tf_ops(path)
    busy: List[Interval] = []
    modules: List[Tuple[str, float, float]] = []
    ops: Dict[str, List[Interval]] = {}
    host: List[Tuple[str, float, float]] = []
    lo, hi, n_dev = float("inf"), float("-inf"), 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            n_dev += 1
            dev_busy: List[Interval] = []
            mod_busy: List[Interval] = []
            runs: List[Tuple[float, float, int]] = []
            op_events = []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        s, d = e.start_ns * 1e-9, e.duration_ns * 1e-9
                        mod_busy.append((s, s + d))
                        program = e.name.split("(")[-1].rstrip(")")
                        runs.append((s, s + d, int(program)
                                     if program.isdigit() else -1))
                        modules.append((jit_name(e.name), s, s + d))
                elif line.name in ("XLA Ops", "Async XLA Ops"):
                    for e in line.events:
                        s, d = e.start_ns * 1e-9, e.duration_ns * 1e-9
                        dev_busy.append((s, s + d))
                        if line.name == "XLA Ops":
                            op_events.append((e.name, s, s + d))
            runs.sort()
            starts = [r[0] for r in runs]
            for name, s, e in op_events:
                i = bisect.bisect_right(starts, s) - 1
                if i < 0 or s > runs[i][1]:
                    continue
                tf_op = meta.get((name, runs[i][2]))
                if tf_op is not None:
                    ops.setdefault(tf_op, []).append((s, e))
            merged = merge(dev_busy or mod_busy)
            busy += merged
            if merged:
                lo, hi = min(lo, merged[0][0]), max(hi, merged[-1][1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    s, d = e.start_ns * 1e-9, e.duration_ns * 1e-9
                    lo, hi = min(lo, s), max(hi, s + d)
                    if e.name.startswith(PROGRAM + (HARNESS,)):
                        host.append((e.name, s, s + d))
    if not n_dev:
        return None
    if host:         # the serving itself, without the profiler's start/stop
        lo, hi = min(h[1] for h in host), max(h[2] for h in host)
    busy_s = sum(e - s for s, e in busy) / n_dev
    return Spans(window=(lo, hi), busy=merge(busy), busy_s=busy_s,
                 modules=modules, ops=ops, host=sorted(host, key=lambda h: h[1]))


@functools.lru_cache(maxsize=1)
def _reduced(path: str, mtime_ns: int, size: int) -> Optional[Spans]:
    return reduce(path)


def newest(root: Optional[Path] = None) -> Optional[str]:
    """The newest ``*.xplane.pb`` under ``root`` (``.bench_trace/``)."""
    root = TRACES if root is None else root
    found = glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def load(run) -> Optional[Spans]:
    """The traced run's spans, for a metric reader: the newest trace under
    ``.bench_trace/``, confirmed as the run's by its device busy time; None
    in an untraced run or where the two disagree."""
    if run.trace is None:
        return None
    path = newest()
    if path is None:
        return None
    st = os.stat(path)
    s = _reduced(path, st.st_mtime_ns, st.st_size)
    if s is None or abs(s.busy_s - run.trace.busy_s) > 1e-9 * max(
            1.0, run.trace.busy_s):
        return None
    return s


# ---------------------------------------------------------- idle attribution

def attribute(s: Spans) -> Tuple[Dict[Optional[str], float],
                                 List[Tuple[float, float, Optional[str]]]]:
    """Every idle second of the window (first to last span) under the
    innermost host span over it: the covering span that opened last, of any
    thread; ``None`` where no span covers it. Returns the idle seconds per
    span name (``None`` included; they sum to the window's idle time) and
    each idle gap as (start, length, the span holding most of it)."""
    gaps = s.idle()
    bounds = sorted({t for g in gaps for t in g}
                    | {t for _, a, b in s.host for t in (a, b)})
    per: Dict[Optional[str], float] = {}
    per_gap: List[Dict[Optional[str], float]] = [{} for _ in gaps]
    active: List[Tuple[str, float, float]] = []
    j = g = 0
    for a, b in zip(bounds, bounds[1:]):
        # every span and gap edge is a bound: a span covers [a, b] whole
        # or not at all, and so does a gap
        while j < len(s.host) and s.host[j][1] <= a:
            active.append(s.host[j])
            j += 1
        active = [h for h in active if h[2] >= b]
        while g < len(gaps) and gaps[g][1] <= a:
            g += 1
        if g == len(gaps):
            break
        if gaps[g][0] > a:
            continue
        label = max(active, key=lambda h: (h[1], -h[2]))[0] if active \
            else None
        per[label] = per.get(label, 0.0) + (b - a)
        per_gap[g][label] = per_gap[g].get(label, 0.0) + (b - a)
    out_gaps = [(gs, ge - gs, max(d, key=d.get) if d else None)
                for (gs, ge), d in zip(gaps, per_gap)]
    return per, out_gaps


def decode_gap_ms(s: Spans, jit: str = "decode_step",
                  prefill: str = "prefill_step") -> Optional[float]:
    """Mean idle-to-start gap between consecutive ``jit`` executions with no
    ``prefill`` execution between them, in ms."""
    dec = s.executions(jit)
    pre = [a for a, _ in s.executions(prefill)]
    gaps = [b[0] - a[1] for a, b in zip(dec, dec[1:])
            if not any(a[0] < p < b[0] for p in pre)]
    return sum(gaps) / len(gaps) * 1e3 if gaps else None


def report(s: Spans, top: int = 12) -> str:
    per, gaps = attribute(s)
    window = s.window[1] - s.window[0]
    idle = sum(per.values())
    busy = s.busy_within(*s.window)
    prog = sum(v for k, v in per.items() if k and not k.startswith(HARNESS))
    harness = sum(v for k, v in per.items() if k and k.startswith(HARNESS))
    pct = (lambda v: 100.0 * v / idle) if idle > 0 else (lambda v: 0.0)
    lines = [f"window (first to last span) {window:.6f} s, device busy "
             f"{busy:.6f} s, idle {idle:.6f} s ({100.0 * idle / window:.2f}%)",
             f"idle under program spans {pct(prog):.2f}%, under bench.* "
             f"spans alone {pct(harness):.2f}%, under no span "
             f"{pct(per.get(None, 0.0)):.2f}%",
             "idle per innermost span:"]
    for k, v in sorted(per.items(), key=lambda kv: -kv[1]):
        lines.append(f"  {k or '(no span)':28s} {pct(v):6.2f}%  {v:.6f} s")
    lines.append(f"longest gaps (of {len(gaps)}):")
    for gs, length, label in sorted(gaps, key=lambda x: -x[1])[:top]:
        lines.append(f"  {length * 1e3:10.3f} ms  at +{gs - s.window[0]:.3f}"
                     f" s  {label or '(no span)'}")
    for jit, scopes in (("decode_step", ("attn", "mlp", "moe", "logits",
                                         "sample")),
                        ("prefill_step", ("attn", "mlp", "moe",
                                          "moe/router", "moe/experts",
                                          "moe/shared", "logits",
                                          "sample"))):
        runs = s.executions(jit)
        if not runs:
            continue
        mean = sum(e - a for a, e in runs) / len(runs) * 1e3
        parts = [f"{sc} {s.scope_ms(jit, sc):.3f}" for sc in scopes
                 if s.scope_ms(jit, sc) is not None]
        lines.append(f"{jit}: {len(runs)} runs, {mean:.3f} ms each on the "
                     f"device; ms per run by scope: {', '.join(parts) or '-'}")
    host = s.host_ms("engine.decode")
    gap = decode_gap_ms(s)
    if host is not None:
        lines.append(f"engine.decode: host {host:.3f} ms a step outside the "
                     f"device; mean gap between decodes with no prefill "
                     f"between: {gap if gap is None else round(gap, 3)} ms")
    return "\n".join(lines)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.splitlines()[2].strip(), file=sys.stderr)
        return 2
    path = newest(Path(argv[0]))
    if path is None:
        print(f"no *.xplane.pb under {argv[0]}", file=sys.stderr)
        return 1
    s = reduce(path)
    if s is None:
        print(f"{path}: no TPU plane", file=sys.stderr)
        return 1
    print(path)
    print(report(s))
    return 0


if __name__ == "__main__":
    sys.exit(main())
