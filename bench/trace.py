"""Reduction of one profiler trace to what the metric readers read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``. Its
chip planes (``/device:TPU:<n>``; other ``/device:`` planes are not chips)
carry an ``XLA Modules`` line, one event
per executed program named ``<jit name>(<id>)``, an ``XLA Ops`` line, one
event per operation (nested: a loop's event spans its body's), and an
``Async XLA Ops`` line for copies the device runs beside them. The device
is busy where any operation of either line runs. The host plane carries the harness's own
``TraceAnnotation`` spans. All times are in nanoseconds on the trace's one
clock; this module turns them into seconds.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]          # (start_s, end_s)


@dataclasses.dataclass
class Trace:
    window_s: float                                 # traced host window
    modules: List[Tuple[str, float, float]]         # (name, start_s, dur_s)
    ops: Dict[str, float]                           # op name -> device s
    busy: List[Interval]                            # merged busy intervals
    host: List[Tuple[str, float, float]]            # harness spans
    n_devices: int

    @property
    def busy_s(self) -> float:
        return sum(e - s for s, e in self.busy) / max(1, self.n_devices)

    def module_events(self, prefix: str) -> List[Tuple[float, float]]:
        """(start_s, dur_s) of every execution of the jitted step whose XLA
        module name starts with ``prefix``, in time order."""
        return sorted((s, d) for n, s, d in self.modules
                      if n.split("(")[0] == prefix)


def merge(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def reduce(path: str, window_s: float) -> Optional[Trace]:
    """The trace at ``path`` reduced; None where it holds no device plane
    (a CPU run), so device metrics are left out rather than read as 0."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    modules: List[Tuple[str, float, float]] = []
    ops: Dict[str, float] = collections.defaultdict(float)
    busy: List[Interval] = []
    host: List[Tuple[str, float, float]] = []
    n_dev = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            n_dev += 1
            dev_busy: List[Interval] = []
            mod_busy: List[Interval] = []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        s, d = e.start_ns * 1e-9, e.duration_ns * 1e-9
                        modules.append((e.name, s, d))
                        mod_busy.append((s, s + d))
                elif line.name in ("XLA Ops", "Async XLA Ops"):
                    for e in line.events:
                        s, d = e.start_ns * 1e-9, e.duration_ns * 1e-9
                        ops[e.name.split(" = ")[0]] += d
                        dev_busy.append((s, s + d))
            busy += merge(dev_busy or mod_busy)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.name, e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9))
    if not n_dev:
        return None
    return Trace(window_s=window_s, modules=modules, ops=dict(ops),
                 busy=sorted(busy), host=host, n_devices=n_dev)


def idle_gaps(t: Trace, top: int = 10) -> List[Tuple[str, float]]:
    """The longest gaps between busy intervals, each named by the harness
    span that overlaps it most (what the host was doing)."""
    gaps = [(b[0] - a[1], a[1], b[0])
            for a, b in zip(t.busy, t.busy[1:]) if b[0] > a[1]]
    gaps.sort(reverse=True)
    out = []
    for length, s, e in gaps[:top]:
        best, label = 0.0, "no harness span"
        for name, hs, hd in t.host:
            ov = min(e, hs + hd) - max(s, hs)
            if ov > best:
                best, label = ov, name
        out.append((label, length))
    return out


def top_ops(t: Trace, top: int = 10) -> List[Tuple[str, float]]:
    return sorted(t.ops.items(), key=lambda kv: -kv[1])[:top]
