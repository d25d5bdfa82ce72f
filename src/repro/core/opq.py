"""The GPETPU runtime: OPQ / IQ dataflow task scheduler (paper §6.1, Fig. 3).

OpenCtpu semantics reproduced:

  * ``enqueue(kernel, *buffers)``     -> task id   (``openctpu_enqueue``)
  * tasks execute out-of-order, operators within a task serialize;
  * ``sync()`` / ``wait(task_id)``                  (``openctpu_sync/_wait``)

Scheduling policy (paper §6.1): after the Tensorizer rewrites a task's operator
into tile-granularity *instructions* (IQ entries), instructions that share the
same input buffer, quantization flags, and task id are pinned to the device
already holding that data (affinity — avoids re-transfer and re-quantization);
everything else is first-come-first-served onto the least-loaded device.

Production posture: the scheduler also implements *straggler mitigation* by
backup re-issue — if an instruction sits in a device lane longer than
``straggler_factor`` x the lane's moving-average service time, a backup copy is
issued to the fastest lane and whichever finishes first wins (results are
idempotent pure functions, so duplicated execution is safe). This is exercised
in tests with an injected slow executor.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from collections import defaultdict
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np


@dataclasses.dataclass
class Buffer:
    """``openctpu_buffer``: host data + dimensionality + device placement map."""

    data: Any                                  # host array (np/jnp)
    name: str = ""
    _on_device: Dict[int, Any] = dataclasses.field(default_factory=dict)

    def to_device(self, device) -> Any:
        did = device.id
        if did not in self._on_device:
            self._on_device[did] = jax.device_put(self.data, device)
        return self._on_device[did]

    @property
    def resident_devices(self) -> List[int]:
        return list(self._on_device)

    @classmethod
    def resident(cls, data: Any, device, name: str = "") -> "Buffer":
        """Wrap a pytree already living on ``device`` (no transfer): the buffer
        is born resident, so the affinity policy can pin follow-up work to the
        device that holds it. The serving engine uses this for params and the
        in-flight KV cache (serving/engine.py)."""
        buf = cls(data, name)
        buf._on_device[device.id] = data
        return buf


@dataclasses.dataclass
class Instruction:
    """One IQ entry: a pure function applied to buffers (a tile-level op)."""

    task_id: int
    fn: Callable
    buffers: Tuple[Buffer, ...]
    flags: str = ""                            # quantization method etc.
    seq: int = 0                               # order within the task (serialized)


@dataclasses.dataclass
class _Lane:
    """Per-device execution lane with service-time stats for straggler detection."""

    device: Any
    pending: int = 0
    ema_service_s: float = 1e-3

    def observe(self, dt: float) -> None:
        self.ema_service_s = 0.9 * self.ema_service_s + 0.1 * dt


class OPQ:
    """The operation-queue runtime over a set of JAX devices.

    Device-parallelism note: on the CPU container there is a single device, so
    lanes share one executor; on a real machine ``jax.devices()`` exposes all
    accelerators and lanes dispatch concurrently (JAX dispatch is async).
    """

    def __init__(
        self,
        devices: Optional[Sequence[Any]] = None,
        *,
        straggler_factor: float = 8.0,
        enable_backup_tasks: bool = True,
        executor: Optional[Callable[[Instruction, Any], Any]] = None,
    ):
        self.devices = list(devices if devices is not None else jax.devices())
        self.lanes = [_Lane(d) for d in self.devices]
        self.straggler_factor = straggler_factor
        self.enable_backup_tasks = enable_backup_tasks
        self._executor = executor or self._default_executor
        self._task_counter = itertools.count()
        self._task_futures: Dict[int, List[Future]] = defaultdict(list)
        self._task_prev: Dict[int, Future] = {}   # chains in-task serialization
        self._pool = ThreadPoolExecutor(max_workers=max(2, len(self.devices)))
        self._lock = threading.Lock()
        self.stats = {"issued": 0, "backups_issued": 0, "affinity_hits": 0}
        # per-flag instruction counts ("prefill/32", "decode", ...): the
        # audit trail callers use to assert dispatch shape — e.g. the serving
        # engine's fused admission proves zero replay decodes ever ran
        self.flag_counts: Dict[str, int] = defaultdict(int)

    # ------------------------------------------------------------------ API

    def enqueue(self, kernel: Callable, *buffers: Buffer, flags: str = "") -> int:
        """``openctpu_enqueue``: run ``kernel`` which may call :meth:`invoke`.

        The kernel executes immediately on the host (mirroring the paper: the
        kernel function body runs until it reaches ``openctpu_invoke_operator``)
        and its operator invocations are scheduled asynchronously.
        """
        task_id = next(self._task_counter)
        seq = itertools.count()

        def invoke(fn: Callable, *bufs: Buffer, flags: str = flags) -> Future:
            ins = Instruction(task_id, fn, tuple(bufs), flags, next(seq))
            return self._schedule(ins, chain=True)

        kernel(invoke, *buffers)
        # the kernel body has enqueued every instruction — drop the chain tail
        self._task_prev.pop(task_id, None)
        return task_id

    def invoke_operator(self, fn: Callable, *buffers: Buffer, flags: str = "",
                        track: bool = True) -> Future:
        """Single-operator task (``openctpu_invoke_operator`` outside a kernel).

        ``track=False`` skips the task-futures registry: the caller owns the
        returned Future and the result is not retained for ``sync()``. Long-
        running callers (the serving engine: one instruction per decode step,
        forever) must use this or the registry grows without bound."""
        task_id = next(self._task_counter)
        return self._schedule(Instruction(task_id, fn, tuple(buffers), flags),
                              track=track)

    def wait(self, task_id: int):
        """``openctpu_wait``: block until every instruction of a task finished."""
        futs = self._task_futures.get(task_id, [])
        return [f.result() for f in futs]

    def sync(self):
        """``openctpu_sync``: block until *all* tasks finished; returns results
        grouped by task id."""
        out = {}
        for tid in sorted(self._task_futures):
            out[tid] = self.wait(tid)
        return out

    # ------------------------------------------------------------ scheduling

    def _pick_lane(self, ins: Instruction) -> Tuple[_Lane, bool]:
        # Affinity (paper §6.1): same input already resident on a device ->
        # schedule there, avoiding the transfer + re-transformation.
        for b in ins.buffers:
            for did in b.resident_devices:
                for lane in self.lanes:
                    if lane.device.id == did:
                        return lane, True
        # FCFS onto the least-loaded lane otherwise.
        return min(self.lanes, key=lambda l: l.pending), False

    def _schedule(self, ins: Instruction, track: bool = True,
                  chain: bool = False) -> Future:
        lane, affinity = self._pick_lane(ins)
        with self._lock:
            self.stats["issued"] += 1
            self.flag_counts[ins.flags] += 1
            if affinity:
                self.stats["affinity_hits"] += 1
            lane.pending += 1
        # Operators within a task serialize (paper §5): kernel-enqueued
        # instructions (``chain=True``) wait on their task's previous one.
        # Safe with a FIFO pool: a waiter's dependency is always earlier in
        # the queue, so it can never starve. invoke_operator tasks are
        # single-instruction and skip the chain registry entirely (no growth).
        prev = self._task_prev.get(ins.task_id) if chain else None
        if prev is None:
            fut: Future = self._pool.submit(self._run_with_backup, ins, lane)
        else:
            def chained(prev=prev, ins=ins, lane=lane):
                prev.exception()   # wait for predecessor; its failure doesn't
                                   # cancel successors (futures stay per-op)
                return self._run_with_backup(ins, lane)
            fut = self._pool.submit(chained)
        if chain:
            self._task_prev[ins.task_id] = fut
        if track:
            self._task_futures[ins.task_id].append(fut)
        return fut

    def _run_with_backup(self, ins: Instruction, lane: _Lane):
        t0 = time.perf_counter()
        deadline = lane.ema_service_s * self.straggler_factor
        try:
            result = self._executor(ins, lane.device)
        except _StragglerTimeout:
            # Backup-task policy: re-issue on the currently fastest lane.
            with self._lock:
                self.stats["backups_issued"] += 1
            backup = min(self.lanes, key=lambda l: l.ema_service_s)
            result = self._executor(ins, backup.device)
        finally:
            with self._lock:
                lane.pending -= 1
        dt = time.perf_counter() - t0
        lane.observe(dt)
        if self.enable_backup_tasks and dt > deadline and len(self.lanes) > 1:
            # Late detection (post-hoc): record for telemetry; result stands.
            with self._lock:
                self.stats.setdefault("stragglers_detected", 0)
                self.stats["stragglers_detected"] += 1
        return result

    # ------------------------------------------------------------- executors

    @staticmethod
    def _default_executor(ins: Instruction, device):
        """Inputs to the device (span ``opq.put``), the call (``opq.run``:
        dispatch, or the trace and compile of a new shape) and the wait for
        its results (``opq.block``)."""
        # repro.serving imports this module, so the import waits for a call
        from repro.serving.metrics import span
        with span("opq.put"):
            args = [b.to_device(device) for b in ins.buffers]
        with span("opq.run"):
            out = ins.fn(*args)
        with span("opq.block"):
            return jax.block_until_ready(out)

    def shutdown(self):
        self._pool.shutdown(wait=True)


class _StragglerTimeout(Exception):
    """Raised by injectable executors (tests) to trigger the backup path."""
