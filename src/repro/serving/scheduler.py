"""Slot-based continuous-batching scheduler (rtp-llm FIFOScheduler shape).

Requests wait in a FIFO; every engine step the scheduler joins as many waiting
requests as there are free slots into the in-flight decode batch and retires
finished ones — there is no full-batch barrier, a long request never blocks
short ones from entering and leaving around it.

Each admission is prefilled alone, its prompt padded up to a small fixed set
of *prefill buckets*: the number of compiled prefill shapes is
``len(buckets)``, independent of both the prompt lengths and the arrival
pattern seen in traffic.
"""

from __future__ import annotations

from collections import deque
from typing import Deque, Dict, List, Sequence, Tuple

import numpy as np

MIN_BUCKET = 16


def default_buckets(max_len: int) -> Tuple[int, ...]:
    """Powers of two from MIN_BUCKET up, capped at ``max_len``."""
    buckets: List[int] = []
    b = MIN_BUCKET
    while b < max_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_len)
    return tuple(buckets)


def bucket_for(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"prompt length {n} exceeds largest bucket {buckets[-1]}")


class Scheduler:
    def __init__(self, n_slots: int, buckets: Sequence[int]):
        self.n_slots = n_slots
        self.buckets = tuple(sorted(buckets))
        # pop() from the tail — reversed so slot 0 is leased first
        self.free: List[int] = list(range(n_slots))[::-1]
        self.active: Dict[int, object] = {}        # slot -> Request
        self.waiting: Deque[object] = deque()

    # ------------------------------------------------------------------ FIFO

    def enqueue(self, request) -> None:
        self.waiting.append(request)

    @property
    def queue_depth(self) -> int:
        return len(self.waiting)

    @property
    def n_active(self) -> int:
        return len(self.active)

    def has_work(self) -> bool:
        return bool(self.waiting or self.active)

    # ------------------------------------------------------------ join/retire

    def plan_admissions(self, try_lease=None
                        ) -> List[Tuple[int, int, object]]:
        """Lease free slots to waiting requests (FIFO):
        [(bucket_len, slot, request), ...] in arrival order. Mutates the free
        list and active map — the engine must prefill every planned request.

        ``try_lease(slot, request) -> bool`` lets the cache backend reserve
        capacity before the slot is committed (serving/store.py). A False
        return stops planning with the request still at the queue head —
        FIFO-order admission backpressure (e.g. paged block-pool exhaustion),
        resolved when a retire frees capacity."""
        planned: List[Tuple[int, int, object]] = []
        while self.waiting and self.free:
            req = self.waiting[0]
            slot = self.free[-1]
            if try_lease is not None and not try_lease(slot, req):
                break
            self.waiting.popleft()
            self.free.pop()
            self.active[slot] = req
            planned.append((bucket_for(len(req.prompt), self.buckets), slot,
                            req))
        return planned

    def admit_seeded(self, request) -> "int | None":
        """Place an externally-seeded request straight into the in-flight
        batch, bypassing the waiting queue and prefill planning entirely.
        The caller has already materialised the slot's KV (e.g. from an
        imported cross-host block payload), so there is nothing to prefill —
        the request joins the next decode step as-is. Returns the slot, or
        None when no slot is free (the caller keeps the payload and retries
        or falls back to re-prefill)."""
        if not self.free:
            return None
        slot = self.free.pop()
        self.active[slot] = request
        return slot

    def decode_batch(self) -> Tuple[np.ndarray, np.ndarray]:
        """The in-flight batch as fixed-shape host arrays: ``tokens``
        (n_slots, 1) int32 — each active slot's last emitted token, the
        input every decode variant feeds next — and the ``active`` mask
        (n_slots,). Shared by the plain decode step and the speculative
        draft/verify round (serving/engine.py), so the two decode paths can
        never disagree about what a slot feeds."""
        tokens = np.zeros((self.n_slots, 1), np.int32)
        active = np.zeros((self.n_slots,), bool)
        for slot, req in self.active.items():
            tokens[slot, 0] = req.last_token
            active[slot] = True
        return tokens, active

    def sampling_by_slot(self, default) -> List[object]:
        """Each slot's SamplingParams as a fixed-width list aligned with
        ``decode_batch``'s rows: the active request's params (``default``
        when it has none) or ``default`` for idle slots. The engine stacks
        this into the decode batch every step, so params ride the slot state
        through join/preempt/handoff exactly like the cache lease does."""
        out = [default] * self.n_slots
        for slot, req in self.active.items():
            out[slot] = getattr(req, "sampling", None) or default
        return out

    def retire(self, slot: int):
        req = self.active.pop(slot)
        self.free.append(slot)
        return req
