"""Small statistics shared by the metric readers."""

from __future__ import annotations

from typing import Iterable, Optional

import numpy as np


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The q-th percentile, taken as the nearest value at or above it (no
    interpolation, so an infinite value reads as infinite and never as
    NaN); None for no values."""
    v = np.asarray(list(values), np.float64)
    if v.size == 0:
        return None
    return float(np.percentile(v, q, method="higher"))


def ttft_s(rec) -> float:
    """Due time to first token; a request refused or never answered has
    missed every limit."""
    return rec.token_ts[0] - rec.due_s if rec.token_ts else float("inf")


def itl_s(records) -> list:
    """Every inter-token gap of the given requests."""
    out = []
    for r in records:
        out.extend(np.diff(r.token_ts).tolist())
    return out
