"""Latency statistics over all requests of a window."""
from __future__ import annotations

import numpy as np

__all__ = ["percentile", "ttft_samples", "itl_samples"]


def percentile(values, q: float) -> float:
    """The ``q``-th percentile of every value (numpy's linear rule)."""
    v = np.asarray(list(values), np.float64)
    if v.size == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(v, q))


def ttft_samples(records, horizon_s: float) -> list[float]:
    """First-token time minus due time, one per request due in the window.

    ``records`` are dicts with ``due`` and ``token_t`` (host clock of each
    emitted token). A request that never got a first token counts as a miss:
    its sample is ``horizon_s - due``, the longest wait the run could see,
    so misses raise the tail instead of leaving it.
    """
    out = []
    for r in records:
        t = r["token_t"]
        out.append((t[0] if t else horizon_s) - r["due"])
    return out


def itl_samples(records) -> list[float]:
    """Every gap between consecutive emitted tokens of one request, over all
    requests. A step that emits several tokens of one request at once
    contributes zero gaps between them."""
    out: list[float] = []
    for r in records:
        t = r["token_t"]
        out.extend(b - a for a, b in zip(t, t[1:]))
    return out
