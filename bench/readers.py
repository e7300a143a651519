"""Shared arithmetic of the per-layer metric readers in ``bench/metrics``.

A reader gets the run's record (``run``): ``steps``, one entry per engine
step inside the traced part of the window (its kind, its host span, the
device time inside that span, the device time of the prefill program, and
the prompt lengths it prefilled), ``trace`` (the reduced trace), ``cell``,
``family`` (the configuration's module in ``bench/families``), ``dims``
and ``peaks`` (one device's row of ``bench/peaks.json``). A reader that
finds nothing to read returns None, and the metric is left out.
"""
from __future__ import annotations

__all__ = ["mean_span_ms", "mean_host_ms", "prefill_mfu", "idle_share"]


def _of(run, kind):
    return [s for s in run["steps"] if s["kind"] == kind]


def mean_span_ms(run, kind: str):
    """Mean wall time of the benchmark's span around steps of ``kind``."""
    st = _of(run, kind)
    return 1e3 * sum(s["span_s"] for s in st) / len(st) if st else None


def mean_host_ms(run, kind: str):
    """Mean time per step of ``kind`` in which the device ran nothing."""
    st = _of(run, kind)
    if not st:
        return None
    return 1e3 * sum(s["span_s"] - s["busy_s"] for s in st) / len(st)


def prefill_mfu(run):
    """Classical FLOPs of the prompts prefilled, over the prefill program's
    device time (on the cell's first device) times the bf16 peak of all the
    cell's chips, in percent: a share of the whole mesh's peak."""
    st = _of(run, "prefill")
    t = sum(s["prefill_device_s"] for s in st)
    if not st or t <= 0:
        return None
    work = sum(run["family"].prefill_flops(run["dims"], n)
               for s in st for n in s["prompt_lens"])
    peak = run["cell"]["chips"] * run["peaks"]["bf16_flops_per_s"]
    return 100.0 * work / (t * peak)


def idle_share(run):
    """Share of the traced window with no operation on the device, percent."""
    tr = run["trace"]
    if tr["window_s"] <= 0 or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
