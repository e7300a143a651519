"""Collective operations of a run's trace: the time a step of a tensor-
parallel cell spends in them.

A collective is classified by its HLO op name: the instruction's name
(``all-reduce.9``) or its opcode (``%all-reduce.9 = bf16[...] all-reduce(``)
is one of ``KINDS``, or its async ``-start``/``-done`` half. GSPMD's
collectives carry the scope of the operation they serve
(``.../attn/falcon.gemm/dot_general``), so the scope cannot identify them.

Each operation counts its self time: its duration less that of the
operations it holds on the first device's ``XLA Ops`` line. A step is the
program's ``serve.prefill`` or ``serve.decode`` span
(``bench/program_trace.py``), and an operation belongs to the step in which
it starts. Times are nanoseconds on the profiler's clock.
"""
from __future__ import annotations

import bisect
import os
import re

from bench import program_trace as PT
from bench import trace as TR

__all__ = ["KINDS", "is_collective", "self_times", "collective_ms", "load",
           "step_ms"]

KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
         "collective-permute")
_NAMES = {k + half for k in KINDS for half in ("", "-start", "-done")}
_OPCODE = re.compile(r" (%s)(-start|-done)?\(" % "|".join(KINDS))


def is_collective(name: str) -> bool:
    return TR._op_base(name) in _NAMES or bool(_OPCODE.search(name))


def self_times(ops) -> list:
    """``[name, start_ns, self_ns]`` of ``ops`` (``[name, start_ns,
    dur_ns]``): each duration less those of the operations it directly
    holds (ones that start inside it and end by its end)."""
    evs = sorted(ops, key=lambda o: (o[1], -o[2]))
    out = [[n, s, d] for n, s, d in evs]
    stack: list = []                    # indices of the open holders
    for i, (_, s, d) in enumerate(evs):
        while stack and s >= evs[stack[-1]][1] + evs[stack[-1]][2]:
            stack.pop()
        if stack and s + d <= evs[stack[-1]][1] + evs[stack[-1]][2]:
            out[stack[-1]][2] -= d
        stack.append(i)
    return out


def collective_ms(ops, spans, kind: str) -> float | None:
    """Mean self time per ``kind`` step of the collectives that start inside
    the step's ``serve.<kind>`` span; None without such steps or without
    any collective in ``ops``."""
    steps = sorted((s, s + d) for n, s, d in spans
                   if n == PT.SPAN_PREFIX + kind)
    coll = [(s, d) for n, s, d in self_times(ops) if is_collective(n)]
    if not steps or not coll:
        return None
    starts = [lo for lo, _ in steps]
    t = 0
    for s, d in coll:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < steps[i][1]:
            t += d
    return 1e-6 * t / len(steps)


_MEMO: dict = {}


def load(run):
    """``(ops, spans)`` of the run's trace (``bench/out/<cell>/trace``):
    the first device's operations by HLO op name and the engine's
    ``serve.*`` spans, read once per file; None if there is no trace."""
    d = os.path.join(PT.ROOT, "bench", "out", run["cell"]["name"], "trace")
    try:
        path = TR.find_xplane(d)
    except FileNotFoundError:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _MEMO:
        space = PT._read(path)
        pl = TR.device_planes(space.planes, 1)[0]
        names = {e.key: e.value.name for e in pl.event_metadata}
        ops = [[names.get(e.metadata_id, ""), ln.timestamp_ns + e.offset_ps / 1000,
                e.duration_ps / 1000]
               for ln in pl.lines if ln.name == TR.OPS_LINE for e in ln.events]
        _MEMO.clear()
        _MEMO[key] = (ops, PT._engine_spans(space))
    return _MEMO[key]


def step_ms(run, kind: str) -> float | None:
    """:func:`collective_ms` of the run's trace; None without a trace."""
    got = load(run)
    return None if got is None else collective_ms(*got, kind)
