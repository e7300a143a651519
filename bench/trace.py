"""Reduction of a profiler trace to the per-layer numbers of one run.

A trace is read once into :class:`Events`: the operations of the cell's
first device (its TPU plane's ``XLA Ops`` line), that device's program
executions (its ``XLA Modules`` line, named like ``jit_decode_step(12)``),
the host spans the benchmark wrote with ``TraceAnnotation`` (names starting
with ``bench.``), and the operation intervals of the cell's other devices.
Times are nanoseconds on the profiler's common clock. :func:`reduce` then
gives:

- ``window_s``: the traced window, from the first to the last benchmark
  span;
- ``busy_s``: the union of the device-operation intervals inside it, the
  mean over the cell's devices (``busy_by_device``: each one's);
- ``modules``: device seconds per program, the numeric suffix dropped;
- ``top_ops``: the ten operations that took most device time;
- ``idle_by_span``: device-idle seconds inside the window, by the host span
  that covered each idle moment (``host.none`` where none did);
- ``steps``: for each ``bench.step#<i>`` span, its length, the device busy
  time inside it, and the device time of each program that ran inside it.

Programs, operations, idle gaps and steps are the first device's: on a mesh
every device runs the same programs.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import os
import re

__all__ = ["Events", "find_xplane", "from_xspace", "load_json", "save_json",
           "reduce", "join_steps"]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Events:
    ops: list        # [name, start_ns, dur_ns]
    modules: list    # [name, start_ns, dur_ns]
    spans: list      # [name, start_ns, dur_ns]
    # one list of [start_ns, dur_ns] per further device of the cell
    more_ops: list = dataclasses.field(default_factory=list)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def device_planes(planes, n: int):
    """The TPU planes with an ``XLA Ops`` line of the first ``n`` devices,
    by device number."""
    tpu = sorted((int(m.group(1)), pl) for pl in planes
                 if (m := re.fullmatch(r"/device:TPU:(\d+)", pl.name))
                 and any(ln.name == OPS_LINE for ln in pl.lines))
    if len(tpu) < n:
        raise ValueError(f"the trace has {len(tpu)} TPU planes with an "
                         f"'XLA Ops' line, the cell runs on {n}: "
                         + ", ".join(pl.name for pl in planes))
    return [pl for _, pl in tpu[:n]]


def from_xspace(path: str, n_devices: int = 1) -> Events:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    dev, *more = device_planes(planes, n_devices)
    ops, modules, spans = [], [], []
    for ln in dev.lines:
        if ln.name == OPS_LINE:
            ops = [[e.name, e.start_ns, e.duration_ns] for e in ln.events]
        elif ln.name == MODULES_LINE:
            modules = [[e.name, e.start_ns, e.duration_ns] for e in ln.events]
    more_ops = [[[e.start_ns, e.duration_ns] for ln in pl.lines
                 if ln.name == OPS_LINE for e in ln.events] for pl in more]
    for pl in planes:
        if not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            spans.extend([e.name, e.start_ns, e.duration_ns] for e in ln.events
                         if e.name.startswith(SPAN_PREFIX))
    spans.sort(key=lambda s: s[1])
    return Events(ops, modules, spans, more_ops)


def save_json(ev: Events, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(dataclasses.asdict(ev), f)


def load_json(path: str) -> Events:
    with gzip.open(path, "rt") as f:
        return Events(**json.load(f))


def _merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _base(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _op_base(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head) or head


def reduce(ev: Events) -> dict:
    if not ev.spans:
        raise ValueError("the trace holds no benchmark span")
    lo = min(s[1] for s in ev.spans)
    hi = max(s[1] + s[2] for s in ev.spans)
    busy = _merged(_clip([(s, s + d) for _, s, d in ev.ops], lo, hi))
    busy_ns = [sum(e - s for s, e in busy)]
    for more in ev.more_ops:
        iv = _merged(_clip([(s, s + d) for s, d in more], lo, hi))
        busy_ns.append(sum(e - s for s, e in iv))

    modules: dict = {}
    for name, s, d in ev.modules:
        if s >= lo and s < hi:
            b = _base(name)
            modules[b] = modules.get(b, 0.0) + d * 1e-9
    mods = sorted(ev.modules, key=lambda m: m[1])
    m_starts = [m[1] for m in mods]
    ops: dict = {}
    for name, s, d in ev.ops:
        if s >= lo and s < hi:
            i = bisect.bisect_right(m_starts, s) - 1
            prog = _base(mods[i][0]) if i >= 0 and s < mods[i][1] + mods[i][2] \
                else "?"
            b = f"{prog}:{_op_base(name)}"
            ops[b] = ops.get(b, 0.0) + d * 1e-9
    top_ops = sorted(([k, v] for k, v in ops.items()), key=lambda x: -x[1])[:10]

    # idle moments inside the window, attributed to the innermost host span
    # (the latest-starting one) that covers them
    idle = []
    cur = lo
    for s, e in busy:
        if s > cur:
            idle.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        idle.append((cur, hi))
    idle_by: dict = {}
    segs = _labelled_segments(ev.spans, lo, hi)
    j = 0
    for s, e in idle:
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < e:
            a, b = max(segs[k][0], s), min(segs[k][1], e)
            if b > a:
                lab = segs[k][2]
                idle_by[lab] = idle_by.get(lab, 0.0) + (b - a) * 1e-9
            k += 1
    idle_by_span = sorted(([k, v] for k, v in idle_by.items()),
                          key=lambda x: -x[1])[:10]

    steps = []
    b_starts = [a for a, _ in busy]
    for name, s, d in ev.spans:
        if not name.startswith(SPAN_PREFIX + "step#"):
            continue
        e = s + d
        i = max(bisect.bisect_right(b_starts, s) - 1, 0)
        inside = 0
        while i < len(busy) and busy[i][0] < e:
            inside += max(0, min(busy[i][1], e) - max(busy[i][0], s))
            i += 1
        progs: dict = {}
        for mname, ms, md in mods[bisect.bisect_left(m_starts, s):
                                  bisect.bisect_left(m_starts, e)]:
            b = _base(mname)
            progs[b] = progs.get(b, 0.0) + md * 1e-9
        steps.append({"index": int(name.split("#", 1)[1]), "span_s": d * 1e-9,
                      "busy_s": inside * 1e-9, "programs": progs})
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": sum(busy_ns) / len(busy_ns) * 1e-9,
            "busy_by_device": [b * 1e-9 for b in busy_ns],
            "modules": modules, "top_ops": top_ops,
            "idle_by_span": idle_by_span, "steps": steps}


def _span_kind(name: str) -> str:
    return name.split("#", 1)[0]


def _labelled_segments(spans, lo, hi):
    """``[lo, hi)`` cut at every span boundary, each piece labelled by the
    innermost (latest-starting) span covering it, ``host.none`` if none."""
    edges = []
    for i, (name, s, d) in enumerate(spans):
        edges.append((s, 1, i))
        edges.append((s + d, 0, i))
    edges.sort()
    active: dict = {}
    out = []
    cur = lo
    for t, kind, i in edges:
        t = min(max(t, lo), hi)
        if t > cur:
            if active:
                inner = max(active, key=lambda k: (spans[k][1], k))
                label = _span_kind(spans[inner][0])
            else:
                label = "host.none"
            out.append((cur, t, label))
            cur = t
        if kind:
            active[i] = True
        else:
            active.pop(i, None)
    if cur < hi:
        out.append((cur, hi, "host.none"))
    return out


PREFILL_PROGRAM = "jit_chunk_step"


def join_steps(red: dict, host_steps) -> list[dict]:
    """The traced steps, each with the host record of the same index (its
    kind and the prompt lengths it prefilled)."""
    out = []
    for st in red["steps"]:
        i = st["index"]
        if i >= len(host_steps):
            continue
        h = host_steps[i]
        out.append({"kind": h.kind, "span_s": st["span_s"],
                    "busy_s": st["busy_s"],
                    "prefill_device_s": st["programs"].get(PREFILL_PROGRAM, 0.0),
                    "prompt_lens": list(h.prompt_lens)})
    return out
