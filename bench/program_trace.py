"""Second reading of a run's trace: the spans and scopes the program writes.

``bench/trace.py`` reads the benchmark's own spans (``bench.*``) and the
device's operations by name. This module reads the same ``.xplane.pb`` again
and keeps what the program itself put there:

- ``spans``: the engine thread's ``serve.*`` spans (``ServeStats.phase``):
  one ``serve.prefill``, ``serve.decode`` or ``serve.verify`` span per step,
  holding ``serve.inputs``, ``serve.forward`` and ``serve.sample``;
- ``ops``: the device's leaf operations, each with its scope, the HLO
  ``op_name`` of its metadata (``.../mlp/falcon.combine_a/add``). An
  operation that contains others, such as the layer scan's ``while``, is
  left out, so that no time counts twice; a fused operation counts under
  its root's scope, and a link of an in-place chain under the chain's
  (:func:`_chain_scopes`);
- ``busy``: the union of the intervals of every device operation;
- ``modules``: the device's program executions (``jit_decode_step(12)``).

The scope is the ``tf_op`` stat of an operation's event metadata, which
``jax.profiler.ProfileData`` does not expose, so the file is read as the
profiler's ``XSpace`` protobuf. Times are nanoseconds on the profiler's
clock. The readers below give a mean per step of one kind, and None where
the trace holds no such step (a program that writes no ``serve.*`` span) or
no scope to read.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import gzip
import json
import os
import re

from bench import trace as TR

__all__ = ["Program", "from_xspace", "load", "save_json", "load_json",
           "idle_ms", "eager_ms", "stage_split", "stage_ms"]

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPAN_PREFIX = "serve."
# Stat of a TPU operation event that carries its HLO op_name.
SCOPE_STAT = "tf_op"
# Scope names, in the order an operation is classified by (first match).
STAGES = ("falcon.combine_a", "falcon.combine_h", "falcon.lcma_gemm",
          "falcon.gemm", "attn", "mlp", "lm_head")


@dataclasses.dataclass
class Program:
    spans: list      # [name, start_ns, dur_ns], engine thread, serve.*
    ops: list        # [start_ns, dur_ns, scope], leaf device operations
    busy: list       # [start_ns, end_ns], merged
    modules: list    # [name, start_ns, dur_ns]


def _leaves(events):
    """``[start, dur, scope]`` sorted by start, without the operations that
    hold the next one (it starts before and ends by their end)."""
    evs = sorted(events, key=lambda e: (e[0], -e[1]))
    out = []
    for i, (s, d, scope) in enumerate(evs):
        if i + 1 < len(evs):
            s2, d2, _ = evs[i + 1]
            if s2 < s + d and s2 + d2 <= s + d:
                continue
        out.append([s, d, scope])
    return out


def _xplane_classes():
    """Message classes for the parts of the profiler's ``XSpace`` proto read
    here (``tsl/profiler/protobuf/xplane.proto``: same field numbers; a map
    is read as its repeated key-value entries)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto",
                                            package="bench_xplane")

    def msg(name, *fields):
        m = fd.message_type.add(name=name)
        for fname, num, kind, rep in fields:
            f = m.field.add(name=fname, number=num,
                            label=F.LABEL_REPEATED if rep else F.LABEL_OPTIONAL)
            if kind.startswith("."):
                f.type, f.type_name = F.TYPE_MESSAGE, ".bench_xplane" + kind
            else:
                f.type = getattr(F, "TYPE_" + kind)

    msg("XStat", ("metadata_id", 1, "INT64", 0), ("uint64_value", 3, "UINT64", 0),
        ("str_value", 5, "STRING", 0))
    msg("XEvent", ("metadata_id", 1, "INT64", 0), ("offset_ps", 2, "INT64", 0),
        ("duration_ps", 3, "INT64", 0))
    msg("XLine", ("name", 2, "STRING", 0), ("timestamp_ns", 3, "INT64", 0),
        ("events", 4, ".XEvent", 1))
    msg("XEventMetadata", ("id", 1, "INT64", 0), ("name", 2, "STRING", 0),
        ("stats", 5, ".XStat", 1))
    msg("XStatMetadata", ("id", 1, "INT64", 0), ("name", 2, "STRING", 0))
    msg("EventMetadataEntry", ("key", 1, "INT64", 0),
        ("value", 2, ".XEventMetadata", 0))
    msg("StatMetadataEntry", ("key", 1, "INT64", 0),
        ("value", 2, ".XStatMetadata", 0))
    msg("XPlane", ("name", 2, "STRING", 0), ("lines", 3, ".XLine", 1),
        ("event_metadata", 4, ".EventMetadataEntry", 1),
        ("stat_metadata", 5, ".StatMetadataEntry", 1))
    msg("XSpace", ("planes", 1, ".XPlane", 1))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _read(path: str):
    space = _xplane_classes()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def _events(pl, ln):
    """``[name, start_ns, dur_ns]`` of a line's events."""
    names = {e.key: e.value.name for e in pl.event_metadata}
    t0 = ln.timestamp_ns
    return [[names.get(e.metadata_id, ""), t0 + e.offset_ps / 1000,
             e.duration_ps / 1000] for e in ln.events]


def _device_events(space):
    """The first device's operations ``(start_ns, dur_ns, scope)`` and
    programs ``[name, start_ns, dur_ns]``; an operation's scope is the
    ``tf_op`` stat of its event metadata (its HLO ``op_name``). On a mesh
    every device runs the same programs, so one device's are read."""
    pl = TR.device_planes(space.planes, 1)[0]
    stat_id = {e.value.name: e.key for e in pl.stat_metadata}
    names, scopes = {}, {}
    for e in pl.event_metadata:
        st = {x.metadata_id: x for x in e.value.stats}
        names[e.key] = e.value.name
        scope = st.get(stat_id.get(SCOPE_STAT))
        prog = st.get(stat_id.get("program_id"))
        scopes[e.key] = (scope.str_value.rstrip(":") if scope else "",
                         prog.uint64_value if prog else 0)
    scopes = _chain_scopes(names, scopes)
    ops, modules = [], []
    for ln in pl.lines:
        if ln.name == TR.OPS_LINE:
            t0 = ln.timestamp_ns
            ops = [(t0 + e.offset_ps / 1000, e.duration_ps / 1000,
                    scopes.get(e.metadata_id, "")) for e in ln.events]
        elif ln.name == TR.MODULES_LINE:
            modules = _events(pl, ln)
    return ops, modules


# ``%name = <shape> fusion(<shape> %first_operand, ...`` of an HLO fusion
_FUSION = re.compile(r"%(\S+) = .*? fusion\([^%]*%([^,)\s]+)")


def _chain_scopes(names: dict, scopes: dict) -> dict:
    """``{metadata id: scope}``, the scope of an in-place link completed.

    XLA writes a concatenate (Combine A's stack of its R sub-blocks) as a
    chain of dynamic-update-slice fusions into one buffer, each updating the
    previous link's result in place, and keeps the op_name on the last link
    only; the others carry their loop's scope alone. A link without a stage
    of its own takes the scope of the link that updates its result.
    """
    link, next_of = {}, {}
    for k, name in names.items():
        m = _FUSION.match(name)
        if m and "dynamic-update-slice" in m.group(1):
            prog = scopes[k][1]
            link[k] = (prog, m.group(1))
            next_of[(prog, m.group(2))] = k
    out = {}
    for k, (scope, _) in scopes.items():
        j, hops = k, 0
        while _stage(scope) == "rest" and j in link \
                and link[j] in next_of and hops < len(link):
            j, hops = next_of[link[j]], hops + 1
            if _stage(scopes[j][0]) != "rest":
                scope = scopes[j][0]
        out[k] = scope
    return out


def from_xspace(path: str) -> Program:
    space = _read(path)
    ops, modules = _device_events(space)
    busy = TR._merged([(s, s + d) for s, d, _ in ops])
    return Program(_engine_spans(space), _leaves(ops), busy, modules)


def _engine_spans(space) -> list:
    """The ``serve.*`` spans of the host thread (line) that wrote most."""
    best: list = []
    for pl in space.planes:
        if not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            spans = [e for e in _events(pl, ln)
                     if e[0].startswith(SPAN_PREFIX)]
            if len(spans) > len(best):
                best = spans
    return sorted(best, key=lambda s: s[1])


def save_json(pg: Program, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(dataclasses.asdict(pg), f)


def load_json(path: str) -> Program:
    with gzip.open(path, "rt") as f:
        return Program(**json.load(f))


_MEMO: dict = {}


def load(run) -> Program | None:
    """The run's trace (``bench/out/<cell>/trace``), read once per file;
    None if there is none."""
    d = os.path.join(ROOT, "bench", "out", run["cell"]["name"], "trace")
    try:
        path = TR.find_xplane(d)
    except FileNotFoundError:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _MEMO:
        _MEMO.clear()
        _MEMO[key] = from_xspace(path)
    return _MEMO[key]


def _steps(pg: Program, kind: str):
    name = SPAN_PREFIX + kind
    return [(s, s + d) for n, s, d in pg.spans if n == name]


def _inside(pg: Program, lo: int, hi: int, names):
    return [(s, s + d) for n, s, d in pg.spans
            if n in names and s >= lo and s + d <= hi]


def _busy_in(busy, lo: int, hi: int) -> int:
    i = max(bisect.bisect_right(busy, [lo, float("inf")]) - 1, 0)
    t = 0
    while i < len(busy) and busy[i][0] < hi:
        t += max(0, min(busy[i][1], hi) - max(busy[i][0], lo))
        i += 1
    return t


def idle_ms(pg: Program | None, kind: str, phases) -> float | None:
    """Mean device-idle time per ``kind`` step inside its ``phases`` spans."""
    steps = _steps(pg, kind) if pg else []
    if not steps:
        return None
    names = {SPAN_PREFIX + p for p in phases}
    idle = sum(b - a - _busy_in(pg.busy, a, b)
               for lo, hi in steps for a, b in _inside(pg, lo, hi, names))
    return 1e-6 * idle / len(steps)


def eager_ms(pg: Program | None, kind: str, main: str) -> float | None:
    """Mean device time per ``kind`` step of the programs other than
    ``main`` that start inside the step's span."""
    steps = _steps(pg, kind) if pg else []
    if not steps:
        return None
    mods = sorted(pg.modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    t = 0
    for lo, hi in steps:
        for name, s, d in mods[bisect.bisect_left(starts, lo):
                               bisect.bisect_left(starts, hi)]:
            if TR._base(name) != main:
                t += d
    return 1e-6 * t / len(steps)


@functools.lru_cache(maxsize=None)
def _stage(scope: str) -> str:
    """The first of ``STAGES`` among the scope's components, else rest."""
    parts = scope.split("/")
    return next((s for s in STAGES if s in parts), "rest")


def stage_split(pg: Program | None, kind: str) -> dict | None:
    """Mean device self time per ``kind`` step of the leaf operations that
    start inside the step's span, in ms by stage (``STAGES`` and
    ``rest``); None without such steps or without any FalconGEMM scope."""
    steps = _steps(pg, kind) if pg else []
    if not steps:
        return None
    if not any(_stage(sc).startswith("falcon.") for _, _, sc in pg.ops):
        return None
    out = dict.fromkeys(STAGES + ("rest",), 0.0)
    starts = [o[0] for o in pg.ops]
    for lo, hi in steps:
        for s, d, scope in pg.ops[bisect.bisect_left(starts, lo):
                                  bisect.bisect_left(starts, hi)]:
            out[_stage(scope)] += d
    return {k: 1e-6 * v / len(steps) for k, v in out.items()}


def stage_ms(pg: Program | None, kind: str, stages) -> float | None:
    """Sum of ``stage_split`` over ``stages``."""
    split = stage_split(pg, kind)
    return None if split is None else sum(split[s] for s in stages)
