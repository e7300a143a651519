"""Set-up, measured window and correctness check of one benchmark cell.

Data lives in files found by name: ``BENCHMARK.json`` names the cell, its
configuration and its traffic mix; ``bench/cells/<cell>.json``,
``bench/configs/<config>.json`` and ``bench/traffic/<mix>.json`` hold their
numbers. Nothing here names a cell or a model family: the configuration
file names its family, whose module (``bench/families/<family>.py``) holds
the model's arithmetic, and the cell file may name a mesh
(``bench/layout.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import threading
import time
from collections import deque

import numpy as np

from bench import families, layout, traffic

__all__ = ["ROOT", "load_cell", "Engine", "run_window", "served_sample",
           "widest_gap", "DRAIN_CAP_S"]

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")

# How long past the window's close requests due in it may take to finish
# before they count as failed.
DRAIN_CAP_S = 90.0

def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_cell(workload: str, bench_json: dict | None = None) -> dict:
    """The cell named ``workload`` with its configuration and mix attached."""
    if bench_json is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench_json = json.load(f)
    entry = next((w for w in bench_json["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = _json("cells", f"{workload}.json")
    for k in ("config", "traffic"):
        if cell[k] != entry[k]:
            raise ValueError(f"cell file {workload}.json says {k}={cell[k]!r}, "
                             f"BENCHMARK.json says {entry[k]!r}")
    conf = next(c for c in bench_json["configs"] if c["name"] == entry["config"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        cell["conf"] = json.load(f)
    cell["mix"] = _json("traffic", f"{entry['traffic']}.json")
    cell["name"] = workload
    cell["chips"] = entry["chips"]
    layout.mesh_of(cell)            # refused here, before any set-up, if
    families.load(cell["conf"])     # the mesh or the family does not hold
    return cell


class Engine:
    """The serve engine of one cell, built as users build it."""

    def __init__(self, cell: dict, seed: int):
        from repro.serve import BucketPolicy, ServeEngine
        self.cell, self.seed = cell, seed
        self.family = families.load(cell["conf"])
        self.dims = self.family.dims(cell["conf"])
        cfg = self.family.program_config(cell["conf"], self.dims)
        mesh = layout.mesh_of(cell)
        self.layout = layout.for_cell(cell)
        mix = cell["mix"]
        policy = BucketPolicy(prefill_seq=tuple(cell["prefill_seq"]),
                              prefill_batch=tuple(cell["prefill_batch"]),
                              decode_batch=tuple(cell["decode_batch"]))
        t = time.perf_counter()
        params = self.family.make_weights(self.dims, seed, self.layout)
        self.engine = ServeEngine(
            cfg, params=params, max_slots=cell["slots"],
            max_prompt_len=mix["prompt"]["max"],
            max_new_tokens=mix["output"]["max"], policy=policy,
            mesh_shape=mesh)
        del params
        self.t_build = time.perf_counter() - t
        if self.engine.max_len != cell["max_len"]:
            raise ValueError(f"engine max_len {self.engine.max_len} != cell "
                             f"max_len {cell['max_len']}")
        self.t_warm = self.engine.warm()["seconds"]
        t = time.perf_counter()
        self._warm_traffic()
        self.t_warm_traffic = time.perf_counter() - t

    def _warm_traffic(self):
        """Serve requests whose decode batches hold every count of live rows
        from ``slots`` down to 1, and prefill batches of every bucket, so that
        the engine's eager gathers and scatters are compiled before the
        window. Prompts take the smallest sequence bucket."""
        e, cell = self.engine, self.cell
        n, pb = cell["slots"], max(cell["prefill_batch"])
        cap = self.cell["mix"]["output"]["max"]
        base = -(-n // pb) // 2 + 3      # outlives the admission ramp
        if base + n - 1 > cap:
            raise ValueError(f"warm traffic needs outputs up to {base + n - 1},"
                             f" the mix allows {cap}")
        plen = min(cell["prefill_seq"][0], self.cell["mix"]["prompt"]["max"])
        rng = traffic.rng_for(self.seed, 99)
        for i in range(n):
            e.submit(rng.integers(0, self.dims["vocab_size"], plen),
                     max_new_tokens=base + i)
        while e.step():
            pass
        for b in cell["prefill_batch"]:          # each prefill batch bucket
            for _ in range(b):
                e.submit(rng.integers(0, self.dims["vocab_size"], plen),
                         max_new_tokens=2)
            while e.step():
                pass

    def free(self):
        """Drop the program's state so that its device memory is released."""
        self.engine = None
        gc.collect()

    def reference_weights(self):
        """The weights made again from the seed, in the same layout, for the
        plain reference."""
        return self.family.make_weights(self.dims, self.seed, self.layout)


class _Step:
    __slots__ = ("t0", "t1", "kind", "prompt_lens")

    def __init__(self, t0, t1, kind, prompt_lens):
        self.t0, self.t1, self.kind, self.prompt_lens = t0, t1, kind, prompt_lens


def _compile_counter():
    """A list that grows by one for every backend compile in the process."""
    import jax.monitoring
    seen: list = []

    def on(name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            seen.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on)
    return seen


def run_window(eng: Engine, seconds: float, seed: int,
               trace_dir: str | None = None, trace_span=(0.0, 0.0),
               rate: float | None = None) -> dict:
    """Offer the cell's traffic for ``seconds`` and drive the engine.

    Open loop: a generator thread submits each request when it is due;
    requests still running at the close are drained for up to
    ``DRAIN_CAP_S``. Closed loop: the backlog is topped up before every step
    and the window closes at the first step that ends past ``seconds``.
    With ``trace_dir`` the profiler records ``trace_span`` (start, length in
    seconds from the window's start).

    Returns the window's records: per-request due, submit and token times,
    per-step spans, the waiting queue's length at each step, the engine's
    counters before and after, and the compiles inside the window.
    """
    import jax
    from jax.profiler import TraceAnnotation

    e, cell, mix = eng.engine, eng.cell, eng.cell["mix"]
    vocab = eng.dims["vocab_size"]
    compiles = _compile_counter()
    st0 = dataclasses.asdict(e.stats)
    records: list[dict] = []
    just_prefilled: list[dict] = []

    def on_token(rec):
        def cb(req, tok):
            rec["token_t"].append(time.perf_counter())
            if len(rec["token_t"]) == 1:
                just_prefilled.append(rec)
        return cb

    def submit(q, due):
        rec = {"due": due, "token_t": [], "prompt_len": len(q.prompt),
               "req": None, "error": None}
        ts = time.perf_counter()
        try:
            rec["req"] = e.submit(q.prompt, max_new_tokens=q.max_new_tokens,
                                  on_token=on_token(rec))
        except ValueError as err:            # refused at the door: a failure
            rec["error"] = str(err)
        rec["submit_t"] = ts
        records.append(rec)

    open_loop = mix["loop"] == "open"
    # set-up's objects leave the collector's view, so that no full
    # collection over them lands inside the window
    gc.collect()
    gc.freeze()
    t0 = time.perf_counter()
    t_end = t0 + seconds
    gen_done = threading.Event()
    if open_loop:
        offered = traffic.open_loop(mix, rate or cell["rate_per_s"], seconds,
                                    seed, vocab)

        def generate():
            try:
                for q in offered:
                    due = t0 + q.due_s
                    while (d := due - time.perf_counter()) > 0:
                        time.sleep(min(d, 0.02))
                    with TraceAnnotation("bench.submit"):
                        submit(q, due)
            finally:
                gen_done.set()

        gen = threading.Thread(target=generate, name="bench-generator")
        gen.start()
    else:
        backlog = mix["backlog_per_slot"] * cell["slots"]
        pending: deque = deque()
        block = 0

    steps: list[_Step] = []
    queue: list = []                     # (time, requests waiting) per step
    tr_on = tr_done = False
    n_compiles_before = len(compiles)
    while True:
        now = time.perf_counter()
        if trace_dir and not tr_on and not tr_done \
                and now >= t0 + trace_span[0]:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0         # benchmark spans only
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tr_on = True
        if tr_on and now >= t0 + trace_span[0] + trace_span[1]:
            jax.profiler.stop_trace()
            tr_on, tr_done = False, True
        if open_loop:
            if gen_done.is_set() and all(
                    r["req"] is None or r["req"].done for r in records):
                break
            if now > t_end + DRAIN_CAP_S:
                break
        else:
            if now >= t_end:
                break
            with TraceAnnotation("bench.submit"):
                while len(e.queue) < backlog:
                    if not pending:
                        pending.extend(traffic.closed_loop_block(
                            mix, block, seed, vocab))
                        block += 1
                    submit(pending.popleft(), time.perf_counter())
        queue.append((now, len(e.queue)))
        n_p, n_d = e.stats.prefill_steps, e.stats.decode_steps
        with TraceAnnotation(f"bench.step#{len(steps)}"):
            s = time.perf_counter()
            did = e.step()
            f = time.perf_counter()
        if did:
            kind = "prefill" if e.stats.prefill_steps > n_p else \
                "decode" if e.stats.decode_steps > n_d else "other"
            lens = [r["prompt_len"] for r in just_prefilled]
            just_prefilled.clear()
            steps.append(_Step(s, f, kind, lens))
        else:
            steps.append(_Step(s, f, "none", []))
            with TraceAnnotation("bench.idle"):
                e.queue.wait_nonempty(timeout=0.002)
    t_stop = time.perf_counter()
    gc.unfreeze()
    if tr_on:
        jax.profiler.stop_trace()
    if open_loop:
        gen.join()
    n_compiles = len(compiles) - n_compiles_before
    st1 = dataclasses.asdict(e.stats)
    return {"t0": t0, "t_end": t_end, "t_stop": t_stop, "records": records,
            "steps": steps, "stats0": st0, "stats1": st1,
            "compiles_in_window": n_compiles, "queue": queue,
            "open_loop": open_loop}


def served_sample(records: list[dict], seed: int, min_tokens: int) -> list[dict]:
    """Finished requests to compare: the one with the most tokens, then
    others drawn from the seed, until ``min_tokens`` served tokens."""
    done = [r for r in records if r["req"] is not None and r["req"].done]
    if not done:
        return []
    done.sort(key=lambda r: (r["prompt_len"] + len(r["req"].generated),
                             r["req"].rid))
    pick = [done.pop()]
    order = traffic.rng_for(seed, 7).permutation(len(done))
    n = len(pick[0]["req"].generated)
    for i in order:
        if n >= min_tokens:
            break
        pick.append(done[i])
        n += len(done[i]["req"].generated)
    return pick


def widest_gap(family, dims: dict, params, sample: list[dict], seq_len: int,
               max_new: int, controls: tuple = ()) -> dict:
    """Widest gap by which a served token's reference logit lies below the
    reference's best, over every served token of ``sample``.

    Each request runs once through the float32 reference of ``family`` over
    its prompt and served tokens, right-padded to ``seq_len``. For each
    precision in ``controls`` the same positions also run through the
    reference in that precision, and the gap of the token it puts first is
    read (``control_gap.<precision>``).
    """
    worst = 0.0
    worst_ctrl = {c: 0.0 for c in controls}
    n_tok = 0
    for r in sample:
        prompt, gen = list(r["req"].prompt), list(r["req"].generated)
        seq = np.zeros(seq_len, np.int32)
        full = prompt + gen[:-1]
        seq[:len(full)] = full
        pos = [len(prompt) - 1 + j for j in range(len(gen))]
        pos_p = pos + [pos[-1]] * (max_new - len(pos))
        ref = family.logits_at(dims, params, seq, pos_p)[:len(gen)]
        best = ref.max(axis=-1)
        rows = np.arange(len(gen))
        worst = max(worst, float(np.max(best - ref[rows, gen])))
        for c in controls:
            low = family.logits_at(dims, params, seq, pos_p, c)[:len(gen)]
            gap = best - ref[rows, low.argmax(axis=-1)]
            worst_ctrl[c] = max(worst_ctrl[c], float(np.max(gap)))
        n_tok += len(gen)
    out = {"widest_gap": worst, "tokens": n_tok, "requests": len(sample)}
    for c in controls:
        out[f"control_gap.{c}"] = worst_ctrl[c]
    return out


def reference_seq_len(cell: dict) -> int:
    """One padded length for every compared request, so the reference
    compiles once: the longest prompt plus output, to a multiple of 128."""
    mix = cell["mix"]
    return int(math.ceil((mix["prompt"]["max"] + mix["output"]["max"]) / 128)) * 128
