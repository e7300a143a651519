#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the serve engine as users build it (random weights from
``--seed`` on the device, FalconGEMM defaults, precombine on) with a bucket
grid cut to the cell's own shapes, warms those shapes, and then measures for
``--seconds``. ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics read from a profiler trace of part of
the window. Afterwards a sample of the served tokens is compared with the
plain float32 reference. The last stdout line is one JSON object; the last
stderr lines give each compared number beside its limit.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

# The traced part of a window is its last TRACE_S seconds (at most half the
# window), so that the trace stays small, is read well inside a run's time,
# and the stall of writing it out falls after the part it records.
TRACE_S = 6.0


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _fail(msg: str) -> None:
    log(f"bench: {msg}")
    sys.exit(2)


def load_peaks(kind: str) -> dict:
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device_kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def metric_reader(name: str):
    """``read(run)`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(ROOT, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _for_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def run_cell(bench_json: dict, workload: str, seed: int, seconds: float,
             trace: bool, device_info: dict, t_start: float,
             out_dir: str, cell: dict | None = None) -> dict:
    """Everything a run does once the device is known; returns the result.
    ``cell`` stands in for the files of ``workload`` (tests)."""
    import jax
    from bench import harness, trace as TR

    if cell is None:
        cell = harness.load_cell(workload, bench_json)
    eng = harness.Engine(cell, seed)
    e = eng.engine
    log(f"setup: build_s={eng.t_build:.3f} warm_s={eng.t_warm:.3f} "
        f"warm_traffic_s={eng.t_warm_traffic:.3f} max_len={e.max_len} "
        f"precombined={e.n_precombined} schemes={_schemes(e.params)}")
    log(f"lcma: {json.dumps(_lcma_share(e, cell))}")

    trace_dir = os.path.join(out_dir, "trace") if trace else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    length = min(TRACE_S, seconds / 2)
    span = (seconds - length, length)
    w = harness.run_window(eng, seconds, seed, trace_dir=trace_dir,
                           trace_span=span)
    setup_s = w["t0"] - t_start
    devices = jax.devices()[:cell["chips"]]
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    device = dict(device_info, count=len(devices), memory_peak_bytes=max(peaks))

    recs = w["records"]
    failed = [r for r in recs if r["req"] is None or not r["req"].done]
    open_loop = w["open_loop"]
    if not open_loop:        # in flight at the close of a closed loop: not failed
        failed = [r for r in failed if r["req"] is None]
    _diagnostics(w, peaks)

    metrics: dict = {}
    breakdown = None
    if not trace:
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        ends = [m for m in bench_json["end_to_end"] if _for_cell(m, workload)]
        for m in ends:
            name = m["name"]
            if name == "setup_s":
                continue
            v = _end_to_end(name, w)
            if v is not None:
                metrics[name] = {"value": v, "unit": m["unit"]}
    else:
        xs = TR.find_xplane(trace_dir)
        ev = TR.from_xspace(xs, cell["chips"])
        red = TR.reduce(ev)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        run = {"cell": cell, "family": eng.family, "dims": eng.dims,
               "window": w, "trace": red,
               "steps": TR.join_steps(red, w["steps"]),
               "peaks": load_peaks(device["kind"])}
        for m in bench_json["per_layer"]:
            if not _for_cell(m, workload):
                continue
            v = metric_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": red["top_ops"], "idle_gaps": red["idle_by_span"]}
        log(f"trace: window_s={red['window_s']:.6f} busy_s={red['busy_s']:.6f} "
            f"busy_s_by_device={json.dumps(red['busy_by_device'])} "
            f"modules={json.dumps(red['modules'])}")

    # correctness: free the program's state, then the plain reference
    sample = harness.served_sample(recs, seed, cell["correct"]["min_tokens"])
    del e
    eng.free()
    params = eng.reference_weights()
    t = time.perf_counter()
    if sample:
        chk = harness.widest_gap(eng.family, eng.dims, params, sample,
                                 harness.reference_seq_len(cell),
                                 cell["mix"]["output"]["max"])
    else:
        chk = {"widest_gap": None, "tokens": 0, "requests": 0}      # nothing finished
    log(f"reference: {chk['requests']} requests, {chk['tokens']} served "
        f"tokens compared in {time.perf_counter() - t:.3f}s")
    limit = cell["correct"]["widest_gap_limit"]
    correct = chk["widest_gap"] is not None and chk["widest_gap"] <= limit
    checks = {"widest_gap": {"value": chk["widest_gap"], "limit": limit}}
    result = {"correct": correct, "attempted": len(recs), "failed": len(failed),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    log(f"check widest_gap={chk['widest_gap']!r} limit={limit!r} "
        f"(served tokens compared: {chk['tokens']})")
    return result


def _end_to_end(name: str, w: dict):
    from bench import stats as S
    recs = w["records"]
    if name == "itl_p95_ms":
        gaps = S.itl_samples(recs)
        return 1e3 * S.percentile(gaps, 95) if gaps else None
    if name == "tok_s" and not w["open_loop"]:
        s0, s1 = w["stats0"], w["stats1"]
        toks = (s1["prompt_tokens"] - s0["prompt_tokens"]
                + s1["generated_tokens"] - s0["generated_tokens"])
        return toks / (w["t_stop"] - w["t0"])
    return None


def _diagnostics(w: dict, peaks: list) -> None:
    import numpy as np
    from bench import stats as S
    recs = w["records"]
    s0, s1 = w["stats0"], w["stats1"]
    d = {k: s1[k] - s0[k] for k in ("prefill_steps", "decode_steps",
                                    "prompt_tokens", "generated_tokens",
                                    "prefill_padded_tokens", "decode_real_rows",
                                    "decode_padded_tokens", "bucket_misses")}
    late = [r["submit_t"] - r["due"] for r in recs]
    log(f"window: requests={len(recs)} window_s={w['t_stop'] - w['t0']:.3f} "
        f"steps={json.dumps(d)} compiles_in_window={w['compiles_in_window']}")
    pf = d["prefill_padded_tokens"]
    dp = d["decode_padded_tokens"]
    log(f"padding: prefill_waste={1 - d['prompt_tokens'] / pf if pf else 0:.4f} "
        f"decode_waste={1 - d['decode_real_rows'] / dp if dp else 0:.4f}")
    if w["open_loop"]:
        log(f"generator: late_p50_ms={1e3 * S.percentile(late, 50):.3f} "
            f"late_max_ms={1e3 * max(late):.3f} over {len(late)} requests")
        tt = S.ttft_samples(recs, w["t_stop"])
        log(f"ttft: median_ms={1e3 * S.percentile(tt, 50):.3f} "
            f"p95_ms={1e3 * S.percentile(tt, 95):.3f} samples={len(tt)}")
    gaps = S.itl_samples(recs)
    if gaps:
        log(f"itl: median_ms={1e3 * S.percentile(gaps, 50):.3f} "
            f"p95_ms={1e3 * S.percentile(gaps, 95):.3f} samples={len(gaps)}")
    span = w["t_stop"] - w["t0"]
    log(f"throughput: tok_s={(d['prompt_tokens'] + d['generated_tokens']) / span:.3f}"
        f" finished={sum(1 for r in recs if r['req'] is not None and r['req'].done)}")
    log(f"memory: peak_bytes_in_use={max(peaks)} by_device={json.dumps(peaks)}")
    steps = [s for s in w["steps"] if s.kind in ("prefill", "decode")]
    for kind in ("prefill", "decode"):
        ms = [1e3 * (s.t1 - s.t0) for s in steps if s.kind == kind]
        if ms:
            log(f"steps.{kind}: n={len(ms)} median_ms={np.median(ms):.3f} "
                f"mean_ms={np.mean(ms):.3f} max_ms={max(ms):.3f}")
    gaps = sorted((1e3 * (b.t0 - a.t1) for a, b in zip(w["steps"], w["steps"][1:])),
                  reverse=True)[:3]
    log(f"host gaps between steps, longest: {[round(g, 3) for g in gaps]} ms")


def _schemes(params) -> dict:
    import jax
    from repro.core.engine import PlannedWeight
    out: dict = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            params, is_leaf=lambda x: isinstance(x, PlannedWeight))[0]:
        if isinstance(leaf, PlannedWeight) and leaf.precombined:
            out.setdefault(leaf.algo, []).append(jax.tree_util.keystr(path))
    return out


def _lcma_share(e, cell: dict) -> dict:
    """Share of the layer matmul FLOPs of each prefill bucket that the
    Decision Module routes to an LCMA, reckoned from its plans."""
    import dataclasses
    import jax
    import repro.api as falcon
    from repro.core.engine import PlannedWeight
    leaves = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            e.params["layers"], is_leaf=lambda x: isinstance(x, PlannedWeight))[0]:
        name = jax.tree_util.keystr(path)
        if "ln" in name:
            continue
        w = leaf.w if isinstance(leaf, PlannedWeight) else leaf
        leaves.append((name, w.shape[-2], w.shape[-1],
                       leaf.algo if isinstance(leaf, PlannedWeight) else None))
    out = {}
    with falcon.use(e.fcfg):
        for b in cell["prefill_batch"]:
            for s in cell["prefill_seq"]:
                M = b * s
                tot = lc = 0.0
                for name, K, N, algo in leaves:
                    f = 2.0 * M * K * N
                    tot += f
                    if algo is not None:
                        c = dataclasses.replace(e.fcfg, mode="auto", candidates=(algo,))
                        if falcon.plan(M, K, N, c, "bfloat16", precombined_b=True).use_lcma:
                            lc += f
                    elif falcon.plan(M, K, N, e.fcfg, "bfloat16").use_lcma:
                        lc += f
                out[f"{b}x{s}"] = round(lc / tot, 4)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench_json = json.load(f)
    entry = next((w for w in bench_json["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        _fail(f"no workload {args.workload!r} in BENCHMARK.json")
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        _fail("no src/repro in this checkout: the system under test is missing")

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        _fail(f"JAX found no TPU (platform {dev.platform!r}); the benchmark "
              "runs only on the chip")
    if len(devices) < entry["chips"]:
        _fail(f"cell {args.workload} needs {entry['chips']} chips, JAX sees "
              f"{len(devices)}")
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    # every program, the engine's small eager ones too, comes from the cache
    # after a cell's first run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device_info = {"platform": dev.platform, "kind": dev.device_kind,
                   "count": entry["chips"]}
    log(f"device: {json.dumps(device_info)} jax={jax.__version__}")
    out_dir = os.path.join(ROOT, "bench", "out", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    result = run_cell(bench_json, args.workload, args.seed, args.seconds,
                      bool(args.trace), device_info, T_START, out_dir)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
