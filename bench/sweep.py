#!/usr/bin/env python3
"""Offered-load sweep of one open-loop cell, to find its knee, on the chip.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 2,3,4

One set-up, then one window per rate (each drained before the next). For
each rate it prints the requests due and finished, the waiting queue's mean
length in each quarter of the window, and the TTFT and inter-token tails.
The knee is the highest rate at which the waiting queue does not grow over
the window; a cell's fixed rate is set from it by hand, in its cell file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def sweep(cell: dict, seed: int, seconds: float, rates, log=print) -> list[dict]:
    from bench import harness, stats as S
    eng = harness.Engine(cell, seed)
    out = []
    for rate in rates:
        w = harness.run_window(eng, seconds, seed, rate=rate)
        recs = w["records"]
        q = w["queue"]
        quarters = []
        for i in range(4):
            lo, hi = w["t0"] + i * seconds / 4, w["t0"] + (i + 1) * seconds / 4
            v = [n for t, n in q if lo <= t < hi]
            quarters.append(sum(v) / len(v) if v else 0.0)
        tt = S.ttft_samples(recs, w["t_stop"])
        gaps = S.itl_samples(recs)
        row = {"rate": rate, "due": len(recs),
               "finished_in_window": sum(
                   1 for r in recs if r["token_t"] and r["token_t"][-1] <= w["t_end"]
                   and r["req"] is not None and r["req"].done),
               "queue_quarters": quarters,
               "ttft_p50_ms": 1e3 * S.percentile(tt, 50),
               "ttft_p95_ms": 1e3 * S.percentile(tt, 95),
               "itl_p50_ms": 1e3 * S.percentile(gaps, 50) if gaps else None,
               "itl_p95_ms": 1e3 * S.percentile(gaps, 95) if gaps else None,
               "drain_s": w["t_stop"] - w["t_end"],
               "late_max_ms": 1e3 * max(r["submit_t"] - r["due"] for r in recs)}
        log(f"sweep: {json.dumps(row)}")
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests per second")
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("sweep: JAX found no TPU; the sweep runs only on the chip")
    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = harness.load_cell(args.workload)
    rates = [float(r) for r in args.rates.split(",")]
    sweep(cell, args.seed, args.seconds, rates)
    return 0


if __name__ == "__main__":
    sys.exit(main())
