#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

    python bench/control.py --workload <cell> --seeds 1,2,...,12 \\
        --control-seeds 1,2,3 --seconds 8

For each seed it builds the cell's engine, serves the cell's traffic at the
cell's own load for a short window (every request drained), and compares a
sample of the served tokens with the float32 reference: the widest gap by
which a served token's reference logit lies below the reference's best (the
lower reading, over sound runs of the program). For the control seeds it
also runs the reference in each lower precision that the configuration's
family offers as a control (its ``CONTROLS``, such as float8 e4m3 and int8
on every projection and the LM head) over the same prompts and tokens, and
reads the gap of the token each puts first (the upper reading is the least
of the first control's).
One process, so that the programs compile once. The benchmark's own runs
do not run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))



def readings(cell: dict, seeds, control_seeds, seconds: float,
             log=print) -> list[dict]:
    """One reading per seed: ``widest_gap``, and ``control_gap.<precision>``
    for the control seeds."""
    from bench import harness
    out = []
    for seed in seeds:
        eng = harness.Engine(cell, seed)
        w = harness.run_window(eng, seconds, seed)
        sample = harness.served_sample(w["records"], seed,
                                       cell["correct"]["min_tokens"])
        eng.free()
        params = eng.reference_weights()
        r = harness.widest_gap(
            eng.family, eng.dims, params, sample,
            harness.reference_seq_len(cell), cell["mix"]["output"]["max"],
            controls=eng.family.CONTROLS if seed in control_seeds else ())
        r["seed"] = seed
        log(f"reading: {json.dumps(r)}")
        out.append(r)
        del params
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("control: JAX found no TPU; the readings are taken on the chip")
    from bench import families, harness
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = harness.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    rs = readings(cell, seeds, ctrl, args.seconds)
    lower = max(r["widest_gap"] for r in rs)
    upper = {c: min((r[f"control_gap.{c}"] for r in rs
                     if f"control_gap.{c}" in r), default=None)
             for c in families.load(cell["conf"]).CONTROLS}
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "upper": upper, "readings": rs}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
