"""Sharded Decision Module metrics: layout pricing at a simulated pod scale.

Deterministic (modeled on the static tpu_v5e profile, no accelerator or
multi-process runtime needed — CI-gateable on a CPU host): for each
benchmarked shape ``decide_sharded`` prices every layout at D=8 and reports

* ``scaling_eff`` — T(1 device) / (D * T(best layout)): per-device
  throughput scaling efficiency of the chosen layout (1.0 = linear),
* ``coll_frac`` — collective seconds / total seconds of the chosen plan,
* ``layout_flip`` — 1.0 when re-pricing the same shape over a slow 1 GB/s
  interconnect flips the winner to the replicated (communication-free)
  layout: the acceptance property that the collective term is load-bearing.

An optional measured lane (``--measured``, not gated) runs the mesh
ServeEngine on simulated host devices in a CPU-only subprocess and reports
its tokens/s next to the model (a CPU number, not a device one).
"""
from __future__ import annotations

import dataclasses

from repro.core import decision as dec
from repro.core.hardware import TPU_V5E

SLOW_LINK_BW = 1e9          # bytes/s: the "bad interconnect" re-pricing


def run(shapes=((4096, 4096, 4096), (8192, 8192, 8192), (8192, 8192, 32768)),
        n_devices=8, dtype="bfloat16", verbose=True):
    hw = TPU_V5E
    slow_hw = dataclasses.replace(hw, collective_bw=SLOW_LINK_BW)
    rows = []
    for (M, K, N) in shapes:
        d = dec.decide_sharded(M, N, K, hw, dtype, n_devices=n_devices)
        d_slow = dec.decide_sharded(M, N, K, slow_hw, dtype,
                                    n_devices=n_devices)
        single = dec.decide(M, N, K, hw, dtype)
        rows.append({
            "M": M, "K": K, "N": N, "D": n_devices,
            "layout": d.layout,
            "sharded_tflops": dec.effective_tflops(M, N, K, d.seconds),
            "scaling_eff": single.seconds / (n_devices * d.seconds),
            "coll_frac": d.collective_fraction,
            "layout_flip": float(d.layout != d_slow.layout
                                 and d_slow.layout == "replicated"),
            "slow_layout": d_slow.layout,
        })
        if verbose:
            r = rows[-1]
            print(f"{M}x{K}x{N} @ D={n_devices}: layout={r['layout']:10s} "
                  f"scaling_eff={r['scaling_eff']:.2f} "
                  f"coll_frac={r['coll_frac']:.2f} "
                  f"slow-link -> {r['slow_layout']} "
                  f"(flip={int(r['layout_flip'])})")
    return rows


def run_measured(n_devices=8, requests=16, verbose=True):
    """Mesh ServeEngine throughput on simulated CPU devices (un-gated)."""
    import json
    import os
    import subprocess
    import sys
    body = (
        "import json, numpy as np\n"
        "from repro.configs import smoke_config\n"
        "from repro.serve import ServeEngine, StepLoop\n"
        "cfg = smoke_config('granite_3_2b')\n"
        "eng = ServeEngine(cfg, max_slots=4, max_prompt_len=16,\n"
        "                  max_new_tokens=4,\n"
        f"                 mesh_shape={{'data': 1, 'model': {n_devices}}})\n"
        "rng = np.random.default_rng(0)\n"
        f"for _ in range({requests}):\n"
        "    eng.submit(rng.integers(0, cfg.vocab_size, 8), max_new_tokens=4)\n"
        "StepLoop(eng).run_until_idle()\n"
        "print('@@', json.dumps(eng.summary()['tokens_per_s']))\n")
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    # the simulated devices are CPU devices: the child stays off any chip,
    # which a parent that has imported JAX may already hold
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", body], env=env,
                         capture_output=True, text=True, timeout=600)
    if out.returncode != 0:
        raise RuntimeError(f"measured mesh serve failed:\n{out.stderr}")
    tps = json.loads(out.stdout.split("@@")[1].strip().splitlines()[0])
    if verbose:
        print(f"measured mesh serve: {tps:.1f} tok/s over {n_devices} "
              f"simulated devices")
    return {"mesh_tokens_per_s": tps, "D": n_devices}


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--measured", action="store_true",
                    help="also run the real mesh ServeEngine on simulated "
                         "host devices (slow; never gated)")
    args = ap.parse_args()
    for r in run():
        print(f"distributed,{r['M']},{r['K']},{r['N']},{r['D']},{r['layout']},"
              f"{r['scaling_eff']:.3f},{r['coll_frac']:.3f},"
              f"{int(r['layout_flip'])}")
    if args.measured:
        run_measured()


if __name__ == "__main__":
    main()
