#!/usr/bin/env python3
"""Smoke run of the FalconGEMM serve path on a TPU, through its entry points.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # four chips of one host

One chip (the default) runs three phases on ``jax.devices()[0]``:

1. device -- requires a TPU and prints what JAX reports;
2. kernels -- the Pallas LCMA pipeline, compiled, at granite_3_2b widths
   (the mlp_gate and lm_head projections of a 1024-row prefill), each
   compared with ``jnp.dot``, plus the int8 pipeline at the lm_head shape;
3. serve -- ``ServeEngine`` on granite_3_2b at full width and depth, random
   weights from ``--seed``, FalconGEMM defaults (precombine on). It warms,
   serves eight requests to completion, and compares the prefill logits of
   the served prompts with the same weights run with FalconGEMM off.

``--chips 4`` runs only the tensor-parallel path: ``ServeEngine`` on
starcoder2_15b (about 31 GB in bf16, more than one chip holds) over a
``{"data": 1, "model": 4}`` mesh, compared with the same mesh with
FalconGEMM off, and prints each device's bytes in use.

Any failing phase exits non-zero. Without a TPU (``JAX_PLATFORMS=cpu``
included), or outside a checkout of the repository, the script exits
non-zero and prints no result. The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Throughputs printed here are smoke figures, not measurements.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.metadata
import json
import os
import sys
import time

# Kernel outputs against jnp.dot (f32 accumulation of the same bf16 inputs),
# as ||C - ref||_F / ||ref||_F. The bf16 pipeline rounds the combined
# operands and the output to bf16 (2^-9 relative each); the int8 pipeline
# adds blockwise 8-bit quantization of both operands.
KERNEL_REL_TOL = 2e-2
INT8_REL_TOL = 5e-2
# Prefill logits, FalconGEMM on against off, as max|on - off| / max|off|
# over the compared prompts: bf16 rounding that differs between the LCMA
# and the plain GEMM, carried through every layer of the model.
LOGIT_REL_TOL = 5e-2


class SmokeFailure(RuntimeError):
    pass


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _import_repro() -> None:
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        sys.exit("chip_smoke: no src/repro beside this script; run it from "
                 "a checkout of the repository")
    sys.path.insert(0, src)


def phase_device(chips: int):
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
                 f"this script runs only on the chip")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, JAX "
                 f"sees {len(devices)}")
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "unknown"
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__} libtpu={libtpu}",
          flush=True)
    return dev, devices


def _rel_fro(got, ref) -> float:
    import jax.numpy as jnp
    got = got.astype(jnp.float32)
    return float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))


def phase_kernels(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import repro.api as falcon
    from repro.core import algorithms as alg
    from repro.kernels import ops

    M, K = 1024, 2048
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.normal(ka, (M, K), jnp.float32).astype(jnp.bfloat16)
    for name, N, scheme in (("mlp_gate", 8192, "strassen"),
                            ("lm_head", 49155, "laderman")):
        l = alg.get(scheme)
        b = (jax.random.normal(kb, (K, N), jnp.float32)
             / K ** 0.5).astype(jnp.bfloat16)
        ref = jnp.dot(a, b, preferred_element_type=jnp.float32)
        t0 = time.perf_counter()
        c = jax.block_until_ready(ops.falcon_matmul_pallas(a, b, l))
        dt = time.perf_counter() - t0
        _check(c.shape == (M, N), f"kernel {name}: shape {c.shape}")
        _check(bool(jnp.all(jnp.isfinite(c))), f"kernel {name}: non-finite")
        rel = _rel_fro(c, ref)
        print(f"kernel {name} {scheme} ({M},{K})@({K},{N}) pallas compiled: "
              f"rel_fro_err={rel:.3e} (bound {KERNEL_REL_TOL}) "
              f"first_call_s={dt:.2f}", flush=True)
        _check(rel <= KERNEL_REL_TOL, f"kernel {name}: error {rel}")
    # int8: the weight quantized offline as a quantized PlannedWeight, then
    # quantizing Combine A + int8 fused GEMM against its B̃q
    pw = falcon.plan_weight(b, falcon.FalconConfig(mode="strassen",
                                                   quantize=True))
    _check(pw.quantized, "kernel int8 lm_head: weight was not quantized")
    t0 = time.perf_counter()
    c = jax.block_until_ready(ops.falcon_matmul_pallas_quant(
        a, pw.bq, pw.b_scales, pw.lcma, pw.n))
    dt = time.perf_counter() - t0
    _check(bool(jnp.all(jnp.isfinite(c))), "kernel int8 lm_head: non-finite")
    rel = _rel_fro(c, ref)
    print(f"kernel lm_head int8 strassen ({M},{K})@({K},{b.shape[1]}) pallas "
          f"compiled: rel_fro_err={rel:.3e} (bound {INT8_REL_TOL}) "
          f"first_call_s={dt:.2f}", flush=True)
    _check(rel <= INT8_REL_TOL, f"kernel int8 lm_head: error {rel}")


def _planned_schemes(params) -> dict:
    """{scheme: [param paths]} for every precombined weight."""
    import jax
    from repro.core.engine import PlannedWeight
    out: dict = {}
    leaves = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, PlannedWeight))[0]
    for path, leaf in leaves:
        if isinstance(leaf, PlannedWeight) and leaf.precombined:
            name = "/".join(str(getattr(p, "key", p)) for p in path)
            out.setdefault(leaf.algo, []).append(name)
    return out


def _reference_logits(engine, prompts):
    """Last-position logits of ``prompts``, prefilled as one batch of the
    engine's bucket with the engine's weights and FalconGEMM off."""
    import contextlib
    import jax
    import jax.numpy as jnp
    import numpy as np
    import repro.api as falcon
    from repro.models import model as M
    from repro.train.steps import make_chunk_prefill_step

    cfg = dataclasses.replace(engine.cfg, use_falcon=False)
    B, S = engine.policy.prefill_batch[-1], engine.policy.prefill_seq[-1]
    toks = np.zeros((B, S), np.int32)
    last = np.zeros((B,), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
        last[i] = len(p) - 1
    out = None
    mesh_ctx = contextlib.nullcontext()
    if engine.mesh is not None:
        out = jax.sharding.NamedSharding(engine.mesh,
                                         jax.sharding.PartitionSpec())
        mesh_ctx = jax.set_mesh(engine.mesh)
    with falcon.use(M.falcon_config_for(cfg, engine.mesh_shape)), mesh_ctx:
        rows = M.init_cache(cfg, B, engine.max_len)
        if out is not None:
            rows = jax.device_put(rows, out)
        fn = jax.jit(make_chunk_prefill_step(cfg), out_shardings=out)
        logits, _ = fn(engine.params, rows, jnp.asarray(toks),
                       jnp.zeros((B,), jnp.int32), jnp.asarray(last))
    return np.asarray(logits[:len(prompts), -1], np.float32)


def phase_serve(arch: str, seed: int, *, max_slots: int, prompt_len: int,
                new_tokens: int, n_requests: int, mesh_shape=None) -> dict:
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.serve import BucketPolicy, ServeEngine, StepLoop

    cfg = get_config(arch)
    # one prefill and one decode shape: a cold compile takes minutes
    policy = BucketPolicy(prefill_seq=(prompt_len,),
                          prefill_batch=(max_slots,),
                          decode_batch=(max_slots,))
    t0 = time.perf_counter()
    engine = ServeEngine(cfg, max_slots=max_slots, max_prompt_len=prompt_len,
                         max_new_tokens=new_tokens, policy=policy, seed=seed,
                         mesh_shape=mesh_shape, record_logits=True)
    jax.block_until_ready(engine.params)
    t_build = time.perf_counter() - t0
    m_hint = policy.prefill_batch[-1] * policy.prefill_seq[-1]
    schemes = _planned_schemes(engine.params)
    print(f"serve {arch}: layers={cfg.num_layers} d_model={cfg.d_model} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} mesh={mesh_shape or None} "
          f"m_hint={m_hint} n_precombined={engine.n_precombined} "
          f"schemes={ {k: len(v) for k, v in schemes.items()} } "
          f"{schemes} build_s={t_build:.1f}", flush=True)
    w = engine.warm()
    print(f"serve {arch}: warm plans={w['plans']} shapes={w['shapes']} "
          f"warm_compile_s={w['seconds']:.1f}", flush=True)

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, int(n))
               for n in rng.integers(prompt_len // 2, prompt_len + 1,
                                     n_requests)]
    for p in prompts:
        engine.submit(p, max_new_tokens=new_tokens)
    t0 = time.perf_counter()
    done = StepLoop(engine).run_until_idle()
    wall = time.perf_counter() - t0
    s = engine.summary()
    print(f"serve {arch}: completed {len(done)}/{n_requests} requests, "
          f"{s['generated_tokens']} tokens in {wall:.2f}s: "
          f"{s['tokens_per_s']:.1f} tok/s (smoke figure, not a measurement); "
          f"bucket misses={s['bucket_misses']}", flush=True)
    _check(len(done) == n_requests and all(r.done for r in engine.requests),
           f"serve {arch}: {len(done)}/{n_requests} requests completed")
    _check(all(len(r.generated) == new_tokens for r in done),
           f"serve {arch}: a request stopped short")

    # all requests fit one prefill batch, in submission order: the served
    # first-token logits and the reference see the same batch
    _check(s["prefill_steps"] == 1,
           f"serve {arch}: {s['prefill_steps']} prefill steps, expected 1")
    on = np.stack([r.logits[0] for r in engine.requests]).astype(np.float32)
    off = _reference_logits(engine, prompts)
    _check(bool(np.all(np.isfinite(on))), f"serve {arch}: non-finite logits")
    v = cfg.vocab_size
    rel = float(np.max(np.abs(on[:, :v] - off[:, :v]))
                / np.max(np.abs(off[:, :v])))
    agree = float(np.mean(np.argmax(on[:, :v], -1) == np.argmax(off[:, :v], -1)))
    print(f"serve {arch}: served prefill logits, FalconGEMM on vs off, over "
          f"{len(prompts)} prompts: rel_max_err={rel:.3e} "
          f"(bound {LOGIT_REL_TOL}) argmax_agree={agree:.3f}", flush=True)
    _check(rel <= LOGIT_REL_TOL, f"serve {arch}: logit error {rel}")
    return {"engine": engine, "schemes": schemes}


def _memory_line(devices) -> str:
    parts = []
    for d in devices:
        st = d.memory_stats() or {}
        parts.append(f"dev{d.id}: bytes_in_use={st.get('bytes_in_use')} "
                     f"peak_bytes_in_use={st.get('peak_bytes_in_use')} "
                     f"bytes_limit={st.get('bytes_limit')}")
    return "; ".join(parts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip tensor-parallel path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    _import_repro()
    dev, devices = phase_device(args.chips)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    if args.chips == 1:
        phase_kernels(args.seed)
        phase_serve("granite_3_2b", args.seed, max_slots=8, prompt_len=128,
                    new_tokens=16, n_requests=8)
        st = dev.memory_stats() or {}
        print(f"memory: {_memory_line([dev])}", flush=True)
        _check(st.get("peak_bytes_in_use") is not None,
               "memory: the device reports no peak_bytes_in_use")
    else:
        out = phase_serve("starcoder2_15b", args.seed, max_slots=4,
                          prompt_len=64, new_tokens=8, n_requests=4,
                          mesh_shape={"data": 1, "model": 4})
        mesh_devs = list(out["engine"].mesh.devices.flat)
        print(f"memory: {_memory_line(mesh_devs)}", flush=True)
        used = [(d.memory_stats() or {}).get("bytes_in_use", 0)
                for d in mesh_devs]
        _check(min(used) >= 0.5 * max(used),
               f"memory: weights are not spread over the mesh: {used}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        sys.exit(f"chip_smoke: FAILED: {e}")
