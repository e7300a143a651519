"""Serving launcher: a thin CLI over the serving engines.

Two modes share the FalconGEMM serving stack (context-scoped config, offline
Combine B via ``PlannedWeight``, persistent plan cache):

* ``--continuous`` — the continuous-batching :class:`repro.serve.ServeEngine`:
  ``--requests N`` synthetic requests with ragged prompt lengths are admitted
  through bucketed prefill micro-batches and decoded with per-slot positions;
  the engine pre-plans and pre-compiles the whole bucket grid (``--no-warm``
  opts out) and prints the ``ServeStats`` surface (tokens/s, bucket hit rate,
  plan-cache hit rate, padding waste). See ``docs/serving.md``.

* default — the original one-shot batched prefill + autoregressive decode
  (every row advances in lockstep), kept for benchmarks and smoke tests.

``python -m repro.launch.serve --arch granite_3_2b --continuous --requests 32``
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.api as falcon
from repro import compat
from repro.configs import get_config, smoke_config
from repro.core import plan_cache
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serve import ServeEngine, StepLoop
from repro.train.steps import make_decode_step, make_prefill_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--precombine", action="store_true", default=True,
                    help="lift static weights to PlannedWeights (offline "
                         "Combine B) where the Decision Module picks an LCMA")
    ap.add_argument("--no-precombine", dest="precombine", action="store_false")
    ap.add_argument("--quant", action="store_true",
                    help="serve with the int8-quantized decision tier: the "
                         "Decision Module prices quantized execution next to "
                         "fp under the accuracy budget, PlannedWeights carry "
                         "offline-quantized B̃q + scales, and warm() pre-"
                         "plans the quantized buckets (--continuous)")
    ap.add_argument("--plan-cache", default=None, metavar="PATH",
                    help="persistent Decision plan cache (JSON, written by "
                         "repro.tools.tune); loaded before tracing and "
                         "flushed back on exit")
    # continuous-batching engine
    ap.add_argument("--continuous", action="store_true",
                    help="serve --requests jobs through the continuous-"
                         "batching engine instead of one lockstep batch")
    ap.add_argument("--requests", type=int, default=32,
                    help="number of synthetic requests (--continuous)")
    ap.add_argument("--max-slots", type=int, default=8,
                    help="concurrent decode slots (--continuous)")
    ap.add_argument("--min-prompt-len", type=int, default=4,
                    help="ragged prompt lower bound (--continuous)")
    ap.add_argument("--warm", action="store_true", default=True,
                    help="pre-plan + pre-compile the bucket grid before "
                         "serving (--continuous)")
    ap.add_argument("--no-warm", dest="warm", action="store_false")
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="shard over a real (data, model) mesh, e.g. "
                         "--mesh 1,8 for 8-way tensor parallelism (default: "
                         "one device); simulate devices on one host with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    ap.add_argument("--speculate", type=int, default=0, metavar="GAMMA",
                    help="speculative decoding: a self-draft proposes GAMMA "
                         "tokens per round, one (batch, GAMMA+1) verify "
                         "forward accepts greedily — token-exact, attention "
                         "families only (--continuous)")
    ap.add_argument("--draft-layers", type=int, default=None, metavar="N",
                    help="slice the draft to the target's first N layers "
                         "(default: all layers = identity draft)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix-KV cache: repeated or extended "
                         "prompts skip prefilling the shared prefix "
                         "(--continuous)")
    ap.add_argument("--prefill-chunk", type=int, default=None, metavar="S",
                    help="split long prompts into S-token prefill chunks "
                         "(S must be a prefill bucket) interleaved with "
                         "decode (--continuous)")
    ap.add_argument("--stream", action="store_true",
                    help="print the first request's tokens as they are "
                         "emitted (--continuous)")
    args = ap.parse_args()
    args.mesh_shape = _parse_mesh(args.mesh)
    enable_compile_cache()

    if args.plan_cache:
        cache = plan_cache.configure(path=args.plan_cache)
        print(f"plan cache: {len(cache)} plans loaded from {args.plan_cache}")

    cfg = smoke_config(args.arch) if args.reduced else get_config(args.arch)
    if args.continuous:
        _run_continuous(cfg, args)
    else:
        _run_oneshot(cfg, args)
    if args.plan_cache:
        plan_cache.flush()


def _parse_mesh(spec: str | None) -> dict:
    """"DATA,MODEL" -> {"data": DATA, "model": MODEL} (empty without --mesh)."""
    if not spec:
        return {}
    parts = [int(x) for x in spec.replace("x", ",").split(",") if x]
    if len(parts) != 2 or min(parts) < 1:
        raise SystemExit(f"--mesh expects DATA,MODEL (e.g. 1,8); got {spec!r}")
    return {"data": parts[0], "model": parts[1]}


def _run_continuous(cfg, args) -> None:
    engine = ServeEngine(
        cfg, max_slots=args.max_slots, max_prompt_len=args.prompt_len,
        max_new_tokens=args.gen, precombine=args.precombine, seed=args.seed,
        mesh_shape=args.mesh_shape, quantize=args.quant,
        speculate=args.speculate, draft_keep_layers=args.draft_layers,
        prefix_cache=args.prefix_cache, prefill_chunk=args.prefill_chunk)
    if engine.mesh is not None:
        print(f"mesh: {dict(engine.mesh.shape)} over "
              f"{len(jax.devices())} visible device(s)")
    print(f"engine: {args.max_slots} slots, cache len {engine.max_len}, "
          f"{engine.n_precombined} weight tensor(s) precombined"
          f"{' (int8-quantized tier on)' if args.quant else ''}, buckets "
          f"seq={list(engine.policy.prefill_seq)} "
          f"prefill_batch={list(engine.policy.prefill_batch)} "
          f"decode_batch={list(engine.policy.decode_batch)}")
    if args.warm:
        w = engine.warm()
        print(f"warmup: {w['plans']} Decision plans, {w['shapes']} step "
              f"shapes compiled in {w['seconds']:.1f}s")
    rng = np.random.default_rng(args.seed)
    lo = min(args.min_prompt_len, args.prompt_len)
    first = None
    for i in range(args.requests):
        plen = int(rng.integers(lo, args.prompt_len + 1))
        req = engine.submit(
            rng.integers(0, cfg.vocab_size, plen),
            max_new_tokens=int(rng.integers(1, args.gen + 1)),
            on_token=((lambda r, t: print(f"  rid={r.rid} token {t}"))
                      if args.stream and i == 0 else None))
        first = first or req
    t0 = time.perf_counter()
    done = StepLoop(engine).run_until_idle()
    wall = time.perf_counter() - t0
    s = engine.summary()
    print(f"served {len(done)}/{args.requests} requests in {wall:.2f}s: "
          f"{s['prompt_tokens']} prompt + {s['generated_tokens']} generated "
          f"tokens ({s['tokens_per_s']:.1f} tok/s real, "
          f"{s['decode_tokens_per_s']:.1f} decode tok/s)")
    print(f"steps: {s['prefill_steps']} prefill + {s['decode_steps']} decode "
          f"+ {s['verify_steps']} verify | "
          f"bucket hit rate {s['bucket_hit_rate']:.1%} | "
          f"padding waste {s['padding_waste']:.1%}")
    if args.speculate:
        print(f"speculation: gamma={args.speculate}, acceptance rate "
              f"{s['acceptance_rate']:.1%} "
              f"({s['accepted_tokens']}/{s['drafted_tokens']} drafts kept)")
    if args.prefix_cache and s.get("prefix_cache"):
        p = s["prefix_cache"]
        print(f"prefix cache: {p['hits']} hits / {p['misses']} misses, "
              f"{s['prefix_tokens_reused']} prompt tokens reused, "
              f"{p['entries']} entries ({p['evictions']} evicted)")
    pc = s["plan_cache"]
    print(f"plan cache: {pc['hits']} hits / {pc['misses']} misses "
          f"({pc['hit_rate']:.0%} hit rate, {pc['entries']} plans)")
    if done:
        sample = done[0]
        print(f"sample (rid={sample.rid}): {sample.generated[:16]}")


def _run_oneshot(cfg, args) -> None:
    # one device unless --mesh asks for more
    mesh = compat.make_mesh((args.mesh_shape.get("data", 1),
                             args.mesh_shape.get("model", 1)),
                            ("data", "model"))
    fcfg = M.falcon_config_for(cfg, dict(mesh.shape))
    if args.quant:
        fcfg = dataclasses.replace(fcfg, quantize=True)
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    max_len = args.prompt_len + args.gen

    tok_shape = ((args.batch, args.prompt_len, cfg.num_codebooks)
                 if cfg.frontend == "audio_codebooks"
                 else (args.batch, args.prompt_len))
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, tok_shape), jnp.int32)

    prefill = jax.jit(make_prefill_step(cfg, max_len=max_len))
    decode = jax.jit(make_decode_step(cfg), donate_argnums=(1,))

    with jax.set_mesh(mesh), falcon.use(fcfg):
        if args.precombine:
            # Offline Combine B against the prefill shape (the M the Decision
            # Module should price); decode re-decides per its own tiny M.
            params, n_planned = falcon.precombine_params(
                params, m_hint=args.batch * args.prompt_len)
            print(f"offline Combine B: {n_planned} weight tensor(s) "
                  f"precombined into PlannedWeights")
        t0 = time.perf_counter()
        if cfg.frontend == "vision_patches":
            pe = jnp.asarray(rng.standard_normal(
                (args.batch, cfg.num_patches, cfg.d_model)), jnp.dtype(cfg.dtype))
            logits, cache = prefill(params, tokens, pe)
            pos0 = args.prompt_len + cfg.num_patches
        else:
            logits, cache = prefill(params, tokens)
            pos0 = args.prompt_len
        jax.block_until_ready(logits)
        t_prefill = time.perf_counter() - t0

        out_tokens = []
        t0 = time.perf_counter()
        for i in range(args.gen):
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            if cfg.frontend == "audio_codebooks":
                tok = nxt[:, None, :] if nxt.ndim == 2 else jnp.tile(
                    nxt[:, None, None], (1, 1, cfg.num_codebooks))
            else:
                tok = nxt[:, None]
            out_tokens.append(np.asarray(nxt))
            logits, cache = decode(params, cache, tok, pos0 + i)
        jax.block_until_ready(logits)
        t_decode = time.perf_counter() - t0

    print(f"prefill: {t_prefill*1e3:.1f} ms for {args.batch}x{args.prompt_len} tokens")
    print(f"decode:  {t_decode/args.gen*1e3:.2f} ms/token "
          f"({args.batch * args.gen / t_decode:.1f} tok/s)")
    print("sample:", np.stack(out_tokens, 1)[0].reshape(-1)[:16].tolist())
    st = plan_cache.stats()
    print(f"plan cache: {st.hits} hits / {st.misses} misses "
          f"({st.hit_rate:.0%} hit rate, {len(plan_cache.default_cache())} plans)")


if __name__ == "__main__":
    main()
