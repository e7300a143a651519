"""Step functions: train (fwd+bwd+AdamW), eval, prefill, decode.

All steps are pure functions of (params, opt_state, batch, step) so they jit
and pjit cleanly; the launch layer attaches in/out shardings. FalconGEMM
policy resolves from the ambient context (``falcon.use``) at trace time; the
``fcfg`` factory kwarg survives as a deprecated override. The compressed-DP
variant computes gradients inside ``shard_map`` and replaces the implicit
GSPMD gradient all-reduce with the int8 collective from
``repro.parallel.compression``.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import engine
from repro.models import model as M
from repro.optim import AdamWConfig, adamw_update, cosine_schedule
from repro.parallel.compression import compressed_psum_mean

__all__ = ["make_train_step", "make_eval_step", "make_prefill_step",
           "make_serve_prefill_step", "make_chunk_prefill_step",
           "make_decode_step", "make_verify_step",
           "make_compressed_dp_train_step", "warm_train"]


def warm_train(cfg: ModelConfig, batch: int, seq: int) -> int:
    """Pre-plan the forward AND backward shapes of every contraction in
    ``cfg`` at (batch, seq) — dense projections, grouped MoE expert FFNs,
    attention score/value contractions and SSD chunk contractions, all
    enumerated by the workload registry (``core.workloads.contraction_set``).

    Run once before jitting a train step: tracing then resolves every
    Decision-Module query — the forward contractions and the custom-VJP
    backward pair of each layer — from a hot plan cache, so the whole step
    compiles without a single cold candidate enumeration. Returns the number
    of ``plan()`` calls issued.
    """
    fc = engine.active_config() or M.falcon_config_for(cfg)
    return engine.warm_buckets(fc, cfg, [(batch, seq)],
                               dtype=str(cfg.dtype), train=True)


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig,
                    total_steps: int = 10_000, warmup: int = 100,
                    fcfg=None, microbatches: int = 1):
    """fwd+bwd+AdamW step. ``microbatches > 1`` enables gradient accumulation:
    the global batch is scanned in chunks with an f32 grad accumulator —
    activation memory scales with the microbatch while the optimizer sees the
    full batch (how large global batches ride on fixed per-device memory)."""
    if fcfg is not None:
        engine.warn_deprecated_fcfg("make_train_step")

    def grad_of(params, batch):
        def loss_fn(p):
            return M.lm_loss(p, cfg, batch)
        return jax.value_and_grad(loss_fn, has_aux=True)(params)

    def train_step(params, opt_state, batch, step):
        with engine.maybe_use(fcfg):
            if microbatches == 1:
                (loss, metrics), grads = grad_of(params, batch)
            else:
                def split(x):
                    n = microbatches
                    assert x.shape[0] % n == 0, (x.shape, n)
                    return x.reshape((n, x.shape[0] // n) + x.shape[1:])

                mbatch = {k: split(v) for k, v in batch.items()}
                gacc0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

                def body(carry, mb):
                    gacc, lacc = carry
                    (l, _), g = grad_of(params, mb)
                    gacc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), gacc, g)
                    return (gacc, lacc + l), None

                (gacc, lsum), _ = jax.lax.scan(
                    body, (gacc0, jnp.zeros((), jnp.float32)), mbatch)
                grads = jax.tree.map(lambda g: g / microbatches, gacc)
                loss = lsum / microbatches
                metrics = {}
            lr_scale = cosine_schedule(step, warmup, total_steps)
            params, opt_state, om = adamw_update(params, grads, opt_state, opt_cfg,
                                                 lr_scale=lr_scale)
            # Planned params: the optimizer stepped the raw weight (planned
            # grads land there, the B̃ cotangent is zero) — re-derive B̃ so
            # the next forward reads a consistent precombined weight.
            # Identity (and free) for trees without PlannedWeights.
            params = engine.refresh_planned_params(params)
            out = {"loss": loss, "lr_scale": lr_scale, **metrics, **om}
            return params, opt_state, out

    return train_step


def make_eval_step(cfg: ModelConfig, fcfg=None):
    if fcfg is not None:
        engine.warn_deprecated_fcfg("make_eval_step")

    def eval_step(params, batch):
        with engine.maybe_use(fcfg):
            loss, metrics = M.lm_loss(params, cfg, batch)
            return {"loss": loss, **metrics}

    return eval_step


def make_prefill_step(cfg: ModelConfig, max_len: int, fcfg=None):
    """Single-pass prefill: fills the KV cache AND returns last-token logits."""
    if fcfg is not None:
        engine.warn_deprecated_fcfg("make_prefill_step")

    def prefill_step(params, tokens, patch_embeds=None):
        with engine.maybe_use(fcfg):
            B = tokens.shape[0]
            cache = M.init_cache(cfg, B, max_len)
            logits, cache, _ = M.forward(params, cfg, tokens,
                                         patch_embeds=patch_embeds, cache=cache,
                                         cache_index=0, logits_mode="last")
            return logits, cache

    return prefill_step


def make_serve_prefill_step(cfg: ModelConfig, max_len: int, fcfg=None):
    """Prefill for bucketed serving: right-padded prompts, per-row last index.

    The continuous-batching engine pads every prompt in a micro-batch up to
    the bucket length, so "last token" differs per row: ``last_index`` (B,)
    selects each request's true final prompt position before the LM head
    runs (on (B, 1, d) — the padded tail never reaches the vocab matmul).
    A per-row length mask derived from ``last_index`` makes SSM/hybrid
    recurrent state exact under the right padding (dt=0 on pad positions).
    Returns (logits (B, 1, V), cache) with the cache sized to ``max_len`` so
    its rows slot directly into the engine's slot cache.
    """
    if fcfg is not None:
        engine.warn_deprecated_fcfg("make_serve_prefill_step")

    def prefill_step(params, tokens, last_index):
        with engine.maybe_use(fcfg):
            B, S = tokens.shape[0], tokens.shape[1]
            cache = M.init_cache(cfg, B, max_len)
            mask = (jnp.arange(S)[None, :]
                    <= last_index[:, None]).astype(jnp.float32)
            hidden, cache, _ = M.forward(params, cfg, tokens, cache=cache,
                                         cache_index=0, logits_mode="none",
                                         length_mask=mask)
            h_last = jnp.take_along_axis(
                hidden, last_index[:, None, None].astype(jnp.int32), axis=1)
            logits = M.compute_logits(params, cfg, h_last)
            return logits, cache

    return prefill_step


def make_chunk_prefill_step(cfg: ModelConfig, fcfg=None):
    """One prefill *chunk* against existing slot-cache rows.

    Chunked prefill splits a long prompt into bucket-sized pieces the
    scheduler interleaves with decode work. Unlike ``make_serve_prefill_step``
    (which creates a fresh cache), a chunk resumes at per-row offset
    ``start`` (B,) into ``cache`` rows gathered from the engine's slot cache:
    positions ``[start, start+S)`` are written this chunk, attention validity
    admits exactly ``kpos < start + S`` (earlier chunks plus this one — any
    stale K/V from a slot's previous occupant above that is masked until
    overwritten), and SSM/hybrid recurrent state carries chunk-to-chunk
    through the cache (zeroed here for first-chunk rows, since a reused slot
    may still hold the previous occupant's state). ``start > 0`` with a
    fresh request also covers prefix-cache reuse: the reused snapshot is
    copied into the slot first and only the suffix runs. Intermediate chunks
    are full buckets (``last_index = S-1``); the final chunk is right-padded
    and ``last_index`` picks each row's true last position for the LM head.
    Returns (logits (B, 1, V), cache rows).
    """
    if fcfg is not None:
        engine.warn_deprecated_fcfg("make_chunk_prefill_step")

    def chunk_step(params, cache, tokens, start, last_index):
        with engine.maybe_use(fcfg):
            B, S = tokens.shape[0], tokens.shape[1]
            if "state" in cache:
                st = cache["state"]
                fresh = (start > 0).astype(st.dtype)
                cache = {**cache,
                         "state": st * fresh.reshape((1, B) + (1,) * (st.ndim - 2))}
            mask = (jnp.arange(S)[None, :]
                    <= last_index[:, None]).astype(jnp.float32)
            hidden, cache, _ = M.forward(params, cfg, tokens, cache=cache,
                                         cache_index=start, logits_mode="none",
                                         length_mask=mask)
            h_last = jnp.take_along_axis(
                hidden, last_index[:, None, None].astype(jnp.int32), axis=1)
            logits = M.compute_logits(params, cfg, h_last)
            return logits, cache

    return chunk_step


def make_decode_step(cfg: ModelConfig, fcfg=None):
    """One-token decode against a KV cache at position ``index``.

    ``index`` is a scalar (uniform batch) or an int vector (B,) of per-row
    positions — the continuous-batching case where every slot in the decode
    micro-batch sits at its own generation offset.
    """
    if fcfg is not None:
        engine.warn_deprecated_fcfg("make_decode_step")

    def decode_step(params, cache, tokens, index):
        with engine.maybe_use(fcfg):
            logits, new_cache, _ = M.forward(params, cfg, tokens, cache=cache,
                                             cache_index=index,
                                             logits_mode="last")
            return logits, new_cache

    return decode_step


def make_verify_step(cfg: ModelConfig, fcfg=None):
    """Speculative verify: score γ+1 tokens in one forward, logits per row.

    ``tokens`` (B, γ+1) is ``[t_last, d_1 .. d_γ]`` per row — the pending
    committed token followed by the draft proposals — decoded against the KV
    cache at per-row ``index``. Causal masking makes row j's logits exactly
    the sequential next-token distribution after ``t_last, d_1..d_j``, so
    the greedy accept rule (accept ``d_j`` while it equals ``argmax`` of row
    ``j-1``; always emit one bonus token from the first non-matching row)
    reproduces non-speculative greedy decoding token-for-token regardless of
    draft quality. Returns (logits (B, γ+1, V), cache rows); rejected draft
    positions stay in the cache but are overwritten before attention
    validity ever admits them (same argument as right-pad prefill).
    """
    if fcfg is not None:
        engine.warn_deprecated_fcfg("make_verify_step")

    def verify_step(params, cache, tokens, index):
        with engine.maybe_use(fcfg):
            logits, new_cache, _ = M.forward(params, cfg, tokens, cache=cache,
                                             cache_index=index,
                                             logits_mode="all")
            return logits, new_cache

    return verify_step


def make_compressed_dp_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig, mesh,
                                  bits: int = 8, fcfg=None,
                                  total_steps: int = 10_000, warmup: int = 100):
    """Pure-DP train step with int8-compressed gradient all-reduce.

    Params replicated, batch sharded over the DP axes; grads are computed
    per-shard inside shard_map and synced with the compressed collective.
    """
    if fcfg is not None:
        engine.warn_deprecated_fcfg("make_compressed_dp_train_step")

    dp_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    batch_spec = P(dp_axes)

    def sharded_grads(params, batch):
        def loss_fn(p):
            return M.lm_loss(p, cfg, batch)

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        grads = compressed_psum_mean(grads, dp_axes, bits=bits)
        loss = jax.lax.pmean(loss, dp_axes)
        return loss, metrics, grads

    smapped = jax.shard_map(
        sharded_grads, mesh=mesh,
        in_specs=(P(), {"tokens": batch_spec, "labels": batch_spec}),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )

    def train_step(params, opt_state, batch, step):
        with engine.maybe_use(fcfg):
            loss, metrics, grads = smapped(params, batch)
            lr_scale = cosine_schedule(step, warmup, total_steps)
            params, opt_state, om = adamw_update(params, grads, opt_state, opt_cfg,
                                                 lr_scale=lr_scale)
            params = engine.refresh_planned_params(params)
            return params, opt_state, {"loss": loss, **om}

    return train_step
