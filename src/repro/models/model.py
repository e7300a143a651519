"""Unified decoder LM covering all assigned families.

Families: dense (granite/starcoder2/mistral-nemo/gemma3 local-global),
moe (kimi-k2/dbrx), ssm (mamba2 SSD), hybrid (hymba: parallel attn+SSM),
audio (musicgen codebook streams), vlm (pixtral stub patch prefix).

Layers are scanned (``jax.lax.scan`` over stacked params) so the HLO stays
compact for 1T-parameter dry-runs; per-layer attention windows (gemma3's 5:1
local:global pattern) ride along as scan xs so the traced graph is uniform.
All projections are FalconGEMM-dispatched.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine
from repro.core.falcon_gemm import FalconConfig, falcon_dense
from repro.parallel.sharding import BATCH, shard_act
from repro.configs.base import ModelConfig
from . import layers as L
from . import moe as MOE
from . import ssd as SSD

__all__ = ["init_params", "forward", "init_cache", "falcon_config_for",
           "chunked_xent", "lm_loss"]


def falcon_config_for(cfg: ModelConfig, mesh_shape: dict | None = None) -> FalconConfig:
    """Build the FalconGEMM policy for this model; per-device decision scaling
    comes from the model-parallel degree (activations sharded on batch=M,
    weights on N or K)."""
    model_par = (mesh_shape or {}).get("model", 1)
    data_par = (mesh_shape or {}).get("data", 1) * (mesh_shape or {}).get("pod", 1)
    if cfg.parallel_style == "fsdp_only":
        # no TP: weights are gathered for compute; only batch (M) is sharded
        data_par, model_par = data_par * model_par, 1
    return FalconConfig(
        enabled=cfg.use_falcon,
        mode=cfg.falcon_mode,
        backend=cfg.falcon_backend,
        shards=(data_par, 1, model_par),
    )


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _layer_init(key, cfg: ModelConfig) -> dict:
    keys = jax.random.split(key, 8)
    dt = jnp.dtype(cfg.dtype)
    p: dict = {"ln1": L.rmsnorm_init(cfg.d_model, dt)}
    if cfg.family == "ssm":
        p["ssm"] = SSD.ssd_init(keys[0], cfg.d_model, cfg.ssm_state,
                                cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, dt)
        return p
    dims = L.AttnDims(cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                      cfg.resolved_head_dim)
    if cfg.family == "hybrid":
        p["attn"] = L.attn_init(keys[0], dims, dt)
        p["ssm"] = SSD.ssd_init(keys[1], cfg.d_model, cfg.ssm_state,
                                cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, dt)
        p["attn_norm"] = L.rmsnorm_init(cfg.d_model, dt)
        p["ssm_norm"] = L.rmsnorm_init(cfg.d_model, dt)
    else:
        p["attn"] = L.attn_init(keys[0], dims, dt)
    p["ln2"] = L.rmsnorm_init(cfg.d_model, dt)
    if cfg.family == "moe":
        p["moe"] = MOE.moe_init(keys[2], cfg.d_model, cfg.d_ff, cfg.num_experts, dt)
    elif cfg.d_ff > 0:
        p["mlp"] = L.mlp_init(keys[2], cfg.d_model, cfg.d_ff, dt, cfg.mlp_type)
    return p


def padded_vocab(cfg: ModelConfig) -> int:
    """Vocab padded to a multiple of 256 so logits shard over any TP degree
    (non-divisible vocabs like granite's 49155 would otherwise replicate the
    whole logits computation across the model axis — measured 16x waste)."""
    return -(-cfg.vocab_size // 256) * 256


@functools.partial(jax.jit, static_argnums=0)
def init_params(cfg: ModelConfig, key) -> dict:
    """Random parameters for ``cfg`` from ``key``.

    One compiled program: the per-layer stack is built in place, and a caller
    that wraps it in ``jax.jit(..., out_shardings=...)`` gets each leaf
    created directly in its sharded layout, with the same values.
    """
    ke, kl, kh = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.dtype)
    Vp = padded_vocab(cfg)
    params: dict = {}
    if cfg.frontend == "audio_codebooks":
        params["embed"] = (jax.random.normal(
            ke, (cfg.num_codebooks, Vp, cfg.d_model), jnp.float32)
            * 0.02).astype(dt)
    else:
        params["embed"] = (jax.random.normal(
            ke, (Vp, cfg.d_model), jnp.float32) * 0.02).astype(dt)
    layer_keys = jax.random.split(kl, cfg.num_layers)
    params["layers"] = jax.vmap(lambda k: _layer_init(k, cfg))(layer_keys)
    params["final_norm"] = L.rmsnorm_init(cfg.d_model, dt)
    if cfg.frontend == "audio_codebooks":
        params["lm_head"] = (jax.random.normal(
            kh, (cfg.num_codebooks, cfg.d_model, Vp), jnp.float32)
            / np.sqrt(cfg.d_model)).astype(dt)
    elif not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(kh, cfg.d_model, Vp, dt)
    return params


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None) -> dict:
    dt = jnp.dtype(dtype or cfg.dtype)
    Lc = cfg.num_layers
    cache: dict = {}
    if cfg.family in ("dense", "moe", "hybrid", "audio", "vlm"):
        hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        cache["k"] = jnp.zeros((Lc, batch, max_len, hkv, hd), dt)
        cache["v"] = jnp.zeros((Lc, batch, max_len, hkv, hd), dt)
    if cfg.family in ("ssm", "hybrid"):
        cache["state"] = jnp.zeros(
            (Lc, batch, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim), dt)
    return cache


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _embed_tokens(params, cfg: ModelConfig, tokens, patch_embeds=None):
    if cfg.frontend == "audio_codebooks":
        # params["embed"]: (CB, V, d); tokens: (B, S, CB) — sum codebook embeds
        x = 0.0
        for c in range(cfg.num_codebooks):
            x = x + jnp.take(params["embed"][c], tokens[..., c], axis=0)
        return x
    x = jnp.take(params["embed"], tokens, axis=0)  # (B, S, d)
    if cfg.frontend == "vision_patches" and patch_embeds is not None:
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
    return x


def _layer_body(x, lp, window, cfg: ModelConfig, positions, theta,
                cache_layer=None, cache_index=None, length_mask=None):
    """One decoder layer. Returns (x, new_cache_layer, aux).

    ``length_mask`` (B, S) marks real (1) vs right-pad (0) positions; SSD
    mixers zero dt on pad so the recurrent state ignores the padded tail
    (attention is already exact under causal masking + decode validity).
    """
    dims = None if cfg.family == "ssm" else L.AttnDims(
        cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim)
    aux = jnp.zeros((), jnp.float32)
    new_cache: dict = {}
    h = L.rmsnorm(x, lp["ln1"], cfg.norm_eps)
    # SSD runs its recurrence only for true single-token decode; multi-token
    # prefill with a cache uses the chunked scan and stores the final state.
    is_decode = cache_layer is not None and h.shape[1] == 1
    if cfg.family == "ssm":
        st = None if cache_layer is None else cache_layer.get("state")
        y, new_state = SSD.ssd_apply(lp["ssm"], h, cfg, state=st,
                                     decode=is_decode, length_mask=length_mask)
        if cache_layer is not None:
            new_cache["state"] = new_state
        return x + y, new_cache, aux
    if cfg.family == "hybrid":
        kv = None if cache_layer is None else {"k": cache_layer["k"], "v": cache_layer["v"]}
        ya, kv_new = L.attn_apply(lp["attn"], h, dims, positions, theta, window,
                                  cache=kv, cache_index=cache_index)
        st = None if cache_layer is None else cache_layer.get("state")
        ys, new_state = SSD.ssd_apply(lp["ssm"], h, cfg, state=st,
                                      decode=is_decode,
                                      length_mask=length_mask)
        y = 0.5 * (L.rmsnorm(ya, lp["attn_norm"], cfg.norm_eps)
                   + L.rmsnorm(ys, lp["ssm_norm"], cfg.norm_eps))
        x = x + y
        if cache_layer is not None:
            new_cache = {"k": kv_new["k"], "v": kv_new["v"], "state": new_state}
    else:
        kv = None if cache_layer is None else {"k": cache_layer["k"], "v": cache_layer["v"]}
        y, kv_new = L.attn_apply(lp["attn"], h, dims, positions, theta, window,
                                 cache=kv, cache_index=cache_index)
        x = x + y
        if cache_layer is not None:
            new_cache = {"k": kv_new["k"], "v": kv_new["v"]}
    if cfg.parallel_block:
        # PaLM-style parallel block: the FFN reads ln1(x) like attention, and
        # the residual x + y_attn + y_ffn lets XLA's AllReduceReassociate
        # merge the two TP all-reduces into one (AR(a)+AR(b) -> AR(a+b)).
        h2 = h
    else:
        h2 = L.rmsnorm(x, lp["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        from repro.core.workloads import moe_capacity
        T = int(np.prod(h2.shape[:-1]))
        cap = moe_capacity(T, cfg.experts_per_token, cfg.num_experts,
                           cfg.capacity_factor, shard_round=True)
        y2, aux = MOE.moe_apply(lp["moe"], h2, cfg.experts_per_token,
                                cfg.capacity_factor,
                                deterministic_capacity=cap)
    elif cfg.d_ff > 0:
        y2 = L.mlp_apply(lp["mlp"], h2)
    else:
        y2 = jnp.zeros_like(x)
    return x + y2, new_cache, aux


def forward(params, cfg: ModelConfig, tokens, *, patch_embeds=None,
            cache=None, cache_index=None, fcfg: FalconConfig | None = None,
            logits_mode: str = "none", length_mask=None):
    """Run the decoder stack.

    logits_mode: "none" (return hidden), "last" (logits of final position),
    "all" (full logits — small vocab / smoke only; training uses
    ``lm_loss`` with chunked cross-entropy instead).
    ``length_mask`` (B, S): 1 on real positions, 0 on right pad — makes
    bucketed (right-padded) prefill exact for SSM/hybrid recurrent state.
    Returns (out, new_cache, aux_loss).

    FalconGEMM policy resolves from the ambient context (``falcon.use``),
    falling back to this model's ``falcon_config_for``; ``fcfg`` is a
    deprecated per-call override.
    """
    with engine.config_scope(fcfg, "forward", lambda: falcon_config_for(cfg)):
        return _forward(params, cfg, tokens, patch_embeds=patch_embeds,
                        cache=cache, cache_index=cache_index,
                        logits_mode=logits_mode, length_mask=length_mask)


def _forward(params, cfg: ModelConfig, tokens, *, patch_embeds=None,
             cache=None, cache_index=None, logits_mode: str = "none",
             length_mask=None):
    x = shard_act(_embed_tokens(params, cfg, tokens, patch_embeds),
                  BATCH, None, None)
    B, S = x.shape[0], x.shape[1]
    if cache is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    else:
        idx = jnp.asarray(cache_index)
        if idx.ndim == 1:       # per-row offsets (continuous-batching decode)
            positions = idx[:, None] + jnp.arange(S)[None]
        else:
            positions = jnp.broadcast_to(idx[None, None], (B, S)) \
                + jnp.arange(S)[None]
    windows = jnp.asarray(cfg.layer_windows(), jnp.int32)
    theta = cfg.rope_theta

    def body(carry, xs):
        x, aux = carry
        if cache is None:
            lp, w = xs
            cl = None
        else:
            lp, w, cl = xs
        fn = lambda x_: _layer_body(x_, lp, w, cfg, positions, theta,
                                    cache_layer=cl, cache_index=cache_index,
                                    length_mask=length_mask)
        if cfg.remat and cache is None:
            if cfg.remat_policy == "dots":
                # selective: keep matmul outputs, recompute elementwise ops —
                # ~3.1x fwd-flops multiplier instead of 4x at modest memory
                policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                x, nc, a = jax.checkpoint(fn, policy=policy)(x)
            else:
                x, nc, a = jax.checkpoint(fn)(x)
        else:
            x, nc, a = fn(x)
        return (shard_act(x, BATCH, None, None), aux + a), nc

    xs = (params["layers"], windows) if cache is None else (params["layers"], windows, cache)
    (x, aux), new_cache = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)

    if logits_mode == "none":
        return x, new_cache, aux
    if logits_mode == "last":
        x = x[:, -1:]
    logits = compute_logits(params, cfg, x)
    return logits, new_cache, aux


def compute_logits(params, cfg: ModelConfig, x, fcfg: FalconConfig | None = None):
    with engine.config_scope(fcfg, "compute_logits",
                             lambda: falcon_config_for(cfg)), \
            jax.named_scope("lm_head"):
        return _compute_logits(params, cfg, x)


def _compute_logits(params, cfg: ModelConfig, x):
    Vp = padded_vocab(cfg)

    def mask_pad(logits):
        if Vp == cfg.vocab_size:
            return logits
        pad_mask = jnp.arange(Vp) < cfg.vocab_size
        return jnp.where(pad_mask, logits, -1e30)

    if cfg.frontend == "audio_codebooks":
        outs = [falcon_dense(x, params["lm_head"][c])
                for c in range(cfg.num_codebooks)]
        return mask_pad(jnp.stack(outs, axis=2))  # (B, S, CB, Vp)
    w = params["lm_head"] if not cfg.tie_embeddings else params["embed"].T
    return mask_pad(falcon_dense(x, w))


# ---------------------------------------------------------------------------
# Loss (chunked cross-entropy: never materialize (B, S, V) for big vocabs)
# ---------------------------------------------------------------------------

def chunked_xent(params, cfg: ModelConfig, hidden, labels,
                 fcfg: FalconConfig | None = None, chunk: int = 512):
    """hidden: (B, S, d); labels: (B, S[, CB]) -> mean xent (f32)."""
    with engine.config_scope(fcfg, "chunked_xent",
                             lambda: falcon_config_for(cfg)):
        return _chunked_xent(params, cfg, hidden, labels, chunk=chunk)


def _chunked_xent(params, cfg: ModelConfig, hidden, labels, chunk: int = 512):
    B, S = hidden.shape[0], hidden.shape[1]
    chunk = min(chunk, S)
    while S % chunk:
        chunk -= 1
    nc = S // chunk
    hs = hidden.reshape(B, nc, chunk, -1).transpose(1, 0, 2, 3)
    ls = labels.reshape((B, nc, chunk) + labels.shape[2:]).transpose(
        (1, 0, 2) + tuple(range(3, labels.ndim + 1)))

    @jax.checkpoint  # recompute per-chunk logits in bwd: (B,chunk,V) never stored
    def chunk_loss(h, lab):
        logits = _compute_logits(params, cfg, h).astype(jnp.float32)
        logits = shard_act(logits, BATCH, None, "model")
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        return jnp.sum(logz - gold)

    def body(acc, xs):
        h, lab = xs
        return acc + chunk_loss(h, lab), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hs, ls))
    denom = np.prod(labels.shape)
    return total / denom


def lm_loss(params, cfg: ModelConfig, batch: dict, fcfg: FalconConfig | None = None):
    """batch: {'tokens', 'labels'[, 'patch_embeds']} -> (loss, metrics)."""
    with engine.config_scope(fcfg, "lm_loss", lambda: falcon_config_for(cfg)):
        hidden, _, aux = forward(params, cfg, batch["tokens"],
                                 patch_embeds=batch.get("patch_embeds"),
                                 logits_mode="none")
        labels = batch["labels"]
        if cfg.frontend == "vision_patches":
            hidden = hidden[:, -labels.shape[1]:]  # loss on the text positions
        xent = chunked_xent(params, cfg, hidden, labels)
        loss = xent + 0.01 * aux
        return loss, {"xent": xent, "aux": aux}
