"""Core transformer layers (functional, pytree params, FalconGEMM-backed).

Every dense projection routes through ``falcon_dense`` and the attention
contractions through ``falcon.einsum``, so the paper's technique is a
first-class backend of the whole model zoo. Dispatch policy is the
context-scoped config (``repro.api.use``); the legacy per-call ``fcfg``
argument survives as a deprecated override. ``shards`` in the active config
reflects each matmul's sharding so the Decision Module prices the
*per-device* problem.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine
from repro.core.falcon_gemm import FalconConfig, falcon_dense
from repro.parallel.sharding import BATCH, kv_layout, shard_act

# ---------------------------------------------------------------------------
# Param helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype) -> jnp.ndarray:
    scale = 1.0 / np.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def rmsnorm_init(d: int, dtype) -> jnp.ndarray:
    return jnp.ones((d,), dtype)


def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, hd), positions: (B, S) -> rotated x."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = (theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ---------------------------------------------------------------------------
# Attention (GQA, optional sliding window, flash-style chunking)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _mask(qpos, kpos, window):
    """Causal + optional sliding-window mask. window: traced scalar (0 = off)."""
    causal = kpos[None, :] <= qpos[:, None]
    in_window = jnp.where(window > 0, kpos[None, :] > qpos[:, None] - window, True)
    return causal & in_window


def attention_scores(q, k, v, qpos, kpos, window, kv_valid=None,
                     ragged: bool = False):
    """Direct attention. q: (B,Sq,H,hd) k,v: (B,Sk,Hkv,hd) -> (B,Sq,H,hd).

    GQA groups Q onto the KV heads: q becomes (B,Sq,Hkv,rep,hd) and each KV
    head contracts against its own ``rep`` query heads, so K/V are read at
    their own size and never copied. Under a mesh whose "model" axis does
    not divide Hkv, K/V are first repeated up to H heads, so the query
    heads keep their sharding (``sharding.kv_layout`` is the rule).

    ``ragged=True`` builds the mask per batch row (positions differ across
    the batch — continuous-batching decode where every slot sits at its own
    offset). The uniform path keeps one (Sq, Sk) mask for the whole batch.
    """
    B, Sq, H, hd = q.shape
    if kv_layout(H, k.shape[2]) == "repeat":
        rep = H // k.shape[2]
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    Hkv = k.shape[2]
    q = shard_act(q.reshape(B, Sq, Hkv, H // Hkv, hd), BATCH, None, "model")
    logits = engine.einsum("bqgrd,bkgd->bgrqk", q, k).astype(jnp.float32)
    logits *= 1.0 / np.sqrt(hd)
    if ragged:
        m = jax.vmap(lambda qp, kp: _mask(qp, kp, window))(qpos, kpos)
        if kv_valid is not None:
            m = m & kv_valid[:, None, :]
        logits = jnp.where(m[:, None, None], logits, NEG_INF)
    else:
        m = _mask(qpos[0], kpos[0], window)  # positions identical across batch
        if kv_valid is not None:
            m = m & kv_valid[0][None, :]
        logits = jnp.where(m[None, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return engine.einsum("bgrqk,bkgd->bqgrd", p, v).reshape(B, Sq, H, hd)


def flash_attention(q, k, v, qpos, kpos, window, kv_valid=None,
                    q_chunk: int = 512, ragged: bool = False):
    """Memory-bounded attention: scan over query chunks.

    Keeps the score tensor at (B, H, q_chunk, Sk), so a long prompt never
    materializes S^2 scores.
    """
    B, Sq, H, hd = q.shape
    if Sq <= q_chunk:
        return attention_scores(q, k, v, qpos, kpos, window, kv_valid=kv_valid,
                                ragged=ragged)
    assert Sq % q_chunk == 0, (Sq, q_chunk)
    nc = Sq // q_chunk
    qc = q.reshape(B, nc, q_chunk, H, hd).transpose(1, 0, 2, 3, 4)
    pc = qpos.reshape(B, nc, q_chunk).transpose(1, 0, 2)

    @jax.checkpoint  # never store the (B,H,qc,Sk) score tensor for bwd
    def chunk_attn(qi, pi, kk, vv):
        return attention_scores(qi, kk, vv, pi, kpos, window, kv_valid=kv_valid,
                                ragged=ragged)

    def body(carry, xs):
        qi, pi = xs
        return carry, chunk_attn(qi, pi, k, v)

    _, outs = jax.lax.scan(body, None, (qc, pc))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd)


@dataclasses.dataclass(frozen=True)
class AttnDims:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int


def attn_init(key, dims: AttnDims, dtype) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    H, Hkv, hd, d = dims.num_heads, dims.num_kv_heads, dims.head_dim, dims.d_model
    return {
        "w_q": dense_init(kq, d, H * hd, dtype),
        "w_k": dense_init(kk, d, Hkv * hd, dtype),
        "w_v": dense_init(kv, d, Hkv * hd, dtype),
        "w_o": dense_init(ko, H * hd, d, dtype),
    }


def attn_apply(p: dict, x: jnp.ndarray, dims: AttnDims, positions, theta: float,
               window, fcfg: FalconConfig | None = None,
               cache: dict | None = None, cache_index=None):
    """Attention with optional KV cache.

    prefill/train: cache=None -> self-attention over x.
    decode: cache={'k','v'} (B, S_max, Hkv, hd); x is (B, 1, d) at
    ``cache_index``; returns (out, new_cache).

    ``cache_index`` may be a scalar (all rows at the same offset — the
    one-shot serve path) or a (B,) vector of per-row offsets (continuous
    batching: each slot decodes at its own position; K/V writes, validity
    and the causal mask are then applied per row).

    Dispatch policy comes from the context config; ``fcfg`` is a deprecated
    per-call override.
    """
    with engine.deprecated_fcfg(fcfg, "attn_apply"), jax.named_scope("attn"):
        B, S, d = x.shape
        H, Hkv, hd = dims.num_heads, dims.num_kv_heads, dims.head_dim
        q = shard_act(falcon_dense(x, p["w_q"]).reshape(B, S, H, hd),
                      BATCH, None, "model")
        k = shard_act(falcon_dense(x, p["w_k"]).reshape(B, S, Hkv, hd),
                      BATCH, None, "model")
        v = shard_act(falcon_dense(x, p["w_v"]).reshape(B, S, Hkv, hd),
                      BATCH, None, "model")
        q = rope(q, positions, theta)
        k = rope(k, positions, theta)
        if cache is None:
            out = flash_attention(q, k, v, positions, positions, window)
            new_cache = None
        else:
            idx = jnp.asarray(cache_index)
            ragged = idx.ndim == 1
            if ragged:
                def upd(c, u, i):
                    return jax.vmap(
                        lambda cr, ur, ir: jax.lax.dynamic_update_slice(
                            cr, ur.astype(cr.dtype), (ir, 0, 0)))(c, u, i)
                ck = upd(cache["k"], k, idx)
                cv = upd(cache["v"], v, idx)
            else:
                ck = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, cache_index, 0, 0))
                cv = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, cache_index, 0, 0))
            S_max = ck.shape[1]
            kpos = jnp.broadcast_to(jnp.arange(S_max)[None], (B, S_max))
            # everything written so far (prompt prefill writes S tokens at once)
            kv_valid = kpos < (idx[:, None] if ragged else idx) + S
            out = flash_attention(q, ck, cv, positions, kpos, window,
                                  kv_valid=kv_valid, ragged=ragged)
            new_cache = {"k": ck, "v": cv}
        out = falcon_dense(out.reshape(B, S, H * hd), p["w_o"])
        return shard_act(out, BATCH, None, None), new_cache


# ---------------------------------------------------------------------------
# MLP (SwiGLU)
# ---------------------------------------------------------------------------

def mlp_init(key, d: int, d_ff: int, dtype, mlp_type: str = "swiglu") -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    if mlp_type == "gelu":  # classic 2-matrix MLP (starcoder2, musicgen)
        return {
            "mlp_up": dense_init(k2, d, d_ff, dtype),
            "mlp_down": dense_init(k3, d_ff, d, dtype),
        }
    return {
        "mlp_gate": dense_init(k1, d, d_ff, dtype),
        "mlp_up": dense_init(k2, d, d_ff, dtype),
        "mlp_down": dense_init(k3, d_ff, d, dtype),
    }


def mlp_apply(p: dict, x: jnp.ndarray,
              fcfg: FalconConfig | None = None) -> jnp.ndarray:
    with engine.deprecated_fcfg(fcfg, "mlp_apply"), jax.named_scope("mlp"):
        u = shard_act(falcon_dense(x, p["mlp_up"]), BATCH, None, "model")
        if "mlp_gate" in p:
            g = shard_act(falcon_dense(x, p["mlp_gate"]), BATCH, None, "model")
            h = jax.nn.silu(g) * u
        else:
            h = jax.nn.gelu(u)
        out = falcon_dense(h, p["mlp_down"])
        return shard_act(out, BATCH, None, None)
