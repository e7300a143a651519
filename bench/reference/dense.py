"""Plain forward pass of the dense decoder, in ``jax.numpy``.

It imports nothing of the program. It follows the model as the program runs
it (the departures from the published configurations are listed under
``assumed`` in each ``bench/configs/*.json``): pre-norm RMSNorm layers,
rotary embeddings on the two halves of each head, grouped-query causal
attention with scale 1/sqrt(head_dim), a SwiGLU or tanh-GELU MLP, a final
RMSNorm and the LM head (the embedding, transposed, when tied); logits past
the true vocabulary are dropped.

``precision`` picks the arithmetic:

- ``"float32"``: weights (stored in bfloat16) raised to float32 and every
  contraction at ``Precision.HIGHEST`` -- the reference;
- ``"bfloat16"``: activations rounded to bfloat16 before every contraction,
  float32 accumulation -- one step below the reference, for tests;
- ``"int8"``: every projection and the LM head as int8 x int8 with int32
  accumulation (activations per row, weights per output column, absmax
  scales), attention in bfloat16;
- ``"fp8"``: the same, with the operands rounded to float8 e4m3 (scaled so
  that each row's or column's absmax is 448) and float32 accumulation.

``int8`` and ``fp8`` are the lower-precision controls for a model served in
bfloat16.

The layer stack is a ``lax.scan`` that raises one layer's weights at a
time, so a full-width model fits beside its float32 activations.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["hidden_states", "logits_at", "PRECISIONS"]

PRECISIONS = ("float32", "bfloat16", "int8", "fp8")
HI = jax.lax.Precision.HIGHEST


def _quant(x, axis):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s


def _fp8(x, axis):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / 448.0
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)   # exact in bf16
    return q, s


def _mm(x, w, precision):
    """x (..., K) @ w (K, N) in float32 out."""
    w = w.astype(jnp.float32)
    if precision == "float32":
        return jnp.matmul(x, w, precision=HI)
    if precision == "bfloat16":
        return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if precision == "fp8":
        xq, sx = _fp8(x, -1)
        wq, sw = _fp8(w, 0)
        return jnp.matmul(xq, wq, preferred_element_type=jnp.float32) * sx * sw
    xq, sx = _quant(x, -1)
    wq, sw = _quant(w, 0)
    acc = jax.lax.dot_general(xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * sw


def _ein(spec, a, b, precision):
    if precision == "float32":
        return jnp.einsum(spec, a, b, precision=HI)
    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _rmsnorm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(jnp.float32)


def _rope(x, theta):
    """x (S, H, hd) at positions 0..S-1."""
    S, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(c, precision, x, lp):
    S = x.shape[0]
    H, Hkv, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
    a = lp["attn"]
    h = _rmsnorm(x, lp["ln1"], c["norm_eps"])
    q = _rope(_mm(h, a["w_q"], precision).reshape(S, H, hd), c["rope_theta"])
    k = _rope(_mm(h, a["w_k"], precision).reshape(S, Hkv, hd), c["rope_theta"])
    v = _mm(h, a["w_v"], precision).reshape(S, Hkv, hd)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    att = _ein("qhd,khd->hqk", q, k, precision) / np.sqrt(hd)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    att = jax.nn.softmax(jnp.where(causal[None], att, -jnp.inf), axis=-1)
    o = _ein("hqk,khd->qhd", att, v, precision).reshape(S, H * hd)
    x = x + _mm(o, a["w_o"], precision)
    h = _rmsnorm(x, lp["ln2"], c["norm_eps"])
    m = lp["mlp"]
    if c["mlp_type"] == "swiglu":
        u = jax.nn.silu(_mm(h, m["mlp_gate"], precision)) * _mm(h, m["mlp_up"], precision)
    else:
        u = jax.nn.gelu(_mm(h, m["mlp_up"], precision), approximate=True)
    return x + _mm(u, m["mlp_down"], precision)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _hidden(spec: str, precision: str, params, tokens):
    c = json.loads(spec)
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)

    def body(x, lp):
        return _layer(c, precision, x, lp), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return _rmsnorm(x, params["final_norm"], c["norm_eps"])


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(spec: str, precision: str, params, h):
    c = json.loads(spec)
    w = params["lm_head"] if "lm_head" in params else params["embed"].T
    return _mm(h, w, precision)[:, :c["vocab_size"]]


def _spec(c: dict) -> str:
    keys = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
            "d_ff", "mlp_type", "vocab_size", "rope_theta", "norm_eps")
    return json.dumps({k: c[k] for k in keys}, sort_keys=True)


def hidden_states(c: dict, params, tokens, precision: str = "float32"):
    """Final-norm hidden states (S, d), float32, of one token sequence."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    return _hidden(_spec(c), precision, params, jnp.asarray(tokens, jnp.int32))


def logits_at(c: dict, params, tokens, positions,
              precision: str = "float32") -> np.ndarray:
    """Logits (len(positions), vocab) after each of ``positions`` of
    ``tokens``. ``tokens`` may be right-padded: causal attention keeps the
    padding out of every earlier position."""
    h = hidden_states(c, params, tokens, precision)
    h = jnp.take(h, jnp.asarray(positions, jnp.int32), axis=0)
    return np.asarray(_head(_spec(c), precision, params, h))
