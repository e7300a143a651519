"""Mixture-of-Experts layer: top-k routing, capacity dispatch, EP sharding.

Two dispatch paths:

  * ``_moe_shardmap`` (production, used whenever a mesh with a "model" axis is
    active): experts are sharded over "model", tokens over ("pod","data").
    Inside ``jax.shard_map`` each device routes its *local* tokens to its
    *local* experts — the dispatch scatter never crosses devices, the only
    collectives are an (T_loc, E) router-logit all-gather and the final psum
    that sums each token's k expert contributions across the EP shards.
    GSPMD is never asked to partition a giant scatter (which it does by
    replication — measured 1.1 TB/device on kimi-k2 before this path).

  * ``_moe_dense`` (fallback without a mesh: CPU smoke tests, examples).

Per-expert projections execute as **grouped batched FalconGEMM**
(``engine.grouped_matmul``): the E experts' capacity-C token blocks are one
planned grouped contraction — the Decision Module prices the whole
``E x (C, K) @ (K, N)`` group (``plan_batched``, one plan-cache key) and the
backend runs the R*E intermediate products as a single grouped GEMM, instead
of E unplanned small GEMMs under ``vmap``. Expert weights may be lifted to
stacked :class:`~repro.core.engine.PlannedWeight`\\ s
(``falcon.precombine_params``) so serving never pays Combine B.
``engine.warm_buckets`` pre-plans the grouped expert shapes per bucket.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import engine
from repro.core.falcon_gemm import FalconConfig
from repro.core.workloads import moe_capacity
from repro.parallel.sharding import resolve_batch_axes
from .layers import dense_init

__all__ = ["moe_init", "moe_apply"]


def moe_init(key, d: int, d_ff: int, num_experts: int, dtype) -> dict:
    kr, kg, ku, kd = jax.random.split(key, 4)
    return {
        "router": dense_init(kr, d, num_experts, dtype),
        "moe_gate": (jax.random.normal(kg, (num_experts, d, d_ff), jnp.float32)
                     / np.sqrt(d)).astype(dtype),
        "moe_up": (jax.random.normal(ku, (num_experts, d, d_ff), jnp.float32)
                   / np.sqrt(d)).astype(dtype),
        "moe_down": (jax.random.normal(kd, (num_experts, d_ff, d), jnp.float32)
                     / np.sqrt(d_ff)).astype(dtype),
    }


def _expert_ffn(p_gate, p_up, p_down, xb: jnp.ndarray) -> jnp.ndarray:
    """xb: (E, C, d) -> (E, C, d). Grouped per-expert SwiGLU.

    Each projection is ONE planned grouped contraction over all E experts
    (weights may be raw ``(E, K, N)`` arrays or stacked PlannedWeights) —
    the group-parallel replacement for the old ``vmap``'d 2-D core.
    """
    g = engine.grouped_matmul(xb, p_gate)
    u = engine.grouped_matmul(xb, p_up)
    return engine.grouped_matmul(jax.nn.silu(g) * u, p_down)


def _route(xt, router_logits, top_k):
    probs = jax.nn.softmax(router_logits, axis=-1)            # (T, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)       # (T, k)
    gate_vals = gate_vals / jnp.clip(jnp.sum(gate_vals, -1, keepdims=True), 1e-9)
    return probs, gate_vals, expert_idx


def _aux_loss(probs, expert_idx, E):
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32), axis=0)
    return E * jnp.sum(me * ce)


def _dispatch_compute_combine(xt, probs, gate_vals, expert_idx, C, p_gate,
                              p_up, p_down, E_local, e_offset):
    """Token-local dispatch into (E_local, C, d), FFN, weighted combine.

    Per-slot loop (k is small) so no (T*k, d) token replication is ever
    materialized.
    """
    T, d = xt.shape
    top_k = expert_idx.shape[1]
    e_rel = expert_idx - e_offset
    local = (e_rel >= 0) & (e_rel < E_local)
    e_rel = jnp.clip(e_rel, 0, E_local - 1)
    oh = jax.nn.one_hot(e_rel, E_local, dtype=jnp.int32) * local[..., None].astype(jnp.int32)
    flat = oh.reshape(T * top_k, E_local)
    pos_all = jnp.cumsum(flat, axis=0) - flat
    pos = jnp.sum(pos_all * flat, axis=-1).reshape(T, top_k)
    keep = local & (pos < C)

    buf = jnp.zeros((E_local, C, d), xt.dtype)
    for s in range(top_k):
        w = keep[:, s].astype(xt.dtype)[:, None]
        buf = buf.at[e_rel[:, s], jnp.where(keep[:, s], pos[:, s], C - 1)].add(
            xt * w, mode="drop")

    yb = _expert_ffn(p_gate, p_up, p_down, buf)                # (E_local, C, d)

    y = jnp.zeros_like(xt)
    for s in range(top_k):
        contrib = yb[e_rel[:, s], jnp.where(keep[:, s], pos[:, s], C - 1)]
        w = (gate_vals[:, s] * keep[:, s].astype(gate_vals.dtype)).astype(xt.dtype)
        y = y + contrib * w[:, None]
    return y


def _moe_dense(p, x, top_k, C):
    B, S, d = x.shape
    E = p["router"].shape[1]
    xt = x.reshape(B * S, d)
    logits = xt.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs, gate_vals, expert_idx = _route(xt, logits, top_k)
    y = _dispatch_compute_combine(xt, probs, gate_vals, expert_idx, C,
                                  p["moe_gate"], p["moe_up"], p["moe_down"],
                                  E_local=E, e_offset=0)
    return y.reshape(B, S, d), _aux_loss(probs, expert_idx, E)


def _shard_operand(w):
    """(array, in_spec, rebuild) for one stacked expert operand.

    ``shard_map`` in_specs take arrays, so PlannedWeights are unbundled at
    the boundary: the kept raw weight (E, K, N) — or, for keep_weight=False
    precombines, the stacked B̃ (E, R, K/k, N/n) — is what crosses into the
    body, sharded on the leading expert dim. ``rebuild`` re-wraps the local
    B̃ slice back into a PlannedWeight inside the body, so dropping the raw
    weights (the point of keep_weight=False: half the expert HBM) no longer
    forfeits the expert-parallel path.
    """
    if isinstance(w, engine.PlannedWeight):
        if w.w is not None:
            arr = w.w            # body re-plans the local grouped shapes
            rebuild = lambda loc: loc  # noqa: E731
        elif w.bt is not None:
            arr = w.bt           # offline Combine B̃ shards like the weight
            rebuild = lambda loc, _pw=w: engine.PlannedWeight(  # noqa: E731
                w=None, bt=loc, algo=_pw.algo, k=_pw.k, n=_pw.n)
        else:
            raise ValueError(
                "MoE expert-parallel (shard_map) path got a PlannedWeight "
                "with neither raw weights nor a precombined B̃")
        return arr, P("model", *([None] * (arr.ndim - 1))), rebuild
    return w, P("model", None, None), lambda loc: loc


def _moe_shardmap(p, x, top_k, C_global, mesh):
    B, S, d = x.shape
    E = p["router"].shape[1]
    names = set(mesh.axis_names)
    # use all present batch axes only if B divides them
    present = tuple(a for a in resolve_batch_axes() if a in names)
    dp = int(np.prod([dict(mesh.shape)[a] for a in present])) if present else 1
    dp_axes = present if (present and B % dp == 0) else ()
    dp = int(np.prod([dict(mesh.shape)[a] for a in dp_axes])) if dp_axes else 1
    nm = dict(mesh.shape).get("model", 1)
    E_local = E // nm
    C_local = max(int(np.ceil(C_global / dp)), 8)

    xspec = P(dp_axes if dp_axes else None, None, None)
    wg_arr, wg_spec, wg_rb = _shard_operand(p["moe_gate"])
    wu_arr, wu_spec, wu_rb = _shard_operand(p["moe_up"])
    wd_arr, wd_spec, wd_rb = _shard_operand(p["moe_down"])

    def body(x_loc, router_loc, wg, wu, wd):
        Bl, Sl, _ = x_loc.shape
        xt = x_loc.reshape(Bl * Sl, d)
        # local router slice -> all-gather logits over the EP axis
        logits_loc = xt.astype(jnp.float32) @ router_loc.astype(jnp.float32)
        logits = jax.lax.all_gather(logits_loc, "model", axis=1, tiled=True)
        probs, gate_vals, expert_idx = _route(xt, logits, top_k)
        midx = jax.lax.axis_index("model")
        y = _dispatch_compute_combine(
            xt, probs, gate_vals, expert_idx, C_local,
            wg_rb(wg), wu_rb(wu), wd_rb(wd),
            E_local=E_local, e_offset=midx * E_local)
        # sum each token's k expert contributions across EP shards
        y = jax.lax.psum(y, "model")
        aux = _aux_loss(probs, expert_idx, E)
        if dp_axes:
            aux = jax.lax.pmean(aux, dp_axes)
        return y.reshape(Bl, Sl, d), aux

    out, aux = jax.shard_map(
        body,
        in_specs=(xspec, P(None, "model"), wg_spec, wu_spec, wd_spec),
        out_specs=(xspec, P()),
        check_vma=False,
    )(x, p["router"], wg_arr, wu_arr, wd_arr)
    return out, aux


def moe_apply(p: dict, x: jnp.ndarray, top_k: int, capacity_factor: float,
              fcfg: FalconConfig | None = None,
              deterministic_capacity: int | None = None):
    """x: (B, S, d) -> (y, aux_loss). Token-drop capacity MoE (Switch-style).

    Dispatch policy comes from the context config; ``fcfg`` is a deprecated
    per-call override.
    """
    with engine.deprecated_fcfg(fcfg, "moe_apply"):
        B, S, d = x.shape
        E = p["router"].shape[1]
        T = B * S
        C = deterministic_capacity or moe_capacity(T, top_k, E,
                                                   capacity_factor)
        from repro.parallel.sharding import get_parallel_style
        mesh = compat.get_abstract_mesh()
        nm = dict(mesh.shape).get("model", 1) if mesh is not None else 1
        if nm > 1 and E % nm == 0 and get_parallel_style() == "tp":
            return _moe_shardmap(p, x, top_k, C, mesh)
        return _moe_dense(p, x, top_k, C)
