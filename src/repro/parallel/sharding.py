"""Mesh-axis conventions and parameter/activation sharding rules.

Mesh axes: ``("data", "model")`` single-pod, ``("pod", "data", "model")``
multi-pod. The "pod" axis is pure data parallelism across pods (only the
gradient all-reduce crosses the inter-pod links), "data" is DP/FSDP inside a
pod, "model" is tensor/expert parallelism.

Parameters are sharded by *path-pattern rules* (T5X/MaxText style): a table of
regexes over the flattened param path decides each leaf's PartitionSpec.
``fsdp=True`` additionally shards the non-model dimension of large matrices
over "data" (ZeRO-3 style parameter sharding); ``seq_shard=True`` turns on
sequence/context parallelism for long-context cells (KV cache and activation
sequence dims over "data").
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat

__all__ = ["ShardingRules", "batch_axes", "param_sharding", "activation_specs",
           "named_sharding", "make_rules", "slot_cache_sharding",
           "layouts_for_mesh", "kv_layout"]


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Compiled rule table: list of (path_regex, ndim -> PartitionSpec)."""

    rules: tuple[tuple[str, tuple], ...]
    batch: tuple[str, ...]
    axis_sizes: tuple[tuple[str, int], ...] = ()
    seq_shard: bool = False

    def _fits(self, dim: int, axis) -> bool:
        sizes = dict(self.axis_sizes)
        axes = axis if isinstance(axis, tuple) else (axis,)
        total = int(np.prod([sizes.get(a, 1) for a in axes]))
        return dim % total == 0

    def spec_for(self, path: str, shape: tuple[int, ...]) -> P:
        for pat, spec in self.rules:
            if re.search(pat, path):
                # rules are written for the param's trailing dims; stacked
                # per-layer params carry a leading L dim which is unsharded.
                if len(spec) < len(shape):
                    spec = (None,) * (len(shape) - len(spec)) + tuple(spec)
                elif len(spec) > len(shape):
                    spec = tuple(spec)[-len(shape):]
                # drop axes that do not divide the dim (e.g. 5 KV heads vs a
                # 16-way model axis, vocab 49155 vs 16-way data axis).
                spec = tuple(
                    a if a is not None and self._fits(shape[i], a) else None
                    for i, a in enumerate(spec)
                )
                return P(*spec)
        return P()  # replicate by default (norm scales, biases, ...)


def make_rules(mesh: Mesh, fsdp: bool = False, seq_shard: bool = False,
               style: str = "tp") -> ShardingRules:
    if style == "fsdp_only":
        # no tensor parallelism: batch over every axis, params ZeRO-3-sharded
        # over (data x model) on their first (largest) dim.
        b = batch_axes(mesh) + ("model",)
        fs2 = ("data", "model")
        table = [
            (r"embed|lm_head|w_q|w_qkv|w_k|w_v|w_o|mlp_|moe_|router|ssm_in|ssm_out|frontend",
             (fs2, None)),
        ]
        axis_sizes = tuple((n, int(mesh.shape[n])) for n in mesh.axis_names)
        return ShardingRules(tuple(table), b, axis_sizes, seq_shard)
    b = batch_axes(mesh)
    fs = "data" if fsdp else None
    # NOTE: order matters — first match wins.
    table = [
        # embeddings / tied lm head: vocab over model (=> logits shard over
        # vocab, no (T,V) all-reduce), embed dim unsharded
        (r"embed", ("model", fs)),
        (r"lm_head", (fs, "model")),
        # attention projections
        (r"\bw_q\b|w_qkv|w_kv|\bw_k\b|\bw_v\b", (fs, "model")),
        (r"\bw_o\b", ("model", fs)),
        # MoE: experts over model; per-expert matrices over fsdp/None
        (r"moe_(gate|up)", ("model", fs, None)),
        (r"moe_down", ("model", None, fs)),
        (r"router", (fs, "model")),
        # dense MLP
        (r"mlp_(gate|up)", (fs, "model")),
        (r"mlp_down", ("model", fs)),
        # mamba/SSD: inner channels over model
        (r"ssm_in", (fs, "model")),
        (r"ssm_out", ("model", fs)),
        (r"ssm_(A|D|dt_bias)", ("model",)),
        (r"conv_w", (None, "model")),
        # patch/frame stub frontends
        (r"frontend", (fs, "model")),
    ]
    axis_sizes = tuple((name, int(mesh.shape[name])) for name in mesh.axis_names)
    return ShardingRules(tuple(table), b, axis_sizes, seq_shard)


def _path_str(path) -> str:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        else:
            out.append(str(p))
    return "/".join(out)


def param_sharding(params_shape: Any, mesh: Mesh, rules: ShardingRules):
    """Pytree of NamedShardings matching a pytree of arrays/ShapeDtypeStructs."""
    flat, tree = jax.tree_util.tree_flatten_with_path(params_shape)
    specs = [
        NamedSharding(mesh, rules.spec_for(_path_str(path), leaf.shape))
        for path, leaf in flat
    ]
    return jax.tree_util.tree_unflatten(tree, specs)


def slot_cache_sharding(cache_shape: Any, mesh: Mesh):
    """Shardings of a serve engine's slot cache: ``k``/``v`` leaves
    ``(L, slots, S, Hkv, hd)`` over "model" on their KV-head axis, so that
    each device keeps the K/V of the query heads it computes; replicated
    where the axis size does not divide Hkv (``spec_for``'s rule), as is a
    recurrent ``state`` leaf."""
    rules = ShardingRules(
        ((r"^[kv]$", (None, None, None, "model", None)),), batch_axes(mesh),
        tuple((name, int(mesh.shape[name])) for name in mesh.axis_names))
    return param_sharding(cache_shape, mesh, rules)


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


import contextvars

_STYLE_CTX = contextvars.ContextVar("repro_parallel_style", default="tp")

# sentinel resolved by shard_act according to the active parallel style
BATCH = "BATCH"


def set_parallel_style(style: str):
    """"tp" (default) or "fsdp_only". Returns a token for ContextVar.reset."""
    assert style in ("tp", "fsdp_only"), style
    return _STYLE_CTX.set(style)


def get_parallel_style() -> str:
    return _STYLE_CTX.get()


def resolve_batch_axes() -> tuple[str, ...]:
    if _STYLE_CTX.get() == "fsdp_only":
        return ("pod", "data", "model")
    return ("pod", "data")


def layouts_for_mesh(mesh: Mesh | None = None, style: str | None = None):
    """Candidate shard layouts for pricing a dense contraction on ``mesh``.

    Returns ``(n_devices, layouts)`` for the shard-aware Decision Module
    (``falcon_gemm.plan_sharded``). The rule table is the parallel style's:

      * ``"tp"``        — weights shard over the "model" axis; candidates are
        replicated / column-parallel (all-gather C) / row-parallel
        (all-reduce C), with D = model-axis size;
      * ``"fsdp_only"`` — activations shard over every batch axis; candidates
        are replicated (gather A and B) vs batch-sharded with a weight
        all-gather, with D = the product of batch-axis sizes.

    Without a mesh (or with a trivial axis) this degenerates to
    ``(1, (replicated,))`` — the local model.
    """
    from repro.core import decision as dec

    if mesh is None:
        mesh = compat.get_abstract_mesh()
    if mesh is None:
        return 1, (dec.layout_by_name("replicated"),)
    style = style or get_parallel_style()
    sizes = dict(mesh.shape)
    if style == "fsdp_only":
        axes = tuple(a for a in resolve_batch_axes() if a in sizes)
        d = int(np.prod([sizes[a] for a in axes])) if axes else 1
        layouts = dec.fsdp_layouts()
    else:
        d = int(sizes.get("model", 1))
        layouts = dec.default_layouts()
    if d <= 1:
        return 1, (dec.layout_by_name("replicated"),)
    return d, layouts


def shard_act(x, *spec):
    """Constrain an activation's sharding, tolerantly.

    Usable from model code that may run with or without a mesh context:
    axes not present in the active mesh are dropped, axes that don't divide
    the corresponding dim are dropped (e.g. hymba's 25 heads on a 16-way
    model axis), and without any mesh this is the identity. The BATCH
    sentinel resolves per the active parallel style; under "fsdp_only" the
    model axis belongs to batch, so non-batch "model" references are dropped.
    """
    mesh = compat.get_abstract_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)
    sizes = dict(mesh.shape)
    style = _STYLE_CTX.get()

    def filt(a, dim):
        if a is None:
            return None
        if a == BATCH:
            a = resolve_batch_axes()
        elif style == "fsdp_only":
            return None  # "model"/other TP refs are batch-owned in this style
        axes = a if isinstance(a, tuple) else (a,)
        axes = tuple(ax for ax in axes if ax in names)
        if not axes:
            return None
        total = int(np.prod([sizes[ax] for ax in axes]))
        if dim % total != 0:
            return None
        return axes if len(axes) > 1 else axes[0]

    spec = tuple(spec) + (None,) * (x.ndim - len(spec))
    fspec = P(*[filt(a, d) for a, d in zip(spec, x.shape)])
    return jax.lax.with_sharding_constraint(x, fspec)


def kv_layout(num_heads: int, num_kv_heads: int) -> str:
    """How grouped-query attention meets its K/V heads under the active mesh.

    ``"grouped"``: each KV head contracts against its own group of
    ``num_heads // num_kv_heads`` query heads, so K/V are never copied. That
    keeps the head dim sharded where the "model" axis that ``shard_act``
    would apply to a head dim divides the KV heads (always without a mesh,
    and under "fsdp_only", where heads are not model-sharded).
    ``"repeat"``: K/V are repeated up to ``num_heads``, so the query heads
    keep their own sharding where the axis does not divide the KV heads.
    """
    shards = 1
    mesh = compat.get_abstract_mesh()
    if mesh is not None and _STYLE_CTX.get() != "fsdp_only":
        shards = int(dict(mesh.shape).get("model", 1))
    if num_heads == num_kv_heads or num_kv_heads % shards == 0:
        return "grouped"
    return "repeat"


def activation_specs(rules: ShardingRules) -> dict[str, P]:
    """Canonical activation PartitionSpecs used via with_sharding_constraint."""
    if rules.seq_shard:
        # context parallelism: batch is tiny (e.g. 1); shard sequence over
        # "data" instead, keeping only the pod axis (if any) on batch.
        b = tuple(a for a in rules.batch if a == "pod")
        seq = "data"
    else:
        b, seq = rules.batch, None
    return {
        "tokens": P(b, seq),
        "hidden": P(b, seq, None),               # (B, S, d)
        "heads": P(b, seq, "model", None),       # (B, S, H, hd)
        "kv_cache": P(b, seq, "model", None),    # (B, S_max, Hkv, hd)
        "ffn": P(b, seq, "model"),               # (B, S, d_ff)
        "logits": P(b, seq, "model"),            # (B, S, V)
        "ssm_state": P(b, "model", None, None),  # (B, H, N, P)
        "moe_buf": P("model", b, None),          # (E, C, d)
    }
