"""FalconEngine: the unified dispatch surface for FalconGEMM.

This module is the paper's Deployment-Module promise made real at the API
level — *portable execution across hardware and input configurations*:

* **Context-scoped config** — ``with use(cfg): ...`` installs a
  :class:`~repro.core.falcon_gemm.FalconConfig` in a contextvar;
  ``current_config()`` resolves it anywhere below (layers no longer thread an
  ``fcfg`` argument). Explicit ``cfg=`` arguments remain as overrides.
* **General entry points** — :func:`dot_general` / :func:`einsum` normalize
  batched and transposed contractions down to the planned 2-D core, so
  attention/MoE/SSD contractions hit the Decision Module, not just plain
  dense layers.
* **Backends** — execution strategies resolve through the
  ``core.backends`` registry (``FalconConfig.backend`` is just a name).
* **First-class precombined weights** — :class:`PlannedWeight` carries a
  weight together with its chosen LCMA and offline-combined B̃ (paper §IV-C
  "offline Combine B"); ``dense``/``dot_general``/``matmul`` accept it
  transparently, and :func:`precombine_params` lifts a whole model pytree.
* **Planned autodiff** — the dispatch core carries a ``jax.custom_vjp``: the
  backward GEMMs (``dA = g Bᵀ``, ``dB = Aᵀ g``) run as independently planned
  falcon contractions instead of the autodiff transpose of the combine
  graph, PlannedWeights are trainable, and
  :func:`refresh_planned_params` keeps B̃ consistent across optimizer steps.

``repro.api`` re-exports this surface; ``import repro.api as falcon``.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
import warnings
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import algorithms, backends, hardware, workloads
from .falcon_gemm import (FalconConfig, _lcma_apply, _lcma_apply_grouped,
                          _pad2, grouped_matmul_with_precombined,
                          matmul_with_precombined, plan, plan_batched,
                          plan_training, precombine_weights)
from .lcma import LCMA

__all__ = ["use", "current_config", "active_config", "maybe_use",
           "config_scope", "matmul", "dense", "dot_general", "einsum",
           "grouped_matmul", "PlannedWeight", "plan_weight",
           "precombine_params", "refresh_planned_params",
           "projection_shapes", "grouped_expert_shapes", "warm_buckets",
           "FalconEngine"]


# ---------------------------------------------------------------------------
# Context-scoped configuration
# ---------------------------------------------------------------------------

_CONFIG: contextvars.ContextVar[FalconConfig | None] = \
    contextvars.ContextVar("falcon_config", default=None)


@contextlib.contextmanager
def use(cfg: FalconConfig):
    """Install ``cfg`` as the ambient FalconGEMM config for this context.

    Nests: the innermost ``use`` wins; on exit the previous config is
    restored (also on exception). Config resolution is a trace-time concern,
    so wrapping a ``jax.jit`` *call site* is sufficient — the contextvar is
    read while the function traces.
    """
    token = _CONFIG.set(cfg)
    try:
        yield cfg
    finally:
        _CONFIG.reset(token)


def active_config() -> FalconConfig | None:
    """The config installed by the innermost ``use``, or None outside any."""
    return _CONFIG.get()


def current_config() -> FalconConfig:
    """The ambient config: innermost ``use``, else the default FalconConfig."""
    return _CONFIG.get() or FalconConfig()


def _resolve(cfg: FalconConfig | None) -> FalconConfig:
    return cfg if cfg is not None else current_config()


@contextlib.contextmanager
def maybe_use(cfg: FalconConfig | None):
    """``use(cfg)`` when cfg is not None; no-op otherwise (shim helper)."""
    if cfg is None:
        yield None
    else:
        with use(cfg) as c:
            yield c


def warn_deprecated_fcfg(where: str, stacklevel: int = 3) -> None:
    warnings.warn(
        f"{where}: passing a FalconConfig argument is deprecated; wrap "
        f"the call in `with falcon.use(cfg):` instead",
        DeprecationWarning, stacklevel=stacklevel)


def deprecated_fcfg(fcfg: FalconConfig | None, where: str):
    """Deprecation shim for the legacy per-call ``fcfg`` parameter.

    Returns a context manager that installs ``fcfg`` (warning at the call
    site) or does nothing when ``fcfg`` is None — so ported code paths are
    warning-free under ``-W error::DeprecationWarning``.
    """
    if fcfg is not None:
        warn_deprecated_fcfg(where, stacklevel=4)
    return maybe_use(fcfg)


@contextlib.contextmanager
def config_scope(fcfg: FalconConfig | None, where: str, default_factory):
    """Model-entry config resolution: deprecated override, ambient, default.

    The ordering is load-bearing: the deprecated ``fcfg`` (if any) is
    installed *before* ``active_config()`` is consulted, so an explicit
    legacy argument still overrides the ambient context; absent both, the
    config comes from ``default_factory()`` (e.g. the model's own
    ``falcon_config_for``).
    """
    with deprecated_fcfg(fcfg, where):
        with use(active_config() or default_factory()):
            yield


# ---------------------------------------------------------------------------
# Planned (precombined) weights
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PlannedWeight:
    """A weight bundled with its chosen LCMA and offline-combined B̃.

    ``dense`` / ``matmul`` / ``dot_general`` accept a PlannedWeight wherever
    a (K, N) weight matrix is expected. ``algo is None`` marks a weight the
    Decision Module left on standard GEMM. Registered as a pytree whose
    children are the arrays, so planned params flow through ``jax.jit``,
    ``lax.scan`` layer stacking, and checkpoint trees unchanged; the scheme
    name and logical shape ride in the static treedef.

    Stacked weights (leading layer/codebook dim) are supported: children are
    stacked alike, ``pw[i]`` slices both.

    When planned under ``cfg.quantize``, a 2-D weight additionally carries the
    offline-quantized B̃q (int8) and its f32 block scales
    (``kernels.quant_combine.quantize_b_blockwise``), so the serve path can
    route through the backend's int8 ``apply_quant`` pipeline whenever the
    Decision Module picks the quantized tier at the actual M.
    """

    w: Any                  # original weight (K, N) [or (L, K, N)]; None if dropped
    bt: Any                 # precombined B̃ (R, K/k, N/n) [or (L, ...)]; None if GEMM
    algo: str | None        # LCMA scheme name; None => standard GEMM
    k: int                  # logical K of the matrix (trailing dims)
    n: int                  # logical N
    bq: Any = None          # quantized B̃q int8 (R, K/k, N/n); None if fp-only
    b_scales: Any = None    # f32 block scales (R, (K/k)/by, N/n)

    @property
    def lcma(self) -> LCMA | None:
        return algorithms.get(self.algo) if self.algo is not None else None

    @property
    def precombined(self) -> bool:
        return self.bt is not None

    @property
    def quantized(self) -> bool:
        return self.bq is not None

    def __getitem__(self, idx) -> "PlannedWeight":
        return PlannedWeight(
            w=None if self.w is None else self.w[idx],
            bt=None if self.bt is None else self.bt[idx],
            algo=self.algo, k=self.k, n=self.n,
            bq=None if self.bq is None else self.bq[idx],
            b_scales=None if self.b_scales is None else self.b_scales[idx])

    def tree_flatten(self):
        return (self.w, self.bt, self.bq, self.b_scales), \
            (self.algo, self.k, self.n)

    @classmethod
    def tree_unflatten(cls, aux, children):
        w, bt, bq, b_scales = children
        algo, k, n = aux
        return cls(w=w, bt=bt, algo=algo, k=k, n=n, bq=bq, b_scales=b_scales)


def plan_weight(w: jnp.ndarray, cfg: FalconConfig | None = None,
                m_hint: int = 1024, keep_weight: bool = True,
                grouped: bool = False) -> PlannedWeight:
    """Plan a static weight for serving: pick an LCMA and precombine B̃.

    The Decision Module is consulted with ``precombined_b=True`` — the right
    profitability criterion for a weight whose Combine B runs offline — at an
    activation-rows hint ``m_hint`` (use the serving prefill M). The decision
    goes through the plan cache like every other ``plan()`` call. Weights of
    rank 3 are treated as stacked (leading layer/codebook dim) and combined
    per slice; the per-matrix shape is the trailing (K, N).

    ``grouped=True`` marks a rank-3 stack whose slices execute *together* as
    one grouped contraction (MoE experts via ``grouped_matmul``) rather than
    sequentially (scan-stacked layers): profitability is then judged by
    ``plan_batched`` on the grouped problem — ``m_hint`` still counts total
    activation rows, split evenly across the G slices — matching how
    ``_apply_planned_grouped`` will re-price it at serve time.

    ``keep_weight=False`` drops the raw weight (halves serving memory for the
    planned layers); the precombined path is then always taken.
    """
    cfg = _resolve(cfg)
    if w.ndim not in (2, 3):
        return PlannedWeight(w=w, bt=None, algo=None,
                             k=int(w.shape[-2]) if w.ndim >= 2 else 0,
                             n=int(w.shape[-1]))
    K, N = int(w.shape[-2]), int(w.shape[-1])
    if grouped and w.ndim == 3:
        G = int(w.shape[0])
        d = plan_batched(G, max(m_hint // G, 8), K, N, cfg, str(w.dtype),
                         precombined_b=True)
    else:
        d = plan(m_hint, K, N, cfg, str(w.dtype), precombined_b=True)
    if not d.use_lcma:
        return PlannedWeight(w=w, bt=None, algo=None, k=K, n=N)
    l = d.algo
    bt = precombine_weights(w, l) if w.ndim == 2 else \
        jax.vmap(lambda wi: precombine_weights(wi, l))(w)
    # Under cfg.quantize, also bake the int8 quant buffers — regardless of
    # which precision won at m_hint: the serve-time re-decision picks fp vs
    # int8 at the *actual* M, and both executions must be available from the
    # same PlannedWeight. Stacked (scan-layer) weights quantize per slice;
    # ``pw[i]`` slices the quant buffers alongside w/B̃.
    bq = b_scales = None
    if cfg.quantize \
            and backends.get_backend(cfg.backend).apply_quant is not None:
        if w.ndim == 2:
            bq, b_scales = _quantize_weight(w, l)
        else:
            per = [_quantize_weight(w[i], l) for i in range(w.shape[0])]
            bq = jnp.stack([q for q, _ in per])
            b_scales = jnp.stack([s for _, s in per])
    return PlannedWeight(w=w if keep_weight else None, bt=bt,
                         algo=l.name, k=K, n=N, bq=bq, b_scales=b_scales)


def _quantize_weight(w: jnp.ndarray, l: LCMA, by: int | None = None):
    """Offline Combine-B + blockwise int8 quantization of a 2-D weight.

    Returns ``(B̃q int8 (R, K/k, N/n), f32 scales (R, (K/k)/by, N/n))`` —
    the PlannedWeight quant buffers consumed by the backends' ``apply_quant``
    pipeline. ``by`` defaults to the largest divisor of the combined K
    (<= 128) so the fused int8 kernel's accumulator blocks divide exactly;
    128 << the int32 safe accumulation depth (analysis.stability).
    """
    from repro.kernels.quant_combine import quantize_b_blockwise
    wp = _pad2(w, l.k, l.n)
    Y = wp.shape[0] // l.k
    if by is None:
        by = next(d for d in range(min(128, Y), 0, -1) if Y % d == 0)
    return quantize_b_blockwise(wp, l.V, by=by,
                                interpret=hardware.interpret_kernels())


_DEFAULT_PRECOMBINE_PATTERNS = (
    "w_q", "w_k", "w_v", "w_o", "mlp_gate", "mlp_up", "mlp_down",
    "lm_head", "ssm_in", "ssm_out",
    # MoE expert stacks lift to stacked PlannedWeights; the grouped dispatch
    # (engine.grouped_matmul) applies them per expert against stacked B̃.
    "moe_gate", "moe_up", "moe_down",
)

# Stacks matching these execute as ONE grouped contraction (not per-slice),
# so plan_weight judges them with the grouped decision (plan_batched).
_GROUPED_PRECOMBINE_PATTERNS = ("moe_gate", "moe_up", "moe_down")


def precombine_params(params, cfg: FalconConfig | None = None,
                      m_hint: int = 1024, keep_weight: bool = True,
                      patterns: tuple[str, ...] = _DEFAULT_PRECOMBINE_PATTERNS):
    """Lift a model param pytree into PlannedWeights for serving.

    Dense projection leaves whose path matches ``patterns`` are planned
    (and precombined where the Decision Module picks an LCMA); everything
    else — including leaves that are already ``PlannedWeight``s, so the
    lift is idempotent — passes through untouched.
    Returns (new_params, n_planned).
    """
    cfg = _resolve(cfg)
    n_planned = 0

    def maybe_plan(path, leaf):
        nonlocal n_planned
        if isinstance(leaf, PlannedWeight):
            return leaf
        keys = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        if leaf.ndim not in (2, 3) or not any(pat in keys for pat in patterns):
            return leaf
        grouped = leaf.ndim == 3 and any(
            pat in keys for pat in _GROUPED_PRECOMBINE_PATTERNS)
        pw = plan_weight(leaf, cfg, m_hint=m_hint, keep_weight=keep_weight,
                         grouped=grouped)
        if pw.precombined:
            n_planned += 1
            return pw
        return leaf  # GEMM-bound weight: keep the raw array

    out = jax.tree_util.tree_map_with_path(
        maybe_plan, params, is_leaf=lambda x: isinstance(x, PlannedWeight))
    return out, n_planned


@jax.named_scope("falcon.gemm")
def _gemm(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """The plain-GEMM branch of a 2-D dispatch, named in the op metadata."""
    return jnp.matmul(a, b)


def _apply_planned(x: jnp.ndarray, pw: PlannedWeight,
                   cfg: FalconConfig) -> jnp.ndarray:
    """x (..., K) @ PlannedWeight -> (..., N); serving fast path."""
    *lead, K = x.shape
    if pw.algo is None:
        return _gemm(x, pw.w)
    be = backends.get_backend(cfg.backend)
    if be.dense_hook is not None and pw.w is not None:
        # Layer-level placements (e.g. shard_map_local's per-device local
        # matmul) take precedence: running the precombined combines on a
        # GSPMD-sharded global array is exactly the resharding pathology
        # that hook exists to avoid.
        out = be.dense_hook(x, pw.w, cfg)
        if out is not None:
            return out
    x2 = x.reshape(-1, K)
    use_quant = False
    if cfg.mode == pw.algo or pw.w is None:
        use_pre = True           # forced scheme, or raw weight dropped
        use_quant = pw.quantized and cfg.quantize
    elif not cfg.enabled or cfg.mode == "gemm":
        use_pre = False
    else:
        # Re-decide for the *actual* M (decode M is tiny, prefill M is large)
        # with Combine B free; restrict candidates to the precombined scheme.
        # cfg.quantize rides through the replace, so the decision also picks
        # the precision tier — int8 routes to the baked quant buffers below.
        d = plan(x2.shape[0], K, pw.n,
                 dataclasses.replace(cfg, mode="auto", candidates=(pw.algo,)),
                 str(x.dtype), precombined_b=True)
        use_pre = d.use_lcma
        use_quant = pw.quantized and d.quantized
    if not use_pre:
        return _gemm(x, pw.w)
    if use_quant and be.apply_quant is not None:
        if cfg.planned_vjp and pw.w is not None:
            out2 = _pw_quant_core(cfg, pw.algo, pw.n)(
                x2, pw.w, pw.bq, pw.b_scales)
        else:
            out2 = be.apply_quant(x2, pw.bq, pw.b_scales, pw.lcma, pw.n, cfg)
        return out2.reshape(*lead, pw.n)
    if cfg.planned_vjp:
        # Trainable precombined apply: the custom-VJP core routes the
        # gradient to the raw weight (planned dW = x2ᵀ g) when it is kept,
        # or to B̃ itself via the rotated rank-R scheme when it was dropped.
        if pw.w is not None:
            out2 = _pw_core(cfg, pw.algo, pw.n, True)(x2, pw.w, pw.bt)
        else:
            out2 = _pw_core(cfg, pw.algo, pw.n, False)(x2, pw.bt)
    elif be.apply_precombined is not None:
        out2 = be.apply_precombined(x2, pw.bt, pw.lcma, pw.n, cfg)
    else:  # backend has no native precombined path: generated jnp combines
        out2 = matmul_with_precombined(x2, pw.bt, pw.lcma, pw.n, cfg)
    return out2.reshape(*lead, pw.n)


# ---------------------------------------------------------------------------
# Bucket pre-planning (continuous-batching serve path)
# ---------------------------------------------------------------------------

def projection_shapes(arch) -> list[tuple[int, int]]:
    """Deprecated shim: the (K, N) dense-projection shapes of ``arch``.

    The workload registry (``core.workloads``) is the one source of an
    architecture's contraction inventory now; use
    ``workloads.dense_projection_shapes(arch)`` (or the full
    ``contraction_set``/``resolve_contractions``) instead.
    """
    warnings.warn(
        "falcon.projection_shapes is deprecated; use "
        "repro.core.workloads.dense_projection_shapes / contraction_set "
        "(the workload registry) instead", DeprecationWarning, stacklevel=2)
    return workloads.dense_projection_shapes(arch)


def grouped_expert_shapes(arch, m_tokens: int,
                          mesh_shape: dict | None = None,
                          ) -> list[tuple[int, int, int, int]]:
    """Deprecated shim: grouped (E, C, K, N) MoE contractions of ``arch``.

    Use ``workloads.grouped_moe_shapes(arch, m_tokens, mesh_shape)`` (the
    workload registry) instead.
    """
    warnings.warn(
        "falcon.grouped_expert_shapes is deprecated; use "
        "repro.core.workloads.grouped_moe_shapes (the workload registry) "
        "instead", DeprecationWarning, stacklevel=2)
    return workloads.grouped_moe_shapes(arch, m_tokens, mesh_shape)


def _warm_contraction(c, cfg: FalconConfig, dtype: str,
                      pre_algos: dict, pre_algos_grouped: dict) -> int:
    """Plan one resolved registry contraction (+ precombined variant)."""
    n = 0
    if c.group == 1:
        plan(c.m, c.k, c.n, cfg, dtype)
        n += 1
        if c.weight_static:
            d_pre = plan(c.m, c.k, c.n, cfg, dtype, precombined_b=True)
            if d_pre.use_lcma:
                pre_algos.setdefault((c.k, c.n), set()).add(d_pre.algo.name)
            n += 1
    else:
        plan_batched(c.group, c.m, c.k, c.n, cfg, dtype, shared_b=c.shared_b)
        n += 1
        if c.weight_static:
            d_pre = plan_batched(c.group, c.m, c.k, c.n, cfg, dtype,
                                 precombined_b=True, shared_b=c.shared_b)
            if d_pre.use_lcma:
                pre_algos_grouped.setdefault(
                    (c.group, c.k, c.n), set()).add(d_pre.algo.name)
            n += 1
    return n


def warm_buckets(cfg: FalconConfig | None, arch, buckets,
                 dtype: str | None = None, train: bool = False,
                 mesh_shape: dict | None = None,
                 kv_len: int | None = None,
                 spec_gamma: int | None = None) -> int:
    """Pre-plan the registry contraction set of ``arch`` at every bucket.

    The continuous-batching scheduler only ever launches bucket shapes, so
    running the Decision Module once per bucket x registry contraction —
    both the plain and the precombined-B profitability variants for
    static-weight contractions — means serve-time traces are pure plan-cache
    hits. Returns the number of ``plan()``/``plan_batched()`` calls issued.
    Every shape comes from ``core.workloads`` (the workload registry), the
    one source of an architecture's contraction inventory.

    ``buckets`` entries are either

      * ``int`` — a flat activation-row count (batch x padded-seq for
        prefill buckets, batch for decode buckets): warms the dense
        projections and grouped MoE expert shapes at that M (the batch/seq
        split being unknown, the activation-side attention/SSD groups are
        left to the engine's jit warm loop), or
      * ``(batch, seq)`` — a full call context: resolves the complete
        registry inventory including attention einsums and SSD scan/decode
        contractions (``seq == 1`` with ``kv_len`` set is treated as a
        decode step against a length-``kv_len`` cache).

    ``train=True`` additionally pre-plans both *backward* contractions of
    each forward one (``decision.backward_shapes`` / the grouped grad
    rules), so one warm pass at ``buckets=[(batch, seq)]`` makes a whole
    jitted train step — forward and planned custom-VJP backward — trace
    against a hot plan cache.

    ``mesh_shape`` warms the PER-SHARD grouped MoE shapes a multi-device
    engine dispatches (experts over "model", tokens over "data") instead of
    the global ones no device ever runs.

    ``spec_gamma`` (with ``kv_len``) additionally warms the speculative-
    decoding contexts for every decode batch bucket ``(b, 1)`` in
    ``buckets``: the ``(b, γ+1)`` verify forward (lm head on every row —
    ``spec_verify`` in the workload registry) and the ``(b, 2)`` draft
    catch-up forward, so a speculating engine's rounds are plan-cache hits
    too. The draft model shares these keys: a layer-sliced self-draft has
    identical per-layer contraction shapes.
    """
    cfg = _resolve(cfg)
    dtype = dtype or str(getattr(arch, "dtype", "bfloat16"))
    n = 0
    flat = sorted({int(b) for b in buckets if not isinstance(b, tuple)})
    pairs = sorted({(int(b), int(s)) for (b, s) in
                    (b for b in buckets if isinstance(b, tuple))})
    pre_algos: dict[tuple[int, int], set[str]] = {}
    pre_algos_grouped: dict[tuple[int, int, int], set[str]] = {}

    contractions: list = []
    for M in flat:
        # flat M = batch-of-1 token count: the dense/grouped-MoE inventory
        # (legacy bucket semantics; attention/SSD groups need a batch/seq
        # split, which (batch, seq) buckets provide)
        contractions += [
            c for c in workloads.resolve_contractions(
                arch, 1, M, train=train, mesh_shape=mesh_shape)
            if c.kind in ("dense", "grouped_moe")]
    for (b, s) in pairs:
        decode = kv_len is not None and s == 1
        contractions += workloads.resolve_contractions(
            arch, b, s, train=train, mesh_shape=mesh_shape,
            kv_len=kv_len, decode=decode)
        if spec_gamma and decode:
            # speculative rounds at decode batch b: the (b, γ+1) verify
            # forward and the (b, 2) draft catch-up forward
            contractions += workloads.resolve_contractions(
                arch, b, spec_gamma + 1, train=train, mesh_shape=mesh_shape,
                kv_len=kv_len, spec_verify=True)
            contractions += workloads.resolve_contractions(
                arch, b, 2, train=train, mesh_shape=mesh_shape, kv_len=kv_len)

    # static-weight contractions first, so a shape shared between a weight
    # contraction and an activation one keeps its precombined variant
    contractions.sort(key=lambda c: not c.weight_static)
    seen: set[str] = set()
    for c in contractions:
        tok = c.key_shape()
        if tok in seen:
            continue
        seen.add(tok)
        n += _warm_contraction(c, cfg, dtype, pre_algos, pre_algos_grouped)

    # The PlannedWeight apply path re-decides at the actual M with candidates
    # restricted to the weight's own scheme — a differently-keyed plan (the
    # candidate set is part of the key). Pre-plan those restricted variants
    # for every scheme any bucket's precombined decision picked, so the
    # serve-time re-decision is a cache hit too, at every bucket M.
    if cfg.mode == "auto":
        planned: set[str] = set()
        for c in contractions:
            tok = c.key_shape()
            if not c.weight_static or tok in planned:
                continue
            planned.add(tok)
            if c.group == 1:
                for a in sorted(pre_algos.get((c.k, c.n), ())):
                    plan(c.m, c.k, c.n,
                         dataclasses.replace(cfg, candidates=(a,)),
                         dtype, precombined_b=True)
                    n += 1
            else:
                for a in sorted(pre_algos_grouped.get(
                        (c.group, c.k, c.n), ())):
                    plan_batched(c.group, c.m, c.k, c.n,
                                 dataclasses.replace(cfg, candidates=(a,)),
                                 dtype, precombined_b=True, shared_b=c.shared_b)
                    n += 1
    return n


# ---------------------------------------------------------------------------
# Planned autodiff: the custom-VJP dispatch core
#
# ``jax.value_and_grad`` through the raw combine/R-GEMM/combine graph
# differentiates the *implementation*: the autodiff transpose of the combine
# pipeline is strictly worse than either a planned LCMA or a clean GEMM, and
# the two backward GEMMs (dA = g Bᵀ, dW = Aᵀ g — two-thirds of training
# FLOPs) never meet the Decision Module. The custom VJP below differentiates
# the *contraction*: forward runs the planned dispatch, backward computes dA
# and dB as two independently planned falcon contractions — each backward
# shape runs through plan(), the plan cache and the backend registry exactly
# like a forward call. Side effect: every backend becomes trainable (the
# Pallas kernel pipeline has no autodiff transpose of its own).
# ---------------------------------------------------------------------------

def _dispatch2d(a2: jnp.ndarray, b2: jnp.ndarray,
                cfg: FalconConfig) -> jnp.ndarray:
    """Forward-only planned 2-D contraction: plan(), then LCMA or GEMM."""
    M, K = a2.shape
    N = b2.shape[1]
    d = plan(M, K, N, cfg, str(a2.dtype))
    if d.use_lcma:
        return _lcma_apply(a2, b2, d.algo, cfg)
    return _gemm(a2, b2)


@functools.lru_cache(maxsize=None)
def _planned_core(cfg: FalconConfig):
    """The custom-VJP planned matmul core for ``cfg`` (2-D operands).

    Cached per (frozen, hashable) config so repeated traces reuse one
    ``custom_vjp`` instance — jit caches then key on a stable callable.
    Serves the *unbatched* contractions only: batched ``dot_general``
    lowers through :func:`_grouped_core` (one grouped ``plan_batched``
    decision for the whole group), not a ``vmap`` of this core.
    """

    @jax.custom_vjp
    def core(a2, b2):
        return _dispatch2d(a2, b2, cfg)

    def fwd(a2, b2):
        # This rule only runs under differentiation, so backward-shape
        # pricing happens exactly when a backward pass will exist — a
        # pure-inference trace never pays it (and never pollutes a warmed
        # serving plan cache with dA/dB entries).
        plan_training(a2.shape[0], a2.shape[1], b2.shape[1], cfg,
                      str(a2.dtype))
        return _dispatch2d(a2, b2, cfg), (a2, b2)

    def bwd(res, g):
        a2, b2 = res
        # dA: (M, N) @ (N, K) and dB: (K, M) @ (M, N) — both re-enter the
        # planned dispatch; their shapes were pre-priced by plan_training at
        # trace time, so these plan() calls are cache hits.
        da = _dispatch2d(g, b2.T, cfg).astype(a2.dtype)
        db = _dispatch2d(a2.T, g, cfg).astype(b2.dtype)
        return da, db

    core.defvjp(fwd, bwd)
    return core


def _route_planned(M: int, K: int, N: int, cfg: FalconConfig, dtype: str):
    """Routing decision for one contraction: (use_custom_vjp_core, d_fwd).

    The core is engaged when the forward picks an LCMA; backward shapes are
    priced lazily, inside the custom VJP's fwd rule, which jax invokes only
    under differentiation — a pure-inference trace (the serve engine's
    warmed hot path) never prices dA/dB and keeps its zero-cold-miss
    guarantee. When the forward is plain GEMM the caller keeps its
    bitwise-identical jnp/lax lowering, whose autodiff transpose is plain
    GEMM anyway — and forward-mode jvp keeps working there.
    """
    d = plan(M, K, N, cfg, dtype)
    return (cfg.planned_vjp and d.use_lcma), d


# ---------------------------------------------------------------------------
# Grouped batched dispatch (paper §III-B Group-Parallel Optimizations)
#
# A grouped contraction — B independent (M, K) @ (K, N) products — used to
# lower as ``jax.vmap`` over the independently-combined 2-D core: the
# Decision Module priced ONE group element (so small-M groups like MoE
# expert blocks always declined), and nothing was hoisted. The grouped core
# below plans the whole group at once (``plan_batched``, one plan-cache key
# per grouped shape), hoists Combine B when the B operand is shared across
# the group, and executes the R*B intermediate products as a single grouped
# GEMM through the backend's ``apply_grouped`` path.
# ---------------------------------------------------------------------------

def _dispatch_grouped(a3: jnp.ndarray, b: jnp.ndarray,
                      cfg: FalconConfig) -> jnp.ndarray:
    """Forward-only planned grouped contraction: plan_batched, LCMA or GEMM."""
    G, M, K = a3.shape
    d = plan_batched(G, M, K, b.shape[-1], cfg, str(a3.dtype),
                     shared_b=b.ndim == 2)
    if d.use_lcma:
        return _lcma_apply_grouped(a3, b, d.algo, cfg)
    return jnp.matmul(a3, b)     # broadcasts the shared-b case


@functools.lru_cache(maxsize=None)
def _grouped_core(cfg: FalconConfig, shared_b: bool):
    """The custom-VJP grouped matmul core for ``cfg``.

    Operands: a3 (G, M, K) and b (K, N) when ``shared_b`` else (G, K, N).
    Backward mirrors the 2-D core: both gradients are independently planned
    falcon contractions — grouped ones, except the shared-weight cotangent
    ``dB = Σ_g a3[g]ᵀ g[g]``, which is exactly the flattened 2-D problem
    ``(K, G·M) @ (G·M, N)`` and is planned as such.
    """

    @jax.custom_vjp
    def core(a3, b):
        return _dispatch_grouped(a3, b, cfg)

    def fwd(a3, b):
        # Runs only under differentiation: price the grouped backward shapes
        # here so inference traces (serve) never pay for or cache them.
        G, M, K = a3.shape
        N = b.shape[-1]
        dtype = str(a3.dtype)
        plan_batched(G, M, N, K, cfg, dtype, shared_b=shared_b)      # dA
        if shared_b:
            plan(K, G * M, N, cfg, dtype)                            # dB (2-D)
        else:
            plan_batched(G, K, M, N, cfg, dtype)                     # dB
        return _dispatch_grouped(a3, b, cfg), (a3, b)

    def bwd(res, g3):
        a3, b = res
        if shared_b:
            da = _dispatch_grouped(g3, b.T, cfg).astype(a3.dtype)
            G, M, K = a3.shape
            db = _dispatch2d(a3.reshape(G * M, K).T,
                             g3.reshape(G * M, b.shape[-1]),
                             cfg).astype(b.dtype)
        else:
            da = _dispatch_grouped(g3, jnp.swapaxes(b, 1, 2),
                                   cfg).astype(a3.dtype)
            db = _dispatch_grouped(jnp.swapaxes(a3, 1, 2), g3,
                                   cfg).astype(b.dtype)
        return da, db

    core.defvjp(fwd, bwd)
    return core


def _route_grouped(G: int, M: int, K: int, N: int, cfg: FalconConfig,
                   dtype: str, shared_b: bool):
    """Routing decision for a grouped contraction: (use_custom_vjp_core, d)."""
    d = plan_batched(G, M, K, N, cfg, dtype, shared_b=shared_b)
    return (cfg.planned_vjp and d.use_lcma), d


def _pw_grouped_primal(a3: jnp.ndarray, bt: jnp.ndarray, l: LCMA,
                       n_logical: int, cfg: FalconConfig) -> jnp.ndarray:
    """The grouped precombined-B̃ apply (backend native path or generated)."""
    be = backends.get_backend(cfg.backend)
    if be.apply_grouped_precombined is not None:
        return be.apply_grouped_precombined(a3, bt, l, n_logical, cfg)
    return grouped_matmul_with_precombined(a3, bt, l, n_logical, cfg)


@functools.lru_cache(maxsize=None)
def _pw_grouped_core(cfg: FalconConfig, algo: str, n_logical: int,
                     stacked: bool, trainable: bool):
    """custom-VJP core for a grouped PlannedWeight apply.

    ``trainable=True`` (raw weight kept) — the grouped analogue of the
    trainable branch of :func:`_pw_core`: the primal reads only B̃ (the
    serving fast path), the backward routes the cotangent to the RAW weight
    — ``dw`` as a planned contraction (grouped per expert for a stacked
    weight; the flattened 2-D problem for a shared one, since
    ``dw = Σ_g a3[g]ᵀ g[g]``) — plus a planned grouped ``dx``. The B̃ leaf
    gets a zero cotangent; :func:`refresh_planned_params` re-derives B̃ from
    the updated weight. Without this, training a model with precombined
    (stacked PlannedWeight) experts would silently produce zero gradients
    for the expert weights: the primal never touches ``w``, and the B̃
    cotangent is discarded by the refresh.

    ``trainable=False`` (``keep_weight=False``): B̃ *is* the parameter; both
    cotangents come from the rotated rank-R scheme (:func:`_pw_bwd_rotated`,
    exact — the output is linear in B̃) applied per group element, summed
    over the group for a shared B̃. This also keeps the dropped-weight
    regime trainable on the Pallas backends, whose precombined kernels have
    no autodiff rule of their own.
    """
    l = algorithms.get(algo)

    if trainable:
        @jax.custom_vjp
        def core(a3, w, bt):
            return _pw_grouped_primal(a3, bt, l, n_logical, cfg)

        def fwd(a3, w, bt):
            # runs only under differentiation: price the backward shapes
            # here so inference traces never pay for (or cache) dA/dB plans
            G, M, K = a3.shape
            dtype = str(a3.dtype)
            plan_batched(G, M, n_logical, K, cfg, dtype,
                         shared_b=not stacked)
            if stacked:
                plan_batched(G, K, M, n_logical, cfg, dtype)
            else:
                plan(K, G * M, n_logical, cfg, dtype)
            return _pw_grouped_primal(a3, bt, l, n_logical, cfg), (a3, w, bt)

        def bwd(res, g3):
            a3, w, bt = res
            G, M, K = a3.shape
            if stacked:
                dx = _dispatch_grouped(g3, jnp.swapaxes(w, 1, 2),
                                       cfg).astype(a3.dtype)
                dw = _dispatch_grouped(jnp.swapaxes(a3, 1, 2), g3,
                                       cfg).astype(w.dtype)
            else:
                dx = _dispatch_grouped(g3, w.T, cfg).astype(a3.dtype)
                dw = _dispatch2d(a3.reshape(G * M, K).T,
                                 g3.reshape(G * M, n_logical),
                                 cfg).astype(w.dtype)
            return dx, dw, jnp.zeros_like(bt)

        core.defvjp(fwd, bwd)
        return core

    @jax.custom_vjp
    def core_bt(a3, bt):
        return _pw_grouped_primal(a3, bt, l, n_logical, cfg)

    def fwd_bt(a3, bt):
        return _pw_grouped_primal(a3, bt, l, n_logical, cfg), (a3, bt)

    def bwd_bt(res, g3):
        a3, bt = res
        if stacked:
            dx, dbt = jax.vmap(
                lambda x2, b2, g2: _pw_bwd_rotated(x2, b2, g2, l, cfg))(
                a3, bt, g3)
        else:
            dx, dbt_g = jax.vmap(
                lambda x2, g2: _pw_bwd_rotated(x2, bt, g2, l, cfg))(a3, g3)
            dbt = jnp.sum(dbt_g, axis=0).astype(bt.dtype)
        return dx, dbt

    core_bt.defvjp(fwd_bt, bwd_bt)
    return core_bt


def _apply_planned_grouped(a3: jnp.ndarray, pw: PlannedWeight,
                           cfg: FalconConfig) -> jnp.ndarray:
    """Grouped apply against a PlannedWeight: a3 (G, M, K) -> (G, M, N).

    A 2-D PlannedWeight is the hoisted case — its offline B̃ is shared by the
    whole group. A stacked PlannedWeight (``w (G, K, N)``, MoE experts) is
    applied per group element against its stacked B̃ (G, R, K/k, N/n), still
    as ONE grouped contraction. The Decision Module re-prices the *grouped*
    problem (``precombined_b=True``) at the actual (G, M), restricted to the
    precombined scheme. Trainable under ``cfg.planned_vjp`` via
    :func:`_pw_grouped_core`: with the raw weight kept, gradients route to
    it as planned contractions; with ``keep_weight=False`` B̃ *is* the
    parameter and the rotated rank-R scheme supplies exact cotangents (also
    what keeps the Pallas backends trainable here — their precombined
    kernels have no autodiff rule).
    """
    G, M, K = a3.shape
    if pw.algo is None:
        return jnp.matmul(a3, pw.w)
    stacked = (pw.bt.ndim == 4) if pw.precombined else \
        (pw.w is not None and pw.w.ndim == 3)
    if cfg.mode == pw.algo or pw.w is None:
        use_pre = True
    elif not cfg.enabled or cfg.mode == "gemm":
        use_pre = False
    else:
        d = plan_batched(G, M, K, pw.n,
                         dataclasses.replace(cfg, mode="auto",
                                             candidates=(pw.algo,)),
                         str(a3.dtype), precombined_b=True,
                         shared_b=not stacked)
        use_pre = d.use_lcma
    if not use_pre:
        return jnp.matmul(a3, pw.w)
    if cfg.planned_vjp:
        if pw.w is not None:
            return _pw_grouped_core(cfg, pw.algo, pw.n, stacked,
                                    True)(a3, pw.w, pw.bt)
        return _pw_grouped_core(cfg, pw.algo, pw.n, stacked,
                                False)(a3, pw.bt)
    return _pw_grouped_primal(a3, pw.bt, pw.lcma, pw.n, cfg)


def grouped_matmul(a: jnp.ndarray, b, cfg: FalconConfig | None = None) -> jnp.ndarray:
    """Grouped batched matmul: ``out[g] = a[g] @ b[g]`` as one planned unit.

    ``a``: (G, M, K). ``b``: (K, N) — one operand shared (broadcast) across
    the group, Combine B hoisted and run once — or (G, K, N) per-group
    operands (MoE experts, batched attention), or a :class:`PlannedWeight`
    (2-D or stacked; offline Combine B). The Decision Module prices the
    whole group via ``plan_batched`` (one grouped plan-cache key, not G) and
    the chosen backend executes the R*G intermediate products as a single
    grouped GEMM. Differentiable: under ``cfg.planned_vjp`` gradients are
    independently planned grouped contractions.
    """
    cfg = _resolve(cfg)
    if isinstance(b, PlannedWeight):
        if a.ndim != 3:
            raise ValueError(f"grouped_matmul: a must be (G, M, K), "
                             f"got {tuple(a.shape)}")
        return _apply_planned_grouped(a, b, cfg)
    if a.ndim != 3 or b.ndim not in (2, 3):
        raise ValueError(f"grouped_matmul: expected a (G, M, K) and b "
                         f"(K, N) | (G, K, N); got {tuple(a.shape)} @ "
                         f"{tuple(b.shape)}")
    G, M, K = a.shape
    shared = b.ndim == 2
    if b.shape[-2] != K or (not shared and b.shape[0] != G):
        raise ValueError(f"grouped_matmul: shapes do not conform: "
                         f"{tuple(a.shape)} @ {tuple(b.shape)}")
    use_core, d = _route_grouped(G, M, K, b.shape[-1], cfg, str(a.dtype),
                                 shared_b=shared)
    if use_core:
        return _grouped_core(cfg, shared)(a, b)
    if not d.use_lcma:
        return jnp.matmul(a, b)
    return _lcma_apply_grouped(a, b, d.algo, cfg)


# -- trainable PlannedWeight -------------------------------------------------

def _pw_primal(x2: jnp.ndarray, bt: jnp.ndarray, l: LCMA, n_logical: int,
               cfg: FalconConfig) -> jnp.ndarray:
    """The precombined-B̃ serving apply (backend native path or generated)."""
    be = backends.get_backend(cfg.backend)
    if be.apply_precombined is not None:
        return be.apply_precombined(x2, bt, l, n_logical, cfg)
    return matmul_with_precombined(x2, bt, l, n_logical, cfg)


def _pw_bwd_rotated(x2, bt, g, l: LCMA, cfg: FalconConfig):
    """Exact LCMA-structured backward against B̃ alone (raw weight dropped).

    With H_r = Ãt_r B̃t_r and C[i,j] = Σ_r W[r,i,j] H_r, the cotangents are

        G̃t_r  = Σ_ij W[r,i,j] G[i,j]            (Combine with W coefficients)
        dX[i,l] = Σ_r U[r,i,l] (G̃t_r B̃t_rᵀ)     (R batched GEMMs, Combine U)
        dB̃t_r  = Ãt_rᵀ G̃t_r                     (R batched GEMMs)

    — the rank-R scheme rotated onto the gradient, reusing the stored B̃.
    This is exact (the LCMA identity, not an approximation), so training
    directly on B̃ is sound: the output is linear in B̃.
    """
    Mrows, K = x2.shape
    Ks, Ns = int(bt.shape[1]), int(bt.shape[2])
    xp = _pad2(x2, l.m, l.k)
    Ms = xp.shape[0] // l.m
    gp = _pad2(g, l.m, 1)
    if gp.shape[1] != l.n * Ns:
        gp = jnp.pad(gp, ((0, 0), (0, l.n * Ns - gp.shape[1])))
    U = jnp.asarray(l.U, xp.dtype)
    W = jnp.asarray(l.W, gp.dtype)
    G4 = gp.reshape(l.m, Ms, l.n, Ns)
    Gt = jnp.einsum("rij,ixjz->rxz", W, G4)                    # (R, Ms, Ns)
    At = jnp.einsum("ril,ixly->rxy", U,
                    xp.reshape(l.m, Ms, l.k, Ks))              # (R, Ms, Ks)
    Hb = jnp.einsum("rxz,ryz->rxy", Gt, bt.astype(Gt.dtype))   # G̃t_r B̃t_rᵀ
    dx = jnp.einsum("ril,rxy->ixly", U.astype(Hb.dtype), Hb) \
        .reshape(l.m * Ms, l.k * Ks)[:Mrows, :K].astype(x2.dtype)
    dbt = jnp.einsum("rxy,rxz->ryz", At, Gt).astype(bt.dtype)
    return dx, dbt


@functools.lru_cache(maxsize=None)
def _pw_core(cfg: FalconConfig, algo: str, n_logical: int, trainable: bool):
    """custom-VJP core for a PlannedWeight's precombined apply.

    ``trainable=True`` (raw weight kept): the primal consumes ``(x2, w, bt)``
    — the fast serving path still reads only B̃, but the backward returns the
    raw-weight cotangent ``dW = x2ᵀ g`` as an independently planned falcon
    contraction (the Combine-B map is linear, so the B̃ cotangent transposes
    back to exactly this), plus ``dx = g Wᵀ`` planned likewise. The B̃ leaf
    gets a zero cotangent; the optimizer trains ``w`` and
    :func:`refresh_planned_params` re-derives B̃ after each update.

    ``trainable=False`` (``keep_weight=False``): B̃ *is* the parameter; both
    cotangents come from the rotated rank-R scheme (exact), so B̃ can be
    trained directly.
    """
    l = algorithms.get(algo)

    if trainable:
        @jax.custom_vjp
        def core(x2, w, bt):
            return _pw_primal(x2, bt, l, n_logical, cfg)

        def fwd(x2, w, bt):
            # runs only under differentiation: price the backward triple
            # here so inference traces never pay for (or cache) dA/dB plans
            plan_training(x2.shape[0], x2.shape[1], n_logical, cfg,
                          str(x2.dtype))
            return _pw_primal(x2, bt, l, n_logical, cfg), (x2, w, bt)

        def bwd(res, g):
            x2, w, bt = res
            dx = _dispatch2d(g, w.T, cfg).astype(x2.dtype)
            dw = _dispatch2d(x2.T, g, cfg).astype(w.dtype)
            return dx, dw, jnp.zeros_like(bt)

        core.defvjp(fwd, bwd)
        return core

    @jax.custom_vjp
    def core_bt(x2, bt):
        return _pw_primal(x2, bt, l, n_logical, cfg)

    def fwd_bt(x2, bt):
        return _pw_primal(x2, bt, l, n_logical, cfg), (x2, bt)

    def bwd_bt(res, g):
        x2, bt = res
        return _pw_bwd_rotated(x2, bt, g, l, cfg)

    core_bt.defvjp(fwd_bt, bwd_bt)
    return core_bt


@functools.lru_cache(maxsize=None)
def _pw_quant_core(cfg: FalconConfig, algo: str, n_logical: int):
    """custom-VJP core for a quantized PlannedWeight apply.

    The primal runs the backend's int8 pipeline against the offline-baked
    B̃q + block scales (the quantized serving fast path). The backward stays
    fp: ``dx`` and ``dw`` are independently planned falcon contractions
    against the RAW weight — quantization error never enters the gradient —
    and the quant buffers get symbolic-zero cotangents (B̃q is int8, whose
    tangent type is float0); :func:`refresh_planned_params` re-derives them
    after each optimizer update, exactly like B̃.
    """
    l = algorithms.get(algo)

    def primal(x2, bq, b_scales):
        be = backends.get_backend(cfg.backend)
        return be.apply_quant(x2, bq, b_scales, l, n_logical, cfg)

    @jax.custom_vjp
    def core(x2, w, bq, b_scales):
        return primal(x2, bq, b_scales)

    def fwd(x2, w, bq, b_scales):
        # runs only under differentiation: price the backward triple here so
        # inference traces never pay for (or cache) dA/dB plans
        plan_training(x2.shape[0], x2.shape[1], n_logical, cfg,
                      str(x2.dtype))
        return primal(x2, bq, b_scales), (x2, w, bq, b_scales)

    def bwd(res, g):
        x2, w, bq, b_scales = res
        dx = _dispatch2d(g, w.T, cfg).astype(x2.dtype)
        dw = _dispatch2d(x2.T, g, cfg).astype(w.dtype)
        dbq = np.zeros(bq.shape, jax.dtypes.float0)
        return dx, dw, dbq, jnp.zeros_like(b_scales)

    core.defvjp(fwd, bwd)
    return core


def refresh_planned_params(params):
    """Re-derive every PlannedWeight's B̃ from its (just-updated) raw weight.

    Planned gradients land on the raw weight (the B̃ cotangent is zero), so
    after an optimizer step the stored B̃ is stale; Combine B is linear and
    cheap relative to a train step, so the train steps re-run it here each
    update. Weights without a raw copy (``keep_weight=False``) train directly
    on B̃ and pass through. Identity for trees without PlannedWeights.
    """
    def refresh(leaf):
        if not isinstance(leaf, PlannedWeight) or not leaf.precombined \
                or leaf.w is None:
            return leaf
        lc = leaf.lcma
        bt = precombine_weights(leaf.w, lc) if leaf.w.ndim == 2 else \
            jax.vmap(lambda wi: precombine_weights(wi, lc))(leaf.w)
        if leaf.bq is None:
            return dataclasses.replace(leaf, bt=bt)
        # quantized PlannedWeight: re-bake B̃q + scales from the updated
        # weight too (same block size the original buffers were built with)
        by = int(leaf.bq.shape[1]) // int(leaf.b_scales.shape[1])
        bq, b_scales = _quantize_weight(leaf.w, lc, by=by)
        return dataclasses.replace(leaf, bt=bt, bq=bq, b_scales=b_scales)

    return jax.tree_util.tree_map(
        refresh, params, is_leaf=lambda x: isinstance(x, PlannedWeight))


# ---------------------------------------------------------------------------
# Dispatch entry points
# ---------------------------------------------------------------------------

def matmul(a: jnp.ndarray, b, cfg: FalconConfig | None = None,
           dtype_hint: str | None = None) -> jnp.ndarray:
    """``a @ b`` with FalconGEMM dispatch. ``a``: (..., M, K), ``b``: (K, N).

    Differentiable end to end: under ``cfg.planned_vjp`` the contraction runs
    through the custom-VJP core, so ``jax.grad`` computes both backward GEMMs
    as independently planned falcon contractions.
    """
    cfg = _resolve(cfg)
    if isinstance(b, PlannedWeight):
        return _apply_planned(a, b, cfg)
    *lead, M, K = a.shape
    K2, N = b.shape
    if K != K2:
        raise ValueError(f"matmul: contracting dims differ: "
                         f"{tuple(a.shape)} @ {tuple(b.shape)}")
    Mflat = int(np.prod(lead)) * M if lead else M
    dtype = dtype_hint or str(a.dtype)
    use_core, d = _route_planned(Mflat, K, N, cfg, dtype)
    if use_core:
        c = _planned_core(cfg)(a.reshape(Mflat, K) if lead else a, b)
        return c.reshape(*lead, M, N) if lead else c
    if not d.use_lcma:
        return _gemm(a, b)
    a2 = a.reshape(Mflat, K) if lead else a
    c = _lcma_apply(a2, b, d.algo, cfg)
    return c.reshape(*lead, M, N) if lead else c


def dense(x: jnp.ndarray, w, cfg: FalconConfig | None = None) -> jnp.ndarray:
    """Linear layer contraction: x (..., K) @ w (K, N) [w may be planned]."""
    cfg = _resolve(cfg)
    if isinstance(w, PlannedWeight):
        return _apply_planned(x, w, cfg)
    hook = backends.get_backend(cfg.backend).dense_hook
    if hook is not None:
        out = hook(x, w, cfg)
        if out is not None:
            return out
    *lead, K = x.shape
    return matmul(x.reshape(-1, K), w, cfg).reshape(*lead, w.shape[1])


def dot_general(a: jnp.ndarray, b, dimension_numbers,
                cfg: FalconConfig | None = None, precision=None,
                preferred_element_type=None) -> jnp.ndarray:
    """``jax.lax.dot_general`` with FalconGEMM dispatch.

    Transposed contractions are normalized: free/contracting dims are
    transposed adjacent and flattened to a (M, K) x (K, N) problem. An
    unbatched contraction is priced by ``plan()`` and runs the planned 2-D
    core; a **batched** contraction is priced as a whole group by
    ``plan_batched`` (one grouped decision and ONE grouped plan-cache key
    for the batch — never per-element pricing) and runs the grouped core.
    Under ``cfg.planned_vjp`` an LCMA-routed contraction runs through the
    matching custom-VJP core, so ``jax.grad`` backward contractions are
    independently planned too (backward shapes are priced only under
    differentiation — inference traces never pay for dA/dB plans). When the
    Decision Module declines (or an explicit ``preferred_element_type``
    asks for non-input accumulation semantics the LCMA combines don't
    honor), the call lowers to ``lax.dot_general`` untouched —
    bitwise-identical fallback.
    """
    cfg = _resolve(cfg)
    (ac, bc), (ab, bb) = dimension_numbers
    ac, bc, ab, bb = (tuple(int(i) for i in t) for t in (ac, bc, ab, bb))
    dn = ((ac, bc), (ab, bb))
    if isinstance(b, PlannedWeight):
        if ab or bb or ac != (a.ndim - 1,) or bc != (0,):
            raise ValueError(
                "PlannedWeight only supports the canonical dense contraction "
                f"(((a.ndim-1,), (0,)), ((), ())); got {dn}")
        return _apply_planned(a, b, cfg)
    a_free = tuple(i for i in range(a.ndim) if i not in ac and i not in ab)
    b_free = tuple(i for i in range(b.ndim) if i not in bc and i not in bb)
    M = int(np.prod([a.shape[i] for i in a_free])) if a_free else 1
    K = int(np.prod([a.shape[i] for i in ac])) if ac else 1
    N = int(np.prod([b.shape[i] for i in b_free])) if b_free else 1
    lcma_ok = (M > 0 and N > 0 and K > 0
               and (preferred_element_type is None
                    or jnp.dtype(preferred_element_type) == a.dtype))
    batch_shape = tuple(a.shape[i] for i in ab)
    Bsz = int(np.prod(batch_shape)) if ab else 1
    use_core = d = None
    if lcma_ok and not ab:
        use_core, d = _route_planned(M, K, N, cfg, str(a.dtype))
    elif lcma_ok:
        # Batched contraction: price the whole group (plan_batched — one
        # grouped plan-cache key), not one vmapped element. Both operands
        # carry the batch dims here, so the B operand is per-group.
        use_core, d = _route_grouped(Bsz, M, K, N, cfg, str(a.dtype),
                                     shared_b=False)
    if not use_core and (d is None or not d.use_lcma):
        return jax.lax.dot_general(a, b, dn, precision=precision,
                                   preferred_element_type=preferred_element_type)
    # Normalize: a -> (batch..., free..., contract...), b -> (batch...,
    # contract..., free...), flatten to (B, M, K) x (B, K, N).
    a_perm = ab + a_free + ac
    b_perm = bb + bc + b_free
    at = a if a_perm == tuple(range(a.ndim)) else jnp.transpose(a, a_perm)
    bt = b if b_perm == tuple(range(b.ndim)) else jnp.transpose(b, b_perm)
    out_shape = batch_shape + tuple(a.shape[i] for i in a_free) \
        + tuple(b.shape[i] for i in b_free)
    if not ab:
        core = _planned_core(cfg) if use_core \
            else (lambda x2, y2: _lcma_apply(x2, y2, d.algo, cfg))
        c = core(at.reshape(M, K), bt.reshape(K, N))
        return c.reshape(out_shape)
    a3 = at.reshape(Bsz, M, K)
    b3 = bt.reshape(Bsz, K, N)
    c3 = _grouped_core(cfg, False)(a3, b3) if use_core \
        else _lcma_apply_grouped(a3, b3, d.algo, cfg)
    return c3.reshape(out_shape)


def einsum(subscripts: str, *operands, cfg: FalconConfig | None = None,
           precision=None) -> jnp.ndarray:
    """``jnp.einsum`` with FalconGEMM dispatch for two-operand contractions.

    Two-operand subscripts without ellipsis/repeats/sum-out reduce to
    :func:`dot_general` (and so hit the Decision Module); anything else
    falls back to ``jnp.einsum`` unchanged.
    """
    if len(operands) == 2 and isinstance(subscripts, str):
        a, b = operands
        parsed = _einsum_dimension_numbers(subscripts, a.ndim, b.ndim)
        if parsed is not None:
            dn, perm = parsed
            out = dot_general(a, b, dn, cfg=cfg, precision=precision)
            if perm != tuple(range(len(perm))):
                out = jnp.transpose(out, perm)
            return out
    return jnp.einsum(subscripts, *operands, precision=precision)


def _einsum_dimension_numbers(subscripts: str, a_ndim: int, b_ndim: int):
    """Two-operand einsum -> (dimension_numbers, output transpose) or None.

    None means "not expressible as a single dot_general" (ellipsis, repeated
    labels within an operand, summed-out free labels, rank mismatch) and the
    caller should fall back to ``jnp.einsum``.
    """
    subs = subscripts.replace(" ", "")
    if "." in subs:
        return None
    if "->" in subs:
        lhs, out = subs.split("->")
    else:
        lhs, out = subs, None
    terms = lhs.split(",")
    if len(terms) != 2:
        return None
    ta, tb = terms
    if len(ta) != a_ndim or len(tb) != b_ndim:
        return None
    if len(set(ta)) != len(ta) or len(set(tb)) != len(tb):
        return None
    if out is None:  # implicit mode: alphabetic order of non-shared labels
        out = "".join(sorted(c for c in set(ta + tb)
                             if (ta + tb).count(c) == 1))
    if len(set(out)) != len(out) or any(c not in ta + tb for c in out):
        return None
    shared = [c for c in ta if c in tb]
    batch = tuple(c for c in shared if c in out)
    contract = tuple(c for c in shared if c not in out)
    a_free = [c for c in ta if c not in tb]
    b_free = [c for c in tb if c not in ta]
    if any(c not in out for c in a_free + b_free):
        return None  # summed-out free label: not a plain contraction
    dn = ((tuple(ta.index(c) for c in contract),
           tuple(tb.index(c) for c in contract)),
          (tuple(ta.index(c) for c in batch),
           tuple(tb.index(c) for c in batch)))
    natural = list(batch) + a_free + b_free   # dot_general output order
    perm = tuple(natural.index(c) for c in out)
    return dn, perm


# ---------------------------------------------------------------------------
# The engine object: a bound config + the dispatch surface
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FalconEngine:
    """A FalconConfig bound to the dispatch surface.

    The module-level functions resolve config from the ambient context; an
    engine pins one explicitly — handy for services juggling several
    hardware/policy profiles at once:

        eng = FalconEngine(FalconConfig(hardware="tpu_v5e", backend="pallas"))
        y = eng.dense(x, w)
        with eng.activate():     # or: make it the ambient config
            y = falcon_dense(x, w)
    """

    config: FalconConfig = dataclasses.field(default_factory=FalconConfig)

    def activate(self):
        return use(self.config)

    def plan(self, M: int, K: int, N: int, dtype: str = "bfloat16",
             precombined_b: bool = False):
        return plan(M, K, N, self.config, dtype, precombined_b=precombined_b)

    def matmul(self, a, b, **kw):
        return matmul(a, b, cfg=self.config, **kw)

    def dense(self, x, w):
        return dense(x, w, cfg=self.config)

    def dot_general(self, a, b, dimension_numbers, **kw):
        return dot_general(a, b, dimension_numbers, cfg=self.config, **kw)

    def grouped_matmul(self, a, b):
        return grouped_matmul(a, b, cfg=self.config)

    def plan_batched(self, B: int, M: int, K: int, N: int,
                     dtype: str = "bfloat16", precombined_b: bool = False,
                     shared_b: bool = False):
        return plan_batched(B, M, K, N, self.config, dtype,
                            precombined_b=precombined_b, shared_b=shared_b)

    def einsum(self, subscripts, *operands, **kw):
        return einsum(subscripts, *operands, cfg=self.config, **kw)

    def plan_weight(self, w, **kw):
        return plan_weight(w, cfg=self.config, **kw)

    def precombine_params(self, params, **kw):
        return precombine_params(params, cfg=self.config, **kw)

    def plan_training(self, M: int, K: int, N: int, dtype: str = "bfloat16"):
        return plan_training(M, K, N, self.config, dtype)

    def warm_buckets(self, arch, buckets, **kw):
        return warm_buckets(self.config, arch, buckets, **kw)
