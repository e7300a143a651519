"""FalconGEMM dispatch core: decision-dispatched LCMA matmul + planning.

``falcon_matmul(a, b)`` is the drop-in ``a @ b`` replacement used by the
model zoo's linear layers (the paper's PyTorch-backend integration, §IV-C):

  1. the Decision Module predicts, from the *static trace-time shapes* (scaled
     to per-device shapes by ``cfg.shards`` under pjit), whether an LCMA beats
     standard GEMM on the target hardware,
  2. if yes, the chosen execution **backend** (``core.backends`` registry:
     generated pure-JAX combines, the Pallas kernel pipeline, the shard_map
     local-matmul placement, or anything user-registered) runs the scheme,
  3. otherwise it falls back to ``jnp.dot`` — "keep the best performance".

Configuration is context-scoped (``repro.api.use`` / ``FalconEngine``); the
explicit ``cfg`` argument survives as a compatibility override. Static weights
can be pre-combined offline (``precombine_weights`` / ``PlannedWeight``),
removing the Combine-B stage from serving entirely (paper §IV-C "offline
Combine B").
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import logging
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat

from . import algorithms, backends, codegen, decision as dec, plan_cache
from .hardware import HardwareProfile, device_profile_name, get_profile
from .lcma import LCMA

log = logging.getLogger(__name__)

__all__ = ["FalconConfig", "falcon_matmul", "falcon_dense", "plan",
           "plan_batched", "plan_sharded", "plan_training",
           "precombine_weights", "matmul_with_precombined",
           "grouped_matmul_generated", "grouped_matmul_with_precombined"]


@dataclasses.dataclass(frozen=True)
class FalconConfig:
    """Trace-time policy for FalconGEMM dispatch."""

    enabled: bool = True
    # Profile name; None prices the attached chip (``device_profile_name``).
    hardware: str | None = None
    backend: str = "jnp"             # any name in core.backends registry
    fused: bool = True
    mode: str = "auto"               # "auto" | "gemm" | explicit scheme name
    candidates: tuple[str, ...] | None = None
    min_speedup: float = 1.02        # require a predicted >=2% win before switching
    max_grid: int = 5
    # Static numerical-accuracy ceiling for this call site: candidates whose
    # Higham-style relative-error bound (``LCMA.stability.error_bound``)
    # exceeds the budget are rejected BEFORE pricing (falcon-check's
    # ``stability`` pass, read by the Decision Module). None disables.
    accuracy_budget: float | None = None
    # Put the int8-quantized tier into the Decision Module's search: every
    # budget-eligible candidate is additionally priced quantized
    # (``decision.estimate_quant``) and the winner's tier lands in
    # ``Decision.precision``. Selection stays gated by ``accuracy_budget``
    # (int8 eps = 1/(2*127) in the stability model).
    quantize: bool = False
    # Per-device scaling of (M, K, N) under pjit: number of shards per dim.
    shards: tuple[int, int, int] = (1, 1, 1)
    # Memoize auto-mode Decisions in the process plan cache (serving hot path
    # re-traces the same shapes; see core/plan_cache.py).
    use_plan_cache: bool = True
    # Route autodiff through the planned custom-VJP: the backward GEMMs
    # (dA = g Bᵀ, dB = Aᵀ g) become independently planned falcon contractions
    # instead of the autodiff transpose of the combine graph. False restores
    # differentiate-through semantics (and forward-mode jvp support).
    planned_vjp: bool = True

    @property
    def profile(self) -> HardwareProfile:
        return get_profile(self.hardware or device_profile_name())

    def candidate_schemes(self) -> list[LCMA]:
        if self.candidates is not None:
            return [algorithms.get(n) for n in self.candidates]
        return algorithms.candidates(max_grid=self.max_grid)


# Once-per-key warning dedup for non-divisible shard shapes. Bounded: a
# long-running serve/replan process sees an unbounded stream of distinct
# (shape, shards) keys, and an ever-growing set is a slow leak — oldest keys
# are dropped (worst case: a very old shape warns again). Locked: plan() is
# reached from multiple serve threads, and an unguarded check-then-mutate on
# the OrderedDict can race into a KeyError.
_WARNED_SHARDS_MAX = 512
_warned_shards: "collections.OrderedDict[tuple, None]" = collections.OrderedDict()
_warned_shards_lock = threading.Lock()


def _warn_once_key(key: tuple) -> bool:
    """True if ``key`` has not warned yet; records it in the bounded LRU."""
    with _warned_shards_lock:
        if key in _warned_shards:
            _warned_shards.move_to_end(key)
            return False
        _warned_shards[key] = None
        if len(_warned_shards) > _WARNED_SHARDS_MAX:
            _warned_shards.popitem(last=False)
        return True


def _local_shape(M: int, K: int, N: int, cfg: FalconConfig) -> tuple[int, int, int]:
    """Scale a global shape to the per-device shape by ``cfg.shards``.

    Non-divisible shards round UP (ceil division): the per-device problem the
    partitioner actually materializes is the padded shard, and silently
    truncating (the old ``max(M // sm, 1)``) made the Decision Module price a
    smaller matmul than any device runs. Warns once per (shape, shards).
    """
    sm, sk, sn = cfg.shards
    if min(sm, sk, sn) < 1:
        raise ValueError(f"FalconConfig.shards must be >= 1, got {cfg.shards}")
    if M % sm or K % sk or N % sn:
        key = (M, K, N, cfg.shards)
        if _warn_once_key(key):
            log.warning(
                "FalconGEMM: shards %s do not divide (M=%d, K=%d, N=%d); "
                "pricing the rounded-up per-device shard (%d, %d, %d)",
                cfg.shards, M, K, N, -(-M // sm), -(-K // sk), -(-N // sn))
    return max(-(-M // sm), 1), max(-(-K // sk), 1), max(-(-N // sn), 1)


def plan(M: int, K: int, N: int, cfg: FalconConfig, dtype: str = "bfloat16",
         precombined_b: bool = False, *, mesh=None,
         layouts: tuple[dec.ShardLayout, ...] | None = None,
         n_devices: int | None = None) -> dec.Decision:
    """Run the Decision Module for a (possibly sharded) matmul shape.

    Auto-mode decisions are memoized in the process plan cache (keyed on the
    local shape, dtype, hardware fingerprint and dispatch policy), so repeated
    trace-time shapes — the serving hot path — skip candidate enumeration.

    Passing a mesh context (``mesh=`` — a ``jax.sharding.Mesh``/abstract mesh
    — or explicit ``layouts``/``n_devices``) promotes the plan to the
    shard-aware tier: ``(M, K, N)`` is then the GLOBAL shape, candidate
    layouts come from ``parallel.sharding.layouts_for_mesh`` and the returned
    :class:`~repro.core.decision.ShardedDecision` prices local contraction
    plus collectives (see :func:`plan_sharded`).
    """
    if mesh is not None or layouts is not None or (n_devices or 0) > 1:
        return plan_sharded(M, K, N, cfg, dtype, precombined_b,
                            mesh=mesh, layouts=layouts, n_devices=n_devices)
    Ml, Kl, Nl = _local_shape(M, K, N, cfg)
    if cfg.mode == "gemm" or not cfg.enabled:
        t = dec.gemm_time(Ml, Nl, Kl, cfg.profile, dtype)
        return dec.Decision(Ml, Nl, Kl, dtype, None, t, None, ())
    if cfg.mode != "auto":
        l = algorithms.get(cfg.mode)
        est = dec.estimate(l, Ml, Nl, Kl, cfg.profile, dtype, fused=cfg.fused,
                           precombined_b=precombined_b)
        return dec.Decision(Ml, Nl, Kl, dtype, l,
                            dec.gemm_time(Ml, Nl, Kl, cfg.profile, dtype),
                            est.time, (est,))
    cache = key = None
    if cfg.use_plan_cache:
        cache = plan_cache.default_cache()
        key = plan_cache.plan_key(
            Ml, Kl, Nl, cfg.profile, dtype, fused=cfg.fused,
            precombined_b=precombined_b, mode=cfg.mode,
            candidates=cfg.candidates, max_grid=cfg.max_grid,
            min_speedup=cfg.min_speedup,
            accuracy_budget=cfg.accuracy_budget, quantize=cfg.quantize)
        hit = cache.lookup(key)
        if hit is not None:
            return hit
    d = dec.decide(Ml, Nl, Kl, cfg.profile, dtype,
                   candidates=cfg.candidate_schemes(), fused=cfg.fused,
                   precombined_b=precombined_b, min_speedup=cfg.min_speedup,
                   accuracy_budget=cfg.accuracy_budget, quantize=cfg.quantize)
    if cache is not None:
        cache.insert(key, d)
    return d


def plan_sharded(M: int, K: int, N: int, cfg: FalconConfig,
                 dtype: str = "bfloat16", precombined_b: bool = False, *,
                 mesh=None, layouts: tuple[dec.ShardLayout, ...] | None = None,
                 n_devices: int | None = None) -> dec.ShardedDecision:
    """Run the shard-aware Decision Module for a distributed contraction.

    ``(M, K, N)`` is the GLOBAL shape. The candidate layouts and the device
    count come from an explicit ``layouts``/``n_devices`` pair, or are
    resolved from ``mesh`` (default: the ambient abstract mesh) through the
    ``parallel.sharding`` rules for the active parallel style. Each layout is
    priced as per-shard local time plus its collective bytes over the
    profile's measured-or-profiled collective bandwidth; plan-cache keys
    embed the layout context (candidate set, D, collective bw), so sharded
    plans never alias local ones.

    Non-auto modes restrict the algorithm axis (``"gemm"``/disabled price no
    LCMA; an explicit scheme prices only that scheme) while the layout axis is
    still searched.
    """
    if layouts is None or n_devices is None:
        from repro.parallel.sharding import layouts_for_mesh
        d_mesh, mesh_layouts = layouts_for_mesh(mesh)
        if n_devices is None:
            n_devices = d_mesh
        if layouts is None:
            layouts = mesh_layouts
    n_devices = max(int(n_devices), 1)
    layouts = tuple(layouts)
    if cfg.mode not in ("auto", "gemm") and cfg.enabled:
        # Forced scheme: search only the layout axis (no Eq. 8 guard, like
        # the forced branch of plan()).
        l = algorithms.get(cfg.mode)
        best = None
        for ly in layouts:
            Ml, Nl, Kl = ly.local_shape(M, N, K, n_devices)
            t_coll = dec.collective_cost(ly, M, N, K, n_devices,
                                         cfg.profile, dtype).time
            est = dec.estimate(l, Ml, Nl, Kl, cfg.profile, dtype,
                               fused=cfg.fused, precombined_b=precombined_b)
            sd = dec.ShardedDecision(
                M, N, K, dtype, l,
                dec.gemm_time(Ml, Nl, Kl, cfg.profile, dtype) + t_coll,
                est.time + t_coll, (est,), layout=ly.name,
                n_devices=n_devices, collective_seconds=t_coll,
                local_shape_mnk=(Ml, Nl, Kl))
            if best is None or sd.seconds < best.seconds:
                best = sd
        return best
    cand = [] if (cfg.mode == "gemm" or not cfg.enabled) \
        else cfg.candidate_schemes()
    cache = key = None
    if cfg.use_plan_cache and cfg.mode == "auto" and cfg.enabled:
        cache = plan_cache.default_cache()
        key = plan_cache.plan_key(
            M, K, N, cfg.profile, dtype, fused=cfg.fused,
            precombined_b=precombined_b, mode=cfg.mode,
            candidates=cfg.candidates, max_grid=cfg.max_grid,
            min_speedup=cfg.min_speedup,
            accuracy_budget=cfg.accuracy_budget, quantize=cfg.quantize,
            layout=",".join(l.name for l in layouts), n_devices=n_devices)
        hit = cache.lookup(key)
        if isinstance(hit, dec.ShardedDecision):
            return hit
    d = dec.decide_sharded(M, N, K, cfg.profile, dtype, n_devices=n_devices,
                           layouts=layouts, candidates=cand,
                           fused=cfg.fused, precombined_b=precombined_b,
                           min_speedup=cfg.min_speedup,
                           accuracy_budget=cfg.accuracy_budget,
                           quantize=cfg.quantize)
    if cache is not None:
        cache.insert(key, d)
    return d


def plan_batched(B: int, M: int, K: int, N: int, cfg: FalconConfig,
                 dtype: str = "bfloat16", precombined_b: bool = False,
                 shared_b: bool = False) -> dec.GroupedDecision:
    """Run the Decision Module for a grouped batched contraction.

    One decision — and ONE plan-cache key (``gBxMxKxN``) — for the whole
    ``B x (M, K) @ (K, N)`` group, instead of pricing a per-element 2-D core
    that batching would then ``vmap``. The grouped model amortizes Combine
    setup across the group: Combine B is priced once when the B operand is
    shared (``shared_b=True`` — attention weights, PlannedWeights) and the
    R*B intermediate products are priced as one grouped GEMM. ``cfg.shards``
    scales the per-element (M, K, N); the group dim is not sharded here
    (expert parallelism shards it upstream, inside ``shard_map``).
    """
    Ml, Kl, Nl = _local_shape(M, K, N, cfg)
    B = int(B)
    if B < 1:
        raise ValueError(f"plan_batched: group size must be >= 1, got {B}")
    if cfg.mode == "gemm" or not cfg.enabled:
        t = dec.gemm_time_batched(B, Ml, Nl, Kl, cfg.profile, dtype,
                                  shared_b=shared_b)
        return dec.GroupedDecision(Ml, Nl, Kl, dtype, None, t, None, (),
                                   B=B, shared_b=shared_b)
    if cfg.mode != "auto":
        l = algorithms.get(cfg.mode)
        est = dec.estimate_grouped(l, B, Ml, Nl, Kl, cfg.profile, dtype,
                                   fused=cfg.fused, precombined_b=precombined_b,
                                   shared_b=shared_b)
        return dec.GroupedDecision(
            Ml, Nl, Kl, dtype, l,
            dec.gemm_time_batched(B, Ml, Nl, Kl, cfg.profile, dtype,
                                  shared_b=shared_b),
            est.time, (est,), B=B, shared_b=shared_b)
    cache = key = None
    if cfg.use_plan_cache:
        cache = plan_cache.default_cache()
        key = plan_cache.plan_key(
            Ml, Kl, Nl, cfg.profile, dtype, fused=cfg.fused,
            precombined_b=precombined_b, mode=cfg.mode,
            candidates=cfg.candidates, max_grid=cfg.max_grid,
            min_speedup=cfg.min_speedup, batch=B, shared_b=shared_b,
            accuracy_budget=cfg.accuracy_budget, quantize=cfg.quantize)
        hit = cache.lookup(key)
        if isinstance(hit, dec.GroupedDecision):
            return hit
    d = dec.decide_batched(B, Ml, Nl, Kl, cfg.profile, dtype,
                           candidates=cfg.candidate_schemes(), fused=cfg.fused,
                           precombined_b=precombined_b, shared_b=shared_b,
                           min_speedup=cfg.min_speedup,
                           accuracy_budget=cfg.accuracy_budget,
                           quantize=cfg.quantize)
    if cache is not None:
        cache.insert(key, d)
    return d


def plan_training(M: int, K: int, N: int, cfg: FalconConfig,
                  dtype: str = "bfloat16") -> tuple[dec.Decision, dec.Decision,
                                                    dec.Decision]:
    """Plan a contraction's forward AND both backward shapes.

    Training runs three falcon contractions per layer: the forward
    ``(M, K) @ (K, N)`` plus the two gradients ``dA = g Bᵀ`` (rows M,
    contract N, cols K) and ``dB = Aᵀ g`` (rows K, contract M, cols N).
    Each goes through the Decision Module and plan cache under its own key,
    so a training warm pass (``engine.warm_buckets(train=True)`` /
    ``tools.tune --train``) leaves the whole jitted step plan-cache-hot.
    Returns ``(d_fwd, d_dA, d_dB)``.
    """
    (sa, sb) = dec.backward_shapes(M, K, N)
    return (plan(M, K, N, cfg, dtype),
            plan(*sa, cfg, dtype),
            plan(*sb, cfg, dtype))


def _pad2(x: jnp.ndarray, d0: int, d1: int) -> jnp.ndarray:
    p0 = (-x.shape[0]) % d0
    p1 = (-x.shape[1]) % d1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def _lcma_apply(a2: jnp.ndarray, b: jnp.ndarray, l: LCMA, cfg: FalconConfig) -> jnp.ndarray:
    """Execute the chosen LCMA on 2-D operands via the registered backend."""
    return backends.get_backend(cfg.backend).apply(a2, b, l, cfg)


def _pad3(x: jnp.ndarray, d0: int, d1: int) -> jnp.ndarray:
    p0 = (-x.shape[1]) % d0
    p1 = (-x.shape[2]) % d1
    if p0 or p1:
        x = jnp.pad(x, ((0, 0), (0, p0), (0, p1)))
    return x


def _shared_b_products(at: jnp.ndarray, bt: jnp.ndarray) -> jnp.ndarray:
    """(G, R, X, Y) x shared (R, Y, Z) -> f32 (G, R, X, Z), one dot over r.

    Written as a ``dot_general`` with the activation first: ``jnp.einsum``
    puts the shared operand first for this contraction, and XLA:CPU has no
    bf16 x bf16 -> f32 kernel for that operand order.
    """
    h = jax.lax.dot_general(at, bt, (((3,), (1,)), ((1,), (0,))),
                            preferred_element_type=jnp.float32)
    return h.transpose(1, 0, 2, 3)


def grouped_matmul_generated(a3: jnp.ndarray, b: jnp.ndarray, l: LCMA,
                             cfg: FalconConfig) -> jnp.ndarray:
    """Grouped LCMA via the generated pure-JAX combines (the jnp backend).

    a3 (G, M, K) x b [(K, N) shared | (G, K, N) per-group] -> (G, M, N).
    The group-parallel lowering: per-group Combine A (one vmapped combine),
    Combine B hoisted ONCE when ``b`` is shared, and the G*R intermediate
    products as a single grouped ``dot_general`` (batch dims (g, r) — XLA
    sees one batched GEMM, not G fragmented launches), then per-group
    Combine H from the float32 accumulator.
    """
    G, M, K = a3.shape
    gen = codegen.generate(l, codegen.CodegenOptions(fused=cfg.fused))
    at = jax.vmap(gen.combine_a)(_pad3(a3, l.m, l.k))      # (G, R, X, Ks)
    if b.ndim == 2:
        N = b.shape[1]
        bt = gen.combine_b(_pad2(b, l.k, l.n))             # hoisted: once
        h = _shared_b_products(at, bt)
    else:
        N = b.shape[2]
        bt = jax.vmap(gen.combine_b)(_pad3(b, l.k, l.n))   # (G, R, Ks, Ns)
        h = jnp.einsum("grxy,gryz->grxz", at, bt,
                       preferred_element_type=jnp.float32)
    c = jax.vmap(gen.stages["combine_h"], in_axes=(0, None))(h, a3.dtype)
    return c[:, :M, :N]


def grouped_matmul_with_precombined(a3: jnp.ndarray, bt: jnp.ndarray, l: LCMA,
                                    n_logical: int,
                                    cfg: FalconConfig | None = None) -> jnp.ndarray:
    """Grouped serving-path matmul against precombined B̃ (generated combines).

    ``bt`` is (R, K/k, N/n) — one shared weight — or (G, R, K/k, N/n) for
    stacked per-group weights (a stacked :class:`PlannedWeight`, e.g. MoE
    experts combined offline). Combine B never runs.
    """
    if cfg is None:
        from . import engine
        cfg = engine.current_config()
    G, M, K = a3.shape
    gen = codegen.generate(l, codegen.CodegenOptions(fused=cfg.fused))
    ap = _pad3(a3, l.m, l.k)
    if ap.shape[2] // l.k != bt.shape[-2]:
        raise ValueError(
            f"grouped_matmul_with_precombined: activation K={K} (padded "
            f"{ap.shape[2]}, grid k={l.k}) does not match precombined "
            f"B̃ {tuple(bt.shape)} for scheme {l.name} {l.key}")
    at = jax.vmap(gen.combine_a)(ap)
    if bt.ndim == 3:
        h = _shared_b_products(at, bt.astype(at.dtype))
    else:
        if bt.shape[0] != G:
            raise ValueError(
                f"grouped_matmul_with_precombined: group sizes differ: "
                f"{a3.shape} vs B̃ {tuple(bt.shape)}")
        h = jnp.einsum("grxy,gryz->grxz", at, bt.astype(at.dtype),
                       preferred_element_type=jnp.float32)
    c = jax.vmap(gen.stages["combine_h"], in_axes=(0, None))(h, a3.dtype)
    return c[:, :M, :n_logical]


def _lcma_apply_grouped(a3: jnp.ndarray, b: jnp.ndarray, l: LCMA,
                        cfg: FalconConfig) -> jnp.ndarray:
    """Execute a grouped LCMA via the backend's grouped path (or fallback).

    Backends without a native ``apply_grouped`` fall back to the generated
    grouped lowering — still one grouped GEMM, never a per-element loop.
    """
    be = backends.get_backend(cfg.backend)
    if be.apply_grouped is not None:
        return be.apply_grouped(a3, b, l, cfg)
    return grouped_matmul_generated(a3, b, l, cfg)


def falcon_matmul(a: jnp.ndarray, b, cfg: FalconConfig | None = None,
                  dtype_hint: str | None = None) -> jnp.ndarray:
    """``a @ b`` with FalconGEMM dispatch. ``a``: (..., M, K), ``b``: (K, N).

    Compatibility form of the unified API: ``cfg=None`` resolves the
    context-scoped config (``repro.api.use``). ``b`` may be a
    :class:`~repro.core.engine.PlannedWeight` (offline Combine-B weights).
    """
    from . import engine
    return engine.matmul(a, b, cfg=cfg, dtype_hint=dtype_hint)


def falcon_dense(x: jnp.ndarray, w, cfg: FalconConfig | None = None) -> jnp.ndarray:
    """Linear layer contraction: x (..., K) @ w (K, N).

    ``w`` may be a raw weight matrix or a ``PlannedWeight``; ``cfg=None``
    resolves the context-scoped config.
    """
    from . import engine
    return engine.dense(x, w, cfg=cfg)


def _falcon_dense_shardmap(x: jnp.ndarray, w: jnp.ndarray,
                           cfg: FalconConfig) -> jnp.ndarray | None:
    """Apply LCMA to the per-device LOCAL matmul inside ``shard_map``.

    Lesson from EXPERIMENTS.md §Perf A1: LCMA submatrix slicing on a
    GSPMD-sharded global matmul makes the partitioner reshard every slice
    (7x collective blow-up). The correct placement is the device-local GEMM:
    here tokens are sharded over the batch axes, the weight is gathered to a
    local replica (the same all-gather ZeRO does for the plain matmul), and
    the Decision Module prices the *local* shapes it actually sees.

    Only supported under ``parallel_style="fsdp_only"`` (no TP: the local
    contraction is the full K x N). Returns None to fall back otherwise.

    The plan is the *sharded* tier: the global (T, K, N) is priced per layout
    — batch-sharded local contraction plus the weight all-gather's collective
    bytes vs a fully replicated lowering — so the claim this hook makes on
    the contraction is no longer unpriced. When the replicated layout wins
    (collective-starved link, tiny T) the hook declines and lets GSPMD place
    the op.
    """
    from repro.parallel.sharding import get_parallel_style, resolve_batch_axes
    from jax.sharding import PartitionSpec as P

    mesh = compat.get_abstract_mesh()
    if mesh is None or get_parallel_style() != "fsdp_only":
        return None
    sizes = dict(mesh.shape)
    axes = tuple(a for a in resolve_batch_axes() if a in set(mesh.axis_names))
    nb = int(np.prod([sizes[a] for a in axes])) if axes else 1
    *lead, K = x.shape
    T = int(np.prod(lead))
    if nb <= 1 or T % nb != 0:
        return None
    N = w.shape[1]
    d = plan_sharded(T, K, N, dataclasses.replace(cfg, shards=(1, 1, 1)),
                     str(x.dtype), n_devices=nb, layouts=dec.fsdp_layouts())
    if not d.shard_layout.shard[0]:
        return None   # replicated layout priced cheaper: let GSPMD place it

    def body(xl, wl):
        if d.use_lcma:
            c = _lcma_apply(xl, wl, d.algo, dataclasses.replace(cfg, backend="jnp"))
        else:
            c = jnp.matmul(xl, wl)
        return c

    # flatten tokens so the (possibly small) batch dim times seq shards over
    # the full mesh: (B, S, K) -> (B*S, K) with B*S % n_devices == 0
    xspec = P(axes, None)
    out = jax.shard_map(
        body, in_specs=(xspec, P(None, None)),
        out_specs=xspec, check_vma=False)(x.reshape(T, K), w)
    return out.reshape(*lead, N)


# ---------------------------------------------------------------------------
# Offline Combine B (static weights, serving path)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=1)
def precombine_weights(w: jnp.ndarray, l: LCMA) -> jnp.ndarray:
    """Offline Combine B of a static weight matrix: (K, N) -> (R, K/k, N/n).

    Compiled, so B̃ is written straight into its output: run op by op, the
    slices and partial sums of a full-width layer stack would briefly hold
    about three times B̃ in device memory.
    """
    gen = codegen.generate(l, codegen.CodegenOptions(precombined_b=True))
    return gen.combine_b(_pad2(w, l.k, l.n))


def matmul_with_precombined(a: jnp.ndarray, bt: jnp.ndarray, l: LCMA,
                            n_logical: int, cfg: FalconConfig | None = None) -> jnp.ndarray:
    """Serving-path matmul against pre-combined weights B̃ (R, K/k, N/n)."""
    if cfg is None:
        from . import engine
        cfg = engine.current_config()
    gen = codegen.generate(l, codegen.CodegenOptions(
        fused=cfg.fused, precombined_b=True))
    *lead, M, K = a.shape
    a2 = a.reshape(-1, K)
    ap = _pad2(a2, l.m, l.k)
    if ap.shape[1] // l.k != bt.shape[1]:
        # a bare assert here vanished under ``python -O`` and let mismatched
        # operands flow into the combines, producing garbage instead of a
        # shape error
        raise ValueError(
            f"matmul_with_precombined: activation K={K} (padded "
            f"{ap.shape[1]}, grid k={l.k}) does not match precombined "
            f"B̃ {tuple(bt.shape)} for scheme {l.name} {l.key}")
    c = gen.fn(ap, bt)[: a2.shape[0], :n_logical]
    return c.reshape(*lead, M, n_logical) if lead else c
