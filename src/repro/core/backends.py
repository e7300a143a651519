"""Execution backend registry for FalconGEMM dispatch.

The Decision Module picks *what* to run (an LCMA scheme or standard GEMM);
a **backend** decides *how* the chosen LCMA executes. Historically that was a
string switch buried in ``falcon_gemm._lcma_apply``; this registry makes new
execution strategies (a Low-Rank GEMM approximation, a CUDA-L2-style tuned
kernel, a remote accelerator) pluggable without touching dispatch:

    from repro.core.backends import register_backend, Backend

    def my_apply(a2, b, lcma, cfg):          # 2-D (M,K) @ (K,N) LCMA matmul
        ...
    register_backend("mine", my_apply)
    falcon_matmul(a, b, FalconConfig(backend="mine"))

An ``impl`` may be a bare callable (the 2-D apply) or a :class:`Backend` with
an optional ``dense_hook`` that intercepts whole layer contractions before the
2-D core (how ``shard_map_local`` places LCMA on the per-device local matmul).
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Callable

__all__ = ["Backend", "register_backend", "get_backend", "unregister_backend",
           "available_backends"]

# apply(a2, b, lcma, cfg) -> C : execute the LCMA matmul on 2-D operands.
ApplyFn = Callable
# dense_hook(x, w, cfg) -> out | None : optionally claim a full (..., K) @
# (K, N) layer contraction; returning None falls through to the 2-D core.
DenseHookFn = Callable
# apply_precombined(a2, bt, lcma, n_logical, cfg) -> C : execute against an
# offline-combined B̃ (R, K/k, N/n) — the PlannedWeight serving path. None
# means "no native path"; dispatch falls back to the generated jnp combines.
ApplyPrecombinedFn = Callable
# apply_grouped(a3, b, lcma, cfg) -> C3 : execute a grouped batched LCMA —
# a3 (G, M, K) against b (K, N) (shared; Combine B hoisted once) or
# (G, K, N) (per-group). None falls back to the generated grouped lowering.
ApplyGroupedFn = Callable
# apply_grouped_precombined(a3, bt, lcma, n_logical, cfg) -> C3 : grouped
# serving path against precombined B̃ (R, K/k, N/n) or stacked
# (G, R, K/k, N/n) — the stacked-PlannedWeight / MoE-expert case.
ApplyGroupedPrecombinedFn = Callable
# apply_quant(a2, bq, b_scales, lcma, n_logical, cfg) -> C : int8 serving
# path against offline-quantized B̃q (R, K/k, N/n) int8 + f32 block scales
# (the quantized PlannedWeight tier). None means the backend has no int8
# path and the quantized tier is not servable on it.
ApplyQuantFn = Callable


@dataclasses.dataclass(frozen=True)
class Backend:
    """A registered execution strategy."""

    name: str
    apply: ApplyFn
    dense_hook: DenseHookFn | None = None
    apply_precombined: ApplyPrecombinedFn | None = None
    apply_grouped: ApplyGroupedFn | None = None
    apply_grouped_precombined: ApplyGroupedPrecombinedFn | None = None
    apply_quant: ApplyQuantFn | None = None
    description: str = ""


_REGISTRY: dict[str, Backend] = {}
_LOCK = threading.Lock()


def register_backend(name: str, impl, *, dense_hook: DenseHookFn | None = None,
                     apply_precombined: ApplyPrecombinedFn | None = None,
                     apply_grouped: ApplyGroupedFn | None = None,
                     apply_grouped_precombined: ApplyGroupedPrecombinedFn | None = None,
                     apply_quant: ApplyQuantFn | None = None,
                     description: str = "", overwrite: bool = False) -> Backend:
    """Register an execution backend under ``name``.

    ``impl`` is either a callable ``(a2, b, lcma, cfg) -> C`` or a ready-made
    :class:`Backend`. Re-registering an existing name requires
    ``overwrite=True`` (guards against accidental shadowing of built-ins).
    Backends without the optional grouped hooks still serve grouped batched
    dispatch — the engine falls back to the generated grouped lowering.
    """
    if isinstance(impl, Backend):
        be = dataclasses.replace(impl, name=name)
    elif callable(impl):
        be = Backend(name=name, apply=impl, dense_hook=dense_hook,
                     apply_precombined=apply_precombined,
                     apply_grouped=apply_grouped,
                     apply_grouped_precombined=apply_grouped_precombined,
                     apply_quant=apply_quant,
                     description=description)
    else:
        raise TypeError(f"register_backend: impl must be callable or Backend, "
                        f"got {type(impl).__name__}")
    with _LOCK:
        if name in _REGISTRY and not overwrite:
            raise ValueError(f"backend {name!r} already registered "
                             f"(pass overwrite=True to replace)")
        _REGISTRY[name] = be
    return be


def unregister_backend(name: str) -> None:
    """Remove a backend (tests / plugin teardown). Unknown names are no-ops."""
    with _LOCK:
        _REGISTRY.pop(name, None)


def get_backend(name: str) -> Backend:
    _ensure_builtins()
    be = _REGISTRY.get(name)
    if be is None:
        raise KeyError(f"unknown FalconGEMM backend {name!r}; registered: "
                       f"{sorted(_REGISTRY)} (add one with register_backend)")
    return be


def available_backends() -> list[str]:
    _ensure_builtins()
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# Built-in backends. Registered lazily so importing the registry never drags
# in the kernel stack, and so user registrations can happen before jax init.
# ---------------------------------------------------------------------------

_builtins_ready = False


def _jnp_apply(a2, b, l, cfg):
    from . import codegen
    from .falcon_gemm import _pad2
    M, _ = a2.shape
    N = b.shape[1]
    gen = codegen.generate(l, codegen.CodegenOptions(fused=cfg.fused))
    c = gen.fn(_pad2(a2, l.m, l.k), _pad2(b, l.k, l.n))
    return c[:M, :N]


def _jnp_apply_precombined(a2, bt, l, n_logical, cfg):
    from .falcon_gemm import matmul_with_precombined
    return matmul_with_precombined(a2, bt, l, n_logical, cfg)


def _jnp_apply_grouped(a3, b, l, cfg):
    from .falcon_gemm import grouped_matmul_generated
    return grouped_matmul_generated(a3, b, l, cfg)


def _jnp_apply_grouped_precombined(a3, bt, l, n_logical, cfg):
    from .falcon_gemm import grouped_matmul_with_precombined
    return grouped_matmul_with_precombined(a3, bt, l, n_logical, cfg)


def _jnp_apply_quant(a2, bq, b_scales, l, n_logical, cfg):
    # the int8 pipeline exists only as Pallas kernels; they run compiled on
    # a TPU and interpreted on the CPU, so the jnp backend serves --quant too
    from repro.kernels import ops
    from .hardware import interpret_kernels
    return ops.falcon_matmul_pallas_quant(
        a2, bq, b_scales, l, n_logical, interpret=interpret_kernels())


def _pallas_apply_factory(interpret: bool):
    def apply(a2, b, l, cfg):
        from repro.kernels import ops
        return ops.falcon_matmul_pallas(a2, b, l, interpret=interpret)
    return apply


def _pallas_precombined_factory(interpret: bool):
    def apply_precombined(a2, bt, l, n_logical, cfg):
        from repro.kernels import ops
        return ops.falcon_matmul_pallas_precombined(
            a2, bt, l, n_logical, interpret=interpret)
    return apply_precombined


def _pallas_grouped_factory(interpret: bool):
    def apply_grouped(a3, b, l, cfg):
        from repro.kernels import ops
        return ops.falcon_grouped_matmul_pallas(a3, b, l, interpret=interpret)
    return apply_grouped


def _pallas_grouped_precombined_factory(interpret: bool):
    def apply_grouped_precombined(a3, bt, l, n_logical, cfg):
        from repro.kernels import ops
        return ops.falcon_grouped_matmul_pallas_precombined(
            a3, bt, l, n_logical, interpret=interpret)
    return apply_grouped_precombined


def _pallas_quant_factory(interpret: bool):
    def apply_quant(a2, bq, b_scales, l, n_logical, cfg):
        from repro.kernels import ops
        return ops.falcon_matmul_pallas_quant(
            a2, bq, b_scales, l, n_logical, interpret=interpret)
    return apply_quant


def _shardmap_dense_hook(x, w, cfg):
    from .falcon_gemm import _falcon_dense_shardmap
    return _falcon_dense_shardmap(x, w, cfg)


def _ensure_builtins() -> None:
    global _builtins_ready
    if _builtins_ready:
        return
    with _LOCK:
        if _builtins_ready:
            return
        defaults = {
            "jnp": Backend(
                "jnp", _jnp_apply,
                apply_precombined=_jnp_apply_precombined,
                apply_grouped=_jnp_apply_grouped,
                apply_grouped_precombined=_jnp_apply_grouped_precombined,
                apply_quant=_jnp_apply_quant,
                description="generated pure-JAX combines (GSPMD-shardable)"),
            "pallas": Backend(
                "pallas", _pallas_apply_factory(False),
                apply_precombined=_pallas_precombined_factory(False),
                apply_grouped=_pallas_grouped_factory(False),
                apply_grouped_precombined=_pallas_grouped_precombined_factory(False),
                apply_quant=_pallas_quant_factory(False),
                description="on-TPU Pallas kernel pipeline"),
            "pallas_interpret": Backend(
                "pallas_interpret", _pallas_apply_factory(True),
                apply_precombined=_pallas_precombined_factory(True),
                apply_grouped=_pallas_grouped_factory(True),
                apply_grouped_precombined=_pallas_grouped_precombined_factory(True),
                apply_quant=_pallas_quant_factory(True),
                description="Pallas pipeline in interpret mode (CPU CI)"),
            "shard_map_local": Backend(
                "shard_map_local", _jnp_apply,
                dense_hook=_shardmap_dense_hook,
                apply_precombined=_jnp_apply_precombined,
                apply_grouped=_jnp_apply_grouped,
                apply_grouped_precombined=_jnp_apply_grouped_precombined,
                description="LCMA on the per-device local matmul inside "
                            "shard_map (fsdp_only)"),
        }
        for name, be in defaults.items():
            _REGISTRY.setdefault(name, be)
        _builtins_ready = True
