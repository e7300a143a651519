"""Deployment Module: automated code generation for LCMAs (paper §III-A).

A meta-programming engine emits Python/JAX source for a given scheme
``L = <m,k,n,R,U,V,W>``.  The coefficient tensors are baked into the emitted
source as literal ``+``/``-`` terms, so:

  * zero coefficients are pruned at generation time (constant folding),
  * no runtime memory traffic is spent on coefficients (the paper stores them
    in the I-cache; here they live in the traced program),
  * XLA sees a fully unrolled combine, which it fuses into elementwise ops.

Two workflow variants are generated:

  * ``fused=True``  — Algorithm 2 (Group-Parallel): grouped combines, ONE
    batched GEMM over the rank dimension, Combine-H applied to the
    high-precision accumulator before any downcast (paper §IV-F).
  * ``fused=False`` — Algorithm 1 (staged, the H_r-parallel baseline): four
    separate stages, R fragmented GEMMs, H materialized (optionally downcast,
    reproducing the AlphaTensor-style precision loss).

The emitted source is kept on the returned object (``.source``) — it is the
deployment artifact, inspectable and diffable. The Pallas backend wires the
same coefficients into on-chip kernels (see ``repro.kernels``).
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from .lcma import LCMA

__all__ = ["CodegenOptions", "GeneratedLCMA", "generate"]


@dataclasses.dataclass(frozen=True)
class CodegenOptions:
    fused: bool = True
    accum_dtype: str = "float32"     # GEMM accumulation / H precision
    downcast_h: bool = False         # unfused: materialize H in input dtype
    precombined_b: bool = False      # offline Combine B for static weights
    gemm_backend: str = "batched"    # "batched" (Alg.2) | "loop" (Alg.1 fragmentation)

    def cache_key(self, name: str) -> tuple:
        return (name, self.fused, self.accum_dtype, self.downcast_h,
                self.precombined_b, self.gemm_backend)


@dataclasses.dataclass(frozen=True)
class GeneratedLCMA:
    """A deployed LCMA: generated source + compiled callables."""

    lcma: LCMA
    options: CodegenOptions
    source: str
    fn: Callable            # (A, B) -> C           [or (A, Bt) if precombined_b]
    combine_a: Callable     # (A,)  -> At (R, M/m, K/k)
    combine_b: Callable     # (B,)  -> Bt (R, K/k, N/n)
    stages: dict            # name -> callable, for the step-wise benchmark


# --------------------------------------------------------------------------
# Source emission helpers
# --------------------------------------------------------------------------

def _lin_comb(terms: list[tuple[int, str]]) -> str:
    """Emit ``+x - 2 * y + z`` from [(coeff, name), ...] for integer coeffs.

    Magnitudes other than 1 (AlphaTensor standard-arithmetic listings,
    Smirnov-family schemes) are emitted as literal scalings so constant
    folding still applies; dropping them silently computed wrong results.
    """
    if not terms:
        return "0.0"
    out = []
    for idx, (c, name) in enumerate(terms):
        term = name if abs(c) == 1 else f"{abs(c)} * {name}"
        if idx == 0:
            out.append(term if c > 0 else f"-{term}")
        else:
            out.append(f"+ {term}" if c > 0 else f"- {term}")
    return " ".join(out)


def _emit_combine(coeff: np.ndarray, part: str, out: str, d1: int, d2: int) -> list[str]:
    """Emit the group-combine of ``part_{i}_{l}`` into ``out_r`` for all r."""
    lines = []
    R = coeff.shape[0]
    for r in range(R):
        terms = [
            (int(coeff[r, i, l]), f"{part}_{i}_{l}")
            for i in range(d1) for l in range(d2)
            if coeff[r, i, l] != 0
        ]
        lines.append(f"{out}_{r} = {_lin_comb(terms)}")
    return lines


def _emit_slices(var: str, part: str, d1: int, d2: int, s1: str, s2: str) -> list[str]:
    lines = []
    for i in range(d1):
        for l in range(d2):
            lines.append(
                f"{part}_{i}_{l} = jax.lax.slice({var}, "
                f"({i} * {s1}, {l} * {s2}), (({i} + 1) * {s1}, ({l} + 1) * {s2}))"
            )
    return lines


def _emit_source(l: LCMA, o: CodegenOptions) -> str:
    m, k, n, R = l.m, l.k, l.n, l.R
    U, V, W = l.U, l.V, l.W
    body: list[str] = []
    e = body.append

    e("def combine_a(A):")
    e("    M, K = A.shape")
    e(f"    Ms, Ks = M // {m}, K // {k}")
    for ln in _emit_slices("A", "a", m, k, "Ms", "Ks"):
        e("    " + ln)
    e("    # Group Combine A (Eq. 3) -- coefficients are compile-time constants")
    for ln in _emit_combine(U, "a", "at", m, k):
        e("    " + ln)
    e("    return jnp.stack([" + ", ".join(f"at_{r}" for r in range(R)) + "])")
    e("")

    e("def combine_b(B):")
    e("    K, N = B.shape")
    e(f"    Ks, Ns = K // {k}, N // {n}")
    for ln in _emit_slices("B", "b", k, n, "Ks", "Ns"):
        e("    " + ln)
    e("    # Group Combine B (Eq. 4)")
    for ln in _emit_combine(V, "b", "bt", k, n):
        e("    " + ln)
    e("    return jnp.stack([" + ", ".join(f"bt_{r}" for r in range(R)) + "])")
    e("")

    # --- GEMM stage ---
    e("def gemm_stage(At, Bt):")
    if o.gemm_backend == "batched":
        e("    # single batched GEMM over the rank dimension (Eq. 5)")
        e("    H = jax.lax.dot_general(At, Bt, dimension_numbers=(((2,), (1,)), ((0,), (0,))),")
        e(f"                            preferred_element_type=jnp.{o.accum_dtype})")
    else:
        e("    # H_r-parallel baseline: R fragmented GEMMs (paper §II-B drawback 2)")
        e("    hs = []")
        e(f"    for r in range({R}):")
        e(f"        hs.append(jax.lax.dot_general(At[r], Bt[r], dimension_numbers=((( 1,), (0,)), ((), ())),")
        e(f"                                      preferred_element_type=jnp.{o.accum_dtype}))")
        e("    H = jnp.stack(hs)")
    if o.downcast_h:
        e("    H = H.astype(At.dtype)  # AlphaTensor-style downcast before materialization")
    e("    return H")
    e("")

    e("def combine_h(H, out_dtype):")
    e("    # Group Combine H (Eq. 6); fused path keeps H in accum dtype on-chip")
    rows = []
    for i in range(m):
        cols = []
        for j in range(n):
            terms = [(int(W[r, i, j]), f"H[{r}]") for r in range(R) if W[r, i, j] != 0]
            e(f"    c_{i}_{j} = ({_lin_comb(terms)}).astype(out_dtype)")
            cols.append(f"c_{i}_{j}")
        rows.append("jnp.concatenate([" + ", ".join(cols) + "], axis=1)")
    e("    return jnp.concatenate([" + ", ".join(rows) + "], axis=0)")
    e("")

    args = "A, Bt" if o.precombined_b else "A, B"
    e(f"def lcma_matmul({args}):")
    e('    """%s %s | fused=%s precombined_b=%s"""' % (l.name, l.key, o.fused, o.precombined_b))
    e("    out_dtype = A.dtype")
    e("    At = combine_a(A)")
    if not o.precombined_b:
        e("    Bt = combine_b(B)")
    e("    H = gemm_stage(At, Bt)")
    e("    return combine_h(H, out_dtype)")
    return "\n".join(body) + "\n"


# --------------------------------------------------------------------------
# Compilation
# --------------------------------------------------------------------------

@lru_cache(maxsize=512)
def _generate_cached(l_id: int, key: tuple) -> GeneratedLCMA:  # pragma: no cover
    raise RuntimeError("use generate()")


_CACHE: dict[tuple, GeneratedLCMA] = {}

_STAGE_SCOPES = {"combine_a": "falcon.combine_a",
                 "gemm_stage": "falcon.lcma_gemm",
                 "combine_h": "falcon.combine_h"}


def generate(l: LCMA, options: CodegenOptions | None = None) -> GeneratedLCMA:
    """Generate + compile the LCMA implementation for scheme ``l``."""
    o = options or CodegenOptions()
    key = o.cache_key(l.name)
    hit = _CACHE.get(key)
    if hit is not None:
        return hit
    src = _emit_source(l, o)
    ns: dict = {"jax": jax, "jnp": jnp}
    exec(compile(src, f"<lcma:{l.name}>", "exec"), ns)  # noqa: S102 - trusted, self-emitted
    # name the stages in the lowered program's op metadata, so a device
    # trace attributes each op to its stage; lcma_matmul looks them up here
    for stage, scope in _STAGE_SCOPES.items():
        ns[stage] = jax.named_scope(scope)(ns[stage])
    gen = GeneratedLCMA(
        lcma=l,
        options=o,
        source=src,
        fn=ns["lcma_matmul"],
        combine_a=ns["combine_a"],
        combine_b=ns["combine_b"],
        stages={
            "combine_a": ns["combine_a"],
            "combine_b": ns["combine_b"],
            "gemm": ns["gemm_stage"],
            "combine_h": ns["combine_h"],
        },
    )
    _CACHE[key] = gen
    return gen
