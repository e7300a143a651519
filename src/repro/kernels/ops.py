"""Jitted wrappers around the Pallas kernels (padding, reassembly, dispatch).

``falcon_matmul_pallas`` is the full on-TPU LCMA pipeline:
  Group Combine A  ->  Group Combine B  ->  fused GEMM + Group Combine H
with all padding/unpadding handled here so kernels see exact tiles: every
part of A and B is zero-padded up to whole TPU tiles (``tuning.part_dims``),
so every block the planner picks is tile-aligned.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.lcma import LCMA
# one padding definition shared with the generated-jnp pipeline — the two
# execution paths must pad identically or their outputs diverge at the edges
from repro.core.falcon_gemm import _pad2, _pad3
from . import tuning
from .fused_gemm import (batched_fused_gemm_combine_h, fused_gemm_combine_h,
                         tiled_matmul)
from .group_combine import batched_group_combine, group_combine
from .quant_combine import fused_gemm_combine_h_quant, group_combine_quant


def _pad_last2(x: jnp.ndarray, s1: int, s2: int) -> jnp.ndarray:
    p1, p2 = s1 - x.shape[-2], s2 - x.shape[-1]
    if not (p1 or p2):
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, p1), (0, p2)])


def _assemble(cp: jnp.ndarray, xs: int, zs: int, M: int,
              N: int) -> jnp.ndarray:
    """C parts (..., m, n, X, Z) -> (..., M, N): unpad each part, then tile."""
    cp = cp[..., :xs, :zs]
    *lead, m, n, X, Z = cp.shape
    nl = len(lead)
    perm = tuple(range(nl)) + (nl, nl + 2, nl + 1, nl + 3)
    c = cp.transpose(perm).reshape(*lead, m * X, n * Z)
    return c[..., :M, :N]


def _check_precombined(where: str, K: int, bt, l: LCMA) -> None:
    if -(-K // l.k) != bt.shape[-2]:
        raise ValueError(
            f"{where}: activation K={K} (grid k={l.k}) does not match "
            f"precombined B̃ {tuple(bt.shape)} for scheme {l.name} {l.key}")


@partial(jax.jit, static_argnames=("l", "block_combine", "block_gemm", "interpret"))
def falcon_matmul_pallas(a: jnp.ndarray, b: jnp.ndarray, l: LCMA,
                         block_combine: tuple[int, int] | None = None,
                         block_gemm: tuple[int, int, int] | None = None,
                         interpret: bool = False) -> jnp.ndarray:
    """LCMA matmul via the Pallas kernel pipeline. Handles arbitrary shapes."""
    M, K = a.shape
    K2, N = b.shape
    if K != K2:
        raise ValueError(f"falcon_matmul_pallas: contracting dims differ: "
                         f"{a.shape} @ {b.shape}")
    X, Y, Z = tuning.part_dims(l, M, K, N, a.dtype)
    ap = tuning.pad_parts(_pad2(a, l.m, l.k), (l.m, l.k), (X, Y))
    bp = tuning.pad_parts(_pad2(b, l.k, l.n), (l.k, l.n), (Y, Z))
    at = group_combine(ap, l.U, block=block_combine, interpret=interpret)
    bt = group_combine(bp, l.V, block=block_combine, interpret=interpret)
    cp = fused_gemm_combine_h(at, bt, l.W, block=block_gemm,
                              out_dtype=a.dtype, interpret=interpret)
    return _assemble(cp, -(-M // l.m), -(-N // l.n), M, N)


@partial(jax.jit, static_argnames=("l", "n_logical", "block_combine",
                                   "block_gemm", "interpret"))
def falcon_matmul_pallas_precombined(
        a: jnp.ndarray, bt: jnp.ndarray, l: LCMA, n_logical: int,
        block_combine: tuple[int, int] | None = None,
        block_gemm: tuple[int, int, int] | None = None,
        interpret: bool = False) -> jnp.ndarray:
    """Serving-path kernel pipeline against pre-combined B̃ (R, K/k, N/n).

    The offline Combine-B (paper §IV-C) variant of ``falcon_matmul_pallas``:
    Combine B never runs — only Group Combine A and the fused GEMM+Combine H.
    ``bt`` layout matches ``codegen``'s ``combine_b`` output (verified
    bitwise-identical to the kernel ``group_combine``), so weights combined
    offline by either path are interchangeable.
    """
    M, K = a.shape
    _check_precombined("falcon_matmul_pallas_precombined", K, bt, l)
    ys, zs = bt.shape[-2:]
    X, Y, Z = tuning.part_dims(l, M, ys * l.k, zs * l.n, a.dtype)
    ap = tuning.pad_parts(_pad2(a, l.m, l.k), (l.m, l.k), (X, Y))
    at = group_combine(ap, l.U, block=block_combine, interpret=interpret)
    cp = fused_gemm_combine_h(at, _pad_last2(bt, Y, Z), l.W, block=block_gemm,
                              out_dtype=a.dtype, interpret=interpret)
    return _assemble(cp, -(-M // l.m), zs, M, n_logical)


@partial(jax.jit, static_argnames=("l", "n_logical", "block_combine",
                                   "block_gemm", "interpret"))
def falcon_matmul_pallas_quant(
        a: jnp.ndarray, bq: jnp.ndarray, b_scales: jnp.ndarray, l: LCMA,
        n_logical: int, block_combine: tuple[int, int] | None = None,
        block_gemm: tuple[int, int, int] | None = None,
        interpret: bool = False) -> jnp.ndarray:
    """Quantized serving pipeline against offline-quantized B̃q + scales.

    The int8 variant of ``falcon_matmul_pallas_precombined``: Group Combine A
    runs fused with quantization (``group_combine_quant`` — one HBM pass over
    A, int8 Ã plus per-(row, K-block) f32 scales out), then the fused int8
    GEMM + dequantizing Combine H. ``bq``/``b_scales`` come from
    ``quantize_b_blockwise`` (the PlannedWeight quant buffers); the A-side
    scale block is forced to B's so the two block-scale grids line up. The
    rows and columns of each part are padded to int8 tiles here; the
    combined K is already a whole number of scale blocks.
    """
    M, K = a.shape
    _check_precombined("falcon_matmul_pallas_quant", K, bq, l)
    ys, zs = bq.shape[1:]
    by = ys // b_scales.shape[1]
    X = tuning.round_up(-(-M // l.m), tuning.sublane(jnp.int8))
    Z = tuning.round_up(zs, tuning.LANE)
    ap = tuning.pad_parts(_pad2(a, l.m, l.k), (l.m, l.k), (X, ys))
    bcx = block_combine[0] if block_combine else \
        tuning.snap_block(X, tuning.sublane(jnp.int8))
    at, a_scales = group_combine_quant(ap, l.U, block=(bcx, by),
                                       interpret=interpret)
    if block_gemm is not None:
        bx, bz = block_gemm[0], block_gemm[1]
    else:
        bx = tuning.snap_block(X, tuning.sublane(jnp.int8))
        bz = tuning.snap_block(Z, tuning.LANE)
    cp = fused_gemm_combine_h_quant(at, a_scales, _pad_last2(bq, ys, Z),
                                    _pad_last2(b_scales, b_scales.shape[1], Z),
                                    l.W, block=(bx, bz, by), out_dtype=a.dtype,
                                    interpret=interpret)
    return _assemble(cp, -(-M // l.m), zs, M, n_logical)


@partial(jax.jit, static_argnames=("l", "block_combine", "block_gemm", "interpret"))
def falcon_grouped_matmul_pallas(a3: jnp.ndarray, b: jnp.ndarray, l: LCMA,
                                 block_combine: tuple[int, int] | None = None,
                                 block_gemm: tuple[int, int, int] | None = None,
                                 interpret: bool = False) -> jnp.ndarray:
    """Grouped LCMA matmul: a3 (G, M, K) x b [(K, N) | (G, K, N)] -> (G, M, N).

    The Group-Parallel batched pipeline: per-group Combine A (one batched
    kernel launch), Combine B run ONCE when ``b`` is shared across the group
    (2-D) or per group otherwise, then one grouped fused GEMM+Combine-H over
    all G*R intermediate products. Handles arbitrary shapes via padding.
    """
    G, M, K = a3.shape
    shared = b.ndim == 2
    Kb, N = (b.shape if shared else b.shape[1:])
    if K != Kb:
        raise ValueError(f"falcon_grouped_matmul_pallas: contracting dims "
                         f"differ: {a3.shape} @ {b.shape}")
    if not shared and b.shape[0] != G:
        raise ValueError(f"falcon_grouped_matmul_pallas: group sizes differ: "
                         f"{a3.shape} @ {b.shape}")
    X, Y, Z = tuning.part_dims(l, M, K, N, a3.dtype)
    ap = tuning.pad_parts(_pad3(a3, l.m, l.k), (l.m, l.k), (X, Y))
    at = batched_group_combine(ap, l.U, block=block_combine,
                               interpret=interpret)
    if shared:
        bp = tuning.pad_parts(_pad2(b, l.k, l.n), (l.k, l.n), (Y, Z))
        bt = group_combine(bp, l.V, block=block_combine, interpret=interpret)
    else:
        bp = tuning.pad_parts(_pad3(b, l.k, l.n), (l.k, l.n), (Y, Z))
        bt = batched_group_combine(bp, l.V, block=block_combine,
                                   interpret=interpret)
    cp = batched_fused_gemm_combine_h(at, bt, l.W, block=block_gemm,
                                      out_dtype=a3.dtype, interpret=interpret)
    return _assemble(cp, -(-M // l.m), -(-N // l.n), M, N)


@partial(jax.jit, static_argnames=("l", "n_logical", "block_combine",
                                   "block_gemm", "interpret"))
def falcon_grouped_matmul_pallas_precombined(
        a3: jnp.ndarray, bt: jnp.ndarray, l: LCMA, n_logical: int,
        block_combine: tuple[int, int] | None = None,
        block_gemm: tuple[int, int, int] | None = None,
        interpret: bool = False) -> jnp.ndarray:
    """Grouped serving pipeline against precombined B̃.

    ``bt`` is (R, K/k, N/n) — one weight shared by the group (a PlannedWeight
    under a batched activation) — or (G, R, K/k, N/n) for stacked per-group
    weights (MoE experts precombined offline). Combine B never runs.
    """
    G, M, K = a3.shape
    _check_precombined("falcon_grouped_matmul_pallas_precombined", K, bt, l)
    if bt.ndim == 4 and bt.shape[0] != G:
        raise ValueError(
            f"falcon_grouped_matmul_pallas_precombined: group sizes differ: "
            f"{a3.shape} vs B̃ {tuple(bt.shape)}")
    ys, zs = bt.shape[-2:]
    X, Y, Z = tuning.part_dims(l, M, ys * l.k, zs * l.n, a3.dtype)
    ap = tuning.pad_parts(_pad3(a3, l.m, l.k), (l.m, l.k), (X, Y))
    at = batched_group_combine(ap, l.U, block=block_combine,
                               interpret=interpret)
    cp = batched_fused_gemm_combine_h(at, _pad_last2(bt, Y, Z), l.W,
                                      block=block_gemm, out_dtype=a3.dtype,
                                      interpret=interpret)
    return _assemble(cp, -(-M // l.m), zs, M, n_logical)


@partial(jax.jit, static_argnames=("block", "interpret"))
def matmul_pallas(a: jnp.ndarray, b: jnp.ndarray,
                  block: tuple[int, int, int] | None = None,
                  interpret: bool = False) -> jnp.ndarray:
    """Standard tiled-matmul kernel with padding."""
    M, K = a.shape
    _, N = b.shape
    ap = _pad2(a, tuning.sublane(a.dtype), tuning.LANE)
    bp = _pad2(b, tuning.LANE, tuning.LANE)
    if ap.shape[1] != bp.shape[0]:
        kp = max(ap.shape[1], bp.shape[0])
        ap = jnp.pad(ap, ((0, 0), (0, kp - ap.shape[1])))
        bp = jnp.pad(bp, ((0, kp - bp.shape[0]), (0, 0)))
    c = tiled_matmul(ap, bp, block=block, interpret=interpret)
    return c[:M, :N]
