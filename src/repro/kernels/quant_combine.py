"""Quantization fused into Group Combine (paper §IV-C, TPU int8 adaptation).

The paper fuses FP8 (1x128 block-scaled) quantization into the Combine-A
stage so low-precision serving pays no extra quantization pass. On TPU the
low-precision MXU path is int8, so:

  * ``group_combine_quant`` — one Pallas program per (x, y) tile computes the
    whole R-group combine in f32 VMEM and emits int8 values + per-(row,
    K-block) f32 scales, all in a single HBM pass over A,
  * ``fused_gemm_combine_h_quant`` — the fused GEMM accumulates int8xint8
    MXU products per K-block, applies the a/b block scales while the partial
    product is still in VMEM, and runs Group Combine H on the f32
    accumulators exactly like the bf16 kernel.

Block-scale granularity is (1 row) x (by K-block) — the TPU-aligned analogue
of the paper's 1x128 scheme (by defaults to 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Inside the kernels the block scales are laid out with the scaled dim last
# but one beside a unit dim -- A's as (R, Yb, X, 1), B's as (R, Yb, 1, Z) --
# so each block's last two dims are (rows, 1) / (1, cols): whole-array dims
# or tile multiples, as Mosaic requires. The public layouts stay (R, X, Yb)
# and (R, Yb, Z).


def _quant_combine_kernel(*refs, coeff, nin):
    in_refs = refs[:nin]
    q_ref, s_ref = refs[nin], refs[nin + 1]
    R, d1, d2 = coeff.shape[0], coeff.shape[1], coeff.shape[2]
    for r in range(R):
        acc = None
        for i in range(d1):
            for l in range(d2):
                c = int(coeff[r, i, l])
                if c == 0:
                    continue
                t = in_refs[i * d2 + l][...].astype(jnp.float32)
                t = t if c == 1 else (-t if c == -1 else t * c)
                acc = t if acc is None else acc + t
        if acc is None:
            acc = jnp.zeros(q_ref.shape[1:], jnp.float32)
        # per-row scale over this K-block (the (1, by) block-scaling)
        s = jnp.max(jnp.abs(acc), axis=1, keepdims=True) / 127.0
        s = jnp.maximum(s, 1e-12)
        q = jnp.clip(jnp.round(acc / s), -127, 127).astype(jnp.int8)
        q_ref[r, :, :] = q
        s_ref[r, 0, :, :] = s


def group_combine_quant(x: jnp.ndarray, coeff: np.ndarray, *,
                        block: tuple[int, int] = (128, 128),
                        interpret: bool = False):
    """x: (d1*X, d2*Y) -> (q int8 (R, X, Y), scales f32 (R, X, Y/by)).

    ``block`` (bx, by) must tile the part (X, Y); ``by`` is the K-block the
    scales cover. Padding is the caller's (`repro.kernels.ops`).
    """
    R, d1, d2 = coeff.shape
    M, K = x.shape
    assert M % d1 == 0 and K % d2 == 0
    X, Y = M // d1, K // d2
    bx, by = min(block[0], X), min(block[1], Y)
    if X % bx or Y % by:
        raise ValueError(f"group_combine_quant: block {(bx, by)} does not "
                         f"tile the part {(X, Y)}")
    grid = (X // bx, Y // by)
    in_specs = []
    for i in range(d1):
        for l in range(d2):
            in_specs.append(pl.BlockSpec(
                (bx, by),
                functools.partial(
                    lambda gx, gy, i=i, l=l: (i * (X // bx) + gx, l * (Y // by) + gy))))
    out_specs = [
        pl.BlockSpec((R, bx, by), lambda gx, gy: (0, gx, gy)),
        pl.BlockSpec((R, 1, bx, 1), lambda gx, gy: (0, gy, gx, 0)),
    ]
    kernel = functools.partial(_quant_combine_kernel, coeff=coeff, nin=d1 * d2)
    fn = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct((R, X, Y), jnp.int8),
                   jax.ShapeDtypeStruct((R, Y // by, X, 1), jnp.float32)],
        interpret=interpret)
    q, s = fn(*([x] * (d1 * d2)))
    return q, s[..., 0].transpose(0, 2, 1)


def _fused_quant_kernel(aq_ref, as_ref, bq_ref, bs_ref, out_ref, acc_ref, *,
                        w, grid_y):
    R, m, n = w.shape
    y = pl.program_id(2)

    @pl.when(y == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for r in range(R):
        # int8 x int8 -> int32 on the MXU; dequantize the K-block partial
        # product with the (row x block) and (block x col) scales in VMEM
        part = jax.lax.dot_general(
            aq_ref[r], bq_ref[r], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32).astype(jnp.float32)
        acc_ref[r, :, :] += part * as_ref[r, 0] * bs_ref[r, 0]

    @pl.when(y == grid_y - 1)
    def _combine_h():
        for i in range(m):
            for j in range(n):
                acc = None
                for r in range(R):
                    c = int(w[r, i, j])
                    if c == 0:
                        continue
                    t = acc_ref[r, :, :]
                    t = t if c == 1 else (-t if c == -1 else t * c)
                    acc = t if acc is None else acc + t
                if acc is None:
                    acc = jnp.zeros_like(acc_ref[0])
                out_ref[i, j, :, :] = acc.astype(out_ref.dtype)


def fused_gemm_combine_h_quant(aq, a_scales, bq, b_scales, w: np.ndarray, *,
                               block: tuple[int, int, int] | None = None,
                               out_dtype=jnp.float32, interpret: bool = False):
    """int8 fused LCMA GEMM + Combine H with (1 x K-block) scaling.

    aq: (R, X, Y) int8; a_scales: (R, X, Yb); bq: (R, Y, Z) int8;
    b_scales: (R, Yb, Z). The K-block size is Y // Yb and must equal the
    kernel's reduction block ``by``.
    """
    R, m, n = w.shape
    _, X, Y = aq.shape
    _, _, Z = bq.shape
    Yb = a_scales.shape[2]
    by = Y // Yb
    # Static overflow guard (falcon-check's stability pass): the kernel sums
    # `by` int8*int8 products into an int32 lane before dequantizing, so the
    # K-block depth must keep the worst-case |sum| = by * 127^2 inside int32.
    from repro.analysis.stability import max_safe_accum_depth
    if by > max_safe_accum_depth(32):
        raise ValueError(
            f"fused_gemm_combine_h_quant: K-block depth {by} overflows the "
            f"int32 accumulator (worst |sum| = {by} * 127^2); max safe depth "
            f"is {max_safe_accum_depth(32)} — use a smaller scale block")
    bx, bz = (block[0], block[1]) if block else (min(128, X), min(128, Z))
    assert X % bx == 0 and Z % bz == 0 and Y % by == 0
    grid = (X // bx, Z // bz, Yb)
    kernel = functools.partial(_fused_quant_kernel, w=w, grid_y=Yb)
    fn = pl.pallas_call(
        kernel, grid=grid,
        in_specs=[
            pl.BlockSpec((R, bx, by), lambda x, z, y: (0, x, y)),
            pl.BlockSpec((R, 1, bx, 1), lambda x, z, y: (0, y, x, 0)),
            pl.BlockSpec((R, by, bz), lambda x, z, y: (0, y, z)),
            pl.BlockSpec((R, 1, 1, bz), lambda x, z, y: (0, y, 0, z)),
        ],
        out_specs=pl.BlockSpec((m, n, bx, bz), lambda x, z, y: (0, 0, x, z)),
        out_shape=jax.ShapeDtypeStruct((m, n, X, Z), out_dtype),
        scratch_shapes=[pltpu.VMEM((R, bx, bz), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret)
    a_s = jnp.transpose(a_scales, (0, 2, 1))[..., None]      # (R, Yb, X, 1)
    b_s = b_scales[:, :, None, :]                             # (R, Yb, 1, Z)
    return fn(aq, a_s, bq, b_s)


def quantize_b_blockwise(b: jnp.ndarray, coeff: np.ndarray, by: int = 128,
                         interpret: bool = False):
    """Offline Combine-B + quantization for static weights (serving path).

    Returns (bq int8 (R, Y, Z), b_scales (R, Yb, Z)) with per-(K-block, col)
    scales, matching ``fused_gemm_combine_h_quant``. ``b`` is (k*Y, n*Z);
    each part is padded to whole tiles for the combine and cut back after.
    """
    from . import tuning
    from .group_combine import group_combine
    R, k, n = coeff.shape
    Y, Z = b.shape[0] // k, b.shape[1] // n
    parts = (tuning.round_up(Y, tuning.sublane(b.dtype)),
             tuning.round_up(Z, tuning.LANE))
    bp = tuning.pad_parts(b, (k, n), parts)
    bt = group_combine(bp, coeff, interpret=interpret)[:, :Y, :Z]
    bt = bt.astype(jnp.float32)
    assert Y % by == 0
    btb = bt.reshape(R, Y // by, by, Z)
    s = jnp.maximum(jnp.max(jnp.abs(btb), axis=2) / 127.0, 1e-12)  # (R, Yb, Z)
    q = jnp.clip(jnp.round(btb / s[:, :, None, :]), -127, 127).astype(jnp.int8)
    return q.reshape(R, Y, Z), s
