"""Pallas TPU kernel: fused GEMM + Group Combine H (paper Alg. 2, stages 3-4).

One program instance owns the group ``{H_r[x,z]}_{r=1..R}`` at output tile
coordinate ``(x, z)``: the R accumulators live in a persistent VMEM scratch
``(R, bx, bz) float32`` across the K-reduction grid dimension, and on the last
reduction step the W-combination produces all m*n output tiles
``{C_ij[x,z]}`` on-chip.  Consequences (paper §III-B):

  * H_r is NEVER materialized to HBM — the ``R/mn`` bandwidth term of Eq. 9
    disappears (Eq. 10),
  * there are no write conflicts: each C tile has exactly one producer,
  * C is combined from float32 H on-chip => the §IV-F precision win.

TPU adaptation of Split-Group/Cache-Aware scheduling: the Pallas grid is
executed sequentially per core with pipelined HBM->VMEM copies, so GPU-style
SM load imbalance and L2 thrashing across concurrent CTAs have no analogue;
the corresponding knobs here are the grid iteration order (reduction dimension
innermost, ``dimension_semantics=("parallel","parallel","arbitrary")``) and
the block planner in ``tuning.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _fused_kernel(at_ref, bt_ref, out_ref, acc_ref, *, w, grid_y):
    R, m, n = w.shape
    y = pl.program_id(2)

    @pl.when(y == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Accumulate the whole group on-chip: H_r[x,z] += At_r[x,y] @ Bt_r[y,z].
    # The r-loop is unrolled at trace time (one MXU issue per rank).
    for r in range(R):
        acc_ref[r, :, :] += jnp.dot(
            at_ref[r], bt_ref[r], preferred_element_type=jnp.float32
        )

    @pl.when(y == grid_y - 1)
    def _combine_h():
        # Group Combine H from float32 accumulators; coefficients unrolled.
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


def fused_gemm_combine_h(at: jnp.ndarray, bt: jnp.ndarray, w: np.ndarray,
                         *, block: tuple[int, int, int] | None = None,
                         out_dtype=None, interpret: bool = False) -> jnp.ndarray:
    """(R, X, Y) x (R, Y, Z) --W--> C parts (m, n, X, Z); H stays in VMEM."""
    from .tuning import plan_fused_gemm_blocks

    R, m, n = w.shape
    R2, X, Y = at.shape
    R3, Y2, Z = bt.shape
    assert R == R2 == R3 and Y == Y2, (at.shape, bt.shape, w.shape)
    out_dtype = out_dtype or at.dtype
    bx, bz, by = block or plan_fused_gemm_blocks(X, Z, Y, R, m, n, at.dtype)
    assert X % bx == 0 and Z % bz == 0 and Y % by == 0, ((X, Z, Y), (bx, bz, by))
    grid = (X // bx, Z // bz, Y // by)

    kernel = functools.partial(_fused_kernel, w=w, grid_y=grid[2])
    fn = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((R, bx, by), lambda x, z, y: (0, x, y)),
            pl.BlockSpec((R, by, bz), lambda x, z, y: (0, y, z)),
        ],
        out_specs=pl.BlockSpec((m, n, bx, bz), lambda x, z, y: (0, 0, x, z)),
        out_shape=jax.ShapeDtypeStruct((m, n, X, Z), out_dtype),
        scratch_shapes=[pltpu.VMEM((R, bx, bz), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )
    return fn(at, bt)


def _batched_fused_kernel(at_ref, bt_ref, out_ref, acc_ref, *, w, grid_y,
                          bt_batched):
    """Grouped Alg. 2: leading parallel group axis; reduction is grid dim 3.

    ``bt_batched=False`` is the hoisted shared-B case: the bt block carries
    no group axis (its index map ignores ``g``), so one combined B̃ feeds
    every group element — the Combine-B work was done once for the group.
    """
    R, m, n = w.shape
    y = pl.program_id(3)

    @pl.when(y == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for r in range(R):
        bt_r = bt_ref[0, r] if bt_batched else bt_ref[r]
        acc_ref[r, :, :] += jnp.dot(
            at_ref[0, r], bt_r, preferred_element_type=jnp.float32
        )

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
                out_ref[0, i, j, :, :] = acc.astype(out_ref.dtype)


def batched_fused_gemm_combine_h(at: jnp.ndarray, bt: jnp.ndarray,
                                 w: np.ndarray, *,
                                 block: tuple[int, int, int] | None = None,
                                 out_dtype=None,
                                 interpret: bool = False) -> jnp.ndarray:
    """Grouped fused GEMM + Combine H: (G, R, X, Y) x bt --W--> (G, m, n, X, Z).

    ``bt`` is either (G, R, Y, Z) — per-group combined B (MoE experts,
    batched attention operands) — or (R, Y, Z), the *hoisted* shared-B form:
    the same B̃ group is contracted against every at[g] without ever being
    recombined or replicated in HBM. Either way the whole group's R
    accumulators live in one persistent VMEM scratch per (g, x, z) tile and
    H never reaches HBM.
    """
    from .tuning import plan_fused_gemm_blocks

    R, m, n = w.shape
    G, R2, X, Y = at.shape
    bt_batched = bt.ndim == 4
    if bt_batched:
        G3, R3, Y2, Z = bt.shape
        assert G3 == G, (at.shape, bt.shape)
    else:
        R3, Y2, Z = bt.shape
    assert R == R2 == R3 and Y == Y2, (at.shape, bt.shape, w.shape)
    out_dtype = out_dtype or at.dtype
    bx, bz, by = block or plan_fused_gemm_blocks(X, Z, Y, R, m, n, at.dtype)
    assert X % bx == 0 and Z % bz == 0 and Y % by == 0, ((X, Z, Y), (bx, bz, by))
    grid = (G, X // bx, Z // bz, Y // by)

    if bt_batched:
        bt_spec = pl.BlockSpec((1, R, by, bz), lambda g, x, z, y: (g, 0, y, z))
    else:
        bt_spec = pl.BlockSpec((R, by, bz), lambda g, x, z, y: (0, y, z))

    kernel = functools.partial(_batched_fused_kernel, w=w, grid_y=grid[3],
                               bt_batched=bt_batched)
    fn = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, R, bx, by), lambda g, x, z, y: (g, 0, x, y)),
            bt_spec,
        ],
        out_specs=pl.BlockSpec((1, m, n, bx, bz),
                               lambda g, x, z, y: (g, 0, 0, x, z)),
        out_shape=jax.ShapeDtypeStruct((G, m, n, X, Z), out_dtype),
        scratch_shapes=[pltpu.VMEM((R, bx, bz), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )
    return fn(at, bt)


def _matmul_kernel(a_ref, b_ref, out_ref, acc_ref, *, grid_y):
    y = pl.program_id(2)

    @pl.when(y == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...], preferred_element_type=jnp.float32)

    @pl.when(y == grid_y - 1)
    def _store():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def tiled_matmul(a: jnp.ndarray, b: jnp.ndarray, *, block: tuple[int, int, int] | None = None,
                 out_dtype=None, interpret: bool = False) -> jnp.ndarray:
    """Standard tiled MXU matmul — the non-LCMA baseline kernel."""
    from .tuning import plan_fused_gemm_blocks

    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    out_dtype = out_dtype or a.dtype
    bx, bz, by = block or plan_fused_gemm_blocks(M, N, K, 1, 1, 1, a.dtype)
    assert M % bx == 0 and N % bz == 0 and K % by == 0
    grid = (M // bx, N // bz, K // by)
    kernel = functools.partial(_matmul_kernel, grid_y=grid[2])
    fn = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bx, by), lambda x, z, y: (x, y)),
            pl.BlockSpec((by, bz), lambda x, z, y: (y, z)),
        ],
        out_specs=pl.BlockSpec((bx, bz), lambda x, z, y: (x, z)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bx, bz), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )
    return fn(a, b)
