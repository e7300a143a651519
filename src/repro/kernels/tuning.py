"""On-chip Resource Planning (paper §III-A micro-optimization 1).

Evaluates the VMEM footprint a kernel configuration will claim and shrinks
block shapes until the plan fits the hardware budget, keeping MXU dimensions
aligned to the systolic array (multiples of 128 where the problem allows).
High-rank schemes (e.g. <4,4,4>;49) hit the budget first through the
``(R, bx, bz)`` float32 accumulator — exactly the failure AlphaTensor's large-R
kernels hit on GPU shared memory (paper §IV-C); the planner degrades block
sizes instead of falling back to Strassen.
"""
from __future__ import annotations

import jax.numpy as jnp

# Conservative per-core VMEM budget (bytes) for kernel working sets; the
# Pallas pipeline double-buffers in/out blocks, which the estimates include.
VMEM_BUDGET = 12 << 20
MXU = 128
LANE = 128


def sublane(dtype) -> int:
    """Rows of one native TPU tile of ``dtype``: 8 (32-bit), 16 (bf16), 32 (int8)."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def round_up(dim: int, align: int) -> int:
    return -(-dim // align) * align


def part_dims(l, M: int, K: int, N: int, dtype) -> tuple[int, int, int]:
    """Part sizes ``(X, Y, Z)`` of the padded problem the Pallas kernels run.

    A splits into m x k parts of X x Y, B into k x n parts of Y x Z. Each part
    is padded up to whole TPU tiles -- X to the dtype's sublane rows, Y and Z
    to 128 lanes (Y is a lane dim of Ã and a row dim of B̃) -- because Mosaic
    takes a block only if its last two dims are multiples of (sublane, 128)
    or span the whole array, and a part never spans the whole array.
    """
    return (round_up(-(-M // l.m), sublane(dtype)),
            round_up(-(-K // l.k), LANE), round_up(-(-N // l.n), LANE))


def pad_parts(x: jnp.ndarray, grid: tuple[int, int],
               parts: tuple[int, int]) -> jnp.ndarray:
    """Zero-pad each part of ``x`` at its end, keeping the part in place.

    ``x`` is (..., d1*a, d2*b), a d1 x d2 grid of (a, b) parts; the result
    is (..., d1*s1, d2*s2). A part padded this way combines to the part
    padded the same way, so an offline B̃ needs only its last two dims
    padded to match.
    """
    (d1, d2), (s1, s2) = grid, parts
    *lead, r, c = x.shape
    a, b = r // d1, c // d2
    if (a, b) == (s1, s2):
        return x
    x = x.reshape(*lead, d1, a, d2, b)
    pad = [(0, 0)] * len(lead) + [(0, 0), (0, s1 - a), (0, 0), (0, s2 - b)]
    return jnp.pad(x, pad).reshape(*lead, d1 * s1, d2 * s2)


def snap_block(dim: int, align: int, cap: int = 128) -> int:
    """Largest multiple of ``align`` that divides ``dim`` and is <= ``cap``."""
    return max(c for c in range(align, max(min(dim, cap), align) + 1, align)
               if dim % c == 0)


def _align_candidates(dim: int, align: int = MXU) -> list[int]:
    """Block sizes for a dimension: multiples of ``align`` dividing it, <= 512.

    Falls back to every divisor only for a dimension that is not a multiple
    of ``align`` (never one ``part_dims`` produced)."""
    cands = [c for c in range(min(dim, 512) // align * align, 0, -align)
             if dim % c == 0]
    return cands or _all_divisors(dim)


def _all_divisors(dim: int) -> list[int]:
    """Every block size that tiles ``dim`` exactly, largest first (<= 512)."""
    return [d for d in range(min(dim, 512), 0, -1) if dim % d == 0]


def combine_vmem(bx: int, by: int, R: int, nparts: int, itemsize: int) -> int:
    # double-buffered: nparts input blocks + R output blocks
    return 2 * (nparts + R) * bx * by * itemsize


def plan_combine_blocks(X: int, Y: int, R: int, nparts: int, dtype,
                        budget: int = VMEM_BUDGET) -> tuple[int, int]:
    """Pick (bx, by) for a combine over parts of X x Y: rows, then lanes."""
    it = jnp.dtype(dtype).itemsize
    best = None
    for bx in _align_candidates(X, sublane(dtype)):
        for by in _align_candidates(Y, LANE):
            if combine_vmem(bx, by, R, nparts, it) <= budget:
                cand = (bx, by)
                if best is None or bx * by > best[0] * best[1]:
                    best = cand
    if best is None:
        # No MXU-preferred tile fits (high-R schemes, tight budgets): degrade
        # through the full divisor lattice for the largest fitting pair.
        for bx in _all_divisors(X):
            for by in _all_divisors(Y):
                if combine_vmem(bx, by, R, nparts, it) <= budget and \
                        (best is None or bx * by > best[0] * best[1]):
                    best = (bx, by)
    if best is None:
        best = (_all_divisors(X)[-1], _all_divisors(Y)[-1])
    return best


def block_plans(l, M: int, K: int, N: int, dtype="float32",
                budget: int = VMEM_BUDGET, hw=None) -> dict:
    """Full block-plan summary for one LCMA application on a padded problem.

    The export surface for the autotuner (``core.autotune``) and the tune CLI:
    everything the Pallas pipeline would pick for this shape, as plain data
    that can be embedded in a calibrated-profile JSON and inspected offline.

    ``hw`` (a ``HardwareProfile``) clamps the budget to the profile's
    per-core VMEM when that is tighter than ``budget`` — so plans exported
    for a specific part never claim more on-chip memory than it has, and
    falcon-check's plan lint can flag a default-budget plan against a
    smaller device.
    """
    if hw is not None:
        hw_vmem = getattr(hw, "vmem_bytes", None)
        if hw_vmem:
            budget = min(budget, int(hw_vmem))
    it = jnp.dtype(dtype).itemsize
    X, Ks, Z = part_dims(l, M, K, N, dtype)
    Mp, Kp, Np = X * l.m, Ks * l.k, Z * l.n
    ca = plan_combine_blocks(X, Ks, l.R, l.m * l.k, dtype, budget)
    cb = plan_combine_blocks(Ks, Z, l.R, l.k * l.n, dtype, budget)
    fg = plan_fused_gemm_blocks(X, Z, Ks, l.R, l.m, l.n, dtype, budget)
    return {
        "grid": [l.m, l.k, l.n], "R": l.R,
        "padded_shape": [Mp, Kp, Np],
        "combine_a": list(ca), "combine_b": list(cb),
        "fused_gemm": list(fg),
        "combine_a_vmem_bytes": combine_vmem(*ca, l.R, l.m * l.k, it),
        "combine_b_vmem_bytes": combine_vmem(*cb, l.R, l.k * l.n, it),
        "fused_gemm_vmem_bytes": fused_gemm_vmem(*fg, l.R, l.m, l.n, it),
        "vmem_budget_bytes": budget,
    }


def fused_gemm_vmem(bx: int, bz: int, by: int, R: int, m: int, n: int,
                    itemsize: int, acc_itemsize: int = 4) -> int:
    io = 2 * R * (bx * by + by * bz) * itemsize   # double-buffered At/Bt blocks
    acc = R * bx * bz * acc_itemsize              # persistent accumulator
    out = 2 * m * n * bx * bz * itemsize          # double-buffered C parts
    return io + acc + out


def plan_fused_gemm_blocks(X: int, Z: int, Y: int, R: int, m: int, n: int, dtype,
                           budget: int = VMEM_BUDGET) -> tuple[int, int, int]:
    """Pick (bx, bz, by) fitting the budget, preferring large MXU-aligned tiles."""
    it = jnp.dtype(dtype).itemsize
    best, best_score = None, -1.0
    for bx in _align_candidates(X, sublane(dtype)):
        for bz in _align_candidates(Z, LANE):
            for by in _align_candidates(Y, LANE):
                if fused_gemm_vmem(bx, bz, by, R, m, n, it) > budget:
                    continue
                # score: MXU utilization proxy — prefer 128-multiples and
                # larger K-blocks (fewer accumulator passes).
                score = bx * bz * min(by, 512)
                if bx % MXU == 0 and bz % MXU == 0:
                    score *= 4
                if score > best_score:
                    best, best_score = (bx, bz, by), score
    if best is None:
        # No MXU-preferred tile fits (the (R, bx, bz) accumulator of a
        # high-R scheme claims the budget first): degrade through the full
        # divisor lattice instead of emitting an over-budget plan.
        for bx in _all_divisors(X):
            for bz in _all_divisors(Z):
                for by in _all_divisors(Y):
                    if fused_gemm_vmem(bx, bz, by, R, m, n, it) > budget:
                        continue
                    score = bx * bz * min(by, 512)
                    if score > best_score:
                        best, best_score = (bx, bz, by), score
    if best is None:
        best = (_all_divisors(X)[-1], _all_divisors(Z)[-1], _all_divisors(Y)[-1])
    return best
