"""Empirical autotuning: calibrate the Decision Module against measured reality.

The paper's Decision Module (§III-C) prices candidates with an *analytical*
roofline ``(FLOPS_x, FLOPS_+, beta)`` model. Real machines miss those peaks by
workload-dependent factors (XLA-CPU reaches ~35% of stream bandwidth through a
combine's slice+add+stack pattern; batched small GEMMs run below one big GEMM).
This module measures the factors the model actually uses, on a small grid of
probe shapes, and emits a calibrated :class:`HardwareProfile` that
``decision.decide`` consumes in place of the static tables in ``hardware.py``:

  * ``flops_mul``  — effective matmul throughput, from timing the backend's
    GEMM (``jnp.dot``, or the Pallas ``matmul_pallas`` kernel);
  * ``beta``       — effective HBM/memory bandwidth, from timing a real Group
    Combine A (the memory-bound LCMA stage), not a synthetic stream;
  * ``flops_add``  — elementwise throughput at that effective bandwidth;
  * ``lcma_gemm_efficiency`` — the R-batched LCMA GEMM stage relative to one
    big GEMM (through ``dot_general`` or the fused Pallas kernel).

Each probe is timed best-of-``reps`` after warmup; fits take the median across
probe shapes so one noisy probe cannot skew the profile. The measurement
clock is injectable (``timer=``) so tests can calibrate deterministically.

``python -m repro.tools.tune`` is the CLI wrapper that writes the profile JSON
(plus per-scheme Pallas block plans from ``kernels.tuning``) and warms the
persistent plan cache.
"""
from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Callable, Sequence

from . import algorithms, codegen
from . import decision as dec
from .hardware import (HardwareProfile, get_profile, interpret_kernels,
                       register_profile, save_profile)
from .lcma import LCMA

__all__ = ["ProbeMeasurement", "CalibrationReport", "autotune", "calibrate",
           "default_probe_shapes", "best_of_timer"]

# Probe grids per backend: big enough to exercise the pipelines, small enough
# to finish in seconds. Interpret-mode Pallas executes Python per grid step,
# so its probes stay tiny.
_PROBE_SHAPES = {
    "jnp": [(256, 256, 256), (256, 512, 384), (512, 512, 512)],
    "pallas": [(256, 256, 256), (256, 512, 384), (512, 512, 512)],
    "pallas_interpret": [(32, 32, 32), (64, 32, 64)],
}


def default_probe_shapes(backend: str) -> list[tuple[int, int, int]]:
    return list(_PROBE_SHAPES.get(backend, _PROBE_SHAPES["jnp"]))


def best_of_timer(reps: int = 3, warmup: int = 1) -> Callable:
    """Wall-clock best-of timer for jitted JAX callables (the default)."""
    import jax

    def timer(fn, *args) -> float:
        for _ in range(warmup):
            jax.block_until_ready(fn(*args))
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append(time.perf_counter() - t0)
        return min(ts)

    return timer


@dataclasses.dataclass(frozen=True)
class ProbeMeasurement:
    """One (M, K, N) probe: raw seconds + the per-probe derived quantities."""
    M: int
    K: int
    N: int
    dtype: str
    t_gemm: float                # backend GEMM on the full problem
    t_combine_a: float           # Group Combine A of the probe scheme
    t_batched: float             # R-batched LCMA GEMM stage
    t_pipeline: float | None     # full LCMA pipeline (validation; may be skipped)
    flops_mul_est: float
    beta_est: float
    eff_est: float
    # (G*R)-batched grouped GEMM stage vs one big GEMM — validates the
    # decision model's eff_B amortization law (``estimate_grouped``); None
    # when the grouped probe is skipped (group_size <= 1)
    eff_grouped_est: float | None = None
    group_size: int = 1
    # int8 probes (``quant=True``): raw int8 GEMM throughput on the full
    # problem (the FLOPS_int8 the quantized tier is priced with) and the
    # fused Combine-A+quantize pass (the quant-pass beta). None when the
    # quant probe was skipped.
    t_gemm_int8: float | None = None
    t_quant_combine: float | None = None
    flops_int8_est: float | None = None
    beta_quant_est: float | None = None

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class CalibrationReport:
    base: str
    backend: str
    dtype: str
    scheme: str
    probes: list[ProbeMeasurement]
    profile: HardwareProfile
    # per-probe relative error of the calibrated model's predicted LCMA
    # pipeline time vs the measured pipeline (empty when validation skipped)
    model_rel_err: list[float]
    # measured (G*R)-batched grouped-stage efficiency (median over probes)
    # vs the eff_B amortization law the grouped decision model assumes —
    # None when the grouped probe was skipped
    eff_grouped: float | None = None
    eff_grouped_predicted: float | None = None
    # medians of the int8 probes (``quant=True``); flops_int8 is what lands
    # in the profile's dtype_flops["int8"], beta_quant rides in metadata
    flops_int8: float | None = None
    beta_quant: float | None = None

    @property
    def max_rel_err(self) -> float | None:
        return max(self.model_rel_err) if self.model_rel_err else None

    def metadata(self) -> dict:
        return {
            "base": self.base, "backend": self.backend, "dtype": self.dtype,
            "scheme": self.scheme,
            "probes": [p.as_dict() for p in self.probes],
            "model_rel_err": self.model_rel_err,
            "eff_grouped": self.eff_grouped,
            "eff_grouped_predicted": self.eff_grouped_predicted,
            "flops_int8": self.flops_int8,
            "beta_quant": self.beta_quant,
        }


def _combine_bytes(l: LCMA, Mp: int, Kp: int, itemsize: int) -> int:
    # Combine A moves M*K reads + R*(M/m)*(K/k) writes (Table II).
    return (Mp * Kp + l.R * (Mp // l.m) * (Kp // l.k)) * itemsize


def _measure_probe(M: int, K: int, N: int, l: LCMA, backend: str, dtype: str,
                   timer: Callable, validate: bool,
                   group_size: int = 1, quant: bool = False) -> ProbeMeasurement:
    import jax
    import jax.numpy as jnp

    jdt = jnp.dtype(dtype)
    itemsize = jdt.itemsize
    a = jnp.ones((M, K), jdt)
    b = jnp.ones((K, N), jdt)

    def pad(x, d0, d1):
        return jnp.pad(x, ((0, (-x.shape[0]) % d0), (0, (-x.shape[1]) % d1)))

    ap = pad(a, l.m, l.k)
    bp = pad(b, l.k, l.n)
    Mp, Kp = ap.shape
    Np = bp.shape[1]
    X, Ks, Z = Mp // l.m, Kp // l.k, Np // l.n
    interpret = backend == "pallas_interpret"

    if backend in ("pallas", "pallas_interpret"):
        from repro.kernels import ops
        from repro.kernels.group_combine import group_combine
        from repro.kernels.fused_gemm import fused_gemm_combine_h

        # jit every timed callable: the GEMM wrapper is already @jax.jit'd,
        # and timing the combines eagerly would charge them per-call trace
        # overhead the GEMM doesn't pay, biasing beta/efficiency low.
        comb = jax.jit(lambda x: group_combine(x, l.U, interpret=interpret))
        bat = jax.jit(lambda x, y: fused_gemm_combine_h(
            x, y, l.W, out_dtype=jdt, interpret=interpret))
        t_gemm = timer(lambda x, y: ops.matmul_pallas(x, y, interpret=interpret), a, b)
        t_comb = timer(comb, ap)
        at = group_combine(ap, l.U, interpret=interpret)
        bt = group_combine(bp, l.V, interpret=interpret)
        t_bat = timer(bat, at, bt)
        t_pipe = (timer(lambda x, y: ops.falcon_matmul_pallas(
            x, y, l, interpret=interpret), a, b) if validate else None)
    else:
        gen = codegen.generate(l)
        mm = jax.jit(lambda x, y: jnp.dot(x, y))
        comb = jax.jit(gen.combine_a)
        bmm = jax.jit(lambda x, y: jax.lax.dot_general(
            x, y, (((2,), (1,)), ((0,), (0,)))))
        full = jax.jit(gen.fn)
        t_gemm = timer(mm, a, b)
        t_comb = timer(comb, ap)
        at = jnp.ones((l.R, X, Ks), jdt)
        bt = jnp.ones((l.R, Ks, Z), jdt)
        t_bat = timer(bmm, at, bt)
        t_pipe = timer(full, ap, bp) if validate else None

    flops_mul = 2.0 * M * N * K / t_gemm
    beta = _combine_bytes(l, Mp, Kp, itemsize) / t_comb
    batched_flops = 2.0 * l.R * X * Ks * Z / t_bat
    eff = min(batched_flops / flops_mul, 1.0)
    eff_grouped = None
    if group_size > 1 and backend not in ("pallas", "pallas_interpret"):
        # Grouped stage: G groups of R products as ONE (G*R)-batched GEMM —
        # the Execution Module's group-parallel lowering. Measured relative
        # to the big GEMM it validates the eff_B amortization law used by
        # decision.estimate_grouped (jnp backend only: the Pallas grouped
        # kernel adds a grid dim, not a bigger dot_general).
        G = int(group_size)
        ag = jnp.ones((G * l.R, X, Ks), jdt)
        bg = jnp.ones((G * l.R, Ks, Z), jdt)
        gmm = jax.jit(lambda x, y: jax.lax.dot_general(
            x, y, (((2,), (1,)), ((0,), (0,)))))
        t_grp = timer(gmm, ag, bg)
        eff_grouped = min(2.0 * G * l.R * X * Ks * Z / t_grp / flops_mul, 1.0)
    t_g8 = t_qc = flops_int8 = beta_quant = None
    if quant:
        # FLOPS_int8: the raw int8 GEMM (int32 accumulation) on the full
        # problem — the per-dtype peak the quantized tier's GEMM stage is
        # priced with (``hw.flops_for("int8")``).
        a8 = jnp.ones((M, K), jnp.int8)
        b8 = jnp.ones((K, N), jnp.int8)
        mm8 = jax.jit(lambda x, y: jax.lax.dot_general(
            x, y, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32))
        t_g8 = timer(mm8, a8, b8)
        flops_int8 = 2.0 * M * N * K / t_g8
        # quant-pass beta: the fused Combine-A + blockwise-quantize kernel —
        # reads the fp operand, writes int8 Ã plus f32 block scales.
        from repro.kernels.quant_combine import group_combine_quant
        qi = interpret or interpret_kernels()
        qcomb = jax.jit(lambda x: group_combine_quant(x, l.U, interpret=qi))
        t_qc = timer(qcomb, ap)
        by = next(d for d in range(min(128, Ks), 0, -1) if Ks % d == 0)
        qbytes = Mp * Kp * itemsize + l.R * X * Ks + l.R * X * (Ks // by) * 4
        beta_quant = qbytes / t_qc
    return ProbeMeasurement(M, K, N, dtype, t_gemm, t_comb, t_bat, t_pipe,
                            flops_mul, beta, eff,
                            eff_grouped_est=eff_grouped,
                            group_size=int(group_size),
                            t_gemm_int8=t_g8, t_quant_combine=t_qc,
                            flops_int8_est=flops_int8,
                            beta_quant_est=beta_quant)


def measure_collective_bw(size_bytes: int = 8 << 20, reps: int = 3,
                          warmup: int = 1,
                          timer: Callable | None = None) -> float | None:
    """Measure effective per-device collective bandwidth (bytes/s).

    Times a ring all-gather and a reduce-scatter over every local device
    (simulated host devices included) under ``shard_map`` and reports the
    slower of the two as bytes-moved-per-device / seconds — the number the
    sharded decision model divides collective bytes by. Returns ``None`` on
    single-device hosts, where the profile's static ``link_bw`` remains the
    fallback.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro import compat

    D = len(jax.devices())
    if D < 2:
        return None
    timer = timer or best_of_timer(reps=reps, warmup=warmup)
    mesh = compat.make_mesh((D,), ("coll",))
    n = max(size_bytes // 4 // D, 1)          # float32 elements per shard
    x = jnp.ones((D * n,), jnp.float32)

    def ag(xl):
        return jax.lax.all_gather(xl, "coll", tiled=True)

    def rs(xl):
        return jax.lax.psum_scatter(xl, "coll", tiled=True)

    with jax.set_mesh(mesh):
        f_ag = jax.jit(jax.shard_map(ag, in_specs=P("coll"),
                                     out_specs=P(None), check_vma=False))
        f_rs = jax.jit(jax.shard_map(rs, in_specs=P(None),
                                     out_specs=P("coll"), check_vma=False))
        t_ag = timer(f_ag, x)
        t_rs = timer(f_rs, x)
    moved = (D - 1) * n * 4                   # ring model: (D-1)/D of total
    return moved / max(t_ag, t_rs)


def autotune(base: str | HardwareProfile = "cpu_host", backend: str = "jnp",
             shapes: Sequence[tuple[int, int, int]] | None = None,
             dtype: str = "float32", scheme: str = "strassen",
             reps: int = 3, warmup: int = 1,
             timer: Callable | None = None, name: str | None = None,
             validate: bool = True, group_size: int = 4,
             collectives: bool = False,
             quant: bool = False) -> CalibrationReport:
    """Measure the backend on probe shapes and fit a calibrated profile.

    Returns a :class:`CalibrationReport`; ``report.profile`` is registered
    with ``hardware`` so ``FalconConfig(hardware=report.profile.name)`` and
    ``decide(..., hw=report.profile.name)`` resolve it immediately.

    ``quant=True`` additionally measures the int8 stage — the raw int8 GEMM
    throughput and the fused Combine-A+quantize pass — and persists the
    measured FLOPS_int8 as the profile's ``dtype_flops["int8"]``, so the
    quantized decision tier is priced against measured (not assumed) int8
    throughput. The profile fingerprint hashes ``dtype_flops``, so persisted
    plan caches from an unquantized calibration invalidate automatically.
    """
    base_prof = get_profile(base) if isinstance(base, str) else base
    if backend not in ("jnp", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown autotune backend {backend!r}")
    shapes = list(shapes) if shapes is not None else default_probe_shapes(backend)
    timer = timer or best_of_timer(reps=reps, warmup=warmup)
    l = algorithms.get(scheme)

    probes = [_measure_probe(M, K, N, l, backend, dtype, timer, validate,
                             group_size=group_size, quant=quant)
              for (M, K, N) in shapes]

    flops_mul = statistics.median(p.flops_mul_est for p in probes)
    beta = statistics.median(p.beta_est for p in probes)
    eff = statistics.median(p.eff_est for p in probes)
    flops_add = beta / dec._dtype_bytes(dtype)  # 1 add/elem at effective BW

    flops_int8 = beta_quant = None
    if quant:
        f8s = [p.flops_int8_est for p in probes if p.flops_int8_est]
        bqs = [p.beta_quant_est for p in probes if p.beta_quant_est]
        flops_int8 = statistics.median(f8s) if f8s else None
        beta_quant = statistics.median(bqs) if bqs else None

    coll_bw = base_prof.collective_bw
    if collectives:
        measured = measure_collective_bw(reps=reps, warmup=warmup, timer=timer)
        if measured is not None:
            coll_bw = measured

    prof = dataclasses.replace(
        base_prof,
        name=name or f"{base_prof.name}_autotuned",
        flops_mul=flops_mul,
        flops_add=flops_add,
        beta=beta,
        lcma_gemm_efficiency=eff,
        collective_bw=coll_bw,
        # calibration is per measured dtype; the only per-dtype override a
        # calibrated profile carries is the measured int8 peak (quant=True)
        dtype_flops={"int8": flops_int8} if flops_int8 else None,
    )
    register_profile(prof)

    rel_err = []
    for p in probes:
        if p.t_pipeline is None:
            continue
        pred = dec.lcma_time(l, p.M, p.N, p.K, prof, dtype=dtype)
        rel_err.append(abs(pred - p.t_pipeline) / p.t_pipeline)

    # Validate the grouped decision model against the grouped-stage probe:
    # eff_B = B*eff/(B*eff + 1 - eff) should track the measured (G*R)-batched
    # efficiency. A large gap means grouped decisions on this host deserve a
    # second look (the report records both; tune CLI prints them).
    eff_grouped = eff_grouped_pred = None
    grouped_meas = [p.eff_grouped_est for p in probes
                    if p.eff_grouped_est is not None]
    if grouped_meas:
        eff_grouped = statistics.median(grouped_meas)
        G = next(p.group_size for p in probes if p.eff_grouped_est is not None)
        eff_grouped_pred = G * eff / (G * eff + 1.0 - eff)
        if abs(eff_grouped - eff_grouped_pred) > 0.25:
            import logging
            logging.getLogger(__name__).warning(
                "autotune: grouped GEMM stage measured %.2f efficiency vs "
                "eff_B model prediction %.2f (G=%d, eff=%.2f) — grouped "
                "decisions may be mispriced on this backend",
                eff_grouped, eff_grouped_pred, G, eff)

    return CalibrationReport(base=base_prof.name, backend=backend, dtype=dtype,
                             scheme=scheme, probes=probes, profile=prof,
                             model_rel_err=rel_err, eff_grouped=eff_grouped,
                             eff_grouped_predicted=eff_grouped_pred,
                             flops_int8=flops_int8, beta_quant=beta_quant)


def calibrate(path: str | None = None, block_plan_shapes: bool = True,
              **kw) -> tuple[CalibrationReport, str]:
    """``autotune`` + persist the profile JSON (the one-call convenience).

    The saved metadata embeds the probe measurements and, when requested, the
    per-candidate Pallas block plans from ``kernels.tuning`` for a
    representative serving shape — so a deploy host can inspect exactly what
    the tuner saw.
    """
    report = autotune(**kw)
    meta = report.metadata()
    if block_plan_shapes:
        from repro.kernels import tuning
        M, K, N = 4096, 4096, 4096
        meta["block_plans"] = {
            l.name: tuning.block_plans(l, M, K, N, dtype=report.dtype)
            for l in algorithms.candidates(max_grid=3)
        }
    out = save_profile(report.profile, path, metadata=meta)
    return report, out
