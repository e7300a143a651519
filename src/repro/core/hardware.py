"""Hardware abstraction for the Decision Module and roofline analysis.

The paper abstracts a platform as ``(FLOPS_x, FLOPS_+, beta)`` (§III-C):
  * ``FLOPS_x`` — matrix-multiply throughput (MXU / Tensor Core),
  * ``FLOPS_+`` — elementwise add/sub throughput (VPU / CUDA cores),
  * ``beta``    — off-chip (HBM) bandwidth for the target dtype.

We extend it with the interconnect and on-chip capacities needed for the
multi-pod roofline and the Pallas resource planner.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

__all__ = ["HardwareProfile", "TPU_V5E", "TPU_V5E_POD", "CPU_HOST", "get_profile",
           "calibrate_cpu", "register_profile", "profile_dir", "profile_path",
           "save_profile", "load_profile", "ENV_PROFILE_DIR", "DEVICE_PROFILES",
           "device_profile_name", "interpret_kernels"]


@dataclasses.dataclass(frozen=True)
class HardwareProfile:
    name: str
    flops_mul: float            # FLOPS_x  (per chip, matmul units, bf16 unless noted)
    flops_add: float            # FLOPS_+  (per chip, vector units)
    beta: float                 # HBM bytes/s per chip
    link_bw: float = 50e9       # ICI bytes/s per link per chip
    hbm_bytes: int = 16 << 30
    vmem_bytes: int = 16 << 20  # conservative Pallas VMEM budget
    mxu_align: int = 128        # MXU systolic dimension
    dtype_flops: dict | None = None  # per-dtype FLOPS_x override
    # throughput of the R-batched LCMA GEMM relative to one big GEMM
    # (1.0 on TPU MXU; <1 through XLA-CPU's batched dot — calibrated)
    lcma_gemm_efficiency: float = 1.0
    # effective per-device collective (all-gather / reduce-scatter) bytes/s,
    # measured by the autotuner's --collectives probe; 0.0 => not measured,
    # fall back to the static per-link ICI number.
    collective_bw: float = 0.0

    def flops_for(self, dtype: str) -> float:
        if self.dtype_flops and dtype in self.dtype_flops:
            return self.dtype_flops[dtype]
        return self.flops_mul

    def coll_bw(self) -> float:
        """Collective bandwidth for the sharded decision model: the measured
        value when the --collectives probe ran, else the profiled link rate."""
        return self.collective_bw if self.collective_bw > 0 else self.link_bw

    @property
    def ridge_intensity(self) -> float:
        """FLOPS_x / beta — the roofline ridge point (FLOP per byte)."""
        return self.flops_mul / self.beta

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "HardwareProfile":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})


# TPU v5e: 197 TFLOP/s bf16 MXU, 819 GB/s HBM, ~50 GB/s/link ICI (per prompt).
# FLOPS_+ : VPU — 8 ALUs x (8,128) lanes x ~0.94 GHz ~= 7.7 TFLOP/s f32; we use
# a conservative 4.9 TFLOP/s to absorb load/store issue overheads.
TPU_V5E = HardwareProfile(
    name="tpu_v5e",
    flops_mul=197e12,
    flops_add=4.9e12,
    beta=819e9,
    link_bw=50e9,
    hbm_bytes=16 << 30,
    vmem_bytes=16 << 20,
    dtype_flops={"bfloat16": 197e12, "float32": 49.25e12, "int8": 394e12},
)

# A full v5e pod slice as used by the dry-run mesh (per-chip numbers identical;
# kept as a distinct profile so collective constants can differ later).
TPU_V5E_POD = dataclasses.replace(TPU_V5E, name="tpu_v5e_pod")

# The container host (1 core) — used for *measured* CPU benchmarks, mirroring
# the paper's CPU (x86/ARM) evaluations. Rough defaults; ``calibrate_cpu``
# measures the real numbers at benchmark time.
CPU_HOST = HardwareProfile(
    name="cpu_host",
    flops_mul=6.0e10,
    flops_add=1.5e10,
    beta=2.0e10,
    link_bw=1e9,
    hbm_bytes=32 << 30,
    vmem_bytes=32 << 20,   # L2/L3 analogue
    mxu_align=8,
    dtype_flops=None,
)

_PROFILES = {p.name: p for p in (TPU_V5E, TPU_V5E_POD, CPU_HOST)}

# ``device_kind`` as JAX reports it -> the profile that prices that chip. A
# v5e reports "TPU v5 lite". A TPU that is not listed is an error: pricing it
# with another chip's peaks would pick its schemes on wrong numbers.
DEVICE_PROFILES = {"TPU v5 lite": "tpu_v5e"}


def device_profile_name(device=None) -> str:
    """Name of the profile for ``device`` (default: ``jax.devices()[0]``).

    A TPU resolves by ``device_kind`` through :data:`DEVICE_PROFILES`; an
    unlisted kind raises ``KeyError``. Any other platform -- the CPU that
    tests and rehearsals run on -- prices the analytic ``tpu_v5e`` profile,
    the chip these plans are made for.
    """
    import jax
    device = device or jax.devices()[0]
    if device.platform != "tpu":
        return TPU_V5E.name
    try:
        return DEVICE_PROFILES[device.device_kind]
    except KeyError:
        raise KeyError(
            f"no hardware profile for TPU device_kind "
            f"{device.device_kind!r}; known: {sorted(DEVICE_PROFILES)}") from None


def interpret_kernels() -> bool:
    """Whether Pallas kernels must run in interpret mode: only on the CPU.

    The one place that decides it, so no path that reaches a TPU interprets.
    """
    import jax
    return jax.default_backend() == "cpu"


# Calibrated profiles written by ``repro.tools.tune`` live here; set the env
# var to relocate (CI, multi-host). Looked up lazily by ``get_profile``.
ENV_PROFILE_DIR = "FALCON_PROFILE_DIR"


def profile_dir() -> str:
    return os.environ.get(ENV_PROFILE_DIR) or os.path.join(
        os.path.expanduser("~"), ".cache", "falcon_gemm", "profiles")


def profile_path(name: str) -> str:
    return os.path.join(profile_dir(), f"{name}.json")


def register_profile(p: HardwareProfile) -> HardwareProfile:
    """Make a profile resolvable by name (``FalconConfig.hardware``)."""
    _PROFILES[p.name] = p
    return p


def save_profile(p: HardwareProfile, path: str | None = None,
                 metadata: dict | None = None) -> str:
    """Write a profile (plus optional calibration metadata) as JSON."""
    path = path or profile_path(p.name)
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    doc = p.to_dict()
    if metadata:
        doc["_metadata"] = metadata
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
    os.replace(tmp, path)
    return path


def load_profile(path: str, register: bool = True) -> HardwareProfile:
    with open(path) as f:
        doc = json.load(f)
    p = HardwareProfile.from_dict(doc)
    if register:
        register_profile(p)
    return p


def get_profile(name: str) -> HardwareProfile:
    """Resolve a profile by name: built-ins/registered first, then the
    on-disk calibrated-profile directory (autotune output)."""
    p = _PROFILES.get(name)
    if p is not None:
        return p
    path = profile_path(name)
    if os.path.exists(path):
        return load_profile(path)
    raise KeyError(f"unknown hardware profile {name!r} "
                   f"(no built-in and no {path})")


_CPU_CAL_CACHE: dict = {}


def calibrate_cpu(size: int = 1024, dtype="float32") -> HardwareProfile:
    """Measure the host's (FLOPS_x, FLOPS_+, beta) for honest CPU decisions.

    beta is measured from a REAL Group-Combine-A (Strassen) rather than a
    plain stream add: through XLA-CPU the combine's slice+add+stack pattern
    reaches only a fraction of stream bandwidth (~3.5 GB/s on this container
    vs ~10 GB/s stream), and an uncalibrated model mispredicts the LCMA
    cutoff — a refuted-hypothesis lesson recorded in EXPERIMENTS.md §Perf.
    """
    key = (size, str(dtype))
    if key in _CPU_CAL_CACHE:
        return _CPU_CAL_CACHE[key]
    import jax
    import jax.numpy as jnp
    from repro.core import algorithms as _alg, codegen as _cg

    a = jnp.ones((size, size), dtype)
    b = jnp.ones((size, size), dtype)
    mm = jax.jit(lambda x, y: x @ y)
    gen = _cg.generate(_alg.get("strassen"))
    comb = jax.jit(gen.combine_a)

    def best(f, *args, reps=3):
        f(*args).block_until_ready()
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            f(*args).block_until_ready()
            ts.append(time.perf_counter() - t0)
        return min(ts)

    t_mm = best(mm, a, b)
    t_comb = best(comb, a)
    flops_mul = 2 * size**3 / t_mm
    # batched-GEMM efficiency: the LCMA GEMM stage is an R-batched matmul
    h = size // 2
    ab = jnp.ones((7, h, h), dtype)
    bb = jnp.ones((7, h, h), dtype)
    bmm = jax.jit(lambda x, y: jax.lax.dot_general(
        x, y, (((2,), (1,)), ((0,), (0,)))))
    t_bmm = best(bmm, ab, bb)
    batched_flops = 2 * 7 * h**3 / t_bmm
    eff = min(batched_flops / flops_mul, 1.0)
    itemsize = jnp.dtype(dtype).itemsize
    # Combine-A moves MK reads + R*(M/2)(K/2) writes at the EFFECTIVE rate.
    comb_bytes = (size * size + 7 * (size // 2) ** 2) * itemsize
    beta = comb_bytes / t_comb
    flops_add = beta / itemsize  # 1 add per element at effective bandwidth
    prof = dataclasses.replace(
        CPU_HOST, flops_mul=flops_mul, flops_add=flops_add, beta=beta,
        lcma_gemm_efficiency=eff, name="cpu_host_calibrated",
    )
    _CPU_CAL_CACHE[key] = prof
    _PROFILES[prof.name] = prof  # resolvable via FalconConfig.hardware
    return prof
