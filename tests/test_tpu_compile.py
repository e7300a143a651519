"""Compiles of the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers a kernel at granite_3_2b widths (d_model
2048, d_ff 8192, vocab 49155) and compiles it for one chip of a described
``v5e:2x2`` topology. What the chip's compiler refuses -- a block that is
not tile-aligned, too much VMEM -- fails here without a chip. Interpret-mode
tests cannot see either.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and test workers import every test file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import algorithms as alg
from repro.kernels import ops
from repro.kernels.fused_gemm import (batched_fused_gemm_combine_h,
                                      fused_gemm_combine_h, tiled_matmul)
from repro.kernels.group_combine import group_combine
from repro.kernels.quant_combine import quantize_b_blockwise

BF16 = jnp.bfloat16
M_PREFILL, D_MODEL, D_FF, VOCAB = 1024, 2048, 8192, 49155


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent cache off.

    A compile for a described chip is written to the persistent cache but
    cannot be read back without a chip, so the cache is off around these.
    """
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("name", ["strassen", "laderman"])
@pytest.mark.parametrize("N", [D_FF, VOCAB], ids=["mlp_gate", "lm_head"])
def test_falcon_matmul_pallas_compiles(one_chip, name, N):
    """The whole pipeline (Combine A, Combine B, fused GEMM + Combine H)."""
    l = alg.get(name)
    _compile(lambda a, b: ops.falcon_matmul_pallas(a, b, l), one_chip,
             ((M_PREFILL, D_MODEL), BF16), ((D_MODEL, N), BF16))


def test_s444_pipeline_compiles_at_long_prefill(one_chip):
    l = alg.get("s444")
    _compile(lambda a, b: ops.falcon_matmul_pallas(a, b, l), one_chip,
             ((4096, D_MODEL), BF16), ((D_MODEL, D_FF), BF16))


def test_precombined_pipeline_compiles_at_lm_head(one_chip):
    """Serving against offline B̃ whose N/n (24578) is not lane-aligned."""
    l = alg.get("strassen")
    bt = (l.R, D_MODEL // l.k, -(-VOCAB // l.n))
    _compile(lambda a, b: ops.falcon_matmul_pallas_precombined(a, b, l, VOCAB),
             one_chip, ((M_PREFILL, D_MODEL), BF16), (bt, BF16))


def test_group_combine_compiles(one_chip):
    l = alg.get("strassen")
    _compile(lambda a: group_combine(a, l.U), one_chip,
             ((M_PREFILL, D_MODEL), BF16))


def test_fused_gemm_combine_h_compiles(one_chip):
    l = alg.get("strassen")
    X, Y, Z = M_PREFILL // 2, D_MODEL // 2, D_FF // 2
    _compile(lambda at, bt: fused_gemm_combine_h(at, bt, l.W), one_chip,
             ((l.R, X, Y), BF16), ((l.R, Y, Z), BF16))


@pytest.mark.parametrize("shared", [True, False], ids=["shared_b", "per_group"])
def test_batched_fused_gemm_combine_h_compiles(one_chip, shared):
    l = alg.get("strassen")
    G, X, Y, Z = 4, 256, D_MODEL // 2, D_FF // 2
    bt = (l.R, Y, Z) if shared else (G, l.R, Y, Z)
    _compile(lambda at, b: batched_fused_gemm_combine_h(at, b, l.W), one_chip,
             ((G, l.R, X, Y), BF16), (bt, BF16))


def test_tiled_matmul_compiles(one_chip):
    _compile(tiled_matmul, one_chip,
             ((M_PREFILL, D_MODEL), BF16), ((D_MODEL, D_FF), BF16))


@pytest.mark.parametrize("N", [D_FF, VOCAB], ids=["mlp_gate", "lm_head"])
def test_int8_quant_pipeline_compiles(one_chip, N):
    """Quantizing Combine A + int8 fused GEMM against offline B̃q + scales."""
    l = alg.get("strassen")
    Y, Z = D_MODEL // l.k, -(-N // l.n)
    _compile(lambda a, bq, bs: ops.falcon_matmul_pallas_quant(a, bq, bs, l, N),
             one_chip, ((M_PREFILL, D_MODEL), BF16), ((l.R, Y, Z), np.int8),
             ((l.R, Y // 128, Z), jnp.float32))


def test_offline_weight_quantization_compiles_at_lm_head(one_chip):
    """Combine B + blockwise int8 of a weight whose N/n is not lane-aligned."""
    l = alg.get("strassen")
    N = -(-VOCAB // l.n) * l.n
    compiled = _compile(lambda b: quantize_b_blockwise(b, l.V, by=128),
                        one_chip, ((D_MODEL, N), BF16))
    bq, scales = compiled.out_info
    assert bq.shape == (l.R, D_MODEL // l.k, N // l.n)
    assert scales.shape == (l.R, D_MODEL // l.k // 128, N // l.n)
