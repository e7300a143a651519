"""Compiles of the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers a kernel at granite_3_2b widths (d_model
2048, d_ff 8192, vocab 49155) and compiles it for one chip of a described
``v5e:2x2`` topology. What the chip's compiler refuses -- a block that is
not tile-aligned, too much VMEM -- fails here without a chip. Interpret-mode
tests cannot see either. The serve engine's step programs compile here too,
on one chip and over all four as a ``{"data": 1, "model": 4}`` mesh.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and test workers import every test file.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import repro.api as falcon
from repro.core import algorithms as alg
from repro.core.falcon_gemm import FalconConfig
from repro.kernels import ops
from repro.kernels.fused_gemm import (batched_fused_gemm_combine_h,
                                      fused_gemm_combine_h, tiled_matmul)
from repro.kernels.group_combine import group_combine
from repro.kernels.quant_combine import quantize_b_blockwise

BF16 = jnp.bfloat16
M_PREFILL, D_MODEL, D_FF, VOCAB = 1024, 2048, 8192, 49155


@pytest.fixture(scope="module")
def v5e_2x2():
    """A described v5e:2x2, with the persistent cache off.

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
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


@pytest.fixture(scope="module")
def four_chips(v5e_2x2):
    """The four chips as a ``{"data": 1, "model": 4}`` mesh."""
    from jax.sharding import Mesh
    return Mesh(np.array(v5e_2x2.devices).reshape(1, 4), ("data", "model"))


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


def test_lcma_stage_scopes_survive_the_chip_compiler(one_chip):
    """The jnp-backend Strassen projection against offline B̃ at granite's
    MLP width keeps the stage names a device trace attributes ops by."""
    cfg = FalconConfig(mode="strassen", backend="jnp")
    w = jax.ShapeDtypeStruct((D_MODEL, D_FF), BF16)
    pw = jax.eval_shape(
        lambda w_: falcon.plan_weight(w_, cfg=cfg, m_hint=M_PREFILL), w)
    assert pw.algo == "strassen" and pw.bt is not None
    pw = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), pw)
    x = jax.ShapeDtypeStruct((M_PREFILL, D_MODEL), BF16, sharding=one_chip)

    def f(x_, p_):
        with falcon.use(cfg):
            return falcon.dense(x_, p_)

    txt = jax.jit(f).lower(x, pw).compile().as_text()
    scopes = {part for n in re.findall(r'op_name="([^"]*)"', txt)
              for part in n.split("/")}
    assert {"falcon.combine_a", "falcon.lcma_gemm", "falcon.combine_h"} <= scopes


def test_slot_step_writes_the_donated_cache_in_place(one_chip):
    """The serve engine's decode step (granite smoke widths, 4 slots, a
    padded bucket of 2) compiled for the chip: each slot-cache leaf is
    aliased to its output, so the write-back is in place and the donated
    cache is not copied."""
    from repro.configs import registry
    from repro.models import model as M
    from repro.serve.engine import on_slots
    from repro.train.steps import make_decode_step

    cfg = registry.smoke_config("granite_3_2b")

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(M.init_params, cfg,
                                    jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: M.init_cache(cfg, 4, 64)))
    # the device's default layout of each leaf, as a live cache has it
    formats = jax.jit(lambda c: c).lower(cache).compile().input_formats[0][0]
    layouts = jax.tree.map(lambda f: f.layout, formats)
    b = 2
    step = jax.jit(on_slots(make_decode_step(cfg), layouts), donate_argnums=1)
    with falcon.use(FalconConfig(mode="gemm")):
        txt = step.lower(params, cache,
                         *on_chip((jax.ShapeDtypeStruct((b,), jnp.int32),
                                   jax.ShapeDtypeStruct((b, 1), jnp.int32),
                                   jax.ShapeDtypeStruct((b,), jnp.int32)))
                         ).compile().as_text()
    header = txt.splitlines()[0]                    # HloModule ..., attributes
    pairs = dict(re.findall(r"\{(\d+)\}: \((\d+), \{\}, [\w-]*alias\)",
                            header))
    n = len(jax.tree.leaves(params))
    assert pairs == {"1": str(n), "2": str(n + 1)}, pairs   # k, v


def test_tp4_decode_step_keeps_the_slot_cache_sharded_by_kv_head(four_chips):
    """The serve engine's decode step for starcoder2-15b's widths (2 of its
    40 layers) over four described chips, as ``ServeEngine`` builds it under
    a mesh: each device holds a quarter of the slot cache (its KV head),
    which is donated and aliased, and no cache rows are gathered; the
    row-parallel projections all-reduce."""
    import dataclasses

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.models import model as M
    from repro.parallel import sharding as SH
    from repro.serve.engine import on_slots
    from repro.train.steps import make_decode_step

    mesh = four_chips
    cfg = dataclasses.replace(get_config("starcoder2_15b"), num_layers=2)
    slots, max_len, b = 8, 2048, 8

    def placed(tree, shardings):
        return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh), tree, shardings)

    pshape = jax.eval_shape(M.init_params, cfg, jax.random.PRNGKey(0))
    cshape = jax.eval_shape(lambda: M.init_cache(cfg, slots, max_len))
    csh = SH.slot_cache_sharding(cshape, mesh)
    params = placed(pshape, SH.param_sharding(pshape, mesh, SH.make_rules(mesh)))
    cache = placed(cshape, csh)
    repl = NamedSharding(mesh, P())
    step = jax.jit(on_slots(make_decode_step(cfg), None), donate_argnums=1,
                   out_shardings=(repl, csh))
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=repl)
            for s in ((b,), (b, 1), (b,))]
    with jax.set_mesh(mesh), falcon.use(M.falcon_config_for(cfg, dict(mesh.shape))):
        compiled = step.lower(params, cache, *args).compile()
    txt = compiled.as_text()
    whole = sum(c.size * c.dtype.itemsize for c in jax.tree.leaves(cshape))
    assert compiled.memory_analysis().alias_size_in_bytes == whole // 4
    gathered = re.findall(r"= \w+\[([\d,]*)\]\S* all-gather(?:-start)?\(", txt)
    assert all(len(s.split(",")) < 5 for s in gathered), gathered
    assert re.search(r" all-reduce(-start)?\(", txt)
