"""Unified dispatch API: context config, backend registry, dot_general/einsum
normalization, PlannedWeight, and the deprecation/compat shims.

This module must stay clean under ``-W error::DeprecationWarning`` (the CI
deprecation lane): tests that exercise the legacy ``fcfg`` shim capture the
warning explicitly with ``pytest.warns``.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.api as falcon
from repro.core import backends, decision as dec, engine
from repro.core.falcon_gemm import FalconConfig, plan

FORCE = FalconConfig(mode="strassen", backend="jnp")


@pytest.fixture
def rng():
    return np.random.default_rng(0)


# ---------------------------------------------------------------------------
# Context-scoped config
# ---------------------------------------------------------------------------

def test_use_context_nesting_and_restoration():
    assert falcon.active_config() is None
    assert falcon.current_config() == FalconConfig()
    outer = FalconConfig(mode="strassen")
    inner = FalconConfig(mode="gemm", hardware="a100")
    with falcon.use(outer):
        assert falcon.current_config() is outer
        with falcon.use(inner):
            assert falcon.current_config() is inner
        assert falcon.current_config() is outer
    assert falcon.active_config() is None


def test_use_context_restores_on_exception():
    with pytest.raises(RuntimeError):
        with falcon.use(FalconConfig(mode="strassen")):
            raise RuntimeError("boom")
    assert falcon.active_config() is None


def test_context_config_drives_dispatch(rng):
    A = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((32, 48)), jnp.float32)
    with falcon.use(FORCE):
        got = falcon.matmul(A, B)           # no cfg argument anywhere
    np.testing.assert_allclose(np.asarray(got), np.asarray(A @ B),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------

def test_register_dispatch_unregister(rng):
    calls = []

    def spy(a2, b, l, cfg):
        calls.append((a2.shape, b.shape, l.name))
        return backends.get_backend("jnp").apply(a2, b, l, cfg)

    falcon.register_backend("spy_backend", spy)
    try:
        A = jnp.asarray(rng.standard_normal((32, 32)), jnp.float32)
        B = jnp.asarray(rng.standard_normal((32, 32)), jnp.float32)
        got = falcon.matmul(A, B, cfg=dataclasses.replace(FORCE,
                                                          backend="spy_backend"))
        np.testing.assert_allclose(np.asarray(got), np.asarray(A @ B),
                                   rtol=1e-4, atol=1e-4)
        assert calls == [((32, 32), (32, 32), "strassen")]
        assert "spy_backend" in falcon.available_backends()
    finally:
        falcon.unregister_backend("spy_backend")
    assert "spy_backend" not in falcon.available_backends()


def test_unknown_backend_error_lists_registered(rng):
    A = jnp.ones((16, 16), jnp.float32)
    with pytest.raises(KeyError, match="no_such_backend"):
        falcon.matmul(A, A, cfg=dataclasses.replace(FORCE,
                                                    backend="no_such_backend"))
    with pytest.raises(KeyError, match="jnp"):
        backends.get_backend("no_such_backend")


def test_reregister_requires_overwrite():
    falcon.register_backend("dup_backend", lambda *a: None)
    try:
        with pytest.raises(ValueError, match="already registered"):
            falcon.register_backend("dup_backend", lambda *a: None)
        falcon.register_backend("dup_backend", lambda *a: None, overwrite=True)
    finally:
        falcon.unregister_backend("dup_backend")


def test_builtin_backends_present():
    for name in ("jnp", "pallas", "pallas_interpret", "shard_map_local"):
        assert name in falcon.available_backends()


# ---------------------------------------------------------------------------
# dot_general / einsum normalization
# ---------------------------------------------------------------------------

DOT_CASES = [
    # (a_shape, b_shape, dimension_numbers)
    ((64, 32), (32, 48), (((1,), (0,)), ((), ()))),          # plain dense
    ((32, 64), (32, 48), (((0,), (0,)), ((), ()))),          # transposed lhs
    ((64, 32), (48, 32), (((1,), (1,)), ((), ()))),          # transposed rhs
    ((4, 24, 16), (4, 16, 20), (((2,), (1,)), ((0,), (0,)))),  # batched
    ((4, 16, 24), (4, 16, 20), (((1,), (1,)), ((0,), (0,)))),  # batched + T
    ((3, 5, 24, 16), (3, 5, 16, 10),
     (((3,), (2,)), ((0, 1), (0, 1)))),                      # 2 batch dims
    ((6, 8, 10), (8, 10, 7), (((1, 2), (0, 1)), ((), ()))),  # 2 contract dims
]


@pytest.mark.parametrize("ashape,bshape,dn", DOT_CASES)
@pytest.mark.parametrize("mode", ["strassen", "auto"])
def test_dot_general_matches_lax(rng, ashape, bshape, dn, mode):
    a = jnp.asarray(rng.standard_normal(ashape), jnp.float32)
    b = jnp.asarray(rng.standard_normal(bshape), jnp.float32)
    cfg = dataclasses.replace(FORCE, mode=mode)
    got = falcon.dot_general(a, b, dn, cfg=cfg)
    want = jax.lax.dot_general(a, b, dn)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_dot_general_under_jit_and_grad(rng):
    a = jnp.asarray(rng.standard_normal((48, 32)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((32, 40)), jnp.float32)
    dn = (((1,), (0,)), ((), ()))
    f = lambda x, y: jnp.sum(jnp.sin(falcon.dot_general(x, y, dn, cfg=FORCE)))
    g_got = jax.jit(jax.grad(f))(a, b)
    g_want = jax.grad(lambda x, y: jnp.sum(jnp.sin(x @ y)))(a, b)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_want),
                               rtol=1e-3, atol=1e-3)


def test_dot_general_preferred_element_type_falls_back(rng):
    a = jnp.asarray(rng.standard_normal((32, 32)), jnp.bfloat16)
    b = jnp.asarray(rng.standard_normal((32, 32)), jnp.bfloat16)
    dn = (((1,), (0,)), ((), ()))
    got = falcon.dot_general(a, b, dn, cfg=FORCE,
                             preferred_element_type=jnp.float32)
    want = jax.lax.dot_general(a, b, dn, preferred_element_type=jnp.float32)
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


EINSUM_CASES = [
    ("mk,kn->mn", (40, 24), (24, 32)),
    ("km,kn->mn", (24, 40), (24, 32)),       # transposed
    ("bqhd,bkhd->bhqk", (2, 16, 4, 8), (2, 12, 4, 8)),   # attention scores
    ("bhqk,bkhd->bqhd", (2, 4, 16, 12), (2, 12, 4, 8)),  # attention values
    ("bij,bjk->bik", (3, 20, 16), (3, 16, 24)),
    ("ij,kj->ik", (20, 16), (24, 16)),
]


@pytest.mark.parametrize("subs,ashape,bshape", EINSUM_CASES)
def test_einsum_matches_jnp(rng, subs, ashape, bshape):
    a = jnp.asarray(rng.standard_normal(ashape), jnp.float32)
    b = jnp.asarray(rng.standard_normal(bshape), jnp.float32)
    got = falcon.einsum(subs, a, b, cfg=FORCE)
    want = jnp.einsum(subs, a, b)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_einsum_fallback_paths(rng):
    # sum-out label, single operand, three operands: all must fall back to
    # jnp.einsum semantics rather than erroring.
    a = jnp.asarray(rng.standard_normal((8, 6)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((6, 4)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((4, 5)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(falcon.einsum("ij,jk->k", a, b, cfg=FORCE)),
        np.asarray(jnp.einsum("ij,jk->k", a, b)), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(falcon.einsum("ii->i", jnp.eye(5) * 3.0)),
        np.asarray(jnp.einsum("ii->i", jnp.eye(5) * 3.0)), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(falcon.einsum("ij,jk,kl->il", a, b, c, cfg=FORCE)),
        np.asarray(jnp.einsum("ij,jk,kl->il", a, b, c)), rtol=1e-5, atol=1e-5)


def test_einsum_parser_rejects_unsupported():
    p = engine._einsum_dimension_numbers
    assert p("...ij,jk->...ik", 3, 2) is None        # ellipsis
    assert p("ii,ij->ij", 2, 2) is None              # repeated label
    assert p("ij,jk->k", 2, 2) is None               # summed-out free label
    assert p("ij,jk", 3, 2) is None                  # rank mismatch
    dn, perm = p("ij,jk", 2, 2)                      # implicit output
    assert dn == (((1,), (0,)), ((), ())) and perm == (0, 1)


# ---------------------------------------------------------------------------
# PlannedWeight (offline Combine B)
# ---------------------------------------------------------------------------

def test_planned_weight_matches_eager(rng):
    W = jnp.asarray(rng.standard_normal((128, 96)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((4, 8, 128)), jnp.float32)
    pw = falcon.plan_weight(W, cfg=FORCE, m_hint=256)
    assert pw.precombined and pw.algo == "strassen"
    eager = falcon.dense(x, W, cfg=FORCE)
    planned = falcon.dense(x, pw, cfg=FORCE)
    np.testing.assert_allclose(np.asarray(planned), np.asarray(eager),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(planned), np.asarray(x @ W),
                               rtol=1e-3, atol=1e-3)


def test_planned_weight_is_a_pytree_through_jit(rng):
    W = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    pw = falcon.plan_weight(W, cfg=FORCE)
    leaves = jax.tree.leaves(pw)
    assert len(leaves) == 2  # w and bt ride as children; scheme is static
    got = jax.jit(lambda x_, p_: falcon.dense(x_, p_, cfg=FORCE))(x, pw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ W),
                               rtol=1e-3, atol=1e-3)


def test_planned_weight_gemm_bound_passthrough(rng):
    # auto mode on a tiny shape: the Decision Module declines, the wrapper
    # degrades to a plain weight and matches jnp.matmul bitwise.
    W = jnp.asarray(rng.standard_normal((16, 16)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((4, 16)), jnp.float32)
    pw = falcon.plan_weight(W, cfg=FalconConfig(), m_hint=4)
    assert pw.algo is None and not pw.precombined
    np.testing.assert_array_equal(
        np.asarray(falcon.dense(x, pw)), np.asarray(x @ W))


def test_planned_weight_keep_weight_false(rng):
    W = jnp.asarray(rng.standard_normal((64, 48)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)
    pw = falcon.plan_weight(W, cfg=FORCE, keep_weight=False)
    assert pw.w is None and pw.precombined
    # raw weight dropped: the precombined path is always taken, even in auto
    got = falcon.dense(x, pw, cfg=FalconConfig())
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ W),
                               rtol=1e-3, atol=1e-3)


def test_planned_weight_stacked_and_getitem(rng):
    W = jnp.asarray(rng.standard_normal((3, 64, 48)), jnp.float32)
    pw = falcon.plan_weight(W, cfg=FORCE)
    assert pw.precombined and pw.bt.shape[0] == 3
    x = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)
    got = falcon.dense(x, pw[1], cfg=FORCE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ W[1]),
                               rtol=1e-3, atol=1e-3)


def test_precombine_params_plans_dense_projections(rng):
    params = {
        "layers": {"attn": {"w_q": jnp.asarray(
            rng.standard_normal((2, 64, 64)), jnp.float32)}},
        "embed": jnp.asarray(rng.standard_normal((100, 64)), jnp.float32),
        "final_norm": jnp.ones((64,), jnp.float32),
    }
    new, n = falcon.precombine_params(params, cfg=FORCE, m_hint=256)
    assert n == 1
    assert isinstance(new["layers"]["attn"]["w_q"], falcon.PlannedWeight)
    assert new["embed"] is params["embed"]          # not a projection pattern
    assert new["final_norm"] is params["final_norm"]


def test_precombine_params_idempotent(rng):
    params = {"w_q": jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)}
    once, n1 = falcon.precombine_params(params, cfg=FORCE, m_hint=256)
    twice, n2 = falcon.precombine_params(once, cfg=FORCE, m_hint=256)
    assert n1 == 1 and n2 == 0
    assert isinstance(twice["w_q"], falcon.PlannedWeight)
    assert not isinstance(twice["w_q"].w, falcon.PlannedWeight)
    x = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)
    got = falcon.dense(x, twice["w_q"], cfg=FORCE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ params["w_q"]),
                               rtol=1e-3, atol=1e-3)


def test_planned_weight_pallas_backend(rng):
    # the precombined serving path must route through the selected backend's
    # apply_precombined (kernel pipeline), not silently fall back to jnp
    W = jnp.asarray(rng.standard_normal((64, 48)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((10, 64)), jnp.float32)
    cfg = dataclasses.replace(FORCE, backend="pallas_interpret")
    pw = falcon.plan_weight(W, cfg=cfg)
    got = falcon.dense(x, pw, cfg=cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ W),
                               rtol=1e-3, atol=1e-3)


def test_backend_apply_precombined_is_dispatched(rng):
    calls = []

    def pre_spy(a2, bt, l, n_logical, cfg):
        calls.append((a2.shape, bt.shape, l.name, n_logical))
        return backends.get_backend("jnp").apply_precombined(
            a2, bt, l, n_logical, cfg)

    falcon.register_backend("pre_spy", backends.get_backend("jnp").apply,
                            apply_precombined=pre_spy)
    try:
        cfg = dataclasses.replace(FORCE, backend="pre_spy")
        W = jnp.asarray(rng.standard_normal((32, 32)), jnp.float32)
        x = jnp.asarray(rng.standard_normal((8, 32)), jnp.float32)
        pw = falcon.plan_weight(W, cfg=cfg)
        got = falcon.dense(x, pw, cfg=cfg)
        assert calls and calls[0][2] == "strassen" and calls[0][3] == 32
        np.testing.assert_allclose(np.asarray(got), np.asarray(x @ W),
                                   rtol=1e-3, atol=1e-3)
    finally:
        falcon.unregister_backend("pre_spy")


def test_dot_general_accepts_planned_weight(rng):
    W = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)
    pw = falcon.plan_weight(W, cfg=FORCE)
    dn = (((1,), (0,)), ((), ()))
    got = falcon.dot_general(x, pw, dn, cfg=FORCE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ W),
                               rtol=1e-3, atol=1e-3)
    with pytest.raises(ValueError, match="canonical dense contraction"):
        falcon.dot_general(x, pw, (((0,), (0,)), ((), ())), cfg=FORCE)


# ---------------------------------------------------------------------------
# Deprecation shim: legacy fcfg arguments warn; ported paths are clean
# ---------------------------------------------------------------------------

def test_explicit_fcfg_still_works_but_warns(rng):
    from repro.models.layers import mlp_apply
    p = {"mlp_up": jnp.asarray(rng.standard_normal((16, 32)), jnp.float32),
         "mlp_down": jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)}
    x = jnp.asarray(rng.standard_normal((2, 4, 16)), jnp.float32)
    with pytest.warns(DeprecationWarning, match="falcon.use"):
        got = mlp_apply(p, x, FalconConfig(enabled=False))
    want = mlp_apply(p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_model_forward_ported_path_is_warning_free():
    from repro.configs import registry
    from repro.models import model as M
    cfg = registry.smoke_config("granite_3_2b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 8), jnp.int32)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        with falcon.use(M.falcon_config_for(cfg)):
            hidden, _, _ = M.forward(params, cfg, tokens)
            loss, _ = M.lm_loss(params, cfg,
                                {"tokens": tokens, "labels": tokens})
    assert np.isfinite(float(loss))


def test_forward_fcfg_kwarg_warns_and_overrides():
    from repro.configs import registry
    from repro.models import model as M
    cfg = registry.smoke_config("granite_3_2b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 4), jnp.int32)
    with pytest.warns(DeprecationWarning):
        M.forward(params, cfg, tokens, fcfg=FalconConfig(enabled=False))


# ---------------------------------------------------------------------------
# Satellites: _dtype_bytes fallback, shard round-up, compat shims
# ---------------------------------------------------------------------------

def test_dtype_bytes_extended_dtypes():
    assert dec._dtype_bytes("bfloat16") == 2
    assert dec._dtype_bytes("int32") == 4
    assert dec._dtype_bytes("float8_e4m3fn") == 1
    with pytest.raises(ValueError, match="unknown dtype"):
        dec._dtype_bytes("not_a_dtype")


def test_decide_on_extended_dtype_does_not_raise():
    d = dec.decide(4096, 4096, 4096, "tpu_v5e", "int32")
    assert d.gemm_seconds > 0


def test_plan_shards_round_up_not_truncate(caplog):
    cfg = FalconConfig(mode="gemm", shards=(3, 1, 1))
    d = plan(100, 64, 64, cfg, "float32")
    assert d.M == 34  # ceil(100/3), not 33
    cfg16 = FalconConfig(mode="gemm", shards=(16, 1, 16))
    d2 = plan(100, 64, 100, cfg16, "float32")
    assert d2.M == 7 and d2.N == 7
    with pytest.raises(ValueError, match="shards"):
        plan(64, 64, 64, FalconConfig(shards=(0, 1, 1)), "float32")


def test_plan_shards_warns_once(caplog):
    import logging
    cfg = FalconConfig(mode="gemm", shards=(7, 1, 1))
    with caplog.at_level(logging.WARNING, logger="repro.core.falcon_gemm"):
        plan(99, 32, 32, cfg, "float32")
        plan(99, 32, 32, cfg, "float32")
    hits = [r for r in caplog.records if "do not divide" in r.message]
    assert len(hits) == 1


def test_compat_mesh_roundtrip():
    from repro import compat
    assert compat.get_abstract_mesh() is None
    mesh = compat.make_mesh((1,), ("data",))
    with jax.set_mesh(mesh):
        m = compat.get_abstract_mesh()
        assert m is not None and "data" in m.axis_names
    assert compat.get_abstract_mesh() is None


def test_compat_shard_map_single_device():
    from jax.sharding import PartitionSpec as P
    from repro import compat
    mesh = compat.make_mesh((1,), ("data",))
    f = jax.shard_map(lambda x: x * 2, mesh=mesh, in_specs=P("data"),
                      out_specs=P("data"), check_vma=False)
    np.testing.assert_array_equal(
        np.asarray(f(jnp.arange(4.0))), np.arange(4.0) * 2)
