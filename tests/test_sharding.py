"""Sharding rules, divisibility filtering, shard_map MoE, compressed psum."""
import numpy as np

from conftest import run_multidevice


def test_spec_divisibility_filtering():
    from repro.parallel.sharding import ShardingRules
    rules = ShardingRules(
        rules=((r"w_q", (None, "model")), (r"embed", ("model", None))),
        batch=("data",), axis_sizes=(("data", 16), ("model", 16)))
    # divisible: kept
    assert str(rules.spec_for("layers/attn/w_q", (2048, 1600))) == \
        str(rules.spec_for("layers/attn/w_q", (2048, 1600)))
    s = rules.spec_for("layers/attn/w_q", (2048, 1600))
    assert s[1] == "model"
    # not divisible (hymba 25 heads -> 25*hd=... use odd dim): dropped
    s2 = rules.spec_for("layers/attn/w_q", (2048, 1601))
    assert s2[1] is None
    # leading stacked-layer dim is padded with None
    s3 = rules.spec_for("embed", (4, 49152, 64))
    assert s3[0] is None and s3[1] == "model"


def test_shard_act_identity_without_mesh():
    import jax.numpy as jnp
    from repro.parallel.sharding import shard_act
    x = jnp.ones((4, 4))
    y = shard_act(x, ("pod", "data"), "model")
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_moe_shardmap_equals_dense():
    out = run_multidevice("""
        import jax, jax.numpy as jnp, numpy as np
        import repro.api as falcon
        from repro import compat
        from repro.models import moe as MOE
        p = MOE.moe_init(jax.random.PRNGKey(0), 32, 64, 8, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32))
        with falcon.use(falcon.FalconConfig(enabled=False)):
            y0, _ = MOE._moe_dense(p, x, 2, 256)
            mesh = compat.make_mesh((4, 2), ("data", "model"))
            with jax.set_mesh(mesh):
                y1, _ = jax.jit(lambda p_, x_: MOE.moe_apply(
                    p_, x_, 2, 1.25, deterministic_capacity=256))(p, x)
        err = float(jnp.max(jnp.abs(y0 - y1)))
        assert err < 1e-5, err
        print("MOE_OK", err)
    """)
    assert "MOE_OK" in out


def test_moe_shardmap_precombined_without_raw_weight():
    """keep_weight=False expert PlannedWeights must shard over the mesh.

    The B̃-only precombine drops the raw (E, K, N) arrays to halve expert
    HBM; the shard_map path used to raise on it, forcing keep_weight=True
    under any TP mesh. Now the stacked B̃ crosses the boundary (sharded on
    the expert dim) and is re-wrapped per device.
    """
    out = run_multidevice("""
        import jax, jax.numpy as jnp, numpy as np
        import repro.api as falcon
        from repro import compat
        from repro.core import engine
        from repro.models import moe as MOE
        p = MOE.moe_init(jax.random.PRNGKey(0), 32, 64, 8, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32))
        cfg = falcon.FalconConfig(mode="strassen", backend="jnp",
                                  use_plan_cache=False)
        with falcon.use(cfg):
            for k in ("moe_gate", "moe_up", "moe_down"):
                p[k] = engine.plan_weight(p[k], keep_weight=False, grouped=True)
                assert p[k].w is None and p[k].bt is not None, k
            y0, _ = MOE._moe_dense(p, x, 2, 256)
            mesh = compat.make_mesh((4, 2), ("data", "model"))
            with jax.set_mesh(mesh):
                y1, _ = jax.jit(lambda p_, x_: MOE.moe_apply(
                    p_, x_, 2, 1.25, deterministic_capacity=256))(p, x)
        err = float(jnp.max(jnp.abs(y0 - y1)))
        assert err < 1e-4, err
        print("MOE_PRE_OK", err)
    """)
    assert "MOE_PRE_OK" in out


def test_compressed_psum_accuracy_and_train_step():
    out = run_multidevice("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro import compat
        from repro.parallel.compression import compressed_psum_mean, psum_mean
        mesh = compat.make_mesh((8,), ("data",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 128)) * 0.01

        def body(gl):
            exact = psum_mean({"g": gl}, ("data",))["g"]
            comp = compressed_psum_mean({"g": gl}, ("data",))["g"]
            return exact, comp
        with jax.set_mesh(mesh):
            exact, comp = jax.jit(jax.shard_map(
                body, in_specs=P("data", None),
                out_specs=(P(None, None), P(None, None)), check_vma=False))(g)
        rel = float(jnp.linalg.norm(exact - comp) / jnp.linalg.norm(exact))
        assert rel < 2e-2, rel
        print("COMP_OK", rel)

        # end-to-end: compressed-DP train step decreases loss
        from repro.configs import registry
        from repro.models import model as M
        from repro.optim import AdamWConfig, adamw_init
        from repro.data import DataConfig, SyntheticLMData
        from repro.train.steps import make_compressed_dp_train_step
        cfg = registry.smoke_config("granite_3_2b")
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        oc = AdamWConfig(lr=1e-3)
        ost = adamw_init(params, oc)
        data = SyntheticLMData(DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                                          global_batch=8))
        # warmup=1: the default 100-step warmup leaves lr_scale ~0 over a
        # short smoke run, reducing the "learns" assertion to batch noise.
        step = jax.jit(make_compressed_dp_train_step(cfg, oc, mesh, warmup=1))
        batch = data.batch(0)  # fixed batch: loss must drop deterministically
        with jax.set_mesh(mesh):
            losses = []
            for s in range(8):
                params, ost, m = step(params, ost, batch, s)
                losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] - 0.05, losses
        print("CDP_OK", round(losses[0], 3), round(losses[-1], 3))
    """, timeout=420)
    assert "COMP_OK" in out and "CDP_OK" in out


def test_param_sharding_rules_on_mesh():
    out = run_multidevice("""
        import jax, numpy as np
        from repro import compat
        from repro.configs import registry
        from repro.models import model as M
        from repro.parallel import sharding as SH
        mesh = compat.make_mesh((2, 4), ("data", "model"))
        cfg = registry.smoke_config("dbrx_132b")
        sds = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
        rules = SH.make_rules(mesh, fsdp=True)
        sh = SH.param_sharding(sds, mesh, rules)
        flat, _ = jax.tree_util.tree_flatten_with_path(sh)
        specs = {"/".join(str(getattr(p, "key", p)) for p in path): s.spec
                 for path, s in flat}
        moe_gate = [v for k, v in specs.items() if "moe_gate" in k][0]
        assert moe_gate[1] == "model", moe_gate   # experts over model (after L dim)
        wq = [v for k, v in specs.items() if "w_q" in k][0]
        assert "model" in str(wq)
        print("RULES_OK")
    """)
    assert "RULES_OK" in out
