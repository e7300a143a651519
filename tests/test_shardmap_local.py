import numpy as np
from conftest import run_multidevice

def test_shard_map_local_backend():
    out = run_multidevice("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import compat
        from repro.core.falcon_gemm import FalconConfig, falcon_dense
        from repro.parallel import sharding as SH
        mesh = compat.make_mesh((4, 2), ("data", "model"))
        SH.set_parallel_style("fsdp_only")
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 12, 48))
        w = jax.random.normal(jax.random.PRNGKey(1), (48, 40))
        cfg = FalconConfig(mode="strassen", backend="shard_map_local")
        with jax.set_mesh(mesh):
            got = jax.jit(lambda a, b: falcon_dense(a, b, cfg))(x, w)
            # grads flow through the shard_map + LCMA path
            g = jax.jit(jax.grad(lambda b: jnp.sum(falcon_dense(x, b, cfg) ** 2)))(w)
        ref = np.asarray(x) @ np.asarray(w)
        err = float(np.max(np.abs(np.asarray(got) - ref)))
        assert err < 1e-3, err
        g0 = jax.grad(lambda b: jnp.sum((x @ b) ** 2))(w)
        gerr = float(jnp.max(jnp.abs(g - g0)))
        assert gerr < 1e-2, gerr
        print("SM_LOCAL_OK", err, gerr)
    """)
    assert "SM_LOCAL_OK" in out
