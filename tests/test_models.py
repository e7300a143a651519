"""Model zoo: per-arch smoke, decode==forward consistency, SSD correctness."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import model as M
from repro.models import ssd as SSD


def _batch_for(cfg, rng, B=2, S=16):
    if cfg.frontend == "audio_codebooks":
        t = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S, cfg.num_codebooks)),
                        jnp.int32)
        return {"tokens": t, "labels": t}
    t = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    out = {"tokens": t, "labels": t}
    if cfg.frontend == "vision_patches":
        out["patch_embeds"] = jnp.asarray(
            rng.standard_normal((B, cfg.num_patches, cfg.d_model)), jnp.float32)
    return out


@pytest.mark.parametrize("arch", registry.list_archs())
def test_arch_smoke_train_and_decode(arch, rng):
    """Reduced config: one loss eval (finite) + one cached decode step."""
    cfg = registry.smoke_config(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch_for(cfg, rng)
    loss, metrics = jax.jit(lambda p, b: M.lm_loss(p, cfg, b))(params, batch)
    assert np.isfinite(float(loss)), arch
    assert float(loss) > 0
    cache = M.init_cache(cfg, 2, 32)
    tok = batch["tokens"][:, :1]
    logits, new_cache, _ = jax.jit(
        lambda p, t, c: M.forward(p, cfg, t, cache=c, cache_index=0,
                                  logits_mode="last"))(params, tok, cache)
    assert np.all(np.isfinite(np.asarray(logits, np.float32))), arch


@pytest.mark.parametrize("arch", ["granite_3_2b", "hymba_1_5b", "mamba2_370m"])
def test_decode_matches_full_forward(arch, rng):
    """Autoregressive consistency: prefill+decode logits == full forward."""
    cfg = dataclasses.replace(registry.smoke_config(arch), remat=False)
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    B, S = 1, 12
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)

    # full forward logits at every position
    hidden, _, _ = M.forward(params, cfg, tokens, logits_mode="none")
    full_logits = M.compute_logits(params, cfg, hidden)

    # prefill on the first S-1 tokens, then decode token S-1
    cache = M.init_cache(cfg, B, S + 4)
    _, cache, _ = M.forward(params, cfg, tokens[:, :S - 1], cache=cache,
                            cache_index=0, logits_mode="none")
    dec_logits, _, _ = M.forward(params, cfg, tokens[:, S - 1:S], cache=cache,
                                 cache_index=S - 1, logits_mode="last")
    np.testing.assert_allclose(
        np.asarray(dec_logits[:, 0], np.float32),
        np.asarray(full_logits[:, -1], np.float32), rtol=2e-3, atol=2e-3)


def test_ssd_scan_matches_sequential(rng):
    """Chunked SSD == naive per-step recurrence."""
    B, L, H, P, G, N = 2, 16, 4, 8, 2, 16
    chunk = 4
    x = jnp.asarray(rng.standard_normal((B, L, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.1, 0.9, (B, L, H)), jnp.float32)
    A = jnp.asarray(rng.uniform(-1.5, -0.2, (H,)), jnp.float32)
    B_ = jnp.asarray(rng.standard_normal((B, L, G, N)) * 0.3, jnp.float32)
    C_ = jnp.asarray(rng.standard_normal((B, L, G, N)) * 0.3, jnp.float32)

    y_chunk, s_chunk = SSD.ssd_scan(x, dt, A, B_, C_, chunk)

    # sequential oracle via the decode step
    s = jnp.zeros((B, H, N, P), jnp.float32)
    ys = []
    for t in range(L):
        y_t, s = SSD.ssd_decode_step(x[:, t:t + 1], dt[:, t:t + 1], A,
                                     B_[:, t:t + 1], C_[:, t:t + 1], s)
        ys.append(y_t)
    y_seq = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_seq),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s_chunk), np.asarray(s),
                               rtol=2e-4, atol=2e-4)


def test_gemma3_layer_window_pattern():
    cfg = registry.get_config("gemma3_27b")
    w = cfg.layer_windows()
    assert len(w) == 62
    assert w[5] == 0 and all(x == 1024 for x in w[:5])  # 5 local : 1 global
    assert sum(1 for x in w if x == 0) == 62 // 6


def test_sliding_window_masks_old_tokens(rng):
    """A token beyond the window must not influence local-attention logits."""
    from repro.models.layers import attention_scores
    B, S, H, hd = 1, 8, 2, 4
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    pos = jnp.arange(S)[None]
    out1 = attention_scores(q, k, v, pos, pos, window=2)
    k2 = k.at[:, 0].set(99.0)  # outside the window of the last query
    v2 = v.at[:, 0].set(99.0)
    out2 = attention_scores(q, k2, v2, pos, pos, window=2)
    np.testing.assert_allclose(np.asarray(out1[:, -1]), np.asarray(out2[:, -1]),
                               atol=1e-5)


def test_param_counts_match_literature():
    """Full-config param counts are in the right ballpark (catches config typos)."""
    import repro.launch  # noqa: F401
    expect = {
        "granite_3_2b": (2.0e9, 3.5e9),
        "gemma3_27b": (24e9, 30e9),
        "starcoder2_15b": (13e9, 17e9),
        "mistral_nemo_12b": (11e9, 14e9),
        "kimi_k2_1t": (0.95e12, 1.15e12),
        "dbrx_132b": (1.2e11, 1.45e11),
        "mamba2_370m": (3.0e8, 4.6e8),
        "hymba_1_5b": (1.2e9, 1.9e9),
        "musicgen_large": (1.5e9, 2.6e9),
        "pixtral_12b": (11e9, 14e9),
    }
    import jax as _jax
    from repro.configs import get_config
    from repro.models import model as MM
    for arch, (lo, hi) in expect.items():
        cfg = get_config(arch)
        sds = _jax.eval_shape(lambda c=cfg: MM.init_params(c, _jax.random.PRNGKey(0)))
        n = sum(int(np.prod(l.shape)) for l in _jax.tree.leaves(sds))
        assert lo <= n <= hi, f"{arch}: {n:.3e} outside [{lo:.1e},{hi:.1e}]"


def _repeated_attention(q, k, v, qpos, kpos, window, kv_valid=None,
                        ragged=False):
    """GQA by repeating K/V up to the query heads: the reference form."""
    from repro.models.layers import NEG_INF, _mask
    hd = q.shape[-1]
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    logits *= 1.0 / np.sqrt(hd)
    if ragged:
        m = jax.vmap(lambda qp, kp: _mask(qp, kp, window))(qpos, kpos)
        if kv_valid is not None:
            m = m & kv_valid[:, None, :]
        logits = jnp.where(m[:, None], logits, NEG_INF)
    else:
        m = _mask(qpos[0], kpos[0], window)
        if kv_valid is not None:
            m = m & kv_valid[0][None, :]
        logits = jnp.where(m[None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("ragged", [False, True], ids=["uniform", "ragged"])
@pytest.mark.parametrize("rep", [1, 4, 12])
def test_grouped_attention_matches_the_repeated_form(rep, ragged, dtype, rng):
    """Each KV head contracting against its own query group equals K/V
    repeated up to the query heads, up to summation order: full causal, a
    sliding window, a cache with ``kv_valid``, and (through
    ``flash_attention``) a query longer than ``q_chunk``, whose
    ``jax.checkpoint`` backward gives the same gradients."""
    from repro.models.layers import attention_scores, flash_attention
    B, Sq, Sk, Hkv, hd = 2, 16, 24, 2, 8
    H = Hkv * rep
    dt = jnp.dtype(dtype)
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == "float32" \
        else dict(rtol=2e-2, atol=2e-2)

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), dt)

    q, k, v = arr(B, Sq, H, hd), arr(B, Sk, Hkv, hd), arr(B, Sk, Hkv, hd)
    # rows at their own offsets when ragged, else one offset for the batch
    start = jnp.asarray([8, 3] if ragged else [8, 8])
    qpos = start[:, None] + jnp.arange(Sq)[None]
    kpos = jnp.broadcast_to(jnp.arange(Sk)[None], (B, Sk))
    kv_valid = kpos < (start + Sq)[:, None]
    cases = [dict(window=0), dict(window=5), dict(window=0, kv_valid=kv_valid)]

    def close(a, b):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol)

    for kw in cases:
        want = _repeated_attention(q, k, v, qpos, kpos, ragged=ragged, **kw)
        close(attention_scores(q, k, v, qpos, kpos, ragged=ragged, **kw), want)
        close(flash_attention(q, k, v, qpos, kpos, q_chunk=8, ragged=ragged,
                              **kw), want)
    if dtype == "float32":
        def loss(fn, **kw):
            return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v, qpos, kpos, 5,
                                                      ragged=ragged, **kw)))
        got = jax.grad(loss(flash_attention, q_chunk=8), (0, 1, 2))(q, k, v)
        want = jax.grad(loss(_repeated_attention), (0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            close(g, w)


def _cell_attention_shapes():
    """(cell, heads, KV heads, head dim, shards, [(batch, query length,
    keys)]) of every benchmark cell: its prefill and decode buckets."""
    import json
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        bench = json.load(f)
    confs = {c["name"]: c["file"] for c in bench["configs"]}
    out = []
    for w in bench["workloads"]:
        with open(os.path.join(repo, "bench", "cells", w["name"] + ".json")) as f:
            cell = json.load(f)
        with open(os.path.join(repo, confs[w["config"]])) as f:
            conf = json.load(f)
        H, Hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
        hd = conf.get("head_dim") or conf["hidden_size"] // H
        mesh = cell.get("mesh", {})
        shards = (mesh.get("data", 1), 1, mesh.get("model", 1))
        L = cell["max_len"]
        shapes = [(b, s, L) for b in cell["prefill_batch"]
                  for s in cell["prefill_seq"]]
        shapes += [(b, 1, L) for b in cell["decode_batch"]]
        out.append((w["name"], H, Hkv, hd, shards, shapes))
    return out


@pytest.mark.parametrize("cell", _cell_attention_shapes(), ids=lambda c: c[0])
def test_grouped_attention_keeps_the_plain_gemm_route(cell, monkeypatch):
    """On the TPU v5e profile, the attention contractions of every benchmark
    cell's buckets stay plain ``lax.dot_general`` now that they are grouped
    (B·Hkv groups of rep·Sq query rows), as they were repeated (B·H groups
    of Sq rows): the Decision Module picks no LCMA for either."""
    import repro.api as falcon
    from repro.core import engine
    from repro.core.falcon_gemm import FalconConfig, plan_batched
    from repro.models.layers import flash_attention

    name, H, Hkv, hd, shards, shapes = cell
    cfg = FalconConfig(hardware="tpu_v5e", shards=shards)
    rep = H // Hkv
    routes = []
    route = engine._route_grouped

    def spy(G, M_, K, N, cfg_, dtype, shared_b):
        use_core, d = route(G, M_, K, N, cfg_, dtype, shared_b)
        routes.append(((G, M_, K, N), d.use_lcma))
        return use_core, d

    monkeypatch.setattr(engine, "_route_grouped", spy)
    bf16 = jnp.bfloat16
    for B, S, L in shapes:
        sds = jax.ShapeDtypeStruct
        args = (sds((B, S, H, hd), bf16), sds((B, L, Hkv, hd), bf16),
                sds((B, L, Hkv, hd), bf16), sds((B, S), jnp.int32),
                sds((B, L), jnp.int32))
        routes.clear()
        with falcon.use(cfg):
            jax.eval_shape(lambda q, k, v, qp, kp: flash_attention(
                q, k, v, qp, kp, 0, kv_valid=kp < L, ragged=True), *args)
        sq = min(S, 512)                     # flash_attention's query chunk
        grouped = [(B * Hkv, rep * sq, hd, L), (B * Hkv, rep * sq, L, hd)]
        assert routes == [(s, False) for s in grouped], (name, B, S, routes)
        for G, M_, K, N in ((B * H, sq, hd, L), (B * H, sq, L, hd)):
            assert not plan_batched(G, M_, K, N, cfg, "bfloat16").use_lcma
