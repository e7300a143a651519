"""One program per serve step: the slot cache goes into the jitted step as a
donated argument, and the step reads and writes its rows itself.

Served tokens and logits are held to per-request eager decode
(``tests/_serve_reference.py``) in every case that moves rows differently:
padded decode buckets, chunked prefill, prefix-cache loads, speculative
verify, a recurrent ``state`` leaf and a simulated two-device mesh. Pad rows
write nothing, the old cache is donated, and the programs keep the names a
device trace reads them by.
"""
import jax
import numpy as np
import pytest

from conftest import REPO, run_multidevice
from repro.configs import registry
from repro.serve import ServeEngine
from tests._serve_reference import assert_matches_reference

CFG = registry.smoke_config("granite_3_2b")


def _engine(arch="granite_3_2b", **kw):
    eng = ServeEngine(registry.smoke_config(arch), max_slots=4,
                      max_prompt_len=16, max_new_tokens=4, record_logits=True,
                      seed=0, **kw)
    eng.warm()
    return eng


def _prompt(rng, n, cfg=CFG):
    return [int(t) for t in rng.integers(1, cfg.vocab_size, n)]


def _serve(eng, waves, max_new_tokens=4):
    """Submit each wave of prompts, run it to the end; all requests."""
    reqs = []
    for wave in waves:
        reqs += [eng.submit(p, max_new_tokens=max_new_tokens) for p in wave]
        eng.run()
    assert all(r.done for r in reqs)
    return reqs


def _waves(case, rng):
    if case == "two_chunks":                # 13 tokens: chunks of 8 and 5
        return [[_prompt(rng, 13), _prompt(rng, 4)]]
    if case == "prefix_hit":                # resubmitted and extended prompt
        a = _prompt(rng, 11)
        return [[a], [a + _prompt(rng, 3), list(a)]]
    return [[_prompt(rng, 5), _prompt(rng, 7), _prompt(rng, 3)]]


CASES = {
    # three live rows run in the decode bucket of 4: one pad row
    "padded_decode": ("granite_3_2b", {}),
    "two_chunks": ("granite_3_2b", {"prefill_chunk": 8}),
    "prefix_hit": ("granite_3_2b", {"prefix_cache": True}),
    "speculate": ("granite_3_2b", {"speculate": 2}),
    # hybrid: K, V and the recurrent state leaf
    "state": ("hymba_1_5b", {}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_served_tokens_match_eager_decode(case):
    arch, kw = CASES[case]
    eng = _engine(arch, **kw)
    reqs = _serve(eng, _waves(case, np.random.default_rng(11)))
    for r in reqs:
        assert_matches_reference(eng, r)
    st = eng.stats
    assert st.bucket_misses == 0
    assert st.kv_eager_writes == st.prefix_hits
    if case == "padded_decode":
        assert st.decode_padded_tokens > st.decode_real_rows
    elif case == "two_chunks":
        assert st.prefill_steps >= 2 and st.prompt_tokens == 17
    elif case == "prefix_hit":
        assert st.prefix_hits == 2
    elif case == "speculate":
        assert st.verify_steps > 0 and st.decode_steps == 0
    else:
        assert "state" in eng.cache


def test_mesh_engine_matches_eager_decode_and_donates():
    """Two simulated devices, model axis 2: the cache, sharded by KV head
    (4 KV heads over the axis of 2), goes in donated and comes back with its
    sharding, and the served tokens and logits match eager decode."""
    out = run_multidevice(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import jax
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import smoke_config
        from repro.serve import ServeEngine
        from tests._serve_reference import assert_matches_reference

        cfg = smoke_config("granite_3_2b")
        eng = ServeEngine(cfg, max_slots=4, max_prompt_len=16,
                          max_new_tokens=4, record_logits=True, seed=0,
                          mesh_shape={{"data": 1, "model": 2}})
        eng.warm()
        rng = np.random.default_rng(5)
        reqs = [eng.submit(rng.integers(1, cfg.vocab_size, n),
                           max_new_tokens=4) for n in (5, 9, 3)]
        old = jax.tree.leaves(eng.cache)
        assert eng.step()
        assert all(c.is_deleted() for c in old)
        eng.run()
        heads = NamedSharding(eng.mesh, P(None, None, None, "model", None))
        for c in jax.tree.leaves(eng.cache):
            assert c.sharding.is_equivalent_to(heads, c.ndim), c.sharding
        for r in reqs:
            assert_matches_reference(eng, r)
        print("MESH_OK", len(reqs), eng.stats.kv_eager_writes)
    """, n_devices=2, timeout=600)
    assert "MESH_OK 3 0" in out


@pytest.fixture(scope="module")
def granite():
    return _engine()


def _spy_decode(eng):
    """Record (slots, bucket, cache before and after) of each decode step."""
    seen, run = [], eng._run_decode

    def spy(work):
        before = jax.tree.map(np.asarray, eng.cache)
        run(work)
        seen.append((list(work.slots), work.batch_pad, before,
                     jax.tree.map(np.asarray, eng.cache)))

    eng._run_decode = spy
    return seen


def test_padded_step_leaves_other_slots_bit_identical(granite):
    """A request that ends at its prefill leaves stale K/V in the last slot,
    the one a pad row's out-of-range index would clamp to; the three live
    requests then decode in the bucket of 4 with one pad row. Every slot
    outside the micro-batch keeps its bytes, and the step writes the rows
    of the real requests."""
    eng = granite
    seen = _spy_decode(eng)
    rng = np.random.default_rng(3)
    try:
        for n in (5, 7, 3):                 # slots 0, 1, 2
            eng.submit(_prompt(rng, n), max_new_tokens=4)
        eng.submit(_prompt(rng, 6), max_new_tokens=1)      # slot 3
        eng.run()
    finally:
        del eng._run_decode
    padded = [s for s in seen if len(s[0]) < s[1]]
    assert padded
    assert any(eng.max_slots - 1 not in s[0] for s in padded)
    for slots, _, before, after in padded:
        others = [i for i in range(eng.max_slots) if i not in slots]
        assert others
        for name in before:
            np.testing.assert_array_equal(after[name][:, others],
                                          before[name][:, others])
            assert not np.array_equal(after[name][:, slots],
                                      before[name][:, slots])


def test_warm_writes_nothing():
    """Warm runs every step program against the real cache with every slot
    out of range: a cache full of data comes back bit-identical."""
    eng = ServeEngine(CFG, max_slots=2, max_prompt_len=8, max_new_tokens=2,
                      seed=0)
    rng = np.random.default_rng(0)
    eng.cache = jax.tree.map(
        lambda c: jax.numpy.asarray(rng.standard_normal(c.shape), c.dtype),
        eng.cache)
    before = jax.tree.map(np.asarray, eng.cache)
    eng.warm()
    for name, c in eng.cache.items():
        np.testing.assert_array_equal(np.asarray(c), before[name])


def test_step_donates_the_slot_cache(granite):
    eng = granite
    eng.submit(_prompt(np.random.default_rng(1), 5), max_new_tokens=3)
    for _ in range(2):                      # a prefill, then a decode
        old = jax.tree.leaves(eng.cache)
        assert eng.step()
        assert all(c.is_deleted() for c in old)
        assert not any(c.is_deleted() for c in jax.tree.leaves(eng.cache))
    eng.run()


def test_step_programs_keep_their_names(granite):
    """Device traces read step time by program name (``jit_chunk_step``,
    ``jit_decode_step``, ``jit_verify_step``)."""
    eng = granite
    b, i32 = 2, jax.numpy.int32
    z = jax.numpy.zeros((b,), i32)
    slots = eng._pad_slots(b)
    lowered = {
        "chunk_step": eng._prefill_fn.lower(
            eng.params, eng.cache, slots, jax.numpy.zeros((b, 8), i32), z, z),
        "decode_step": eng._decode_fn.lower(
            eng.params, eng.cache, slots, jax.numpy.zeros((b, 1), i32), z),
        "verify_step": eng._verify_fn.lower(
            eng.params, eng.cache, slots, jax.numpy.zeros((b, 3), i32), z),
    }
    for name, low in lowered.items():
        assert f"module @jit_{name} " in low.as_text()


@pytest.mark.parametrize("prefix_cache", [False, True],
                         ids=["no_prefix_cache", "prefix_cache"])
def test_kv_eager_writes_counts_prefix_loads(prefix_cache):
    """The engine dispatches no eager program against the slot cache but
    one per prefix-cache load; ``summary()`` reports the count."""
    eng = ServeEngine(CFG, max_slots=2, max_prompt_len=16, max_new_tokens=2,
                      seed=0, prefix_cache=prefix_cache)
    eng.warm()
    a = _prompt(np.random.default_rng(2), 9)
    _serve(eng, [[a], [a, a + [7]]], max_new_tokens=2)
    s = eng.summary()
    assert s["kv_eager_writes"] == (2 if prefix_cache else 0)
    assert s["kv_eager_writes"] == s["prefix_hits"]
    assert "kv_gather" not in s["phase_s"] and "kv_scatter" not in s["phase_s"]


@pytest.mark.parametrize("warm", [True, False], ids=["warmed", "unwarmed"])
def test_live_compiles_counts_the_step_programs_compiled_after_warm(warm):
    """The counter reads 0 after ``warm()`` and, as its positive control,
    one executable per step shape on an engine that serves unwarmed (each
    shape is then a bucket miss, compiled live); ``summary()`` reports it."""
    eng = ServeEngine(CFG, max_slots=4, max_prompt_len=16, max_new_tokens=4,
                      seed=0)
    if warm:
        eng.warm()
    _serve(eng, _waves("padded_decode", np.random.default_rng(11)))
    s = eng.summary()
    if warm:
        assert s["live_compiles"] == s["bucket_misses"] == 0
    else:
        assert s["live_compiles"] == s["bucket_misses"] >= 2
