"""Serving over a four-device mesh, ``{"data": 1, "model": 4}``, as
``launch/serve.py --mesh 1,4`` builds the engine.

A tiny dense model with 4 KV heads serves a padded decode bucket, a
two-chunk prefill and two prefix-cache hits; the served tokens and logits
are held to per-request eager decode (``tests/_serve_reference.py``). The
slot cache's K and V shard by KV head over "model" and go into each step
donated; warm-up leaves the live steps nothing to compile; the compiled
decode step gathers no cache rows. A model with 2 KV heads, which the
4-way axis does not divide, keeps a replicated cache and serves the same
way, speculative rounds (the self-draft's own cache) included. An engine
that serves unwarmed counts its live compiles, the counter's positive
control. Attention groups each KV head's query heads where the axis divides
the KV heads (16 query heads over 4 KV heads: no all-gather, the
one-device result) and repeats K/V up to the query heads where it does not
(2 KV heads), which ``engine.summary()["attn_kv_layout"]`` reports. All of it runs in one child process, since the devices are
simulated only where ``XLA_FLAGS`` is set before JAX starts.
"""
import json

import pytest

from conftest import REPO, run_multidevice

CHILD = f"""
import dataclasses, json, re, sys
sys.path.insert(0, {REPO!r})
import jax
import numpy as np
import repro.api as falcon
from repro.configs import smoke_config
from repro.serve import ServeEngine
from tests._serve_reference import assert_matches_reference

MESH = {{"data": 1, "model": 4}}


def prompt(rng, n):
    return [int(t) for t in rng.integers(1, 256, n)]


def matches(eng, reqs):
    try:
        for r in reqs:
            assert_matches_reference(eng, r)
        return True
    except AssertionError as e:
        print("MISMATCH", e, file=sys.stderr)
        return False


def cache_all_gathers(eng):
    # result shapes of the all-gathers in the compiled decode step (bucket 2)
    args = [np.zeros(s, np.int32) for s in ((2,), (2, 1), (2,))]
    with falcon.use(eng.fcfg), eng._mesh_ctx():
        txt = eng._decode_fn.lower(eng.params, eng.cache, *args).compile().as_text()
    return re.findall(r"= \\w+\\[([\\d,]*)\\]\\S* all-gather(?:-start)?\\(", txt)


def serve(num_kv_heads, num_heads=4):
    cfg = dataclasses.replace(smoke_config("granite_3_2b"),
                              num_heads=num_heads, num_kv_heads=num_kv_heads)
    eng = ServeEngine(cfg, max_slots=4, max_prompt_len=16, max_new_tokens=4,
                      record_logits=True, seed=0, mesh_shape=MESH,
                      prefill_chunk=8, prefix_cache=True)
    eng.warm()
    rng = np.random.default_rng(11)
    a = prompt(rng, 11)
    # three short prompts decode in the bucket of 4 with a pad row
    reqs = [eng.submit(prompt(rng, n), max_new_tokens=4) for n in (5, 7, 3)]
    old = jax.tree.leaves(eng.cache)
    assert eng.step()
    donated = all(c.is_deleted() for c in old)
    eng.run()
    # the 13-token prompt prefills in chunks of 8 and 5
    reqs += [eng.submit(prompt(rng, 13), max_new_tokens=4),
             eng.submit(a, max_new_tokens=4)]
    eng.run()
    # a resubmitted and an extended prompt: two prefix-cache loads
    reqs += [eng.submit(a + prompt(rng, 3), max_new_tokens=4),
             eng.submit(list(a), max_new_tokens=4)]
    eng.run()
    st = eng.summary()
    return {{"match": all(r.done for r in reqs) and matches(eng, reqs),
            "specs": {{k: str(c.sharding.spec) for k, c in eng.cache.items()}},
            "donated": donated,
            "live_compiles": st["live_compiles"],
            "prefix_hits": st["prefix_hits"],
            "kv_eager_writes": st["kv_eager_writes"],
            "padded_decode": st["decode_padded_tokens"] > st["decode_real_rows"],
            "attn_kv_layout": st["attn_kv_layout"],
            "all_gathers": cache_all_gathers(eng)}}


def speculate(num_kv_heads):
    cfg = dataclasses.replace(smoke_config("granite_3_2b"),
                              num_kv_heads=num_kv_heads)
    eng = ServeEngine(cfg, max_slots=4, max_prompt_len=16, max_new_tokens=4,
                      record_logits=True, seed=0, mesh_shape=MESH, speculate=2)
    eng.warm()
    rng = np.random.default_rng(5)
    reqs = [eng.submit(prompt(rng, n), max_new_tokens=4) for n in (5, 9, 3)]
    eng.run()
    st = eng.summary()
    return {{"match": matches(eng, reqs), "live_compiles": st["live_compiles"],
            "verify_steps": st["verify_steps"]}}


def unwarmed():
    # the counter's positive control: served without warm(), every step
    # shape compiles live, one executable each
    eng = ServeEngine(smoke_config("granite_3_2b"), max_slots=4,
                      max_prompt_len=16, max_new_tokens=4, seed=0,
                      mesh_shape=MESH)
    rng = np.random.default_rng(11)
    for n in (5, 7, 3):
        eng.submit(prompt(rng, n), max_new_tokens=4)
    eng.run()
    st = eng.summary()
    return {{"live_compiles": st["live_compiles"],
            "bucket_misses": st["bucket_misses"]}}


def attention(num_kv_heads):
    # 16 query heads over a 4-way model axis, as the engine shards them;
    # K/V by KV head where the axis divides them, else replicated
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import compat
    from repro.models.layers import flash_attention
    from repro.parallel.sharding import kv_layout
    mesh = compat.make_mesh((1, 4), ("data", "model"))
    rng = np.random.default_rng(3)
    B, S, L, H, hd = 2, 8, 32, 16, 8
    q = rng.standard_normal((B, S, H, hd)).astype(np.float32)
    k, v = (rng.standard_normal((B, L, num_kv_heads, hd)).astype(np.float32)
            for _ in range(2))
    start = np.array([3, 11])
    qpos = start[:, None] + np.arange(S)
    kpos = np.broadcast_to(np.arange(L), (B, L))
    valid = kpos < (start + S)[:, None]

    def fn(q, k, v):
        return flash_attention(q, k, v, qpos, kpos, 0, kv_valid=valid,
                               q_chunk=4, ragged=True)

    want = np.asarray(jax.jit(fn)(q, k, v))
    heads = NamedSharding(mesh, P(None, None, "model", None))
    kv = heads if num_kv_heads % 4 == 0 else NamedSharding(mesh, P())
    with jax.set_mesh(mesh):
        layout = kv_layout(H, num_kv_heads)
        step = jax.jit(fn, in_shardings=(heads, kv, kv))
        got = np.asarray(step(q, k, v))
        txt = step.lower(q, k, v).compile().as_text()
    return {{"layout": layout,
            "match": bool(np.allclose(got, want, rtol=1e-5, atol=1e-5)),
            "all_gathers": len(re.findall(r" all-gather(?:-start)?\\(", txt))}}


print("RESULT", json.dumps({{"hkv4": serve(4), "hkv2": serve(2),
                            "gqa": serve(4, num_heads=16),
                            "attention_hkv4": attention(4),
                            "attention_hkv2": attention(2),
                            "speculate": speculate(4),
                            "speculate_hkv2": speculate(2),
                            "unwarmed": unwarmed()}}))
"""


@pytest.fixture(scope="module")
def served():
    out = run_multidevice(CHILD, n_devices=4, timeout=900)
    line = next(l for l in out.splitlines() if l.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


CASES = ["hkv4", "hkv2", "gqa", "speculate", "speculate_hkv2"]


@pytest.mark.parametrize("case", CASES)
def test_served_logits_match_eager_decode(served, case):
    assert served[case]["match"] is True


@pytest.mark.parametrize("case", ["hkv4", "hkv2"])
def test_each_path_is_exercised(served, case):
    got = served[case]
    assert got["padded_decode"]
    assert got["prefix_hits"] == 2 and got["kv_eager_writes"] == 2


@pytest.mark.parametrize("case,spec", [
    ("hkv4", "PartitionSpec(None, None, None, 'model', None)"),
    ("gqa", "PartitionSpec(None, None, None, 'model', None)"),
    # 2 KV heads on a 4-way axis: replicated, as the parameter table does
    ("hkv2", "PartitionSpec(None, None, None, None, None)")])
def test_kv_leaves_shard_by_kv_head_and_are_donated(served, case, spec):
    assert served[case]["specs"] == {"k": spec, "v": spec}
    assert served[case]["donated"] is True


@pytest.mark.parametrize("case", CASES)
def test_warm_leaves_live_steps_nothing_to_compile(served, case):
    assert served[case]["live_compiles"] == 0
    if case.startswith("speculate"):
        assert served[case]["verify_steps"] > 0


def test_an_unwarmed_engine_counts_its_live_compiles(served):
    got = served["unwarmed"]
    assert got["live_compiles"] == got["bucket_misses"] >= 2


def test_the_decode_step_gathers_no_cache_rows(served):
    """Only the logits (vocabulary over "model") are gathered; no operand
    of the cache's rank (layers, rows, positions, KV heads, head dim)."""
    gathers = served["hkv4"]["all_gathers"]
    assert gathers
    assert all(len(s.split(",")) < 5 for s in gathers), gathers


@pytest.mark.parametrize("case,layout", [
    ("hkv4", "grouped"), ("gqa", "grouped"),
    # 2 KV heads on a 4-way axis: K/V repeated up to the query heads
    ("hkv2", "repeat")])
def test_the_engine_reports_how_attention_meets_the_kv_heads(served, case,
                                                             layout):
    assert served[case]["attn_kv_layout"] == layout


@pytest.mark.parametrize("case,layout", [("attention_hkv4", "grouped"),
                                         ("attention_hkv2", "repeat")])
def test_attention_groups_query_heads_where_the_axis_divides_kv_heads(
        served, case, layout):
    """Both layouts give the one-device result; the grouped one keeps each
    device's query group beside its KV head, so nothing is gathered."""
    got = served[case]
    assert got["layout"] == layout
    assert got["match"] is True
    if layout == "grouped":
        assert got["all_gathers"] == 0
