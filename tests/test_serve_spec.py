"""Speculative decoding, prefix reuse, chunked prefill and streaming.

The contract under test is *token-exactness*: every serving optimization in
this file — γ-token speculation with greedy verify/rollback, radix prefix-KV
reuse, fixed-bucket chunked prefill, per-token streaming — must emit exactly
the tokens the plain non-speculative engine emits, while adding zero
Decision-Module plan keys beyond ``warm()``. Properties (radix invariants,
bucket monotonicity) go through ``tests/_propcheck.py`` so they run with or
without hypothesis installed.
"""
import random
import threading

import numpy as np
import pytest

from repro.configs import registry
from repro.core import plan_cache
from repro.serve import (BucketPolicy, DraftModel, Request, RequestQueue,
                         Scheduler, SelfDraft, ServeEngine, ServeStats)
from repro.serve.prefix_cache import RadixPrefixCache
from repro.serve.scheduler import DecodeWork, PrefillWork
from tests._propcheck import given, settings, st

CFG = registry.smoke_config("granite_3_2b")

# one ragged request set shared by every exactness test in this file; the
# lengths cross both seq buckets (8, 16) and exercise chunk boundaries
PROMPT_LENS = (5, 11, 3, 16, 7, 9)


def _prompts(rng, cfg=CFG, lens=PROMPT_LENS):
    return [list(rng.integers(1, cfg.vocab_size, int(n))) for n in lens]


@pytest.fixture(scope="module")
def baseline():
    """Non-speculative reference: prompts -> greedy generations (+ logits)."""
    plan_cache.reset()
    engine = ServeEngine(CFG, max_slots=4, max_prompt_len=16,
                         max_new_tokens=6, record_logits=True, seed=0)
    engine.warm()
    prompts = _prompts(np.random.default_rng(3))
    reqs = [engine.submit(p, max_new_tokens=6) for p in prompts]
    engine.run()
    out = {tuple(p): (list(r.generated), [np.asarray(x) for x in r.logits])
           for p, r in zip(prompts, reqs)}
    return prompts, out


@pytest.fixture(scope="module")
def spec_served(baseline):
    """One engine with every tier-2 feature on, serving ``baseline``'s
    prompts twice (second pass = prefix-cache hits)."""
    prompts, _ = baseline
    plan_cache.reset()
    engine = ServeEngine(CFG, max_slots=4, max_prompt_len=16,
                         max_new_tokens=6, seed=0, speculate=2,
                         prefix_cache=True, prefill_chunk=8)
    engine.warm()
    first = [engine.submit(p, max_new_tokens=6) for p in prompts]
    engine.run()
    second = [engine.submit(p, max_new_tokens=6) for p in prompts]
    engine.run()
    return engine, first, second


# ---------------------------------------------------------------------------
# Radix prefix cache: properties against a naive reference
# ---------------------------------------------------------------------------

def _naive_longest_prefix(inserted: dict, key: tuple) -> tuple:
    best = ()
    for toks in inserted:
        if len(toks) > len(best) and key[:len(toks)] == toks:
            best = toks
    return best


@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_radix_longest_prefix_matches_naive(seed):
    """lookup() returns exactly the longest inserted key that prefixes the
    query — checked against a brute-force scan over random small-alphabet
    token sequences (shared prefixes guaranteed by the tiny alphabet)."""
    rnd = random.Random(seed)
    cache = RadixPrefixCache(max_entries=64)
    inserted = {}
    for i in range(30):
        toks = tuple(rnd.randrange(4) for _ in range(rnd.randint(1, 12)))
        cache.insert(toks, {"id": i})
        inserted[toks] = i
    for _ in range(30):
        query = tuple(rnd.randrange(4) for _ in range(rnd.randint(1, 14)))
        n, entry = cache.lookup(query)
        best = _naive_longest_prefix(inserted, query)
        assert n == len(best)
        if best:
            assert entry is not None and tuple(entry.tokens) == best
            assert entry.payload["id"] == inserted[best]
        else:
            assert entry is None


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=0, max_value=10_000),
       st.integers(min_value=1, max_value=8))
def test_radix_capacity_and_pinned_survival(seed, max_entries):
    """Eviction keeps ``entries <= max_entries`` whenever an unpinned victim
    exists, and a pinned entry is NEVER evicted regardless of pressure."""
    rnd = random.Random(seed)
    cache = RadixPrefixCache(max_entries=max_entries)
    pinned_key = tuple(rnd.randrange(4) for _ in range(6))
    cache.insert(pinned_key, {"pinned": True})
    n, entry = cache.lookup(pinned_key, pin=True)
    assert n == len(pinned_key)
    for i in range(4 * max_entries):
        toks = tuple(rnd.randrange(4) for _ in range(rnd.randint(1, 10)))
        if toks != pinned_key:
            cache.insert(toks, {"i": i})
        assert cache.stats()["entries"] <= max_entries + cache.stats()["pinned"]
        m, e = cache.lookup(pinned_key)
        assert m == len(pinned_key) and e is entry, \
            "pinned entry evicted under pressure"
    cache.release(entry)


def test_radix_lru_eviction_order():
    cache = RadixPrefixCache(max_entries=2)
    cache.insert((1, 2, 3), {"a": 1})
    cache.insert((1, 2, 4), {"b": 2})
    cache.lookup((1, 2, 3))                     # refresh a -> b is now LRU
    cache.insert((5, 6), {"c": 3})              # evicts b
    assert cache.lookup((1, 2, 3))[0] == 3
    # b is gone, and no surviving entry prefixes (1, 2, 4)
    assert cache.lookup((1, 2, 4)) == (0, None)
    assert cache.stats()["evictions"] == 1


def test_radix_edge_split_preserves_entries():
    cache = RadixPrefixCache(max_entries=8)
    cache.insert((7, 8, 9, 10), {"long": 1})
    cache.insert((7, 8), {"short": 1})          # splits the (7,8,9,10) edge
    n, e = cache.lookup((7, 8, 9, 10, 11))
    assert n == 4 and e.payload == {"long": 1}
    n, e = cache.lookup((7, 8, 9))
    assert n == 2 and e.payload == {"short": 1}


# ---------------------------------------------------------------------------
# Bucket monotonicity: speculative verify shapes stay on the pow2 grid
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=1, max_value=8),
       st.integers(min_value=1, max_value=8),
       st.integers(min_value=1, max_value=4))
def test_verify_batch_bucket_monotone(b1, b2, gamma):
    """decode_batch_bucket is monotone and idempotent, so a verify launch
    (batch_bucket, γ+1) never leaves the warmed grid: the γ+1 axis is a
    compile-time constant and the batch axis only ever rounds up pow2."""
    policy = BucketPolicy.build(max_prompt_len=16, max_slots=8, min_seq=8)
    lo, hi = sorted((b1, b2))
    assert policy.decode_batch_bucket(lo) <= policy.decode_batch_bucket(hi)
    assert policy.decode_batch_bucket(policy.decode_batch_bucket(b1)) == \
        policy.decode_batch_bucket(b1)
    assert policy.decode_batch_bucket(b1) >= b1
    # the verify row-shape set over every reachable batch is exactly the
    # decode-batch grid x {gamma+1}: no data-dependent shapes exist
    shapes = {(policy.decode_batch_bucket(b), gamma + 1) for b in range(1, 9)}
    assert shapes == {(b, gamma + 1) for b in policy.decode_batch}


# ---------------------------------------------------------------------------
# Scheduler: decode-fairness cap (starvation regression)
# ---------------------------------------------------------------------------

def _drive_scheduler(cap, steps=60):
    """Simulate serving with instantaneous steps and continuous arrivals;
    returns the per-work-item sequence of ("P"|"D") labels."""
    q = RequestQueue()
    policy = BucketPolicy.build(max_prompt_len=16, max_slots=8, min_seq=8)
    s = Scheduler(q, policy, max_slots=8, max_consecutive_prefills=cap)
    rng = np.random.default_rng(0)

    def arrive(n):
        for _ in range(n):
            plen = int(rng.choice([5, 16]))     # mixed buckets: small groups
            q.submit(Request(prompt=list(range(1, plen + 1)),
                             max_new_tokens=4))

    arrive(16)
    trace = []
    for _ in range(steps):
        work = s.next_work()
        if work is None:
            arrive(4)
            continue
        if isinstance(work, PrefillWork):
            trace.append("P")
        else:
            trace.append("D")
            # fake one decode step: age every request, retire finished ones
            for r in work.requests:
                r.generated.append(0)
                if len(r.generated) >= r.max_new_tokens:
                    r.state = "done"
                    s.release(r)
            arrive(2)                           # arrivals keep pressure up
    return "".join(trace)


def test_scheduler_decode_fairness_cap_bounds_gaps():
    """With the cap, no in-flight decode ever waits more than
    ``max_consecutive_prefills`` work items; with the cap disabled the same
    arrival stream produces longer prefill runs (the cap is load-bearing)."""
    capped = _drive_scheduler(cap=2)
    assert "D" in capped
    # after the first decode becomes ready, prefill runs are bounded by 2
    first_d = capped.index("D")
    runs = [len(r) for r in capped[first_d:].split("D") if r]
    assert runs and max(runs) <= 2, capped
    uncapped = _drive_scheduler(cap=0)
    runs0 = [len(r) for r in uncapped.split("D") if r]
    assert max(runs0) > 2, uncapped             # starvation without the cap


def test_scheduler_rejects_off_grid_prefill_chunk():
    q = RequestQueue()
    policy = BucketPolicy.build(max_prompt_len=16, max_slots=4, min_seq=8)
    with pytest.raises(ValueError):
        Scheduler(q, policy, max_slots=4, prefill_chunk=12)
    Scheduler(q, policy, max_slots=4, prefill_chunk=8)


def test_scheduler_chunked_prefill_work_geometry():
    """A long prompt splits into exactly-full intermediate chunks plus a
    bucketed final chunk, and the slot decodes only after the final chunk."""
    q = RequestQueue()
    policy = BucketPolicy.build(max_prompt_len=32, max_slots=2, min_seq=8)
    s = Scheduler(q, policy, max_slots=2, prefill_chunk=8,
                  max_consecutive_prefills=0)
    q.submit(Request(prompt=list(range(1, 21)), max_new_tokens=2))  # plen 20
    chunks = []
    for _ in range(3):
        w = s.next_work()
        assert isinstance(w, PrefillWork)
        chunks.append((w.starts[0], w.lengths[0], w.seq_pad, w.final[0]))
    assert chunks == [(0, 8, 8, False), (8, 8, 8, False), (16, 4, 8, True)]
    assert isinstance(s.next_work(), DecodeWork)


# ---------------------------------------------------------------------------
# ServeStats: accounting invariants + stable observable surface
# ---------------------------------------------------------------------------

def test_serve_stats_as_dict_keys_are_stable():
    """Dashboards key on this dict: adding a field is fine, renaming or
    dropping one is a breaking change this assertion makes loud."""
    expected = {
        "prefill_steps", "decode_steps", "verify_steps", "steps",
        "prompt_tokens", "generated_tokens", "decode_real_rows",
        "decode_emitted_tokens", "prefill_padded_tokens",
        "decode_padded_tokens", "drafted_tokens", "accepted_tokens",
        "prefix_hits", "prefix_misses", "prefix_tokens_reused",
        "bucket_hits", "bucket_misses", "warmed_shapes", "warm_plans",
        "t_warm", "t_prefill", "t_decode", "phase_s", "phase_max_s",
        "requests_admitted",
        "requests_finished", "bucket_hit_rate", "padding_waste",
        "tokens_per_s", "decode_tokens_per_s", "acceptance_rate",
        "prefix_hit_rate",
    }
    assert set(ServeStats().as_dict()) == expected


def test_spec_phases_nest_in_prefill_and_verify_steps(spec_served):
    """Under speculation every decode step is a verify round; the draft's
    work is its own phase, and the phases nested in the steps take no more
    time than the steps themselves."""
    engine, _, _ = spec_served
    ph = engine.stats.phase_s
    assert ph["verify"] > 0 and ph["draft"] > 0 and ph["decode"] == 0.0
    nested = sum(ph[p] for p in ("inputs", "draft", "kv_gather", "forward",
                                 "kv_scatter", "sample"))
    assert nested <= ph["prefill"] + ph["verify"]
    assert engine.stats.t_decode == ph["verify"]


def test_serve_stats_rates_safe_on_zero():
    s = ServeStats()
    assert s.acceptance_rate == 0.0 and s.prefix_hit_rate == 0.0
    assert s.decode_tokens_per_s == 0.0 and s.padding_waste == 0.0


def test_spec_stats_attribution(spec_served):
    """Speculation's accounting: verify rows are launched work (padding
    waste), accepted tokens are throughput (decode_tokens_per_s numerator),
    and each request's first token still comes from prefill."""
    engine, first, second = spec_served
    s = engine.stats
    n_req = len(first) + len(second)
    assert s.requests_finished == n_req
    assert 0 < s.acceptance_rate <= 1.0
    assert s.accepted_tokens <= s.drafted_tokens
    # every verify step launches gamma+1 rows per real request and drafts
    # gamma per real request, so rows = drafted * (gamma+1)/gamma (gamma=2)
    assert s.decode_real_rows == (s.drafted_tokens // 2) * 3
    assert s.generated_tokens == s.decode_emitted_tokens + n_req
    assert s.decode_padded_tokens >= s.decode_real_rows
    assert s.padding_waste < 1.0
    d = s.as_dict()
    assert d["acceptance_rate"] == round(s.acceptance_rate, 4)
    assert d["prefix_hit_rate"] == round(s.prefix_hit_rate, 4)


# ---------------------------------------------------------------------------
# Token-exactness: speculation + prefix reuse + chunked prefill
# ---------------------------------------------------------------------------

def test_speculative_identity_draft_token_exact(baseline, spec_served):
    prompts, out = baseline
    engine, first, second = spec_served
    for p, r in zip(prompts, first):
        assert list(r.generated) == out[tuple(p)][0], (r.rid, r.generated)
    assert engine.stats.acceptance_rate > 0
    assert engine.stats.verify_steps > 0 and engine.stats.decode_steps == 0


def test_prefix_reuse_token_exact_and_hits(baseline, spec_served):
    """The second pass over identical prompts reuses prompt[:-1] KV from the
    radix cache and still emits identical tokens."""
    prompts, out = baseline
    engine, _, second = spec_served
    for p, r in zip(prompts, second):
        assert list(r.generated) == out[tuple(p)][0]
    st_ = engine.prefix.stats()
    assert engine.stats.prefix_hits == len(prompts)
    assert st_["hits"] == len(prompts)
    assert engine.stats.prefix_tokens_reused == \
        sum(len(p) - 1 for p in prompts)


@pytest.mark.parametrize("arch", ["granite_3_2b", "dbrx_132b"])
def test_speculative_shrunk_draft_token_exact(arch):
    """A 1-layer sliced draft mispredicts freely on random weights; greedy
    verify/rollback must still emit exactly the non-speculative tokens on
    both a dense and a MoE attention arch."""
    cfg = registry.smoke_config(arch)
    plan_cache.reset()
    rng = np.random.default_rng(11)
    prompts = _prompts(rng, cfg, lens=(6, 13, 4))
    base = ServeEngine(cfg, max_slots=2, max_prompt_len=16,
                       max_new_tokens=6, seed=0)
    base.warm()
    base_reqs = [base.submit(p, max_new_tokens=6) for p in prompts]
    base.run()
    want = [list(r.generated) for r in base_reqs]
    eng = ServeEngine(cfg, max_slots=2, max_prompt_len=16, max_new_tokens=6,
                      seed=0, speculate=2, draft_keep_layers=1)
    eng.warm()
    assert isinstance(eng.draft, SelfDraft)
    assert isinstance(eng.draft, DraftModel)   # protocol conformance
    assert eng.draft.keep_layers == 1 < cfg.num_layers
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run()
    assert [list(r.generated) for r in reqs] == want
    assert eng.stats.acceptance_rate > 0       # some drafts survive ...
    assert eng.stats.accepted_tokens < eng.stats.drafted_tokens  # ... not all


def test_ssm_family_rejects_speculation():
    """Recurrent state cannot roll back a rejected draft; the engine must
    refuse rather than silently emit wrong tokens."""
    with pytest.raises(ValueError, match="specul"):
        ServeEngine(registry.smoke_config("mamba2_370m"), max_slots=2,
                    max_prompt_len=8, max_new_tokens=2, speculate=2)


def test_chunked_prefill_logits_allclose_one_shot(baseline):
    """Chunked prefill is numerically the same computation: the recorded
    per-step logits of a chunked engine match the one-shot engine's."""
    prompts, out = baseline
    plan_cache.reset()
    eng = ServeEngine(CFG, max_slots=4, max_prompt_len=16, max_new_tokens=6,
                      seed=0, prefill_chunk=8, record_logits=True)
    eng.warm()
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run()
    for p, r in zip(prompts, reqs):
        want_toks, want_logits = out[tuple(p)]
        assert list(r.generated) == want_toks
        for got, ref in zip(r.logits, want_logits):
            np.testing.assert_allclose(np.asarray(got), ref,
                                       rtol=1e-4, atol=1e-4)


def test_ssm_prefix_continuation_token_exact():
    """State-bearing caches key entries at the full prompt; a prompt that
    extends a served one resumes from the exact-length state snapshot."""
    cfg = registry.smoke_config("mamba2_370m")
    plan_cache.reset()
    rng = np.random.default_rng(5)
    head = list(rng.integers(1, cfg.vocab_size, 9))
    cont = head + list(rng.integers(1, cfg.vocab_size, 3))
    base = ServeEngine(cfg, max_slots=2, max_prompt_len=16, max_new_tokens=5,
                       seed=0)
    base.warm()
    rb = base.submit(cont, max_new_tokens=5)
    base.run()
    eng = ServeEngine(cfg, max_slots=2, max_prompt_len=16, max_new_tokens=5,
                      seed=0, prefix_cache=True)
    eng.warm()
    eng.submit(head, max_new_tokens=5)
    eng.run()
    r = eng.submit(cont, max_new_tokens=5)
    eng.run()
    assert list(r.generated) == list(rb.generated)
    assert eng.stats.prefix_hits == 1
    assert eng.stats.prefix_tokens_reused == len(head)


# ---------------------------------------------------------------------------
# Streaming
# ---------------------------------------------------------------------------

def test_streaming_matches_final_output(spec_served):
    """Tokens seen through the iterator and the callback equal the request's
    final ``generated`` list, in order, under speculation."""
    engine, _, _ = spec_served
    rng = np.random.default_rng(9)
    cb = []
    r = engine.submit(list(rng.integers(1, CFG.vocab_size, 7)),
                      max_new_tokens=6, stream=True,
                      on_token=lambda rq, t: cb.append((rq.rid, t)))
    streamed = []
    th = threading.Thread(
        target=lambda: streamed.extend(r.token_stream(timeout=60)))
    th.start()
    engine.run()
    th.join(60)
    assert not th.is_alive()
    assert streamed == list(r.generated) and len(streamed) >= 1
    assert cb == [(r.rid, t) for t in r.generated]


def test_token_stream_requires_stream_submit(spec_served):
    engine, first, _ = spec_served
    with pytest.raises(ValueError):
        next(first[0].token_stream())


# ---------------------------------------------------------------------------
# Warm coverage: speculation adds zero plan keys beyond warm()
# ---------------------------------------------------------------------------

def test_spec_serve_adds_no_plan_keys_beyond_warm():
    """32 ragged speculative requests (γ=2, prefix cache, chunked prefill)
    touch ONLY plan-cache keys ``warm()`` created: the verify and catch-up
    contexts are registry symbols, not runtime surprises."""
    plan_cache.reset()
    try:
        engine = ServeEngine(CFG, max_slots=4, max_prompt_len=16,
                             max_new_tokens=4, seed=0, speculate=2,
                             prefix_cache=True, prefill_chunk=8)
        engine.warm()
        cache = plan_cache.default_cache()
        keys_warm = set(cache.keys())
        misses_warm = plan_cache.stats().misses
        rng = np.random.default_rng(0)
        for plen in rng.integers(2, 16, size=32):
            engine.submit(list(rng.integers(0, CFG.vocab_size, int(plen))),
                          max_new_tokens=4)
        done = engine.run()
        assert len(done) == 32
        assert set(cache.keys()) == keys_warm, (
            "speculative serving created plan keys warm missed: "
            f"{sorted(set(cache.keys()) - keys_warm)}")
        assert plan_cache.stats().misses == misses_warm
        assert engine.stats.bucket_misses == 0
        assert engine.stats.acceptance_rate > 0
    finally:
        plan_cache.reset()
