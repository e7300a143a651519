"""Tracing from inside the program: the serve engine's phase spans on the
profiler's clock, the ``ServeStats`` phase totals behind them, and the
FalconGEMM stage scopes in the lowered program's op metadata."""
import glob
import hashlib
import os
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro.api as falcon
from repro.configs import registry
from repro.core import algorithms as alg, codegen
from repro.core.falcon_gemm import FalconConfig
from repro.serve import ServeEngine
from repro.serve.stats import PHASES, ServeStats

CFG = registry.smoke_config("granite_3_2b")
STEP_KINDS = ("serve.prefill", "serve.decode", "serve.verify")
CHILDREN = ("serve.inputs", "serve.forward", "serve.sample")
FORCE = FalconConfig(mode="strassen", backend="jnp")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A tiny engine, warmed, serving a few requests under the profiler;
    the serve spans of the host plane, each (name, start, end, stats)."""
    eng = ServeEngine(CFG, max_slots=2, max_prompt_len=16, max_new_tokens=4)
    eng.warm()
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(0, CFG.vocab_size, n), max_new_tokens=3)
            for n in (5, 12, 7)]
    d = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(d):
        eng.run()
    assert all(r.done for r in reqs)
    path = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))[0]
    spans = []
    with warnings.catch_warnings():     # jaxlib's stats type, on first use
        warnings.simplefilter("ignore", DeprecationWarning)
        for pl in ProfileData.from_file(path).planes:
            if not pl.name.startswith("/host:"):
                continue
            for ln in pl.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                              dict(e.stats)) for e in ln.events
                             if e.name.startswith("serve."))
    spans.sort(key=lambda s: (s[1], -s[2]))
    return eng, reqs, spans


def _steps(spans):
    return [s for s in spans if s[0] in STEP_KINDS]


def test_each_step_holds_its_phases_in_order(traced):
    eng, _, spans = traced
    steps = _steps(spans)
    assert len(steps) == eng.stats.steps > 0
    for name, s, e, _ in steps:
        inside = [n for n, a, b, _ in spans if s < a and b <= e]
        assert tuple(inside) == CHILDREN, (name, inside)
        before = [n for n, a, b, _ in spans if b <= s]
        assert before and before[-1] == "serve.schedule"


def test_step_spans_name_their_requests_and_bucket(traced):
    eng, reqs, spans = traced
    rids = {r.rid for r in reqs}
    seen = set()
    for name, _, _, meta in _steps(spans):
        ids = {int(x) for x in str(meta["rids"]).split()}
        assert ids and ids <= rids
        seen |= ids
        bucket = str(meta["bucket"])
        if name == "serve.prefill":
            b, s = (int(x) for x in bucket.split("x"))
            assert len(ids) <= b and s in eng.policy.prefill_seq
        else:
            assert int(bucket) in eng.policy.decode_batch
    assert seen == rids


def test_phase_totals_nest_within_the_step_totals(traced):
    eng, _, _ = traced
    st = eng.stats
    nested = sum(st.phase_s[p] for p in PHASES
                 if p not in ("schedule", "prefill", "decode", "verify"))
    assert 0 < nested <= st.phase_s["prefill"] + st.phase_s["decode"] \
        + st.phase_s["verify"]
    assert st.t_prefill == st.phase_s["prefill"] > 0
    assert st.t_decode == st.phase_s["decode"] > 0
    for p in PHASES:
        assert 0 <= st.phase_max_s[p] <= st.phase_s[p]
    summary = eng.summary()
    assert set(summary["phase_s"]) == set(summary["phase_max_s"]) == set(PHASES)


def test_phase_records_time_and_maximum_without_a_trace():
    st = ServeStats()
    for _ in range(3):
        with st.phase("sample"):
            pass
    assert 0 < st.phase_max_s["sample"] <= st.phase_s["sample"]
    assert st.phase_s["forward"] == 0.0
    snap = st.as_dict()
    with st.phase("sample"):
        pass
    assert snap["phase_s"]["sample"] < st.phase_s["sample"]   # a copy


def _op_names(fn, *args):
    txt = jax.jit(fn).lower(*args).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', txt))


def _scopes(names):
    return {part for n in names for part in n.split("/")}


def test_precombined_projection_names_its_lcma_stages():
    W = jnp.ones((64, 96), jnp.float32)
    x = jnp.ones((32, 64), jnp.float32)
    pw = falcon.plan_weight(W, cfg=FORCE, m_hint=32)
    assert pw.precombined and pw.algo == "strassen"

    def f(x_, p_):
        with falcon.use(FORCE):
            return falcon.dense(x_, p_)

    scopes = _scopes(_op_names(f, x, pw))
    assert {"falcon.combine_a", "falcon.lcma_gemm", "falcon.combine_h"} <= scopes
    assert "falcon.gemm" not in scopes


def test_plain_gemm_branch_is_named():
    x = jnp.ones((4, 64), jnp.float32)
    w = jnp.ones((64, 32), jnp.float32)

    def f(x_, w_):
        with falcon.use(FalconConfig(mode="gemm")):
            return falcon.dense(x_, w_)

    scopes = _scopes(_op_names(f, x, w))
    assert "falcon.gemm" in scopes and "falcon.combine_a" not in scopes


def test_model_step_names_attention_mlp_and_head():
    from repro.models import model as M
    params = M.init_params(CFG, jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 8), jnp.int32)
    scopes = _scopes(_op_names(
        lambda p, t: M.forward(p, CFG, t, logits_mode="last")[0],
        params, tokens))
    assert {"attn", "mlp", "lm_head"} <= scopes


# Digests of the emitted LCMA source: the stage scopes wrap the compiled
# callables, so the deployment artifact stays byte for byte as it was.
SOURCE_SHA256 = {
    ("strassen", False): "464e87f25923e1480b7d798c9433c23c612e6400af54bc5a74adcbc835357617",
    ("strassen", True): "af6455048b3952ef4c09400a14cc2fa3c0f7a788247d0fb454adda8ecaaad421",
    ("laderman", False): "2e9a8fe809afa818f2397c3569730caefcf92d5ebd0689e3fcbcda18193c565d",
    ("laderman", True): "8b73f507c701408f0145a73fa8a75b8dbf495b58bf1f4e9e28e10a16673e7559",
    ("s444", False): "b3400282ff01c0db01e91974e112b034a5d5f5b1c0e7cf0657a1aef898cef4cc",
    ("s444", True): "b5fcc7c8ccad0456458106846090bcbcfc67faaec6821a8069365b6cf9112205",
}


@pytest.mark.parametrize("name,precombined", sorted(SOURCE_SHA256))
def test_generated_source_is_unchanged(name, precombined):
    gen = codegen.generate(alg.get(name),
                           codegen.CodegenOptions(precombined_b=precombined))
    assert hashlib.sha256(gen.source.encode()).hexdigest() == \
        SOURCE_SHA256[(name, precombined)]
    assert gen.stages["combine_a"].__name__ == "combine_a"
