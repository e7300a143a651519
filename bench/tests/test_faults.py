"""A run whose timed path is broken underneath reads ``correct`` false.

Each test drives the whole of a run except the look for a chip
(``run.run_cell`` on a tiny cell on the CPU), with one fault planted in the
serve engine for the measured window only.
"""
import json
import os
import time

import pytest

from bench import harness, run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _run(cell, tmp_path, seed=2 ** 35 + 11):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bj = json.load(f)
    for m in bj["end_to_end"]:
        if m["name"] == "itl_p95_ms":
            m["workloads"].append(cell["name"])
    return run.run_cell(bj, cell["name"], seed, 2.0, False,
                        {"platform": "cpu", "kind": "cpu", "count": 1},
                        time.perf_counter(), str(tmp_path), cell=cell)


def _plant(monkeypatch, fault):
    real = harness.run_window

    def broken(eng, *a, **kw):
        fault(eng.engine)
        return real(eng, *a, **kw)

    monkeypatch.setattr(harness, "run_window", broken)


def _alter_tokens(engine):
    emit = engine._emit
    vocab = engine.cfg.vocab_size
    engine._emit = lambda req, tok, row=None: emit(req, (tok + 1) % vocab, row)


def _state_unchanged(engine):
    """The decode step returns the cache it was given, unwritten (the real
    step writes into a copy, so the given cache is not donated)."""
    import jax
    import jax.numpy as jnp
    decode = engine._decode_fn

    def step(params, cache, slots, toks, pos):
        logits, _ = decode(params, jax.tree.map(jnp.copy, cache), slots,
                           toks, pos)
        return logits, cache

    engine._decode_fn = step


def test_a_sound_run_is_correct(tiny_cell, tmp_path):
    res = _run(tiny_cell, tmp_path)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"setup_s", "itl_p95_ms"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged],
                         ids=["token_altered", "decode_state_unchanged"])
def test_a_broken_timed_path_is_not_correct(tiny_cell, tmp_path, monkeypatch,
                                            fault):
    _plant(monkeypatch, fault)
    res = _run(tiny_cell, tmp_path)
    assert res["correct"] is False
    chk = res["checks"]["widest_gap"]
    assert chk["value"] > chk["limit"]
