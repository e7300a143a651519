"""Per-request eager decode: the reference the serve engine is held to.

One request alone, batch 1, no buckets and no padding: the whole prompt
through ``model.forward`` into a fresh cache, then one greedy token at a
time, op by op with the engine's own parameters and FalconGEMM policy.
Shared by the serve tests and the subprocess of the simulated-mesh test.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

import repro.api as falcon
from repro.models import model as M


def eager_reference(engine, prompt, n_tokens: int):
    """Greedy tokens and each token's logits for ``prompt`` decoded alone
    with ``engine``'s parameters."""
    cfg, params = engine.cfg, engine.params
    cache = M.init_cache(cfg, 1, engine.max_len)
    toks, rows = [], []
    with falcon.use(engine.fcfg), engine._mesh_ctx():
        hidden, cache, _ = M.forward(
            params, cfg, jnp.asarray([list(prompt)], jnp.int32), cache=cache,
            cache_index=0, logits_mode="none")
        logits = M.compute_logits(params, cfg, hidden[:, -1:])
        pos = len(prompt)
        while True:
            row = np.asarray(logits[0, -1], np.float32)
            rows.append(row)
            toks.append(int(np.argmax(row)))
            if len(toks) == n_tokens:
                return toks, rows
            logits, cache, _ = M.forward(
                params, cfg, jnp.asarray([[toks[-1]]], jnp.int32), cache=cache,
                cache_index=pos, logits_mode="last")
            pos += 1


def assert_matches_reference(engine, req, rtol=1e-3, atol=1e-3):
    """``req``'s served tokens equal the eager reference's, and its recorded
    logits match within the engine tests' tolerance."""
    toks, rows = eager_reference(engine, req.prompt, len(req.generated))
    assert toks == req.generated, (req.rid, toks, req.generated)
    assert len(req.logits) == len(rows)
    for got, ref in zip(req.logits, rows):
        np.testing.assert_allclose(np.asarray(got, np.float32), ref,
                                   rtol=rtol, atol=atol)
