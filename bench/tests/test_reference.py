"""The plain reference agrees with the program's model at float32, and run
one step lower it misses the tolerance."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from bench import families
from bench.tests.conftest import TINY_CONF

family = families.load(TINY_CONF)

# float32 against float32, the same mathematics in another order
F32_TOL = 2e-4


@pytest.fixture(scope="module")
def setup():
    from repro.configs import get_config
    dims = family.dims(dict(TINY_CONF, torch_dtype="float32"))
    cfg = dataclasses.replace(get_config("granite_3_2b"),
                              **TINY_CONF["program_overrides"], dtype="float32")
    params = family.make_weights(dims, 2 ** 36 + 3)
    toks = np.random.default_rng(0).integers(0, dims["vocab_size"], 24)
    return dims, cfg, params, toks


def _program_logits(cfg, params, toks):
    import repro.api as falcon
    from repro.models import model as M
    with falcon.use(M.falcon_config_for(cfg)):
        out, _, _ = M.forward(params, cfg, jnp.asarray(toks)[None],
                              logits_mode="all")
    return np.asarray(out[0, :, :cfg.vocab_size], np.float32)


def test_reference_agrees_with_the_program_at_float32(setup):
    dims, cfg, params, toks = setup
    prog = _program_logits(cfg, params, toks)
    ref = family.logits_at(dims, params, toks, list(range(len(toks))))
    assert ref.shape == prog.shape
    assert np.max(np.abs(ref - prog)) < F32_TOL


@pytest.mark.parametrize("low", ["bfloat16", "int8"])
def test_reference_in_a_lower_precision_misses_the_tolerance(setup, low):
    dims, cfg, params, toks = setup
    ref = family.logits_at(dims, params, toks, list(range(len(toks))))
    lo = family.logits_at(dims, params, toks, list(range(len(toks))), low)
    assert np.max(np.abs(lo - ref)) > 10 * F32_TOL


def test_right_padding_leaves_earlier_positions_alone(setup):
    dims, _, params, toks = setup
    pad = np.concatenate([toks, np.zeros(8, np.int64)])
    pos = list(range(len(toks)))
    a = family.logits_at(dims, params, toks, pos)
    b = family.logits_at(dims, params, pad, pos)
    assert np.max(np.abs(a - b)) < 1e-5


def test_weights_depend_on_every_bit_of_the_seed():
    dims = family.dims(TINY_CONF)
    a = family.make_weights(dims, 5)["embed"]
    b = family.make_weights(dims, 2 ** 40 + 5)["embed"]
    c = family.make_weights(dims, 5)["embed"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(a), np.asarray(c))
    assert a.dtype == jnp.bfloat16
