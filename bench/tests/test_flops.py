"""The FLOP count agrees with a parameter count of the program's own model."""
import dataclasses

import jax
import numpy as np
import pytest

from bench import families
from bench.tests.conftest import TINY_CONF

family = families.load(TINY_CONF)


def _dims(**over):
    conf = dict(TINY_CONF, **over)
    return family.dims(conf)


@pytest.mark.parametrize("act", ["silu", "gelu_pytorch_tanh"])
def test_layer_matmul_params_match_the_program_init(act):
    from repro.configs import get_config
    from repro.models import model as M
    dims = _dims(hidden_act=act)
    cfg = dataclasses.replace(get_config("granite_3_2b"),
                              **TINY_CONF["program_overrides"],
                              mlp_type=dims["mlp_type"])
    params = jax.eval_shape(M.init_params, cfg, jax.random.PRNGKey(0))
    layers = params["layers"]
    n = sum(int(np.prod(a.shape)) for path, a in
            jax.tree_util.tree_flatten_with_path(layers)[0]
            if "ln" not in jax.tree_util.keystr(path))
    assert n == cfg.num_layers * family.layer_matmul_params(dims)


def test_prefill_flops_count_causal_attention_and_one_head_row():
    d = _dims()
    n = 10
    per_layer = 2 * family.layer_matmul_params(d) * n \
        + 4 * d["num_heads"] * d["head_dim"] * n * (n + 1) / 2
    want = d["num_layers"] * per_layer + 2 * d["d_model"] * d["vocab_size"]
    assert family.prefill_flops(d, n) == pytest.approx(want)
