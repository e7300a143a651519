"""A configuration's family module gives the numbers the benchmark had
before families were found by name, and the harness refuses what it cannot
run as stated."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from bench import families, harness, layout
from bench.tests.conftest import TINY_CONF

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Literal values, read from the benchmark's code before families were
# found by name (then ``harness.model_dims``, ``weights._shapes`` and
# ``flops.prefill_flops``).
PINNED = {
    "granite_3_2b": {
        "dims": {"num_layers": 40, "d_model": 2048, "num_heads": 32,
                 "num_kv_heads": 8, "head_dim": 64, "d_ff": 8192,
                 "mlp_type": "swiglu", "vocab_size": 49155,
                 "rope_theta": 10000.0, "norm_eps": 1e-06,
                 "tie_embeddings": True, "dtype": "bfloat16"},
        "shapes": {"embed": (49408, 2048),
                   "layers": {"ln1": (40, 2048), "ln2": (40, 2048),
                              "attn": {"w_q": (40, 2048, 2048),
                                       "w_k": (40, 2048, 512),
                                       "w_v": (40, 2048, 512),
                                       "w_o": (40, 2048, 2048)},
                              "mlp": {"mlp_up": (40, 2048, 8192),
                                      "mlp_down": (40, 8192, 2048),
                                      "mlp_gate": (40, 2048, 8192)}},
                   "final_norm": (2048,)},
        "prefill_flops": {256: 1256521216000.0, 768: 3833585479680.0,
                          1024: 5154329866240.0, 2048: 10652055777280.0},
    },
    "starcoder2_15b_1chip": {
        "dims": {"num_layers": 3, "d_model": 6144, "num_heads": 48,
                 "num_kv_heads": 4, "head_dim": 128, "d_ff": 24576,
                 "mlp_type": "gelu", "vocab_size": 49152,
                 "rope_theta": 10000.0, "norm_eps": 1e-06,
                 "tie_embeddings": True, "dtype": "bfloat16"},
        "shapes": {"embed": (49152, 6144),
                   "layers": {"ln1": (3, 6144), "ln2": (3, 6144),
                              "attn": {"w_q": (3, 6144, 6144),
                                       "w_k": (3, 6144, 512),
                                       "w_v": (3, 6144, 512),
                                       "w_o": (3, 6144, 6144)},
                              "mlp": {"mlp_up": (3, 6144, 24576),
                                      "mlp_down": (3, 24576, 6144)}},
                   "final_norm": (6144,)},
        "prefill_flops": {256: 592513597440.0, 768: 1790828347392.0,
                          1024: 2397233479680.0, 2048: 4871172390912.0},
    },
}


def _bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _conf(name):
    entry = next(c for c in _bench_json()["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


def test_every_committed_configuration_is_pinned():
    assert {c["name"] for c in _bench_json()["configs"]} == set(PINNED)


@pytest.mark.parametrize("name", sorted(PINNED))
def test_family_gives_the_pinned_dims_shapes_and_flops(name):
    conf, want = _conf(name), PINNED[name]
    fam = families.load(conf)
    dims = fam.dims(conf)
    assert dims == want["dims"]
    assert fam.shapes(dims) == want["shapes"]
    made = jax.eval_shape(lambda: fam.make_weights(dims, 2 ** 40 + 7, None))
    is_shape = lambda x: isinstance(x, tuple)      # noqa: E731
    assert jax.tree.structure(made) == jax.tree.structure(want["shapes"], is_leaf=is_shape)
    for leaf, shape in zip(jax.tree.leaves(made),
                           jax.tree.leaves(want["shapes"], is_leaf=is_shape)):
        assert leaf.shape == shape and leaf.dtype == jnp.bfloat16
    for n, flops in want["prefill_flops"].items():
        assert fam.prefill_flops(dims, n) == flops
    assert fam.program_config(conf, dims).num_layers == dims["num_layers"]


def test_a_missing_family_module_is_refused_with_its_file(tiny_cell):
    tiny_cell["conf"]["family"] = "no_such_family"
    with pytest.raises(ValueError, match=r"bench/families/no_such_family\.py is missing"):
        harness.Engine(tiny_cell, 1)


def test_a_configuration_without_a_family_is_refused():
    conf = {k: v for k, v in TINY_CONF.items() if k != "family"}
    with pytest.raises(ValueError, match="names no family"):
        families.load(conf)


def test_a_mesh_that_does_not_make_the_chips_is_refused(tiny_cell):
    tiny_cell.update(chips=4, mesh={"data": 1, "model": 2})
    with pytest.raises(ValueError, match="spans 2 devices, but the cell has 4 chips"):
        harness.Engine(tiny_cell, 1)
    bj = _bench_json()
    entry = next(w for w in bj["workloads"] if w["name"] == "granite_3_2b.chat_prefill")
    entry["chips"] = 4
    with pytest.raises(ValueError, match="asks for 4 chips but has no mesh"):
        harness.load_cell(entry["name"], bj)


@pytest.mark.parametrize("shape,axis", [
    ((49408, 2048), 0), ((40, 2048, 8192), 2), ((40, 6144, 6144), 1),
    ((3, 6144), 1), ((40, 2048, 512), 1), ((6,), None), ((3, 5, 7), None)])
def test_a_mesh_splits_each_leaf_along_its_largest_even_axis(shape, axis):
    assert layout.split_axis(shape, 4) == axis


def test_a_program_config_that_departs_from_the_file_is_refused():
    fam = families.load(TINY_CONF)
    conf = dict(TINY_CONF, program_overrides=dict(
        TINY_CONF["program_overrides"], d_ff=256))
    with pytest.raises(ValueError, match="departs from the configuration file"):
        fam.program_config(conf, fam.dims(conf))
