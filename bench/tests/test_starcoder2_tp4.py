"""The four-chip configuration ``starcoder2_15b_tp4`` as the dense family
reads it, its cell as the harness loads it, and the reader of the
collectives' time (``bench/collectives.py``) on a hand-made trace with
known answers."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from bench import collectives as C
from bench import families, harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "starcoder2_15b_tp4.chat_prefill"

DIMS = {"num_layers": 40, "d_model": 6144, "num_heads": 48, "num_kv_heads": 4,
        "head_dim": 128, "d_ff": 24576, "mlp_type": "gelu", "vocab_size": 49152,
        "rope_theta": 10000.0, "norm_eps": 1e-06, "tie_embeddings": True,
        "dtype": "bfloat16"}
SHAPES = {"embed": (49152, 6144),
          "layers": {"ln1": (40, 6144), "ln2": (40, 6144),
                     "attn": {"w_q": (40, 6144, 6144), "w_k": (40, 6144, 512),
                              "w_v": (40, 6144, 512), "w_o": (40, 6144, 6144)},
                     "mlp": {"mlp_up": (40, 6144, 24576),
                             "mlp_down": (40, 24576, 6144)}},
          "final_norm": (6144,)}
# The 3-layer configuration's pinned FLOPs (bench/tests/test_families.py)
# with the layers scaled to 40; 512 from the per-layer formula.
FLOPS = {256: 7892732215296.0, 512: 15849284960256.0,
         768: 23870262214656.0, 1024: 31955663978496.0}


def _conf():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bj = json.load(f)
    entry = next(c for c in bj["configs"] if c["name"] == "starcoder2_15b_tp4")
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


def test_the_family_reads_all_forty_layers_at_full_width():
    conf = _conf()
    fam = families.load(conf)
    dims = fam.dims(conf)
    assert dims == DIMS
    assert fam.shapes(dims) == SHAPES
    made = jax.eval_shape(lambda: fam.make_weights(dims, 2 ** 40 + 7, None))
    is_shape = lambda x: isinstance(x, tuple)      # noqa: E731
    assert jax.tree.structure(made) == jax.tree.structure(SHAPES, is_leaf=is_shape)
    for leaf, shape in zip(jax.tree.leaves(made),
                           jax.tree.leaves(SHAPES, is_leaf=is_shape)):
        assert leaf.shape == shape and leaf.dtype == jnp.bfloat16
    n_params = sum(leaf.size for leaf in jax.tree.leaves(made))
    assert 15.64e9 < n_params < 15.66e9
    cfg = fam.program_config(conf, dims)
    assert cfg.name == "starcoder2_15b" and cfg.num_layers == 40


@pytest.mark.parametrize("n", sorted(FLOPS))
def test_prefill_flops(n):
    conf = _conf()
    fam = families.load(conf)
    assert fam.prefill_flops(fam.dims(conf), n) == FLOPS[n]


def test_the_cell_loads_on_a_four_chip_mesh():
    cell = harness.load_cell(CELL)
    assert cell["chips"] == 4 and cell["mesh"] == {"data": 1, "model": 4}
    assert (cell["slots"], cell["max_len"]) == (8, 2048)
    assert cell["prefill_seq"] == [256, 512, 1024]
    assert cell["prefill_batch"] == [1] and cell["decode_batch"] == [1, 2, 4, 8]
    assert cell["correct"]["min_tokens"] == 256


# -- collectives, on a hand-made trace (ns) ------------------------------------

SPANS = [["serve.schedule", 90_000, 10_000],
         ["serve.prefill", 100_000, 900_000],
         ["serve.forward", 150_000, 800_000],
         ["serve.decode", 2_000_000, 500_000],
         ["serve.decode", 3_000_000, 400_000]]
OPS = [
    # prefill: a plain all-reduce, then a while that holds an async pair and
    # an all-gather that holds a copy; a fusion that reads an all-reduce's
    # result is not one
    ["%fusion.1 = bf16[1,1024,6144]{2,1,0} fusion(%p.1), kind=kLoop",
     100_000, 300_000],
    ["%all-reduce.9 = bf16[1,1024,6144]{2,1,0} all-reduce(%fusion.173), "
     "channel_id=1, to_apply=%add.clone", 400_000, 50_000],
    ["%fusion.174 = (f32[1024], bf16[1,1024,6144]) fusion(%gte.1, "
     "%all-reduce.9), kind=kLoop", 450_000, 40_000],
    ["%while.3 = (s32[]) while(%tuple.1), body=%body.1", 490_000, 460_000],
    ["%all-reduce-start.2 = bf16[8,6144] all-reduce-start(%fusion.9)",
     500_000, 10_000],
    ["%fusion.2 = bf16[8,6144] fusion(%p.2), kind=kOutput", 510_000, 100_000],
    ["%all-reduce-done.2 = bf16[8,6144] all-reduce-done(%all-reduce-start.2)",
     610_000, 30_000],
    ["%all-gather.8 = bf16[49152] all-gather(%multiply_reduce_fusion), "
     "dimensions={0}", 700_000, 100_000],
    ["%copy.1 = bf16[49152] copy(%p.3)", 720_000, 40_000],
    # between the steps: not counted
    ["%all-reduce.1 = bf16[8,6144] all-reduce(%fusion.3)", 1_500_000, 70_000],
    # decode: names without their HLO text, and an async pair
    ["collective-permute.1", 2_100_000, 20_000],
    ["all-to-all.3", 2_200_000, 5_000],
    ["%all-reduce-start.5 = bf16[8,6144] all-reduce-start(%fusion.4)",
     3_010_000, 4_000],
    ["%all-reduce-done.5 = bf16[8,6144] all-reduce-done(%all-reduce-start.5)",
     3_100_000, 6_000],
    # after the last step: not counted
    ["%reduce-scatter.1 = bf16[2,6144] reduce-scatter(%fusion.5)",
     3_500_000, 9_000],
]


@pytest.mark.parametrize("name,want", [
    ("%all-reduce.9 = bf16[1,1024,6144]{2,1,0} all-reduce(%fusion.173)", True),
    ("%all-gather-start.1 = (bf16[8], bf16[32]) all-gather-start(%p.1)", True),
    ("%all-gather-done.1 = bf16[32] all-gather-done(%all-gather-start.1)", True),
    ("reduce-scatter.2", True), ("collective-permute-done.4", True),
    ("%fusion.174 = bf16[8] fusion(%gte.1, %all-reduce.9), kind=kLoop", False),
    ("%all-reduce-fusion.3 = bf16[8] fusion(%p.1), kind=kLoop", False),
    ("%dot_general.7 = bf16[8,6144] dot(%a, %b)", False), ("", False)])
def test_a_collective_is_known_by_its_hlo_op_name(name, want):
    assert C.is_collective(name) is want


def test_self_time_leaves_out_what_an_operation_holds():
    got = {n.split(" = ")[0]: d for n, s, d in C.self_times(OPS)}
    assert got["%all-gather.8"] == 60_000
    assert got["%while.3"] == 460_000 - 10_000 - 100_000 - 30_000 - 100_000
    assert got["%copy.1"] == 40_000


def test_collective_time_per_step():
    # prefill: 50 + 10 + 30 + 60 us in its one step
    assert C.collective_ms(OPS, SPANS, "prefill") == pytest.approx(0.150)
    # decode: (20 + 5) and (4 + 6) us over two steps
    assert C.collective_ms(OPS, SPANS, "decode") == pytest.approx(0.0175)


def test_a_trace_without_collectives_or_steps_reads_none():
    plain = [o for o in OPS if not C.is_collective(o[0])]
    assert C.collective_ms(plain, SPANS, "prefill") is None
    assert C.collective_ms(OPS, SPANS, "verify") is None
    assert C.collective_ms(OPS, [], "decode") is None


def test_a_run_without_a_trace_reads_none(tmp_path):
    assert C.step_ms({"cell": {"name": "no.such_cell"}}, "prefill") is None
