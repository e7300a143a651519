"""Tests of the benchmark's own code, on the CPU at a tiny size.

Run with ``JAX_PLATFORMS=cpu python -m pytest bench/tests``.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

TINY_CONF = {
    "arch": "granite_3_2b", "family": "dense", "num_hidden_layers": 2,
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 300,
    "hidden_act": "silu", "tie_word_embeddings": True, "rope_theta": 10000.0,
    "torch_dtype": "bfloat16", "rms_norm_eps": 1e-6,
    "program_overrides": {"num_layers": 2, "d_model": 64, "d_ff": 128,
                          "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
                          "vocab_size": 300},
}


def make_tiny_cell() -> dict:
    """An open-loop cell at a size the CPU serves in seconds."""
    return {
        "name": "tiny.chat", "config": "tiny", "traffic": "chat", "chips": 1,
        "slots": 4, "prefill_seq": [16, 32], "prefill_batch": [1, 2],
        "decode_batch": [1, 2, 4], "max_len": 64, "rate_per_s": 20.0,
        "correct": {"min_tokens": 24, "widest_gap_limit": 0.02},
        "conf": dict(TINY_CONF),
        "mix": {"loop": "open", "arrival": "poisson",
                "prompt": {"median": 20, "sigma": 0.3, "min": 8, "max": 32},
                "output": {"median": 6, "sigma": 0.4, "min": 3, "max": 12}},
    }


@pytest.fixture
def tiny_cell():
    return make_tiny_cell()
