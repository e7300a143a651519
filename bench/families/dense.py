"""The dense decoder family: pre-norm GQA attention and a SwiGLU or GELU MLP
in every layer (``bench/weights.py``, ``bench/reference/dense.py``).

The interface is described in ``bench/families/__init__.py``.
"""
from __future__ import annotations

import dataclasses

from bench import weights
from bench.reference import dense as reference

__all__ = ["CONTROLS", "dims", "program_config", "shapes", "make_weights",
           "logits_at", "layer_matmul_params", "prefill_flops"]

CONTROLS = ("fp8", "int8")

_ACT = {"silu": "swiglu", "gelu_pytorch_tanh": "gelu"}


def dims(conf: dict) -> dict:
    """The dims of ``conf``, read from its own keys (Hugging Face names)."""
    H = conf["num_attention_heads"]
    return {
        "num_layers": conf["num_hidden_layers"],
        "d_model": conf["hidden_size"],
        "num_heads": H,
        "num_kv_heads": conf["num_key_value_heads"],
        "head_dim": conf.get("head_dim") or conf["hidden_size"] // H,
        "d_ff": conf["intermediate_size"],
        "mlp_type": _ACT[conf["hidden_act"]],
        "vocab_size": conf["vocab_size"],
        "rope_theta": float(conf["rope_theta"]),
        "norm_eps": float(conf.get("rms_norm_eps", conf.get("norm_epsilon"))),
        "tie_embeddings": bool(conf["tie_word_embeddings"]),
        "dtype": conf["torch_dtype"],
    }


def program_config(conf: dict, dims: dict):
    """The program's ``ModelConfig`` for ``conf``, checked against ``dims``
    so that the program runs what the configuration file states."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(conf["arch"]),
                              **conf.get("program_overrides", {}))
    got = {"num_layers": cfg.num_layers, "d_model": cfg.d_model,
           "num_heads": cfg.num_heads, "num_kv_heads": cfg.num_kv_heads,
           "head_dim": cfg.resolved_head_dim, "d_ff": cfg.d_ff,
           "mlp_type": cfg.mlp_type, "vocab_size": cfg.vocab_size,
           "rope_theta": float(cfg.rope_theta), "norm_eps": float(cfg.norm_eps),
           "tie_embeddings": cfg.tie_embeddings, "dtype": cfg.dtype}
    bad = {k: (dims[k], got[k]) for k in got if dims[k] != got[k]}
    if bad or cfg.family != "dense" or cfg.sliding_window or not cfg.use_falcon:
        raise ValueError(f"program config {cfg.name} departs from the "
                         f"configuration file: {bad} (file, program)")
    return cfg


def shapes(dims: dict) -> dict:
    return weights.shapes(dims)


def make_weights(dims: dict, seed: int, layout=None):
    return weights.make(dims, seed, layout)


def logits_at(dims: dict, params, tokens, positions, precision="float32"):
    return reference.logits_at(dims, params, tokens, positions, precision)


# Classical FLOPs: the work of the mathematics, whatever algorithm runs it.
# Two FLOPs per multiply-add of every projection (2 x matmul parameters per
# token), causal attention over the keys each token may see, and the LM head
# for the tokens whose logits are produced, so an LCMA that does fewer
# multiplications reads as a higher share of the peak. The projection
# arithmetic follows ``_layer_linear_flops`` of the program's analytic
# roofline, restated here so that the benchmark keeps its own yardstick.

def layer_matmul_params(dims: dict) -> int:
    """Matmul parameters of one layer: q, k, v, o, and gate, up, down for
    SwiGLU or up, down for GELU."""
    d, hd = dims["d_model"], dims["head_dim"]
    attn = d * (dims["num_heads"] * hd + 2 * dims["num_kv_heads"] * hd) \
        + dims["num_heads"] * hd * d
    n_mlp = 3 if dims["mlp_type"] == "swiglu" else 2
    return attn + n_mlp * d * dims["d_ff"]


def prefill_flops(dims: dict, prompt_len: int) -> float:
    """One prompt's prefill: every layer over every prompt token, with
    causal attention (QK^T and PV, each token over the keys up to its own
    position), plus the LM head for the last position."""
    n = prompt_len
    attn = 4.0 * dims["num_heads"] * dims["head_dim"] * n * (n + 1) / 2
    per_layer = 2.0 * layer_matmul_params(dims) * n + attn
    return dims["num_layers"] * per_layer + 2.0 * dims["d_model"] * dims["vocab_size"]
