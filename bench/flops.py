"""Classical FLOPs the tokens of a dense decoder require.

The count is the work of the mathematics, whatever algorithm runs it: two
FLOPs per multiply-add of every projection (2 x matmul parameters per
token), causal attention over the keys each token may see, and the LM head
for the tokens whose logits are produced. An LCMA that does fewer
multiplications therefore reads as a higher share of the peak.

The per-layer projection arithmetic follows ``_layer_linear_flops`` of the
program's analytic roofline, restated here so that the benchmark keeps its
own yardstick.
"""
from __future__ import annotations

__all__ = ["layer_matmul_params", "prefill_flops"]


def layer_matmul_params(c: dict) -> int:
    """Matmul parameters of one dense decoder layer.

    ``c`` holds ``d_model``, ``num_heads``, ``num_kv_heads``, ``head_dim``,
    ``d_ff`` and ``mlp_type`` (``swiglu``: gate, up, down; ``gelu``: up,
    down).
    """
    d, hd = c["d_model"], c["head_dim"]
    attn = d * (c["num_heads"] * hd + 2 * c["num_kv_heads"] * hd) \
        + c["num_heads"] * hd * d
    n_mlp = 3 if c["mlp_type"] == "swiglu" else 2
    return attn + n_mlp * d * c["d_ff"]


def prefill_flops(c: dict, prompt_len: int) -> float:
    """One prompt's prefill: every layer over every prompt token, with
    causal attention (QK^T and PV, each token over the keys up to its own
    position), plus the LM head for the last position."""
    n = prompt_len
    attn = 4.0 * c["num_heads"] * c["head_dim"] * n * (n + 1) / 2
    per_layer = 2.0 * layer_matmul_params(c) * n + attn
    return c["num_layers"] * per_layer + 2.0 * c["d_model"] * c["vocab_size"]
