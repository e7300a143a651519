"""Random weights of a dense decoder, made from ``--seed`` on the device.

The tree has the layout the serve engine takes as ``params``: ``embed``
(padded vocab x d), the layer stack under ``layers`` (leading axis = layer),
``final_norm``, and ``lm_head`` when embeddings are untied. One jitted call
makes every leaf in the type it is served in. The benchmark hands the tree
to the program and, after the window, makes it again from the same seed for
the plain reference: the reference never reads what the program holds.
With a ``layout`` (``bench/layout.py``) the same call makes every leaf
straight into it, with the same values.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

__all__ = ["padded_vocab", "key_for", "shapes", "make"]


def padded_vocab(vocab: int) -> int:
    """The engine's vocabulary rows: a multiple of 256 (rows past ``vocab``
    are masked out of the logits)."""
    return -(-vocab // 256) * 256


def key_for(seed: int):
    """A PRNG key that depends on all 64 bits of ``seed``."""
    s = int(seed) & (2 ** 64 - 1)
    key = jax.random.PRNGKey(s & 0xFFFFFFFF)
    return jax.random.fold_in(key, jnp.uint32(s >> 32))


def shapes(c: dict) -> dict:
    L, d, hd, f = c["num_layers"], c["d_model"], c["head_dim"], c["d_ff"]
    H, Hkv, Vp = c["num_heads"], c["num_kv_heads"], padded_vocab(c["vocab_size"])
    mlp = {"mlp_up": (L, d, f), "mlp_down": (L, f, d)}
    if c["mlp_type"] == "swiglu":
        mlp["mlp_gate"] = (L, d, f)
    tree = {
        "embed": (Vp, d),
        "layers": {
            "ln1": (L, d), "ln2": (L, d),
            "attn": {"w_q": (L, d, H * hd), "w_k": (L, d, Hkv * hd),
                     "w_v": (L, d, Hkv * hd), "w_o": (L, H * hd, d)},
            "mlp": mlp,
        },
        "final_norm": (d,),
    }
    if not c["tie_embeddings"]:
        tree["lm_head"] = (d, Vp)
    return tree


def _build(spec: str, key):
    c = json.loads(spec)
    dt = jnp.dtype(c["dtype"])
    tree = shapes(c)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=lambda x: isinstance(x, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 tree, is_leaf=lambda x: isinstance(x, tuple))[0]]
    out = []
    for i, (path, shape) in enumerate(zip(paths, leaves)):
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shape, jnp.float32)
        if "norm" in path or "ln" in path:
            v = 1.0 + 0.1 * z                       # RMSNorm scales near 1
        elif "embed" in path:
            v = 0.02 * z
        else:                                       # (.., d_in, d_out)
            v = z * (1.0 / shape[-2] ** 0.5)
        out.append(v.astype(dt))
    return jax.tree.unflatten(treedef, out)


_make = jax.jit(_build, static_argnums=0)


@functools.lru_cache(maxsize=4)
def _make_in(layout, spec: str):
    return jax.jit(_build, static_argnums=0,
                   out_shardings=layout.tree(shapes(json.loads(spec))))


def make(c: dict, seed: int, layout=None):
    """The weight tree of dims ``c`` (``dims`` of ``bench/families/dense.py``)
    for ``seed``, on the default device, or in ``layout``."""
    spec = json.dumps(c, sort_keys=True)
    fn = _make if layout is None else _make_in(layout, spec)
    return fn(spec, key_for(seed))
