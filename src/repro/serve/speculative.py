"""Speculative decoding: a draft proposes γ tokens, one verify forward scores
them, greedy accept/rollback keeps output token-exact.

Why this is cheap here: FalconGEMM serving already amortizes Decision-Module
plans over a closed pow2 bucket grid, so draft steps and the ``(batch, γ+1)``
verify forward are *just more buckets* — ``warm_buckets(spec_gamma=γ)``
pre-plans them (the only new registry symbol is ``logit_tokens = B·(γ+1)``,
since the lm head scores every verify row), and a layer-sliced self-draft
shares the target's per-layer contraction shapes, so speculation adds zero
plan-cache keys beyond the two extra bucket contexts.

Greedy accept rule (:meth:`~repro.serve.engine.ServeEngine` verify round):
feed ``[t_last, d_1..d_γ]`` through one cached forward, take per-row argmax
``t'_0..t'_γ``, accept the longest prefix with ``d_j == t'_{j-1}``, emit
``t'_0..t'_{n_acc}`` (the bonus token makes every round emit ≥ 1). By
induction each emitted token equals what sequential greedy decode would have
produced, so exactness never depends on draft quality — acceptance rate only
sets the speedup. Rollback is free for attention KV: decode validity admits
``kpos < pos + S`` and every position is rewritten before it first becomes
visible, so rejected draft K/V is simply never observed. Recurrent SSM state
cannot roll back, which is why the engine gates speculation to the
``dense``/``moe`` families.

The draft keeps its own slot KV consistent with a fixed-shape *catch-up*
trick: every round starts with one ``(B, 2)`` forward feeding
``[t_prev, t_last]`` at ``pos-1`` — re-writing an already-cached position is
idempotent (same prefix ⇒ same K/V) — which repairs the draft cache for any
acceptance count of the previous round, including full acceptance, with
uniform warmed shapes.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import model as M
from repro.train.steps import make_chunk_prefill_step, make_decode_step

__all__ = ["DraftModel", "SelfDraft"]


@runtime_checkable
class DraftModel(Protocol):
    """What the engine needs from a draft: propose tokens, mirror prefill.

    A draft owns its own per-slot cache and stays position-synchronized with
    the target through these four calls; any model with the same tokenizer
    can implement it (a distilled small model, an n-gram cache, ...).
    :class:`SelfDraft` is the registry-derived reference implementation.
    """

    def prefill_chunk(self, tokens, start, last, slots, k) -> None:
        """Mirror one (padded) prefill chunk into the draft's slot cache."""
        ...

    def propose(self, slot_idx, last2, pos, gamma: int, k: int) -> np.ndarray:
        """γ greedy draft tokens per row -> (B, γ) int array."""
        ...

    def snapshot(self, slot: int, length: int) -> Any:
        """Opaque per-slot prefix payload for the radix prefix cache."""
        ...

    def load(self, slot: int, payload: Any, length: int) -> None:
        """Restore a :meth:`snapshot` payload into ``slot``."""
        ...

    def warm(self, policy, gamma: int, params_like=None) -> int:
        """Compile every step shape; returns the number of shapes."""
        ...


class SelfDraft:
    """Layer-sliced self-draft: the target's first ``keep_layers`` layers.

    Built from the *raw* (pre-precombine) target params: the scan-stacked
    ``params["layers"]`` tree is sliced ``[:keep]`` and the embedding, final
    norm and lm head are shared, under ``dataclasses.replace(cfg,
    num_layers=keep)`` (layer windows are index-periodic, so the slice keeps
    each kept layer's own window). Same d_model/heads/ffn ⇒ identical
    per-layer contraction shapes ⇒ the draft hits the same warmed plan-cache
    keys as the target.

    ``keep_layers=None`` keeps every layer — the *identity draft*, whose
    proposals match the target's greedy choice (acceptance ≈ 1.0). That is
    the default for smoke/bench runs on randomly initialized weights, where
    a truncated stack predicts noise; real deployments pick
    ``keep_layers < num_layers`` to trade acceptance for draft speed.
    """

    def __init__(self, model_cfg, params, *, max_slots: int, max_len: int,
                 keep_layers: int | None = None, mesh=None):
        keep = int(keep_layers or model_cfg.num_layers)
        if not 1 <= keep <= model_cfg.num_layers:
            raise ValueError(
                f"keep_layers={keep} out of range 1..{model_cfg.num_layers}")
        self.cfg = dataclasses.replace(model_cfg, num_layers=keep)
        self.keep_layers = keep
        if keep == model_cfg.num_layers:
            self.params = params            # identity draft shares the tree
        else:
            self.params = dict(params)
            self.params["layers"] = jax.tree.map(
                lambda p: p[:keep], params["layers"])
        self.max_len = max_len
        # over a mesh the slot cache, and the rows each step returns, keep
        # the target's layout, as ``mesh`` gives it
        self.cache, sharding = M.init_slot_cache(self.cfg, max_slots, max_len,
                                                 mesh)
        out = None if mesh is None else (NamedSharding(mesh, P()), sharding)
        self._chunk_fn = jax.jit(make_chunk_prefill_step(self.cfg),
                                 out_shardings=out)
        self._decode_fn = jax.jit(make_decode_step(self.cfg),
                                  out_shardings=out)
        # the jitted steps, whose executables the engine counts
        self.programs = (self._chunk_fn, self._decode_fn)

    # -- prefill mirror ------------------------------------------------------

    def prefill_chunk(self, tokens, start, last, slots, k) -> None:
        B = tokens.shape[0]
        rows = self._rows(jnp.asarray(list(slots) + [slots[-1]] * (B - k)))
        logits, new_rows = self._chunk_fn(
            self.params, rows, jnp.asarray(tokens), jnp.asarray(start),
            jnp.asarray(last))
        jax.block_until_ready(logits)
        sl = jnp.asarray(list(slots))
        self.cache = jax.tree.map(
            lambda c, nc: c.at[:, sl].set(nc[:, :k].astype(c.dtype)),
            self.cache, new_rows)

    # -- drafting ------------------------------------------------------------

    def propose(self, slot_idx, last2, pos, gamma: int, k: int) -> np.ndarray:
        """Catch-up ``(B, 2)`` forward, then γ-1 single-token greedy steps."""
        idx = jnp.asarray(slot_idx)
        rows = self._rows(idx)
        # positions and tokens move on the host, so that each step's inputs
        # are made as warm()'s are
        pos = np.asarray(pos)
        # catch-up: re-feed [t_prev, t_last] at pos-1; rewriting the cached
        # position pos-1 is idempotent, and this repairs the draft KV after
        # any acceptance count of the previous round with one fixed shape
        logits, rows = self._decode_fn(
            self.params, rows, jnp.asarray(last2), jnp.asarray(pos - 1))
        out = [np.argmax(np.asarray(logits[:, -1]), axis=-1).astype(np.int32)]
        for j in range(1, gamma):
            logits, rows = self._decode_fn(
                self.params, rows, jnp.asarray(out[-1][:, None]),
                jnp.asarray(pos + j))
            out.append(np.argmax(np.asarray(logits[:, -1]),
                                 axis=-1).astype(np.int32))
        real = idx[:k]
        self.cache = jax.tree.map(
            lambda c, nc: c.at[:, real].set(nc[:, :k].astype(c.dtype)),
            self.cache, rows)
        return np.stack(out, axis=1)

    def _rows(self, idx):
        """The cache rows of slots ``idx`` (B,)."""
        return jax.tree.map(lambda c: c[:, idx], self.cache)

    # -- prefix-cache payloads ----------------------------------------------

    def snapshot(self, slot: int, length: int) -> Any:
        out = {}
        for name, c in self.cache.items():
            out[name] = np.asarray(c[:, slot] if name == "state"
                                   else c[:, slot, :length])
        return out

    def load(self, slot: int, payload: Any, length: int) -> None:
        new = {}
        for name, c in self.cache.items():
            v = jnp.asarray(payload[name]).astype(c.dtype)
            new[name] = (c.at[:, slot].set(v) if name == "state"
                         else c.at[:, slot, :length].set(v))
        self.cache = new

    # -- warmup --------------------------------------------------------------

    def warm(self, policy, gamma: int, params_like=None) -> int:
        """Compile the draft's chunk-prefill, catch-up and single-token
        shapes on zeros so no live round pays a trace. The zeros are host
        arrays made device arrays as live rounds make theirs, so the
        programs compiled are the ones live rounds call."""
        def zeros(*shape):
            return jnp.asarray(np.zeros(shape, np.int32))

        n = 0
        for (b, s) in policy.prefill_shapes():
            rows = self._rows(zeros(b))
            jax.block_until_ready(self._chunk_fn(
                self.params, rows, zeros(b, s), zeros(b), zeros(b)))
            n += 1
        for b in policy.decode_batch:
            rows = self._rows(zeros(b))
            jax.block_until_ready(self._decode_fn(
                self.params, rows, zeros(b, 2), zeros(b)))
            if gamma > 1:
                jax.block_until_ready(self._decode_fn(
                    self.params, rows, zeros(b, 1), zeros(b)))
                n += 1
            n += 1
        return n
