"""ServeEngine: continuous batching over bucketed, pre-planned step shapes.

The engine owns a fixed set of KV-cache **slots**. Requests are admitted by
the :class:`~repro.serve.scheduler.Scheduler` into free slots via bucketed
prefill micro-batches (prompts right-padded to a power-of-two sequence
bucket, per-row last-token indices pick the true logits), then advance over
the active slots in decode micro-batches padded to a power-of-two batch
bucket. Every step therefore launches a shape from the closed
:class:`~repro.serve.buckets.BucketPolicy` grid, so after :meth:`warm`:

* the FalconGEMM Decision Module is a pure plan-cache hit per projection
  (``core.engine.warm_buckets`` pre-planned the bucket grid),
* static weights are already lifted to precombined :class:`PlannedWeight`\\ s
  (offline Combine B ran once at load),
* jit never re-traces — each bucket shape's executable exists.

On top of the PR 3 base the engine serves four production decode features,
all riding the same bucket grid (docs/serving.md has the full story):

* **speculative decoding** (``speculate=γ``): a :class:`DraftModel` proposes
  γ tokens, one ``(B, γ+1)`` verify forward scores them, greedy
  accept/rollback emits 1..γ+1 tokens per round — token-exact vs. the
  non-speculative engine by construction (``serve/speculative.py``).
* **prefix KV reuse** (``prefix_cache=True``): finished prefills snapshot
  their slot KV into a radix cache keyed by prompt tokens; a later request
  sharing a prefix prefills only the suffix (``serve/prefix_cache.py``).
* **chunked prefill** (``prefill_chunk=N``): long prompts prefill in
  full-bucket chunks the scheduler interleaves with decode work.
* **token streaming**: ``submit(stream=True)`` / ``on_token=`` deliver
  tokens as ``_emit`` produces them.

Correctness of padding: pad rows/positions never leak. Right-padded prefill
writes pad K/V above each request's true length, but decode validity masks
``kpos < pos + S`` and each write covers its positions before they first
become visible — the same argument covers rejected speculative drafts and
chunk boundaries; pad *rows* of a micro-batch are sliced off before the slot
cache update. The engine output is token-exact vs. per-request eager decode
(``tests/test_serve_engine.py``, ``tests/test_serve_spec.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import queue as _queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.api as falcon
from repro import compat
from repro.configs.base import ModelConfig
from repro.core import engine as core_engine, plan_cache
from repro.models import model as M
from repro.parallel import sharding as SH
from repro.train.steps import (make_chunk_prefill_step, make_decode_step,
                               make_verify_step)

from .buckets import BucketPolicy, next_pow2
from .prefix_cache import RadixPrefixCache
from .request import Request, RequestQueue
from .scheduler import DecodeWork, PrefillWork, Scheduler
from .speculative import DraftModel, SelfDraft
from .stats import ServeStats

__all__ = ["ServeEngine", "StepLoop"]


class ServeEngine:
    """Continuous-batching serve engine for one model.

    ``submit`` is thread-safe (any frontend thread); ``step``/``run`` are the
    single consumer. All decoder families serve: dense/hybrid KV-cache
    attention is exact under causal masking + decode validity, and SSM/hybrid
    recurrent state is exact because the serve prefill step zeroes dt on
    right-pad positions (see ``make_chunk_prefill_step``). MoE routing is
    approximate under padding (pad rows contend for expert capacity) but
    pad rows are sliced off before the slot cache update. Non-token
    frontends (audio codebooks, vision patches) are rejected — the bucket
    grid assumes one int token stream.

    ``speculate=γ`` turns decode steps into speculative rounds (draft γ,
    verify in one forward, accept greedily — token-exact). Restricted to the
    ``dense``/``moe`` families: recurrent SSM state cannot roll back a
    rejected draft, while attention KV rollback is free (validity masking).
    The draft defaults to the identity :class:`SelfDraft` (every layer kept,
    acceptance ≈ 1) — pass ``draft_keep_layers`` for a truncated self-draft
    or ``draft=`` for any :class:`DraftModel`.

    ``mesh_shape={"data": d, "model": m}`` spanning more than one device
    lifts the engine onto a real mesh: weights shard tensor-parallel by the
    ``parallel.sharding`` rule table (offline Combine B then runs on sharded
    weights), the KV cache stays replicated (decode activations gather back
    each step — "replicated-then-gathered"), and every jitted step runs under
    the mesh context so FalconGEMM's shard-aware plans and ``shard_act``
    constraints see it. Simulate devices on one host with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """

    def __init__(self, model_cfg: ModelConfig, params=None, *,
                 max_slots: int = 8, max_prompt_len: int = 64,
                 max_new_tokens: int = 32, policy: BucketPolicy | None = None,
                 precombine: bool = True, record_logits: bool = False,
                 seed: int = 0, mesh_shape: dict | None = None,
                 quantize: bool = False, speculate: int = 0,
                 draft: DraftModel | None = None,
                 draft_keep_layers: int | None = None,
                 prefix_cache: bool = False, prefix_entries: int = 32,
                 prefill_chunk: int | None = None,
                 max_consecutive_prefills: int = 2):
        if model_cfg.frontend:
            raise NotImplementedError(
                f"ServeEngine serves token-stream decoders; got "
                f"frontend={model_cfg.frontend!r} (bucketed prefill assumes "
                "one int token stream)")
        self.cfg = model_cfg
        self.gamma = int(speculate)
        if self.gamma < 0:
            raise ValueError(f"speculate must be >= 0, got {speculate}")
        if self.gamma and model_cfg.family not in ("dense", "moe"):
            raise ValueError(
                f"speculate requires a rollback-free cache; family="
                f"{model_cfg.family!r} carries recurrent state that cannot "
                "un-advance past rejected draft tokens")
        self.max_prompt_len = max_prompt_len
        self.prefill_chunk = prefill_chunk
        # with chunking, the bucket grid tops out at the chunk size — longer
        # prompts run as several full-chunk micro-batches
        pol_max_seq = min(max_prompt_len, prefill_chunk) if prefill_chunk \
            else max_prompt_len
        self.policy = policy or BucketPolicy.build(pol_max_seq, max_slots)
        self.max_slots = max_slots
        self.max_new_tokens_cap = max_new_tokens
        # speculation writes up to γ provisional positions past the last
        # committed token, so the slot length budgets for them
        self.max_len = next_pow2(
            max(self.policy.prefill_seq[-1], max_prompt_len)
            + max_new_tokens + self.gamma)
        self.record_logits = record_logits
        self.mesh_shape = dict(mesh_shape or {})
        self.mesh = self._build_mesh(self.mesh_shape)
        self.quantize = bool(quantize)
        self.fcfg = M.falcon_config_for(model_cfg, self.mesh_shape)
        if self.quantize:
            # int8-quantized serving: the Decision Module prices the quant
            # tier alongside fp (plan-cache keys gain the quant token),
            # precombine below bakes B̃q + scales into each PlannedWeight,
            # and warm() pre-plans the quantized buckets.
            self.fcfg = dataclasses.replace(self.fcfg, quantize=True)
        with falcon.use(self.fcfg), self._mesh_ctx():
            # Tensor-parallel at rest: raw weights are sharded by the rule
            # table BEFORE precombine, so offline Combine B runs on (and its
            # B̃ output inherits) the sharded layout. Random weights are made
            # in that layout, never whole on one device.
            shard = None
            if self.mesh is not None:
                rules = SH.make_rules(self.mesh)
                shard = lambda tree: SH.param_sharding(tree, self.mesh, rules)
            if params is None:
                key = jax.random.PRNGKey(seed)
                out = None if shard is None else \
                    shard(jax.eval_shape(M.init_params, model_cfg, key))
                params = jax.jit(M.init_params, static_argnums=0,
                                 out_shardings=out)(model_cfg, key)
            elif shard is not None:
                params = jax.device_put(params, shard(params))
            self.params = params
            self.draft: DraftModel | None = draft
            if self.gamma and self.draft is None:
                # built from RAW params: a layer slice of a precombined tree
                # would tear PlannedWeights; the draft precombines its own
                # sliced copy below alongside the target
                self.draft = SelfDraft(model_cfg, self.params,
                                       max_slots=max_slots,
                                       max_len=self.max_len,
                                       keep_layers=draft_keep_layers)
            self.n_precombined = 0
            if precombine:
                # Offline Combine B priced at the largest prefill bucket M;
                # each step re-decides per its actual bucket M (plan-cached).
                m_hint = self.policy.prefill_batch[-1] * self.policy.prefill_seq[-1]
                self.params, self.n_precombined = falcon.precombine_params(
                    self.params, m_hint=m_hint)
                if isinstance(self.draft, SelfDraft):
                    self.draft.params, _ = falcon.precombine_params(
                        self.draft.params, m_hint=m_hint)
        self.queue = RequestQueue()
        self.scheduler = Scheduler(
            self.queue, self.policy, max_slots,
            max_consecutive_prefills=max_consecutive_prefills,
            prefill_chunk=prefill_chunk)
        self.stats = ServeStats()
        self.requests: list[Request] = []
        self.prefix = RadixPrefixCache(max_entries=prefix_entries) \
            if prefix_cache else None
        self.cache = M.init_cache(model_cfg, max_slots, self.max_len)
        if self.mesh is not None:
            # Replicated-then-gathered decode: the KV cache lives replicated on
            # every device; each step's projections run tensor-parallel and the
            # (small) per-step activations gather back before the cache write.
            self.cache = jax.device_put(
                self.cache, NamedSharding(self.mesh, P()))
        self.pos = np.zeros(max_slots, np.int32)   # per-slot next write index
        # Under a mesh, step outputs (logits, cache rows) come back replicated
        # like the cache itself. Left to GSPMD, a KV-head dim that the model
        # axis does not divide comes back partially tiled, in a layout the
        # eager slot update cannot index.
        out = None if self.mesh is None else NamedSharding(self.mesh, P())
        self._prefill_fn = jax.jit(make_chunk_prefill_step(model_cfg),
                                   out_shardings=out)
        self._decode_fn = jax.jit(make_decode_step(model_cfg),
                                  out_shardings=out)
        self._verify_fn = jax.jit(make_verify_step(model_cfg),
                                  out_shardings=out)
        self._compiled: set[tuple] = set()          # step shapes already traced
        self._submit_lock = threading.Lock()

    # -- mesh ----------------------------------------------------------------

    @staticmethod
    def _build_mesh(mesh_shape: dict):
        """A real ("data", "model") mesh when ``mesh_shape`` spans > 1 device."""
        total = 1
        for v in mesh_shape.values():
            total *= int(v)
        if total <= 1:
            return None
        ndev = len(jax.devices())
        if total > ndev:
            raise ValueError(
                f"mesh_shape {mesh_shape} needs {total} devices but only "
                f"{ndev} are visible; simulate with XLA_FLAGS="
                f"--xla_force_host_platform_device_count={total}")
        d = int(mesh_shape.get("data", 1)) * int(mesh_shape.get("pod", 1))
        m = int(mesh_shape.get("model", 1))
        return compat.make_mesh((d, m), ("data", "model"))

    def _mesh_ctx(self):
        return jax.set_mesh(self.mesh) if self.mesh is not None \
            else contextlib.nullcontext()

    # -- admission ----------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int | None = None,
               eos_id: int | None = None, stream: bool = False,
               on_token=None) -> Request:
        """Queue one generation request.

        ``stream=True`` attaches a consumer queue — iterate
        ``req.token_stream()`` from any thread while the engine steps.
        ``on_token(req, tok)`` is called synchronously from the step loop for
        every emitted token (keep it cheap — it sits on the decode path).
        """
        req = Request(prompt=prompt,
                      max_new_tokens=max_new_tokens or self.max_new_tokens_cap,
                      eos_id=eos_id)
        if self.prefill_chunk:
            if req.prompt_len > self.max_prompt_len:
                raise ValueError(
                    f"prompt_len={req.prompt_len} exceeds engine "
                    f"max_prompt_len={self.max_prompt_len}")
        else:
            self.policy.seq_bucket(req.prompt_len)  # raises if off-grid
        if req.max_new_tokens > self.max_new_tokens_cap:
            raise ValueError(
                f"max_new_tokens={req.max_new_tokens} exceeds engine cap "
                f"{self.max_new_tokens_cap} (cache is sized for the cap)")
        if stream:
            req.stream_q = _queue.Queue()
        req.on_token = on_token
        with self._submit_lock:                     # frontend threads race here
            self._lookup_prefix(req)
            self.queue.submit(req)
            self.requests.append(req)
            self.stats.requests_admitted += 1
        return req

    def _lookup_prefix(self, req: Request) -> None:
        """Pin the longest cached prefix of ``prompt[:-1]`` for this request.

        The last prompt token is always excluded so at least one suffix token
        prefills — the request's first logits are always freshly computed,
        and an SSM/hybrid snapshot (state valid only at its exact length) is
        only ever resumed at exactly that length.
        """
        if self.prefix is None:
            return
        n, entry = (0, None) if req.prompt_len < 2 else \
            self.prefix.lookup(req.prompt[:-1], pin=True)
        if entry is not None and self.draft is not None \
                and "draft" not in entry.payload:
            self.prefix.release(entry)              # no draft KV: unusable
            entry = None
        if entry is None:
            self.stats.prefix_misses += 1
            return
        req.prefix_len, req.prefix_entry = n, entry
        self.stats.prefix_hits += 1
        self.stats.prefix_tokens_reused += n

    # -- warmup --------------------------------------------------------------

    def warm(self) -> dict:
        """Pre-plan + pre-compile the whole bucket grid.

        1. ``core.engine.warm_buckets`` runs the Decision Module for every
           contraction the workload registry enumerates at every (batch, seq)
           bucket of the grid — dense projections, grouped MoE expert FFNs,
           attention and SSD scan/decode contractions, plus (under
           ``speculate=γ``) the ``(b, γ+1)`` verify and ``(b, 2)`` draft
           catch-up contexts — so serve-time traces only hit the plan cache,
           including from concurrent engines sharing a warmed cache file.
        2. Each (phase, shape) step function — prefill chunks, decode or
           verify rounds, and the draft's own steps — is traced and compiled
           once on zero inputs, so no live request ever pays a compile.
        """
        t0 = time.perf_counter()
        grid = (list(self.policy.prefill_shapes())
                + [(b, 1) for b in self.policy.decode_batch])
        with falcon.use(self.fcfg), self._mesh_ctx():
            n_plans = core_engine.warm_buckets(
                self.fcfg, self.cfg, grid,
                dtype=str(self.cfg.dtype), mesh_shape=self.mesh_shape,
                kv_len=self.max_len, spec_gamma=self.gamma or None)
            for (b, s) in self.policy.prefill_shapes():
                rows_b = self._broadcast_rows(self.cache, b)
                jax.block_until_ready(self._prefill_fn(
                    self.params, rows_b, jnp.zeros((b, s), jnp.int32),
                    jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32)))
                self._compiled.add(("prefill", b, s))
            for b in self.policy.decode_batch:
                rows_b = self._broadcast_rows(self.cache, b)
                if self.gamma:
                    jax.block_until_ready(self._verify_fn(
                        self.params, rows_b,
                        jnp.zeros((b, self.gamma + 1), jnp.int32),
                        jnp.zeros((b,), jnp.int32)))
                    self._compiled.add(("spec", b))
                else:
                    jax.block_until_ready(self._decode_fn(
                        self.params, rows_b, jnp.zeros((b, 1), jnp.int32),
                        jnp.zeros((b,), jnp.int32)))
                    self._compiled.add(("decode", b))
            if self.draft is not None:
                self.draft.warm(self.policy, self.gamma)
        self.stats.warm_plans = n_plans
        self.stats.warmed_shapes = len(self._compiled)
        self.stats.t_warm = time.perf_counter() - t0
        return {"plans": n_plans, "shapes": len(self._compiled),
                "seconds": self.stats.t_warm}

    @staticmethod
    def _broadcast_rows(cache, b: int):
        return jax.tree.map(
            lambda c: jnp.broadcast_to(
                c[:, :1], (c.shape[0], b) + c.shape[2:]), cache)

    # -- step loop -----------------------------------------------------------

    def step(self) -> bool:
        """Run one scheduler-selected micro-batch. False when idle.

        Each phase is timed into ``stats.phase_s`` and, while the profiler
        records, written as a ``serve.<phase>`` span: ``serve.schedule``,
        then one ``serve.prefill``/``serve.decode``/``serve.verify`` span
        per step, whose metadata names its bucket and request ids.
        """
        with self.stats.phase("schedule"):
            work = self.scheduler.next_work()
        if work is None:
            return False
        if isinstance(work, PrefillWork):
            kind, run = "prefill", self._run_prefill
            bucket = f"{work.batch_pad}x{work.seq_pad}"
        else:
            kind, run = ("verify", self._run_spec_decode) if self.gamma \
                else ("decode", self._run_decode)
            bucket = str(work.batch_pad)
        meta = {}
        if TraceAnnotation.is_enabled():    # no formatting when untraced
            meta = {"bucket": bucket,
                    "rids": " ".join(str(r.rid) for r in work.requests)}
        with self.stats.phase(kind, **meta):
            run(work)
        return True

    def run(self, max_steps: int | None = None) -> list[Request]:
        """Step until idle (or ``max_steps``); returns finished requests."""
        steps = 0
        while max_steps is None or steps < max_steps:
            if not self.step():
                break
            steps += 1
        return [r for r in self.requests if r.done]

    # -- execution -----------------------------------------------------------

    def _note_shape(self, key: tuple) -> None:
        if key in self._compiled:
            self.stats.bucket_hits += 1
        else:
            self.stats.bucket_misses += 1
            self._compiled.add(key)

    def _run_prefill(self, work: PrefillWork) -> None:
        B, S = work.batch_pad, work.seq_pad
        self._note_shape(("prefill", B, S))
        phase = self.stats.phase
        k = len(work.requests)
        # first chunk of a prefix hit: copy the reused KV/state into the slot
        # before this chunk's rows are gathered
        for i, r in enumerate(work.requests):
            if r.prefix_entry is not None and work.starts[i] == r.prefix_len:
                self._load_prefix(work.slots[i], r)
        with phase("inputs"):
            toks = np.zeros((B, S), np.int32)
            last = np.zeros((B,), np.int32)
            start = np.zeros((B,), np.int32)
            for i, r in enumerate(work.requests):
                n = work.lengths[i]
                toks[i, :n] = r.prompt[work.starts[i]:work.starts[i] + n]
                last[i] = n - 1
                start[i] = work.starts[i]
            idx = jnp.asarray(list(work.slots) + [work.slots[-1]] * (B - k))
        with falcon.use(self.fcfg), self._mesh_ctx():
            with phase("kv_gather"):
                rows = jax.tree.map(lambda c: c[:, idx], self.cache)
            with phase("forward"):
                logits, new_rows = self._prefill_fn(
                    self.params, rows, jnp.asarray(toks), jnp.asarray(start),
                    jnp.asarray(last))
                jax.block_until_ready(logits)
            with phase("kv_scatter"):
                slots = jnp.asarray(work.slots)
                # pad rows i >= k are sliced off; pad positions inside a row
                # are overwritten by decode before the validity mask admits
                # them
                self.cache = jax.tree.map(
                    lambda c, nc: c.at[:, slots].set(nc[:, :k].astype(c.dtype)),
                    self.cache, new_rows)
            if self.draft is not None:
                with phase("draft"):
                    self.draft.prefill_chunk(toks, start, last, work.slots, k)
        with phase("sample"):
            step_logits = np.asarray(logits[:, -1])
            now = time.perf_counter()
            self.stats.prefill_steps += 1
            self.stats.prompt_tokens += work.real_tokens
            self.stats.prefill_padded_tokens += work.padded_tokens
            for i, r in enumerate(work.requests):
                r.prefilled = work.starts[i] + work.lengths[i]
                if not work.final[i]:
                    continue                # chunk done; more prompt to go
                if self.prefix is not None and r.prompt_len > 1:
                    self._insert_prefix(r, work.slots[i])
                self.pos[work.slots[i]] = r.prompt_len
                r.first_token_t = now
                self.stats.generated_tokens += 1
                self._emit(r, int(np.argmax(step_logits[i])), step_logits[i])

    # -- prefix cache --------------------------------------------------------

    def _load_prefix(self, slot: int, req: Request) -> None:
        """Copy a pinned prefix snapshot into ``slot``; release the pin."""
        entry = req.prefix_entry
        n = len(entry.tokens)
        payload = entry.payload
        new = {}
        for name, c in self.cache.items():
            v = jnp.asarray(payload[name]).astype(c.dtype)
            new[name] = (c.at[:, slot].set(v) if name == "state"
                         else c.at[:, slot, :n].set(v))
        self.cache = new
        if self.draft is not None:
            self.draft.load(slot, payload["draft"], n)
        self.prefix.release(entry)
        req.prefix_entry = None

    def _insert_prefix(self, req: Request, slot: int) -> None:
        """Snapshot the freshly prefilled prompt KV under its token key.

        Attention K/V slices to any length, so the entry is keyed at
        ``prompt[:-1]`` — the longest key :meth:`_lookup_prefix` can ever
        match (it always leaves one suffix token to prefill), which makes an
        identical resubmission a full hit. A recurrent ``state`` snapshot is
        only valid at its exact length, so state-bearing caches keep the
        whole prompt as key and serve only prompts that extend this one.
        """
        n = req.prompt_len if "state" in self.cache else req.prompt_len - 1
        if n < 1:
            return
        payload = {}
        for name, c in self.cache.items():
            payload[name] = np.asarray(c[:, slot] if name == "state"
                                       else c[:, slot, :n])
        if self.draft is not None:
            payload["draft"] = self.draft.snapshot(slot, n)
        self.prefix.insert(tuple(req.prompt[:n]), payload)

    # -- decode --------------------------------------------------------------

    def _run_decode(self, work: DecodeWork) -> None:
        k = len(work.slots)
        b = work.batch_pad
        self._note_shape(("decode", b))
        phase = self.stats.phase
        with phase("inputs"):
            idx = jnp.asarray(list(work.slots) + [work.slots[-1]] * (b - k))
            toks = np.zeros((b, 1), np.int32)
            pos = np.zeros((b,), np.int32)
            for i, r in enumerate(work.requests):
                toks[i, 0] = r.generated[-1]
                pos[i] = self.pos[work.slots[i]]
        with falcon.use(self.fcfg), self._mesh_ctx():
            with phase("kv_gather"):
                rows = jax.tree.map(lambda c: c[:, idx], self.cache)
            with phase("forward"):
                logits, new_rows = self._decode_fn(
                    self.params, rows, jnp.asarray(toks), jnp.asarray(pos))
                jax.block_until_ready(logits)
        with phase("kv_scatter"):
            slots = jnp.asarray(work.slots)
            self.cache = jax.tree.map(
                lambda c, nc: c.at[:, slots].set(nc[:, :k]), self.cache,
                new_rows)
        with phase("sample"):
            step_logits = np.asarray(logits[:, -1])
            self.stats.decode_steps += 1
            self.stats.generated_tokens += work.real_tokens
            self.stats.decode_real_rows += work.real_tokens
            self.stats.decode_emitted_tokens += work.real_tokens
            self.stats.decode_padded_tokens += work.padded_tokens
            for i, r in enumerate(work.requests):
                self.pos[work.slots[i]] += 1
                self._emit(r, int(np.argmax(step_logits[i])), step_logits[i])

    def _run_spec_decode(self, work: DecodeWork) -> None:
        """One speculative round: draft γ, verify in one forward, accept.

        Per row: feed ``[t_last, d_1..d_γ]`` at the slot position, take the
        verify argmaxes ``t'_0..t'_γ``, accept drafts while ``d_j ==
        t'_{j-1}``, emit ``t'_0..t'_{n_acc}`` (always ≥ 1 — the bonus token
        means a round never stalls). Rejected draft K/V stays in the cache
        above the new position and is overwritten before validity ever
        admits it, so rollback costs nothing.
        """
        k = len(work.slots)
        b = work.batch_pad
        g = self.gamma
        self._note_shape(("spec", b))
        phase = self.stats.phase
        with phase("inputs"):
            idx = jnp.asarray(list(work.slots) + [work.slots[-1]] * (b - k))
            last2 = np.zeros((b, 2), np.int32)
            pos = np.zeros((b,), np.int32)
            for i, r in enumerate(work.requests):
                last2[i] = r.tokens[-2:]
                pos[i] = self.pos[work.slots[i]]
            last2[k:] = last2[k - 1]        # pad rows mirror the last real row
            pos[k:] = pos[k - 1]
        with falcon.use(self.fcfg), self._mesh_ctx():
            with phase("draft"):
                drafts = self.draft.propose(idx, last2, pos, g, k)   # (b, γ)
                verify = np.concatenate([last2[:, 1:], drafts], axis=1)
            with phase("kv_gather"):
                rows = jax.tree.map(lambda c: c[:, idx], self.cache)
            with phase("forward"):
                logits, new_rows = self._verify_fn(
                    self.params, rows, jnp.asarray(verify), jnp.asarray(pos))
                jax.block_until_ready(logits)
            with phase("kv_scatter"):
                slots = jnp.asarray(work.slots)
                self.cache = jax.tree.map(
                    lambda c, nc: c.at[:, slots].set(nc[:, :k]),
                    self.cache, new_rows)
        with phase("sample"):
            logits_np = np.asarray(logits)                       # (b, γ+1, V)
            greedy = np.argmax(logits_np, axis=-1)
            self.stats.verify_steps += 1
            self.stats.drafted_tokens += g * k
            self.stats.decode_real_rows += k * (g + 1)
            self.stats.decode_padded_tokens += b * (g + 1)
            for i, r in enumerate(work.requests):
                n_acc = 0
                while n_acc < g and int(drafts[i, n_acc]) == int(greedy[i, n_acc]):
                    n_acc += 1
                self.stats.accepted_tokens += n_acc
                emitted = 0
                for j in range(n_acc + 1):
                    emitted += 1
                    self._emit(r, int(greedy[i, j]), logits_np[i, j])
                    if r.done:
                        break               # budget/eos cut mid-acceptance
                self.pos[work.slots[i]] += emitted
                self.stats.generated_tokens += emitted
                self.stats.decode_emitted_tokens += emitted

    def _emit(self, req: Request, tok: int, logits_row=None) -> None:
        """Deliver one generated token; retire the request when finished."""
        req.generated.append(tok)
        if self.record_logits and logits_row is not None:
            req.logits.append(np.asarray(logits_row).copy())
        if req.on_token is not None:
            req.on_token(req, tok)
        if req.stream_q is not None:
            req.stream_q.put(tok)
        hit_eos = req.eos_id is not None and tok == req.eos_id
        if hit_eos or len(req.generated) >= req.max_new_tokens:
            req.state = "done"
            req.finish_t = time.perf_counter()
            self.scheduler.release(req)
            self.stats.requests_finished += 1
            if req.stream_q is not None:
                req.stream_q.put(None)      # end-of-stream sentinel

    # -- observability -------------------------------------------------------

    def summary(self) -> dict:
        """ServeStats + the process plan cache, one coherent snapshot."""
        d = self.stats.as_dict()
        d["plan_cache"] = plan_cache.stats().as_dict()
        d["plan_cache"]["entries"] = len(plan_cache.default_cache())
        d["precombined_weights"] = self.n_precombined
        d["quantize"] = self.quantize
        d["max_len"] = self.max_len
        d["max_slots"] = self.max_slots
        d["speculate"] = self.gamma
        d["prefix_cache"] = None if self.prefix is None else self.prefix.stats()
        d["prefill_chunk"] = self.prefill_chunk
        d["mesh"] = self.mesh_shape or None
        d["n_devices"] = (1 if self.mesh is None
                          else int(np.prod(list(dict(self.mesh.shape).values()))))
        return d


class StepLoop:
    """Drives a :class:`ServeEngine` until its queue and slots drain.

    A thin synchronous loop for CLI/batch use; a real deployment would run
    this on a dedicated thread while frontend threads ``submit``.
    """

    def __init__(self, engine: ServeEngine, max_steps: int | None = None):
        self.engine = engine
        self.max_steps = max_steps

    def run_until_idle(self, poll_s: float = 0.0) -> list[Request]:
        """Drain the engine; ``max_steps`` bounds total steps across both the
        initial drain and the polling phase (a watchdog for wedged work)."""
        steps = 0
        while self.max_steps is None or steps < self.max_steps:
            if self.engine.step():
                steps += 1
            elif poll_s and not self.engine.scheduler.idle:
                time.sleep(poll_s)
            else:
                break
        return [r for r in self.engine.requests if r.done]
