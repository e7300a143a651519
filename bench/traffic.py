"""One general traffic generator, driven by a mix file (``bench/traffic/*.json``).

A mix file holds parameters only::

    {"loop": "open", "arrival": "poisson",
     "prompt": {"median": 768, "sigma": 0.35, "min": 256, "max": 1024},
     "output": {"median": 12, "sigma": 0.5, "min": 4, "max": 32}}

``loop`` is ``open`` (requests due on a schedule at the cell's fixed rate,
whether or not earlier ones finished) or ``closed`` (a backlog of
``backlog_per_slot`` x slots requests is kept waiting at all times).
Lengths are lognormal, clipped to ``[min, max]``.

The mix is replayed as a fixed trace: the arrival times and the prompt and
output lengths are drawn once, from a stream that does not depend on the
seed, so every seed offers the same work in the same order and the spread
between runs measures the system, not the draw. Lengths are the
distribution's quantiles at evenly spaced probabilities, shuffled; gaps
are the exponential's quantiles, shuffled. The seed draws the token ids
(and, elsewhere, the weights).
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

__all__ = ["Request", "lognormal_quantiles", "open_loop", "closed_loop_block",
           "rng_for"]

# Number of requests whose lengths form one stratified block in a closed
# loop: each block holds the same multiset of lengths.
CLOSED_BLOCK = 64


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float            # offset from the window's start (open loop)
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A generator for one purpose (``stream``) of one seed, any int size."""
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), int(stream)])


def _norm_ppf(p: np.ndarray) -> np.ndarray:
    """Inverse of the standard normal CDF (Acklam's rational approximation,
    relative error below 1.2e-9), so that numpy alone gives the quantiles."""
    a = [-3.969683028665376e+01, 2.209460984245205e+02, -2.759285104469687e+02,
         1.383577518672690e+02, -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02, -1.556989798598866e+02,
         6.680131188771972e+01, -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01, -2.400758277161838e+00,
         -2.549732539343734e+00, 4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01, 2.445134137142996e+00,
         3.754408661907416e+00]
    p = np.asarray(p, np.float64)
    out = np.empty_like(p)
    lo, hi = p < 0.02425, p > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(p[lo]))
    out[lo] = (((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q + c[5]) / \
        ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    q = np.sqrt(-2 * np.log(1 - p[hi]))
    out[hi] = -(((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q + c[5]) / \
        ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    q = p[mid] - 0.5
    r = q * q
    out[mid] = (((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r + a[5]) * q / \
        (((((b[0] * r + b[1]) * r + b[2]) * r + b[3]) * r + b[4]) * r + 1)
    return out


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of the clipped lognormal."""
    p = (np.arange(n) + 0.5) / n
    x = spec["median"] * np.exp(spec.get("sigma", 0.0) * _norm_ppf(p))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _prompts(rng: np.random.Generator, lengths, vocab: int) -> list[np.ndarray]:
    return [rng.integers(0, vocab, int(n), dtype=np.int32) for n in lengths]


def open_loop(mix: dict, rate: float, seconds: float, seed: int,
              vocab: int) -> list[Request]:
    """The requests due in a window of ``seconds`` at ``rate`` per second.

    Poisson arrivals: the gaps are the exponential's quantiles, shuffled and
    scaled so that the last request is due inside the window; the first is
    due at 0.
    """
    if mix["loop"] != "open":
        raise ValueError(f"open_loop needs an open mix, got {mix['loop']!r}")
    if mix.get("arrival", "poisson") != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrival']!r}")
    n = max(1, int(math.floor(rate * seconds)))
    order = rng_for(0, 1)                            # the same for every seed
    p = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-p) / rate                      # exponential quantiles
    gaps = order.permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    due *= min(1.0, (seconds * (n - 0.5) / n) / max(due[-1], 1e-9))
    plen = order.permutation(lognormal_quantiles(mix["prompt"], n))
    olen = order.permutation(lognormal_quantiles(mix["output"], n))
    prompts = _prompts(rng_for(seed, 2), plen, vocab)
    return [Request(float(t), pr, int(o)) for t, pr, o in zip(due, prompts, olen)]


def closed_loop_block(mix: dict, block: int, seed: int,
                      vocab: int) -> list[Request]:
    """Block ``block`` of the endless closed-loop sequence (CLOSED_BLOCK
    requests, the same lengths in every block, in an order of its own)."""
    if mix["loop"] != "closed":
        raise ValueError(f"closed_loop_block needs a closed mix, got {mix['loop']!r}")
    order = rng_for(0, 1000 + 2 * block)             # the same for every seed
    plen = order.permutation(lognormal_quantiles(mix["prompt"], CLOSED_BLOCK))
    olen = order.permutation(lognormal_quantiles(mix["output"], CLOSED_BLOCK))
    prompts = _prompts(rng_for(seed, 1001 + 2 * block), plen, vocab)
    return [Request(0.0, pr, int(o)) for pr, o in zip(prompts, olen)]
