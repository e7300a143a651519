"""Model families of the benchmark, one module each, found by file name.

A configuration file names its family (``"family": "dense"``), and
``bench/families/<family>.py`` is that family's module. A new family is a
new file: nothing here lists the families. A module provides:

- ``dims(conf)``: the sizes its weights, reference and FLOP count use,
  read from the configuration file's own keys; they hold ``vocab_size``
  and ``dtype``;
- ``program_config(conf, dims)``: the program's ``ModelConfig``, checked
  against ``dims`` so that the program runs what the file states; it
  raises on any departure;
- ``shapes(dims)``: the weight tree as the serve engine takes it, each leaf
  a shape tuple (every leaf has the type ``dims["dtype"]``);
- ``make_weights(dims, seed, layout)``: that tree from ``--seed``, made on
  the device in one jitted call, in ``layout`` (``bench.layout``; None for
  one chip);
- ``logits_at(dims, params, tokens, positions, precision)``: the plain
  reference's logits after each of ``positions``; ``"float32"`` is float32
  at ``Precision.HIGHEST``, and ``CONTROLS`` names the lower precisions the
  family offers as controls;
- ``prefill_flops(dims, prompt_len)``: the classical FLOPs one prompt's
  prefill requires.
"""
from __future__ import annotations

import importlib
import os
import re

__all__ = ["load"]

DIR = os.path.dirname(os.path.abspath(__file__))


def load(conf: dict):
    """The family module that configuration ``conf`` names."""
    name = conf.get("family")
    if not isinstance(name, str) or not re.fullmatch(r"[A-Za-z][A-Za-z0-9_]*", name):
        raise ValueError(f"the configuration file names no family: 'family' is "
                         f"{name!r}, it must name a module bench/families/<family>.py")
    path = os.path.join(DIR, f"{name}.py")
    if not os.path.isfile(path):
        raise ValueError(f"the configuration names family {name!r}, but "
                         f"bench/families/{name}.py is missing")
    return importlib.import_module(f"{__name__}.{name}")
