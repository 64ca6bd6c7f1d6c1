"""Model families: what the benchmark knows about a model's shapes,
weights, reference and work.

A configuration file names its family under ``"family"``; the family is
the module ``<root>/bench/families/<family>.py`` of the checkout whose
``BENCHMARK.json`` named the cell. There is no default. The harness,
``run.py``, ``control.py``, the metric readers and the generic parts of
``weights.py``, ``reference.py`` and ``flops.py`` hold no model-specific
code: they call the family. A new kind of model is a new family module
and a configuration that names it, and no edit of any file that is there.

A family module provides, for a configuration file ``c`` (a dict):

- ``program_config(c)``: the serving program's ``ModelConfig``. The only
  function that may import the program, and it imports lazily.
- ``shapes(c)``: the weight tree, every leaf a shape tuple, in the
  program's layout.
- ``init_leaf(path, shape, key, dtype)``: the value of a leaf that is
  neither a matrix nor a norm gain, or None for the generic rule
  (``weights.make``: normal(0, 0.02) matrices, unit gains); ``path`` is
  the leaf's ``jax.tree_util.keystr``.
- ``logits(c, params, tokens, *, hidden=None, pos=None)``: the plain
  reference, a causal forward in ``jax.numpy`` that imports nothing of
  the program. (b, s) tokens give (b, s, vocab) float32 logits; with
  ``hidden`` (b, s, d) given instead, the layers alone run on it at
  positions ``pos`` and the hidden state comes back.
- The control's walk, in which ``reference.control_tokens`` casts one
  layer's weights at a time: ``control_embed(c, params, tokens)`` the
  hidden state before the first layer; ``control_layers(c, params)`` the
  weights of each layer in order; ``control_layer(c, p, h, pos)`` one
  layer on ``h`` with weights ``p`` (already cast);
  ``control_head(c, dtype, h, params)`` the token ranked first at every
  position, the final norm and head computed in ``dtype``.
- The work the metric readers count (``flops.py``):
  ``prefill_flops(c, lens)``, ``decode_flops(c, ctx)`` and
  ``paged_attention_need(c, ctx, itemsize)``.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path
from types import ModuleType
from typing import Dict

ROOT = Path(__file__).resolve().parents[2]
# the key under which ``attach`` records the module a configuration uses
FILE_KEY = "family_file"

_loaded: Dict[str, ModuleType] = {}


def module_path(c: Dict, root: Path, where: str = "") -> Path:
    """Where the family that ``c`` names lives under ``root``; stops the
    run, naming the configuration and the family, where it names none or
    the module is missing."""
    what = where or c.get("name", "?")
    name = c.get("family")
    if not name:
        raise SystemExit(f"configuration {what} names no family: add a "
                         f"key \"family\", the stem of a module under "
                         f"{root / 'bench' / 'families'}")
    path = root / "bench" / "families" / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"configuration {what} names family {name!r}, "
                         f"but there is no module {path}")
    return path


def attach(c: Dict, root: Path, where: str = "") -> Dict:
    """``c`` with the path of its family's module under ``root``."""
    return {**c, FILE_KEY: str(module_path(c, root, where))}


def of(c: Dict) -> ModuleType:
    """The family module of a configuration: the one ``attach`` recorded,
    or, for one loaded otherwise, the module under this checkout."""
    path = c.get(FILE_KEY) or str(module_path(c, ROOT))
    if path not in _loaded:
        spec = importlib.util.spec_from_file_location(
            "bench_family_" + Path(path).stem, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]
