"""Model families of the benchmark, one plain module each.

A configuration file names its family (``"family": "dense"``), and that
name alone chooses the module ``bench/reference/<family>.py``: the
harness holds no list of families, so a configuration of a new family
joins by adding that file.  A family module provides:

* ``leaves(cfg)``: the leaves of the program's parameter tree, each a
  ``bench.weights.Leaf`` (path, one layer's shape, mean, std, dtype, and
  for a stacked leaf its stack's path prefix and depth).  Stacks may
  differ in depth, as the mixers of a hybrid do.
* ``empty_subtrees(cfg)``: paths of the empty dicts the program's tree
  holds (a norm without parameters).
* ``logits_at(cfg, seed, tokens, rows, quant=None)``: the plain
  reference's next-token logits [len(rows), V] at the (sequence,
  position) pairs ``rows`` of ``tokens`` [B, T], from weights it makes
  again itself from ``seed`` (``bench.weights``); ``quant`` names a lower
  precision for the control of the correctness check.
* the work counts that ``bench/work.py`` serves to the per-layer readers,
  each optional: ``layer_params(cfg)``, ``head_params(cfg)``,
  ``prefill_flops(cfg, P)``, ``decode_token_flops(cfg, n_keys)`` and
  ``decode_attn_work(cfg, n_keys)``.  A count the module lacks leaves the
  metrics that need it out of the result line; no family borrows
  another's formula.

A family module imports nothing of the program under test.
"""
from __future__ import annotations

import importlib.util
import os
import re
from types import ModuleType
from typing import Dict

# the checkout that holds this package
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# modules loaded in this process, by real path
_LOADED: Dict[str, ModuleType] = {}


def path_of(family: str, root: str = ROOT) -> str:
    """Where the module of ``family`` lies in the checkout ``root``."""
    if not re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", family):
        raise ValueError(f"family {family!r} is not a module name")
    return os.path.join(root, "bench", "reference", family + ".py")


def load(path: str) -> ModuleType:
    """The family module in the file ``path``, loaded once per process.
    A missing file raises ``FileNotFoundError``."""
    path = os.path.realpath(path)
    mod = _LOADED.get(path)
    if mod is None:
        if not os.path.isfile(path):
            raise FileNotFoundError(path)
        name = "bench_family_" + os.path.basename(path)[:-len(".py")]
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return mod


def family(cfg: dict) -> ModuleType:
    """The module of ``cfg``'s family: the file that ``load_cell`` found
    for it in the cell's checkout (``cfg["family_file"]``), else
    ``<family>.py`` beside this file."""
    return load(cfg.get("family_file") or path_of(cfg["family"]))
