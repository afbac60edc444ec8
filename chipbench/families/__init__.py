"""Model families: what the harness knows of a model, one module each,
found by the configuration file's ``"family"`` key.

The harness itself (the window, the traffic, the check, the trace readers
and the GaLore-SARA/Adam optimizer reference) is shared by every model.
``load(config)`` imports ``chipbench.families.<family>``, and that module
supplies the rest:

* ``KEYS``: {configuration key: ``ModelConfig`` field}, every size of the
  configuration file that the program's model is built from.
* ``shapes(config)``: the weights as a tree of nested dicts whose leaves
  are shape tuples, in the layout of the program's parameters (the
  harness checks it against ``model.init``).  The tree's flattened order
  is the weights' order: leaf ``i`` is drawn from ``fold_in(key, i)``.
* ``stack_axes(path)``: how many leading axes of the leaf at ``path`` (as
  ``jax.tree_util.keystr`` writes it) stack independent matrices, as
  layers or experts do; 0 for one matrix or vector.  The readings name
  each matrix of a stack ``<path>[<i>]``, ``i`` counted row-major.
* ``lowrank(path)``: whether the leaf takes the low-rank update; its last
  two axes are the matrix, the others its stack.  Every other leaf takes
  full-rank Adam.
* ``ones(path)``: whether the leaf starts at 1 (a norm's scale); every
  other leaf starts N(0, 0.02).
* ``vocab(config)``: the number of token ids the traffic draws from.
* ``constants(config)`` and ``loss(params, tokens, labels, c, prec)``: the
  plain reference forward, ``c`` being what ``constants`` returns; the
  mean next-token loss over positions whose label is >= 0, in float32
  with every matrix product through ``chipbench.reference.mm`` / ``ein``
  (``Precision.HIGHEST``, inputs rounded to ``prec``), importing nothing
  of the program.
* ``flops_per_token(config, seq_len)``: model FLOPs per trained token,
  which ``mfu.*`` reads.
* ``attention_heads(config)``: (heads, key/value heads, head size) of a layer's
  causal attention, which the flash-attention roofline reads; only where
  the family's cells report it.

A PR that adds a configuration of a new family adds these files and
nothing else: its configuration file (``chipbench/configs/<name>.json``,
with ``"family"``), its workload file (``chipbench/workloads/<cell>.json``),
its family module here (which may import ``dense``'s pieces), any
``chipbench/work/`` and ``chipbench/metrics/`` files for per-layer metrics
of its own, and entries in BENCHMARK.json: one under ``configs``, one
under ``workloads``, and its cell's name appended to the ``workloads``
list of each metric it reports.
"""
from __future__ import annotations

import importlib
import pkgutil
from types import ModuleType
from typing import Any, Dict, List


def known() -> List[str]:
    """The family modules of this package, by name."""
    return sorted(m.name for m in pkgutil.iter_modules(__path__)
                  if not m.name.startswith("_"))


def load(config: Dict[str, Any]) -> ModuleType:
    """The family module that the configuration's ``"family"`` names."""
    name = config.get("family")
    found = known()
    if name not in found:
        raise SystemExit(
            f"configuration {config.get('name')!r}: family {name!r} is not "
            f"a module of chipbench/families; found {found}"
        )
    return importlib.import_module(f"{__name__}.{name}")
