"""Plain references, one module per ``model_type`` of a configuration file.

A configuration's module (``bench/reference/<model_type>.py``) is the one
place of the benchmark that knows its architecture.  The harness
(``harness.py``), the weight reading (``weights.py``), the check
(``check.py``, ``control.py``) and the operation counts (``flops.py``) ask
it, through ``reference_module``, for four things:

* ``program_fields(cfg)``: the program's ``ModelConfig`` fields that the
  published ``config.json`` keys set; raises ``ValueError`` for what the
  program cannot compute, and the run is then refused.
* ``published(params, cfg)``: the engine's parameter tree (layout in
  ``weights.py``, whose ``embedding``, ``head`` and ``norm`` undo its
  conventions) read into the names ``forward_logits`` takes, matrices in
  the served dtype: the forward upcasts one layer at a time.
* ``forward_logits(w, cfg, tokens, at, quant=None)``: float32 logits
  [len(at), vocab] of one row ``tokens`` at positions ``at``; ``quant=
  "int8"`` is the control, every linear layer on int8 grids.
* ``layers(cfg)``: one ``Layer`` per layer, in order, which ``flops.py``
  sums over.

``dense.py`` gives ``published``, ``layers`` and the forward of a dense
pre-norm decoder; ``qwen2.py`` and ``qwen3.py`` take them and add
``program_fields``.  A new architecture is a new module here
(and its configuration, traffic and limit files): no other file changes.
"""

from __future__ import annotations

import importlib
from typing import Dict, NamedTuple


class Layer(NamedTuple):
    """One layer's shape, as the operation and byte counts see it."""
    window: int         # keys a query sees at most; 0: every earlier key
    heads: int          # query heads
    kv_heads: int
    head_dim: int
    weights: int        # matrix weights one decode tick reads
    flops: int          # matrix operations per token


def reference_module(cfg: Dict):
    """``bench/reference/<model_type>.py`` of a configuration file."""
    return importlib.import_module(f"bench.reference.{cfg['model_type']}")
