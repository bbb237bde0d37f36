"""Random served weights from ``--seed``, and the engine's conventions.

``make_params`` draws the engine's parameter tree on the device, in
bfloat16, in one jitted call: nothing is drawn on the host and no float32
copy of the tree exists.  The tree follows the engine's layout (shapes from
``repro.models.params.abstract_params``), which differs from the published
one in three conventions, each undone by a reader here:

* RMSNorm weights are stored as ``w - 1`` (the engine multiplies by
  ``1 + scale``): ``norm``;
* the input embedding is stored divided by ``sqrt(hidden_size)``, and the
  engine multiplies each row it takes by that root in the served dtype:
  ``embedding`` forms the table the same way, so where the root is not a
  power of two the reference sees the engine's rounding, not the draw's;
* the output head ``lm_head`` is ``[hidden_size, vocab]``, both it and
  the embedding padded to a multiple of 256 rows of the vocabulary:
  ``head`` reads it.  A tied configuration stores its embedding twice,
  as ``embed`` and as ``lm_head``, the transpose of the table the engine
  forms from ``embed``, so both roles see one matrix at every width; an
  untied one draws ``lm_head`` from its own leaf key.

``published`` reads the tree into the names of the configuration's
reference module (``bench/reference/``), which knows its layers.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import reference_module

NORM_STD = 0.1      # RMSNorm / qk-norm weights are 1 + N(0, NORM_STD^2)
BIAS_STD = 0.05     # q/k/v biases are N(0, BIAS_STD^2); at 0.5 the v bias
#                     dominates every attention output and all positions
#                     collapse onto a few argmax tokens
NORM_LEAVES = ("scale", "q_norm", "k_norm")
BIAS_LEAVES = ("bq", "bk", "bv")


def seed_words(seed: int, n: int = 2) -> np.ndarray:
    """``n`` uint32 words from any non-negative integer seed (wider than 32
    bits too), through numpy's SeedSequence."""
    return np.random.SeedSequence(int(seed)).generate_state(n, np.uint32)


def _leaf_name(path) -> str:
    last = path[-1]
    return getattr(last, "key", str(last))


def make_params(pcfg, seed: int, init_std: float):
    """The engine's parameter tree for ``pcfg``, bfloat16, on the default
    device, drawn from ``seed`` in one jitted call; the embedding, and an
    untied output head, are N(0, ``init_std``^2) (the configuration's
    ``initializer_range``)."""
    from repro.models.params import abstract_params

    shapes = abstract_params(pcfg)
    d, v = pcfg.d_model, pcfg.vocab_size
    emb_std = float(init_std)
    root = math.sqrt(d)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def draw(key):
        keys = jax.random.split(key, len(flat) + 1)
        table = (jax.random.normal(keys[-1], (v, d), jnp.float32)
                 * emb_std).astype(jnp.bfloat16)                    # E
        stored = table / jnp.bfloat16(root)
        formed = stored * jnp.bfloat16(root)    # E as the engine embeds

        def leaf(i, path, s):
            name = _leaf_name(path)
            if name == "embed":
                return jnp.zeros(s.shape, jnp.bfloat16).at[:v].set(stored)
            if name == "lm_head":
                own = formed.T if pcfg.tie_embeddings else (
                    jax.random.normal(keys[i], (d, v), jnp.float32)
                    * emb_std).astype(jnp.bfloat16)
                return jnp.zeros(s.shape, jnp.bfloat16).at[:, :v].set(own)
            if name in NORM_LEAVES:
                std = NORM_STD
            elif name in BIAS_LEAVES:
                std = BIAS_STD
            else:
                std = 1.0 / math.sqrt(s.shape[-2])
            return (jax.random.normal(keys[i], s.shape, jnp.float32)
                    * std).astype(jnp.bfloat16)

        return jax.tree_util.tree_unflatten(
            treedef, [leaf(i, p, s) for i, (p, s) in enumerate(flat)])

    key = jax.random.wrap_key_data(jnp.asarray(seed_words(seed)))
    return jax.jit(draw)(key)


def norm(scale: jax.Array) -> jax.Array:
    """A norm weight as published, float32, from its stored ``w - 1``."""
    return 1.0 + jnp.asarray(scale, jnp.float32)


def embedding(params, cfg: Dict) -> jax.Array:
    """The input embedding [vocab, hidden] as the engine forms it, in the
    served dtype: each stored row times ``sqrt(hidden_size)``, rounded."""
    e = params["embed"][:cfg["vocab_size"]]
    return e * jnp.asarray(math.sqrt(cfg["hidden_size"]), e.dtype)


def head(params, cfg: Dict) -> jax.Array:
    """The output head [hidden, vocab] in the served dtype: the engine's
    ``lm_head``.  Where the configuration ties it, that holds the same
    numbers as ``embedding`` (``make_params``), and the head is read as
    the transposed embedding, the operand the product has always had: the
    same numbers in another layout round the product differently."""
    if cfg["tie_word_embeddings"]:
        return embedding(params, cfg).T
    return params["lm_head"][:, :cfg["vocab_size"]]


def published(params, cfg: Dict) -> Dict:
    """The engine tree read as the published model by the configuration's
    reference module (trace this inside the reference's jit, so nothing is
    kept).  ``cfg`` is the configuration file."""
    return reference_module(cfg).published(params, cfg)
