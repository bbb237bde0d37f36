"""Random served weights from ``--seed``, and their published reading.

``make_params`` draws the engine's parameter tree on the device, in
bfloat16, in one jitted call: nothing is drawn on the host and no float32
copy of the tree exists.  The tree follows the engine's layout (shapes from
``repro.models.params.abstract_params``), which differs from the published
one in three conventions, all undone exactly by ``published``:

* RMSNorm weights are stored as ``w - 1`` (the engine multiplies by
  ``1 + scale``);
* the input embedding is stored divided by ``sqrt(hidden_size)`` (the
  engine multiplies by it; 32 for a width of 1,024, a power of two, so
  the division is exact in bfloat16);
* the tied embedding is stored twice, as ``embed`` and as the output head
  ``lm_head`` (its transpose), both padded to a multiple of 256 rows.

``published`` reads that tree back into the names of ``reference.dense`` in
float32.  It takes the output head from the embedding, as the tied
configuration states, not from the engine's ``lm_head`` copy.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

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
    device, drawn from ``seed`` in one jitted call; the tied embedding is
    N(0, ``init_std``^2) (the configuration's ``initializer_range``)."""
    from repro.models.params import abstract_params

    shapes = abstract_params(pcfg)
    d, v = pcfg.d_model, pcfg.vocab_size
    emb_std = float(init_std)
    root = math.sqrt(d)
    if root != int(root) or int(root) & (int(root) - 1):
        raise ValueError(f"hidden_size {d}: sqrt is not a power of two, so "
                         "the engine's embedding scale is not exact")
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def draw(key):
        keys = jax.random.split(key, len(flat) + 1)
        table = (jax.random.normal(keys[-1], (v, d), jnp.float32)
                 * emb_std).astype(jnp.bfloat16)                    # E

        def leaf(i, path, s):
            name = _leaf_name(path)
            if name == "embed":
                return jnp.zeros(s.shape, jnp.bfloat16).at[:v].set(
                    table / jnp.bfloat16(root))
            if name == "lm_head":
                return jnp.zeros(s.shape, jnp.bfloat16).at[:, :v].set(
                    table.T)
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


def published(params, cfg: Dict) -> Dict:
    """The engine tree read as the published model, in float32 (trace this
    inside the reference's jit, so nothing is kept).  ``cfg`` is the
    configuration file."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)   # noqa: E731
    v, d = cfg["vocab_size"], cfg["hidden_size"]
    if params["rest"] or len(params["blocks"]) != 1:
        raise ValueError("expected one homogeneous stack of layers")
    blk = params["blocks"][0]
    attn, mlp = blk["attn"], blk["mlp"]
    layers = {
        "input_norm": 1.0 + f32(blk["ln1"]["scale"]),
        "q": f32(attn["wq"]), "k": f32(attn["wk"]), "v": f32(attn["wv"]),
        "o": f32(attn["wo"]),
        "post_norm": 1.0 + f32(blk["ln2"]["scale"]),
        "gate": f32(mlp["wg"]), "up": f32(mlp["wu"]),
        "down": f32(mlp["wd"]),
    }
    if "bq" in attn:
        layers.update(q_bias=f32(attn["bq"]), k_bias=f32(attn["bk"]),
                      v_bias=f32(attn["bv"]))
    if "q_norm" in attn:
        layers.update(q_norm=1.0 + f32(attn["q_norm"]),
                      k_norm=1.0 + f32(attn["k_norm"]))
    return {"embed": f32(params["embed"][:v]) * math.sqrt(d),
            "layers": layers,
            "norm": 1.0 + f32(params["final_norm"]["scale"])}
