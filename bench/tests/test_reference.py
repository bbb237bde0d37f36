"""The plain reference against the engine, at reduced widths (CPU only).

The engine runs in float32 through its fused plan (Pallas kernels in
interpret mode): chunked prefill into the paged pools, then paged decode.
Its served greedy tokens must be the reference's first choice at every
position, and its full forward's logits must match the reference's.
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, harness, weights
from bench.reference import dense

CONFIGS = Path(__file__).parents[1] / "configs"
SMALL = dict(hidden_size=64, num_attention_heads=4, head_dim=16,
             intermediate_size=128, num_hidden_layers=2, vocab_size=512)


def small(name, **kw):
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    cfg.update(SMALL, **kw)
    if cfg["model_type"] == "qwen2":
        cfg.pop("head_dim")
    return cfg


@pytest.mark.parametrize("cfg", [
    small("qwen3-0.6b", num_key_value_heads=2),
    small("qwen1.5-0.5b", num_key_value_heads=4),
], ids=["qwen3", "qwen2"])
def test_engine_prefill_and_paged_decode_match_reference(cfg):
    from repro.models import forward_hidden
    from repro.serving import ServingEngine

    pcfg = dataclasses.replace(harness.program_config(cfg), dtype="float32")
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          weights.make_params(pcfg, 5, 0.5))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 512, n).astype(np.int32) for n in (37, 70)]
    with jax.default_matmul_precision("highest"):
        eng = ServingEngine(pcfg, params, batch_slots=2, max_len=128)
        reqs = eng.generate(prompts, 8)
        ref = check.Reference(cfg, 128, 8)
        gaps = ref.gaps(params, reqs)
        assert gaps.shape == (16,)
        assert gaps.max() <= 1e-4, gaps
        # Full forward, teacher-forced over prompt + served tokens.
        toks, at = check.row(reqs[1].prompt, reqs[1].out_tokens, 128)
        h = forward_hidden(params, pcfg, {"tokens": jnp.asarray(toks)[None]},
                           remat=False)
        got = (h[0, at] @ params["lm_head"])[:, :512]
        want = dense.forward_logits(
            weights.published(params, cfg), cfg, jnp.asarray(toks),
            jnp.asarray(at), qk_norm=check.reference_module(cfg).QK_NORM)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)
    assert float(jnp.std(want)) > 0.1      # logits are not degenerate


def test_int8_control_differs_from_f32():
    cfg = small("qwen3-0.6b", num_key_value_heads=2)
    pcfg = harness.program_config(cfg)
    params = weights.make_params(pcfg, 3, 0.5)
    w = weights.published(params, cfg)
    toks = jnp.asarray(np.random.default_rng(1).integers(1, 512, 64))
    at = jnp.arange(64)
    f32 = dense.forward_logits(w, cfg, toks, at, qk_norm=True)
    i8 = dense.forward_logits(w, cfg, toks, at, qk_norm=True, quant="int8")
    err = float(jnp.max(jnp.abs(f32 - i8)))
    assert 1e-3 < err < 0.5 * float(jnp.max(jnp.abs(f32)))
