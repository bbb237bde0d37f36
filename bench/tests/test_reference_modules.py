"""The reference-module contract (``bench/reference/__init__.py``), CPU only.

The qwen configurations draw the same weights as before configurations
brought their own weight reading.  A toy architecture, registered as a
``model_type`` module inside the test alone, goes through the harness's
configuration, the weight draw, the check and the operation counts with
no edit to ``bench/*.py``: a width whose square root is not a power of
two, an untied output head, a period of four layers (three windowed, one
full) held as four block stacks, and two layers left over.
"""

import hashlib
import json
import math
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, flops, harness, weights
from bench.reference import Layer, dense

CONFIGS = Path(__file__).parents[1] / "configs"
SMALL = dict(hidden_size=64, num_attention_heads=4, head_dim=16,
             intermediate_size=128, num_hidden_layers=2, vocab_size=512)

# sha256 over (key path, bytes) of every leaf of ``make_params`` at SMALL
# widths, seed 2**33 + 7, recorded with the draw as it stood at commit
# 4694631, before the weight reading moved into the reference modules.
PARENT_DIGESTS = {
    "qwen3-0.6b": "01bcb37ce47636ffe34617b9017e38bd"
                  "df3d385e0bfaf13821178334499cd5f8",
    "qwen1.5-0.5b": "ad84ccddb83b6f182cccf7660e7fb2bc"
                    "f64eb09a1e9f13f85556c8af41742df7",
}


def small(name, **kw):
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    cfg.update(SMALL, **kw)
    if cfg["model_type"] == "qwen2":
        cfg.pop("head_dim")
    return cfg


@pytest.mark.parametrize("name,kv", [("qwen3-0.6b", 2), ("qwen1.5-0.5b", 4)])
def test_make_params_draws_the_recorded_tree(name, kv):
    cfg = small(name, num_key_value_heads=kv)
    params = weights.make_params(harness.program_config(cfg), 2**33 + 7,
                                 cfg["initializer_range"])
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == PARENT_DIGESTS[name]


# sha256 of the float32 gaps of ``check.Reference`` (row width 512, 8
# served tokens) over three fixed requests, at the draw of
# PARENT_DIGESTS, recorded with the reference as it stood at commit
# 4694631: the program's reading and the int8 control's.
PARENT_GAPS = {
    ("qwen3-0.6b", False): "6dd06c823b436b015ae57a7bb922f590"
                           "4a36fa3b537735ec7d7cf64bd02bc1f3",
    ("qwen3-0.6b", True): "55870e1200e26bbbda11d06fa98c869e"
                          "d8a432e41eeffa5ddb7aa3efb8566220",
    ("qwen1.5-0.5b", False): "c8911e1fb04878ef9a8f5acf0cccec1d"
                             "876313780f4ddeb43296dd786afafe96",
    ("qwen1.5-0.5b", True): "e27022534a3b6006a9b3f9312d6d034a"
                            "cd6de0b333332ef1b4967724f5f70978",
}


@pytest.mark.parametrize("name,kv,control", [
    ("qwen3-0.6b", 2, False), ("qwen3-0.6b", 2, True),
    ("qwen1.5-0.5b", 4, False), ("qwen1.5-0.5b", 4, True)])
def test_reference_reads_the_recorded_gaps(name, kv, control):
    cfg = small(name, num_key_value_heads=kv)
    params = weights.make_params(harness.program_config(cfg), 2**33 + 7,
                                 cfg["initializer_range"])
    rng = np.random.default_rng(11)
    reqs = [types.SimpleNamespace(rid=i, prompt=rng.integers(1, 512, n),
                                  out_tokens=list(rng.integers(0, 512, 8)),
                                  failed=False)
            for i, n in enumerate((37, 300, 500))]
    gaps = check.Reference(cfg, 512, 8).gaps(params, reqs, control=control)
    digest = hashlib.sha256(np.asarray(gaps, np.float32).tobytes())
    assert digest.hexdigest() == PARENT_GAPS[name, control]


def test_a_tied_head_is_the_embedding_the_engine_forms():
    """At a width whose root is not a power of two, the tied ``lm_head``
    holds the embedding as the engine forms it, which the reference reads
    in both roles."""
    cfg = small("qwen3-0.6b", num_key_value_heads=2, hidden_size=48,
                head_dim=12)
    params = weights.make_params(harness.program_config(cfg), 9, 0.02)
    v = cfg["vocab_size"]
    emb = np.asarray(weights.embedding(params, cfg), np.float32)
    np.testing.assert_array_equal(
        np.asarray(params["lm_head"][:, :v], np.float32).T, emb)
    np.testing.assert_array_equal(
        np.asarray(weights.head(params, cfg), np.float32).T, emb)
    w = weights.published(params, cfg)
    np.testing.assert_array_equal(np.asarray(w["embed"], np.float32), emb)


def test_what_the_program_cannot_compute_is_refused():
    cfg = small("qwen3-0.6b", num_key_value_heads=2, hidden_act="gelu")
    with pytest.raises(harness.Refused, match="silu and RMSNorm eps 1e-6"):
        harness.program_config(cfg)


# --------------------------------------------------------------------- #
# A toy architecture: period of four layers, the fourth full attention,
# the others a window of WINDOW keys; untied head; width 48.
# --------------------------------------------------------------------- #

PERIOD, WINDOW = 4, 16
TOY = dict(model_type="toy", program_arch="gemma3-4b", hidden_size=48,
           num_attention_heads=4, num_key_value_heads=2, head_dim=12,
           intermediate_size=96, num_hidden_layers=6, vocab_size=300,
           sliding_window=WINDOW, tie_word_embeddings=False,
           rms_norm_eps=1e-6, rope_theta=10000.0, initializer_range=0.02,
           torch_dtype="bfloat16")


def toy_window(i):
    return 0 if (i + 1) % PERIOD == 0 else WINDOW


def toy_program_fields(cfg):
    return {"num_layers": cfg["num_hidden_layers"],
            "d_model": cfg["hidden_size"],
            "num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"],
            "d_ff": cfg["intermediate_size"],
            "vocab_size": cfg["vocab_size"],
            "rope_theta": cfg["rope_theta"], "activation": "silu",
            "qk_norm": False, "qkv_bias": False,
            "sliding_window": WINDOW, "global_attn_every": PERIOD,
            "tie_embeddings": cfg["tie_word_embeddings"]}


def toy_published(params, cfg):
    """Layer ``g * PERIOD + p`` is group ``g`` of stack ``p``; the layers
    past the last whole period are in ``rest``, unstacked."""
    stacks = params["blocks"]
    groups = stacks[0]["ln1"]["scale"].shape[0]
    blocks = [jax.tree.map(lambda a: a[g], stacks[p])
              for g in range(groups) for p in range(PERIOD)]
    blocks += list(params["rest"])
    return {"embed": weights.embedding(params, cfg),
            "head": weights.head(params, cfg),
            "layers": [{"input_norm": weights.norm(b["ln1"]["scale"]),
                        "q": b["attn"]["wq"], "k": b["attn"]["wk"],
                        "v": b["attn"]["wv"], "o": b["attn"]["wo"],
                        "post_norm": weights.norm(b["ln2"]["scale"]),
                        "gate": b["mlp"]["wg"], "up": b["mlp"]["wu"],
                        "down": b["mlp"]["wd"]} for b in blocks],
            "norm": weights.norm(params["final_norm"]["scale"])}


def toy_forward_logits(w, cfg, tokens, at, quant=None):
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps, s = cfg["rms_norm_eps"], tokens.shape[0]
    pos = jnp.arange(s)
    x = f32(jnp.take(w["embed"], tokens, axis=0))
    for i, lw in enumerate(w["layers"]):
        lw = jax.tree.map(f32, lw)
        h = dense.rms_norm(x, lw["input_norm"], eps)
        q, k, v = (dense.linear(h, lw[n], quant).reshape(s, -1, hd)
                   for n in "qkv")
        q = dense.rope(q, pos, cfg["rope_theta"])
        k = dense.rope(k, pos, cfg["rope_theta"])
        k, v = (jnp.repeat(a, hq // hkv, axis=1) for a in (k, v))
        see = pos[None, :] <= pos[:, None]
        if toy_window(i):
            see &= pos[:, None] - pos[None, :] < toy_window(i)
        sc = jnp.einsum("qhd,khd->hqk", q, k,
                        precision=dense.HIGHEST) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(see[None], sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=dense.HIGHEST)
        x = x + dense.linear(o.reshape(s, hq * hd), lw["o"], quant)
        h = dense.rms_norm(x, lw["post_norm"], eps)
        f = (jax.nn.silu(dense.linear(h, lw["gate"], quant))
             * dense.linear(h, lw["up"], quant))
        x = x + dense.linear(f, lw["down"], quant)
    h = dense.rms_norm(jnp.take(x, at, axis=0), f32(w["norm"]), eps)
    return dense.linear(h, f32(w["head"]), quant)


def toy_layers(cfg):
    d, hq, hkv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                      cfg["num_key_value_heads"], cfg["head_dim"])
    n = d * (hq + 2 * hkv) * hd + hq * hd * d + 3 * d * cfg[
        "intermediate_size"]
    return [Layer(window=toy_window(i), heads=hq, kv_heads=hkv, head_dim=hd,
                  weights=n, flops=2 * n)
            for i in range(cfg["num_hidden_layers"])]


@pytest.fixture
def toy(monkeypatch):
    """The toy registered as ``bench/reference/toy.py`` for this test."""
    mod = types.ModuleType("bench.reference.toy")
    mod.program_fields = toy_program_fields
    mod.published = toy_published
    mod.forward_logits = toy_forward_logits
    mod.layers = toy_layers
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return dict(TOY)


def test_toy_weights_flow_through_make_params(toy):
    pcfg = harness.program_config(toy)
    params = weights.make_params(pcfg, 2**40 + 3, toy["initializer_range"])
    assert len(params["blocks"]) == PERIOD and len(params["rest"]) == 2
    v, root = toy["vocab_size"], math.sqrt(toy["hidden_size"])
    assert root != 2 ** round(math.log2(root))
    emb = weights.embedding(params, toy)
    assert emb.dtype == jnp.bfloat16 and emb.shape == (v, 48)
    np.testing.assert_array_equal(
        np.asarray(emb, np.float32),
        np.asarray(params["embed"][:v] * jnp.bfloat16(root), np.float32))
    head = np.asarray(weights.head(params, toy), np.float32)
    assert head.shape == (48, v)
    assert np.all(np.asarray(params["lm_head"][:, v:]) == 0)
    # Untied: the head is its own draw, not the embedding's transpose.
    table = np.asarray(params["embed"][:v], np.float32) * root
    assert np.corrcoef(head.T.ravel(), table.ravel())[0, 1] < 0.1
    assert 0.015 < head.std() < 0.025            # N(0, 0.02^2)


def test_toy_reference_flows_through_the_check(toy):
    params = weights.make_params(harness.program_config(toy), 7, 0.5)
    w = jax.jit(lambda p: weights.published(p, toy))(params)
    fwd = jax.jit(lambda t, a: toy_forward_logits(w, toy, t, a))
    rng = np.random.default_rng(1)
    n, width = 4, 64
    reqs = []
    for rid, plen in enumerate((30, 40)):   # longer than the window
        prompt = rng.integers(1, toy["vocab_size"], plen)
        served = []
        for _ in range(n):                  # greedy, teacher-forced
            toks, at = check.row(prompt, served + [0], width)
            served.append(int(jnp.argmax(fwd(jnp.asarray(toks),
                                              jnp.asarray(at))[-1])))
        reqs.append(types.SimpleNamespace(rid=rid, prompt=prompt,
                                          out_tokens=served, failed=False))
    ref = check.Reference(toy, width, n)
    gaps = ref.gaps(params, reqs)
    assert gaps.shape == (2 * n,)
    assert gaps.max() <= 1e-5, gaps
    reqs[1].out_tokens[2] = (reqs[1].out_tokens[2] + 1) % toy["vocab_size"]
    assert ref.gaps(params, reqs)[n + 2] > 1e-3
    ctl = ref.gaps(params, reqs, control=True)
    assert ctl.shape == (2 * n,) and np.all(np.isfinite(ctl))


def test_toy_counts_sum_over_its_windowed_and_full_layers(toy):
    per_key = 2 * 2 * 12 * 2            # K and V, 2 kv heads, hd 12, bf16
    # Positions 2..4 see 3 + 4 + 5 keys in every layer: inside the window.
    assert flops.live_kv_bytes(toy, 2, 3) == 6 * 12 * per_key
    # Positions 10..19: a windowed layer sees 11, 12, 13, 14, 15 and then
    # 16 keys five times (145), the full layer 11 + ... + 20 (155).
    assert flops.live_kv_bytes(toy, 10, 10) == (5 * 145 + 155) * per_key
    assert flops.attention_flops(toy, 10, 20) == (
        4 * (5 * 145 + 155) * 4 * 12)
    n = 48 * (4 + 4) * 12 + 48 * 48 + 3 * 48 * 96
    assert flops.layer_matmul_params(toy) == n
    assert flops.decode_weight_bytes(toy) == 2 * (6 * n + 48 * 300)
    assert flops.decode_flops(toy, 10, 10) == (
        10 * 6 * 2 * n + 4 * (5 * 145 + 155) * 4 * 12 + 10 * 2 * 48 * 300)
