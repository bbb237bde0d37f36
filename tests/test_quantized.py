"""Quantized serving: int8/fp8 KV pages + weight-only int8 matmuls
(DESIGN.md §14).

Coverage, bottom-up:

  * Round-trip bounds — ``quantize_kv`` error stays within half an LSB
    of the per-(page, head) scale (int8) / the e4m3 relative precision
    (fp8), including the monotone whole-page requant an append can
    trigger.
  * Paged primitives — quantize-on-write append / window append / chunk
    placement read back through ``gather_pages_dequant`` within those
    bounds; the COW pair duplicates the scale row in the same step as
    the value page, and ``assert_page_accounting`` catches a seeded
    value/scale lockstep violation.
  * Weight-only int8 — per-output-channel quantization is exact on
    zero columns; the fused ``rmsnorm_matmul``/``streamed_ffn`` w8
    twins match the dequantized eager reference; the plan only flags
    ``w8`` where a kernel twin exists.
  * Model parity — one ``prefill_chunk`` + ``decode_step`` +
    ``verify_step`` per (arch, mode) comparing the fused quantized
    kernels against the dense-dequant eager path (GQA and
    sliding-window archs).
  * Engine — greedy tokens under kv_int8 are identical between the
    speculative and plain decode paths and between cold and prefix-hot
    admissions; the quantized pools cut ``kv_bytes_peak`` to ≤ 0.55x
    the bf16 baseline; the accuracy gate (``serving.accuracy``) holds
    greedy equality with f32 on gpt2 (MHA, layernorm) and llama3-8b
    (GQA) for kv_int8 and w8_kv8.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.stream_plan import build_stream_plan
from repro.models import init_params, layers as L
from repro.models.model import decode_step, prefill_chunk, verify_step
from repro.serving import PagedKVCache, ServingEngine
from repro.serving.accuracy import jitter_params, run_accuracy
from repro.serving.kv_cache import (NULL_PAGE, gather_pages,
                                    gather_pages_dequant, kv_quant_dtype,
                                    kv_quant_qmax, paged_append_q,
                                    paged_append_window_q,
                                    place_chunk_pages_q, quantize_kv,
                                    stage_chunk)


def _cfg(arch="qwen1.5-0.5b", **over):
    cfg = get_config(arch).reduced()
    return dataclasses.replace(cfg, **over) if over else cfg


# ------------------------------------------------------ round-trip bounds

@pytest.mark.parametrize("kind", ["int8", "fp8"])
def test_roundtrip_error_bound(kind):
    dtype = kv_quant_dtype(kind)
    qmax = kv_quant_qmax(dtype)
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 4, 16), jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / qmax
    codes = quantize_kv(x, scale, dtype)
    back = codes.astype(jnp.float32) * scale
    err = np.abs(np.asarray(back - x))
    if kind == "int8":
        assert err.max() <= float(scale.max()) * 0.5 + 1e-7
    else:  # e4m3: 3 mantissa bits -> half-ulp relative error 2^-4
        bound = np.abs(np.asarray(x)) * 2.0 ** -4 + float(scale.max()) * 0.5
        assert (err <= bound + 1e-7).all()


def test_quantize_kv_zero_scale_is_safe():
    dtype = kv_quant_dtype("int8")
    x = jnp.zeros((2, 4), jnp.float32)
    codes = quantize_kv(x, jnp.zeros((2, 1)), dtype)
    assert not np.any(np.asarray(codes))


# ------------------------------------------------------ paged primitives

def _quant_pool(kind, pages=5, ps=4, h=2, hd=8):
    dtype = kv_quant_dtype(kind)
    pool = jnp.zeros((pages, h, ps, hd), dtype)
    scale = jnp.zeros((pages, h), jnp.float32)
    return pool, scale


@pytest.mark.parametrize("kind", ["int8", "fp8"])
def test_append_q_gather_dequant_parity(kind):
    pool, scale = _quant_pool(kind)
    table = jnp.asarray([[1, 2]], jnp.int32)
    toks = jax.random.normal(jax.random.PRNGKey(1), (6, 1, 1, 2, 8),
                             jnp.float32)
    for i in range(6):
        pool, scale = paged_append_q(pool, scale, table,
                                     jnp.asarray([i], jnp.int32),
                                     toks[i], layout="bshd")
    dense = np.asarray(gather_pages_dequant(pool, scale, table,
                                            layout="bshd"))[0, :6]
    ref = np.asarray(toks)[:, 0, 0]
    # Monotone requant re-encodes old rows when a page's scale grows:
    # int8 error stays within ~1.5 LSB of the final per-head scale; fp8
    # codes are floating, so the error is relative (ulp = 2^-3) plus the
    # same requant slack.
    lsb = 1.5 * np.asarray(scale)[np.asarray(table)[0]].max() + 1e-6
    bound = lsb if kind == "int8" else np.abs(ref) * 2.0 ** -3 + lsb
    assert (np.abs(dense - ref) <= bound).all()


def test_append_window_q_matches_sequential_appends(kind="int8"):
    pool_w, scale_w = _quant_pool(kind)
    pool_s, scale_s = _quant_pool(kind)
    table = jnp.asarray([[1, 2]], jnp.int32)
    win = jax.random.normal(jax.random.PRNGKey(2), (1, 3, 2, 8),
                            jnp.float32)
    pool_w, scale_w = paged_append_window_q(pool_w, scale_w, table,
                                            jnp.asarray([2], jnp.int32),
                                            win, layout="bshd")
    for i in range(3):
        pool_s, scale_s = paged_append_q(pool_s, scale_s, table,
                                         jnp.asarray([2 + i], jnp.int32),
                                         win[:, i:i + 1], layout="bshd")
    np.testing.assert_array_equal(np.asarray(pool_w), np.asarray(pool_s))
    np.testing.assert_allclose(np.asarray(scale_w), np.asarray(scale_s))


@pytest.mark.parametrize("kind", ["int8", "fp8"])
def test_place_chunk_q_roundtrip(kind):
    pool, scale = _quant_pool(kind)
    seq = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 2, 8),
                            jnp.float32)
    pool, scale = place_chunk_pages_q(pool, scale, seq,
                                      jnp.asarray([1, 3], jnp.int32),
                                      layout="bshd")
    dense = np.asarray(gather_pages_dequant(
        pool, scale, jnp.asarray([[1, 3]], jnp.int32), layout="bshd"))[0]
    ref = np.asarray(seq)[0]
    lsb = 0.5 * np.asarray(scale).max() + 1e-6
    bound = lsb if kind == "int8" else np.abs(ref) * 2.0 ** -3 + lsb
    assert (np.abs(dense - ref) <= bound).all()


def test_cow_copies_scale_row_with_value_page():
    pool, scale = _quant_pool("int8")
    seed = jax.random.normal(jax.random.PRNGKey(4), (1, 4, 2, 8),
                             jnp.float32)
    pool, scale = place_chunk_pages_q(pool, scale, seed,
                                      jnp.asarray([1], jnp.int32),
                                      layout="bshd")
    # Divergent write onto page 3, COW'd from shared page 1.  A tiny
    # token cannot grow the scale, so untouched rows must be VERBATIM
    # copies and the scale row must equal the source's.
    tok = 1e-4 * jax.random.normal(jax.random.PRNGKey(5), (1, 1, 2, 8),
                                   jnp.float32)
    table = jnp.asarray([[3]], jnp.int32)
    pool2, scale2 = paged_append_q(pool, scale, table,
                                   jnp.asarray([1], jnp.int32), tok,
                                   layout="bshd",
                                   cow_src=jnp.int32(1), cow_dst=jnp.int32(3))
    np.testing.assert_allclose(np.asarray(scale2)[3], np.asarray(scale)[1])
    got, src = np.asarray(pool2)[3], np.asarray(pool)[1]
    np.testing.assert_array_equal(got[:, 0], src[:, 0])
    np.testing.assert_array_equal(got[:, 2:], src[:, 2:])
    # ...and the shared source page itself never mutated.
    np.testing.assert_array_equal(np.asarray(pool2)[1], src)


def test_accounting_catches_lockstep_violation():
    cfg = _cfg(quant="kv_int8")
    kv = PagedKVCache(cfg, slots=1, max_len=32, page_size=8)
    kv.assert_page_accounting(kv.init_cache())
    broken = {k: [dict(g) for g in v] for k, v in kv._defs.items()}
    for g in broken["blocks"] + broken["rest"]:
        g.pop("k_scale", None)
    kv._defs = broken
    with pytest.raises(AssertionError):
        kv.assert_page_accounting()


# ------------------------------------------------------ weight-only int8

def test_channelwise_quant_exact_on_zero_columns():
    w = jnp.zeros((8, 4), jnp.float32).at[:, 1].set(
        jnp.linspace(-2.0, 2.0, 8))
    codes, scales = L.quantize_channelwise(w)
    assert float(scales[0]) == 0.0
    back = L.dequantize_channelwise(codes, scales, jnp.float32)
    np.testing.assert_allclose(np.asarray(back)[:, 0], 0.0)
    np.testing.assert_allclose(np.asarray(back)[:, 1], np.asarray(w)[:, 1],
                               atol=2.0 / 127)


def test_fused_norm_matmul_w8_matches_dequant_eager():
    key = jax.random.PRNGKey(6)
    x = jax.random.normal(key, (1, 8, 32), jnp.float32)
    scale = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (32,))
    w = jax.random.normal(jax.random.fold_in(key, 2), (32, 16),
                          jnp.float32)
    got = L.fused_norm_matmul(x, scale, w, w8=1)
    codes, ws = L.quantize_channelwise(w)
    want = L.rms_norm(x, scale) @ L.dequantize_channelwise(
        codes, ws, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-5)


def test_fused_ffn_w8_matches_dequant_eager():
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (1, 8, 16), jnp.float32)
    p = {"wg": jax.random.normal(jax.random.fold_in(key, 1), (16, 32)),
         "wu": jax.random.normal(jax.random.fold_in(key, 2), (16, 32)),
         "wd": jax.random.normal(jax.random.fold_in(key, 3), (32, 16))}
    got = L.fused_ffn(x, p, activation="silu", gated=True, w8=1)

    def dq(w):
        return L.dequantize_channelwise(*L.quantize_channelwise(w),
                                        jnp.float32)
    want = (jax.nn.silu(x @ dq(p["wg"])) * (x @ dq(p["wu"]))) @ dq(p["wd"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-5, rtol=1e-4)


def test_plan_flags_w8_only_where_kernel_twins_exist():
    cfg = _cfg("llama3-8b", quant="w8", use_fused_kernels=True)
    plan = build_stream_plan(cfg, tokens=64)
    assert plan.quant == "w8"
    flagged = [lp for _, lp in plan.layers
               if ("w8", 1) in lp.ffn.blocks or ("w8", 1) in lp.qkv.blocks]
    assert flagged, "w8 plan never flagged a weight-quantized stage"
    for _, lp in plan.layers:
        for choice in (lp.qkv, lp.ffn):
            if ("w8", 1) in choice.blocks:
                assert choice.implementation in ("rmsnorm_matmul",
                                                 "streamed_ffn",
                                                 "streamed_mlp")


# ------------------------------------------------------ model-level parity

@pytest.mark.parametrize("arch,mode", [("llama3-8b", "kv_int8"),
                                       ("gemma3-4b", "kv_fp8")])
def test_fused_quantized_stages_match_dequant_eager(arch, mode):
    """One chunked-prefill + decode + verify dispatch per path: the
    quantized Pallas kernels (scalar-prefetched page scales / per-position
    chunk scales) against the dense ``gather_pages_dequant`` eager
    reference, on GQA (llama3) and sliding-window (gemma3) stacks."""
    cfg_e = _cfg(arch, dtype="float32", quant=mode)
    cfg_f = dataclasses.replace(cfg_e, use_fused_kernels=True)
    params = jitter_params(init_params(jax.random.PRNGKey(0), cfg_e))
    kv = PagedKVCache(cfg_e, slots=1, max_len=64, page_size=8)
    cache = kv.init_cache()
    kv.ensure(0, 24)
    row = kv.table_row(0)
    prompt = np.random.default_rng(0).integers(
        1, cfg_e.vocab_size, 16).astype(np.int32)
    toks, cpages, last = stage_chunk(prompt, 0, 16, row, kv.page_size)
    out = {}
    for cfg in (cfg_e, cfg_f):
        _, lg, cc = prefill_chunk(params, cfg, jnp.asarray(toks)[None],
                                  cache, jnp.asarray(row),
                                  jnp.asarray(cpages), jnp.int32(0),
                                  jnp.int32(last))
        out[cfg.use_fused_kernels] = (np.asarray(lg), cc)
    np.testing.assert_allclose(out[True][0], out[False][0], atol=2e-4)
    cc = out[False][1]
    pos = jnp.asarray([16], jnp.int32)
    dec = {}
    for cfg in (cfg_e, cfg_f):
        _, lg, _ = decode_step(params, cfg, jnp.asarray([[5]], jnp.int32),
                               cc, pos, pos, page_table=kv.page_table)
        dec[cfg.use_fused_kernels] = np.asarray(lg)
    np.testing.assert_allclose(dec[True], dec[False], atol=2e-4)
    ver = {}
    for cfg in (cfg_e, cfg_f):
        _, lg, _ = verify_step(params, cfg,
                               jnp.asarray([[5, 7, 9]], jnp.int32),
                               cc, pos, pos, page_table=kv.page_table)
        ver[cfg.use_fused_kernels] = np.asarray(lg)
    np.testing.assert_allclose(ver[True], ver[False], atol=2e-4)


# ------------------------------------------------------ engine + gate

def _prompts(n, seed=11, length=12, vocab=256):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, length).astype(np.int32)
            for _ in range(n)]


def test_engine_speculative_matches_plain_under_kv_int8():
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = _prompts(2, vocab=cfg.vocab_size)
    kw = dict(batch_slots=2, max_len=64, decode_block=4, quant="kv_int8")
    plain = ServingEngine(cfg, params, **kw)
    r0 = plain.generate([p.copy() for p in prompts], max_new_tokens=10)
    spec = ServingEngine(cfg, params, speculative=True, **kw)
    r1 = spec.generate([p.copy() for p in prompts], max_new_tokens=10)
    assert [r.out_tokens for r in r0] == [r.out_tokens for r in r1]
    assert plain.metrics["quant"] == "kv_int8"
    assert plain.metrics["kv_itemsize_effective"] < 1.1
    plain.kv.assert_page_accounting(plain._slot_cache)
    spec.kv.assert_page_accounting(spec._slot_cache)


def test_engine_prefix_hot_matches_cold_under_kv_int8():
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                        decode_block=4, page_size=4, prefill_chunk=8,
                        quant="kv_int8")
    prompt = _prompts(1, vocab=cfg.vocab_size, length=16)[0]
    cold = eng.generate([prompt.copy()], max_new_tokens=8)
    hits0 = eng.metrics.get("prefix_hits", 0)
    hot = eng.generate([prompt.copy()], max_new_tokens=8)
    assert cold[0].out_tokens == hot[0].out_tokens
    assert eng.metrics.get("prefix_hits", 0) >= hits0
    eng.kv.assert_page_accounting(eng._slot_cache)


def test_kv_int8_cuts_bytes_to_half():
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = _prompts(2, vocab=cfg.vocab_size)
    peak = {}
    for quant in ("none", "kv_int8"):
        eng = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                            decode_block=4, quant=quant)
        eng.generate([p.copy() for p in prompts], max_new_tokens=6)
        peak[quant] = eng.metrics["kv_bytes_peak"]
    assert peak["kv_int8"] <= 0.55 * peak["none"]


def test_engine_rejects_kv_quant_without_paging():
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="paged"):
        ServingEngine(cfg, params, batch_slots=2, max_len=64,
                      paged=False, quant="kv_int8")


@pytest.mark.parametrize("arch", ["gpt2", "llama3-8b"])
def test_accuracy_gate_greedy_matches_f32(arch):
    rep = run_accuracy(arch, modes=("kv_int8", "w8_kv8"), steps=6)
    for mode in ("kv_int8", "w8_kv8"):
        assert rep[mode]["tokens_equal"], \
            f"{arch}/{mode} diverged from the f32 greedy stream"
        assert np.isfinite(rep[mode]["max_logit_err"])
        assert rep[mode]["max_logit_err"] < 0.5
        assert rep[mode]["kv_itemsize"] < 1.1
