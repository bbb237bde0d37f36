"""Paged KV cache + paged decode attention + continuous batching.

Three layers of coverage:

  * ``PagedKVCache`` unit tests — free-list alloc/release, page reuse
    after release, append/gather round trip through the page-table
    indirection, prefill placement, and the shared cache-leaf schema
    (unknown leaves raise instead of being silently whole-replaced).
  * Kernel equivalence — the ``paged_attention`` Pallas kernel
    (interpret mode on CPU) against the eager contiguous
    ``decode_attention`` to 1e-5 for GPT-2-shaped (MHA) and
    llama3-shaped (GQA) heads across mixed per-slot lengths, with and
    without a sliding window.
  * Engine exactness — the continuous-batching engine (mixed prompt
    lengths, mid-stream join/leave, paged or contiguous, eager or
    plan-fused) produces per-request outputs identical to a per-request
    serial decode loop.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import decode_step, init_params, prefill, resolve_plan
from repro.models.params import (cache_leaf_kind, cache_leaf_name,
                                 kv_seq_axis)
from repro.serving import PagedKVCache, ServingEngine, gather_pages, \
    paged_append
from repro.serving.kv_cache import NULL_PAGE


@pytest.fixture(scope="module")
def rng():
    return jax.random.PRNGKey(0)


def _cfg(arch="qwen1.5-0.5b", **over):
    cfg = get_config(arch).reduced()
    return dataclasses.replace(cfg, **over) if over else cfg


# ------------------------------------------------------------ allocator

def test_alloc_release_and_page_reuse():
    cfg = _cfg()
    kv = PagedKVCache(cfg, slots=2, max_len=64, page_size=16)
    assert kv.pages_per_slot == 4 and kv.num_pages == 9
    p0 = kv.ensure(0, 33)                      # 3 pages
    assert len(p0) == 3 and NULL_PAGE not in p0
    assert kv.pages_in_use == 3
    assert kv.bytes_in_use == 3 * kv.page_bytes
    p0b = kv.ensure(0, 20)                     # shrink request: no-op
    assert list(p0b) == list(p0)
    kv.ensure(1, 64)
    assert kv.pages_in_use == 7 and kv.peak_pages == 7
    kv.ensure(0, 64)                           # fills the pool exactly
    assert kv.pages_in_use == 8 and not kv._free
    released = set(kv.slot_pages(0).tolist())
    kv.release(0)
    assert kv.pages_in_use == 4
    assert kv.slot_pages(0).size == 0
    assert np.all(np.asarray(kv.page_table)[0] == NULL_PAGE)
    # Released pages are handed back out to the next occupant.
    p1 = kv.ensure(0, 48)
    assert len(p1) == 3 and set(p1.tolist()) <= released
    assert NULL_PAGE not in p1
    assert kv.peak_pages == 8                  # peak unchanged by churn
    with pytest.raises(ValueError, match="slot capacity"):
        kv.ensure(0, 65)                       # beyond max_len: explicit


def test_unknown_cache_leaf_raises():
    with pytest.raises(ValueError, match="unregistered cache leaf"):
        cache_leaf_kind("mystery_state")
    assert cache_leaf_kind("k") == "kv"
    assert cache_leaf_kind("ssm") == "state"


@pytest.mark.parametrize("layout", ["bshd", "bhsd"])
def test_append_gather_round_trip(layout):
    """Tokens appended through the page indirection read back, in order,
    from ``gather_pages`` — for both cache layouts."""
    ps, n_pages, h, hd, b = 4, 3, 2, 8, 2
    pool = jnp.zeros((1 + b * n_pages, h, ps, hd), jnp.float32)
    table = jnp.asarray(
        np.arange(1, 1 + b * n_pages, dtype=np.int32).reshape(b, n_pages))
    nprng = np.random.default_rng(0)
    toks = nprng.normal(size=(ps * n_pages, b, h, hd)).astype(np.float32)
    for t in range(ps * n_pages):
        new = jnp.asarray(toks[t])[:, None]              # [B, 1, H, hd]
        if layout == "bhsd":
            new = new.transpose(0, 2, 1, 3)              # [B, H, 1, hd]
        pool = paged_append(pool, table, jnp.full((b,), t, jnp.int32),
                            new, layout=layout)
    seq = gather_pages(pool, table, layout=layout)
    if layout == "bhsd":
        seq = seq.transpose(0, 2, 1, 3)
    np.testing.assert_array_equal(np.asarray(seq),
                                  toks.transpose(1, 0, 2, 3))
    # NULL page untouched by table-routed appends.
    np.testing.assert_array_equal(np.asarray(pool[NULL_PAGE]), 0.0)


@pytest.mark.parametrize("layout", ["bshd", "bhsd"])
def test_paged_append_overrun_routes_to_null(layout):
    """Regression: writes at/past the table's extent used to clamp onto
    the slot's LAST REAL KV row (silently overwriting it); they must land
    in the NULL page.  Simulates an over-run decode block: fill a slot to
    capacity, then keep appending past it — the final page's contents
    survive."""
    ps, n_pages, h, hd, b = 4, 2, 2, 8, 1
    extent = ps * n_pages
    pool = jnp.zeros((1 + n_pages, h, ps, hd), jnp.float32)
    table = jnp.asarray([[1, 2]], np.int32)
    nprng = np.random.default_rng(11)
    toks = nprng.normal(size=(extent, b, h, hd)).astype(np.float32)

    def to_layout(a):
        new = jnp.asarray(a)[:, None]                     # [B, 1, H, hd]
        return new.transpose(0, 2, 1, 3) if layout == "bhsd" else new

    for t in range(extent):
        pool = paged_append(pool, table, jnp.full((b,), t, jnp.int32),
                            to_layout(toks[t]), layout=layout)
    filled = np.asarray(pool)
    # Over-run ticks: positions extent .. extent+2 (as a scan running past
    # max_len does) write junk that must not touch the slot's pages.
    for t in range(extent, extent + 3):
        pool = paged_append(pool, table, jnp.full((b,), t, jnp.int32),
                            to_layout(np.full((b, h, hd), 7.0, np.float32)),
                            layout=layout)
    after = np.asarray(pool)
    np.testing.assert_array_equal(after[1:], filled[1:])   # pages intact
    assert np.any(after[NULL_PAGE] == 7.0)                 # junk sunk


def test_place_prefill_round_trip(rng):
    """A batch-1 prefill cache placed into pages gathers back exactly,
    and state leaves land in the slot row."""
    from repro.serving.kv_cache import place_prefill

    cfg = _cfg("zamba2-2.7b")                  # hybrid: kv + ssm/conv leaves
    params = init_params(rng, cfg)
    plen, slots, max_len, page = 12, 3, 32, 8
    kv = PagedKVCache(cfg, slots=slots, max_len=max_len, page_size=page)
    cache = kv.init_cache()
    toks = jax.random.randint(rng, (1, plen), 0, cfg.vocab_size)
    _, fresh = jax.jit(lambda p: prefill(p, cfg, {"tokens": toks}))(params)
    slot = 1
    pages = jnp.asarray(kv.ensure(slot, plen))
    placed = place_prefill(cache, fresh, jnp.int32(slot), pages,
                           layout=cfg.kv_cache_layout)
    table = kv.page_table
    ax = kv_seq_axis(cfg.kv_cache_layout)
    for path, big in jax.tree_util.tree_flatten_with_path(placed)[0]:
        small = fresh
        for k in path:
            small = small[k.key if hasattr(k, "key") else k.idx]
        if cache_leaf_kind(cache_leaf_name(path)) == "kv":
            for g in range(big.shape[0]):
                seq = gather_pages(big[g], table[slot][None],
                                   layout=cfg.kv_cache_layout)[0]
                got = jnp.moveaxis(seq, ax + 3, 0)[:plen]
                want = jnp.moveaxis(small[g, 0], ax + 3, 0) \
                    .astype(big.dtype)
                np.testing.assert_array_equal(
                    np.asarray(got, np.float32),
                    np.asarray(want, np.float32))
        else:
            np.testing.assert_array_equal(
                np.asarray(big[:, slot], np.float32),
                np.asarray(small[:, 0].astype(big.dtype), np.float32))


# ------------------------------------------------------ kernel vs eager

@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2)])   # MHA and GQA
@pytest.mark.parametrize("window", [0, 7])
def test_paged_kernel_matches_eager_decode(hq, hkv, window):
    """Pallas paged decode attention == eager contiguous decode attention
    to 1e-5, across mixed per-slot lengths (bf16 cache, f32 queries)."""
    from repro.kernels import paged_decode_attention
    from repro.models.layers import decode_attention

    b, d, ps, n_pages = 3, 16, 8, 4
    nprng = np.random.default_rng(2)
    q = jnp.asarray(nprng.normal(size=(b, 1, hq, d)).astype(np.float32))
    k_pool = jnp.asarray(nprng.normal(
        size=(1 + b * n_pages, hkv, ps, d)).astype(np.float32)
    ).astype(jnp.bfloat16)
    v_pool = jnp.asarray(nprng.normal(
        size=(1 + b * n_pages, hkv, ps, d)).astype(np.float32)
    ).astype(jnp.bfloat16)
    lengths = np.array([5, 17, 32], np.int32)
    table = np.zeros((b, n_pages), np.int32)
    nxt = 1
    for i in range(b):
        for j in range(-(-int(lengths[i]) // ps)):
            table[i, j] = nxt
            nxt += 1
    table, lengths = jnp.asarray(table), jnp.asarray(lengths)

    out = paged_decode_attention(q, k_pool, v_pool, table, lengths,
                                 window=window)
    kc = gather_pages(k_pool, table, layout="bshd")
    vc = gather_pages(v_pool, table, layout="bshd")
    ref = decode_attention(q, kc, vc, lengths, window=window, layout="bshd")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=1e-5)
    # Inactive slot (length 0, NULL-page table row): finite zeros.
    out0 = paged_decode_attention(q, k_pool, v_pool,
                                  jnp.zeros_like(table),
                                  jnp.zeros((b,), jnp.int32))
    assert np.all(np.asarray(out0) == 0.0)


# -------------------------------------------------------------- engine

def _serial_reference(cfg, params, prompt, new_tokens, max_len):
    """Per-request greedy decode through the contiguous eager path."""
    logits, cache = jax.jit(lambda p, b: prefill(p, cfg, b))(
        params, {"tokens": jnp.asarray(prompt)[None]})
    ax = kv_seq_axis(cfg.kv_cache_layout)

    def pad(path, a):
        if cache_leaf_kind(cache_leaf_name(path)) == "kv":
            pads = [(0, 0)] * a.ndim
            pads[a.ndim + ax] = (0, max_len - a.shape[ax])
            return jnp.pad(a, pads)
        return a

    cache = jax.tree_util.tree_map_with_path(pad, cache)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [int(tok[0, 0])]
    pos = int(prompt.shape[0])
    lengths = jnp.full((1,), pos, jnp.int32)
    step = jax.jit(lambda p, t, c, po, le: decode_step(
        p, cfg, t, c, po, le)[0::2])
    for _ in range(new_tokens - 1):
        tok, cache = step(params, tok, cache, jnp.int32(pos), lengths)
        out.append(int(tok[0, 0]))
        pos += 1
        lengths = lengths + 1
    return out


@pytest.mark.slow
@pytest.mark.parametrize("paged", [True, False])
def test_engine_mixed_lengths_and_midstream_join(rng, paged):
    """5 requests with heterogeneous prompt lengths over 2 slots: requests
    join as slots free mid-stream; every request's output equals its
    serial per-request reference, and true-token metrics hold."""
    cfg = _cfg()
    params = init_params(rng, cfg)
    nprng = np.random.default_rng(3)
    plens = (16, 9, 12, 16, 5)
    prompts = [nprng.integers(1, cfg.vocab_size, n, dtype=np.int32)
               for n in plens]
    new_tokens, max_len = 12, 48
    refs = [_serial_reference(cfg, params, p, new_tokens, max_len)
            for p in prompts]
    engine = ServingEngine(cfg, params, batch_slots=2, max_len=max_len,
                           decode_block=8, paged=paged)
    reqs = engine.generate(prompts, max_new_tokens=new_tokens)
    for r, ref in zip(reqs, refs):
        assert r.out_tokens == ref, f"request {r.rid} diverged"
    assert all(r.done for r in reqs)
    # True tokens: 5 requests x 12, no padded-slot or overshoot inflation.
    assert engine.metrics["generated"] == len(prompts) * new_tokens
    assert engine.metrics["ticks"] <= engine.metrics["scan_ticks"]
    if paged:
        assert engine.kv is not None
        assert engine.kv.pages_in_use == 0          # all pages returned
        # The paged win: bytes-in-use peak stays below the contiguous
        # slots*max_len reservation.
        assert 0 < engine.metrics["kv_bytes_peak"] \
            <= engine.kv.peak_pages * engine.kv.page_bytes
        assert engine.metrics["kv_bytes_peak"] < \
            engine.metrics["kv_bytes_reserved"]


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["gpt2", "llama3-8b"])
def test_engine_fused_paged_attention_matches_eager(rng, arch):
    """Acceptance: the plan-selected Pallas paged-attention decode path
    produces greedy outputs identical to the eager engine for GPT-2
    (layernorm/MHA) and llama3 (RMSNorm/GQA) across mixed lengths."""
    base = dataclasses.replace(get_config(arch).reduced(), dtype="float32")
    fused = dataclasses.replace(base, use_fused_kernels=True)
    plan = resolve_plan(fused, 2, kv_len=40)
    assert plan.layer("attn").decode_attn.implementation == \
        "paged_attention"
    assert plan.decode_page_size() >= 1
    params = init_params(rng, base)
    nprng = np.random.default_rng(4)
    prompts = [nprng.integers(1, base.vocab_size, n, dtype=np.int32)
               for n in (12, 7, 16)]
    r0 = ServingEngine(base, params, batch_slots=2, max_len=40,
                       decode_block=8).generate(prompts, max_new_tokens=10)
    r1 = ServingEngine(fused, params, batch_slots=2, max_len=40,
                       decode_block=8).generate(prompts, max_new_tokens=10)
    for a, b in zip(r0, r1):
        assert a.out_tokens == b.out_tokens, f"request {a.rid} diverged"


@pytest.mark.slow
def test_engine_paged_bhsd_layout(rng):
    """The attention-native bhsd cache layout runs paged too."""
    cfg = _cfg(kv_cache_layout="bhsd")
    params = init_params(rng, cfg)
    nprng = np.random.default_rng(5)
    prompts = [nprng.integers(1, cfg.vocab_size, n, dtype=np.int32)
               for n in (10, 6)]
    refs = [_serial_reference(cfg, params, p, 8, 32) for p in prompts]
    engine = ServingEngine(cfg, params, batch_slots=2, max_len=32,
                           decode_block=8)
    reqs = engine.generate(prompts, max_new_tokens=8)
    for r, ref in zip(reqs, refs):
        assert r.out_tokens == ref


@pytest.mark.slow
def test_engine_single_request_no_padding_inflation(rng):
    """A lone request on a 3-slot engine: the two idle slots ride along in
    every dispatch but contribute nothing to ``generated``."""
    cfg = _cfg()
    params = init_params(rng, cfg)
    prompt = np.random.default_rng(6).integers(
        1, cfg.vocab_size, 8, dtype=np.int32)
    engine = ServingEngine(cfg, params, batch_slots=3, max_len=32,
                           decode_block=8)
    reqs = engine.generate([prompt], max_new_tokens=9)
    assert len(reqs[0].out_tokens) == 9
    assert engine.metrics["generated"] == 9
    assert engine.metrics["prefills"] == 1
