"""Self-speculative decoding: draft-then-verify on the paged engine
(DESIGN.md §11).

Contract pinned here (ISSUE 6 acceptance):

  * Verify attention — the W-row eager reference equals per-row decode
    attention, and the Pallas ``paged_verify_attention`` kernel
    (interpret mode on CPU) equals the eager reference, for MHA and GQA
    heads with and without a sliding window.
  * Greedy exactness — the speculative engine's delivered tokens are
    BIT-IDENTICAL to the non-speculative engine for dense, GQA, and
    sliding-window configs: acceptance only ever keeps tokens that equal
    the model's own greedy argmax, so drafting quality affects speed,
    never output.
  * Zero-acceptance worst case — every verify dispatch still delivers at
    least one token (row 0 is plain greedy decode), so incompressible
    traffic degrades to the non-speculative rate, not below it.
  * Rollback safety — ``rollback_extent`` only ever frees freshly
    allocated, exclusively owned pages (asserted in the allocator);
    rolling back next to COW-shared prefix pages never touches the
    shared pages, and page accounting stays exact through admission /
    rollback / retire churn (``assert_page_accounting`` after every
    rollback via the engine's debug hook).
  * Compile discipline — verify window widths come from a <=3-rung
    ladder, so the verify program traces at most three times no matter
    the draft mix.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.serving import PagedKVCache, ServingEngine
from repro.serving.kv_cache import NULL_PAGE

multi = pytest.mark.skipif(len(jax.devices()) < 8,
                           reason="needs 8 forced host devices")


def _cfg(arch, **over):
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32",
                              use_fused_kernels=True)
    return dataclasses.replace(cfg, **over) if over else cfg


CONFIGS = {
    "dense": lambda: _cfg("gpt2"),
    "gqa": lambda: _cfg("llama3-8b", num_heads=8, num_kv_heads=4,
                        head_dim=8),
    "swa": lambda: _cfg("gemma3-4b", num_heads=8, num_kv_heads=4,
                        head_dim=8),
}


def _repetitive_prompts(cfg):
    """A draft-friendly mix: one strongly periodic prompt (n-gram lookup
    fires), one short arbitrary prompt, one prompt repeating a shared
    block (prefix-cache traffic)."""
    v = cfg.vocab_size
    return [
        np.array(([1, 2, 3, 4, 5, 6, 7, 8] * 4)[:30], np.int32) % v,
        np.array([9, 8, 7, 6, 5], np.int32) % v,
        np.array([1, 2, 3, 4] * 5, np.int32) % v,
    ]


def _run(cfg, params, prompts, *, new_tokens=10, check_pages=False,
         **eng):
    eng.setdefault("batch_slots", 2)
    eng.setdefault("max_len", 96)
    eng.setdefault("decode_block", 4)
    e = ServingEngine(cfg, params, **eng)
    if check_pages:
        e._debug_check_pages = True
    reqs = e.generate([p.copy() for p in prompts],
                      max_new_tokens=new_tokens)
    return e, [r.out_tokens for r in reqs]


# ------------------------------------------------- verify attention math

@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2)])   # MHA and GQA
@pytest.mark.parametrize("window", [0, 7])
def test_verify_attention_matches_per_row_decode(hq, hkv, window):
    """Eager verify attention row i == eager decode attention at length
    q_off + i: the verify window is literally W stacked decode steps."""
    from repro.models.layers import decode_attention, verify_attention

    b, s, d, w = 3, 40, 16, 4
    nprng = np.random.default_rng(3)
    q = jnp.asarray(nprng.normal(size=(b, w, hq, d)).astype(np.float32))
    kc = jnp.asarray(nprng.normal(size=(b, s, hkv, d)).astype(np.float32))
    vc = jnp.asarray(nprng.normal(size=(b, s, hkv, d)).astype(np.float32))
    q_off = jnp.asarray(np.array([5, 17, 33], np.int32))

    out = verify_attention(q, kc, vc, q_off, window=window, layout="bshd")
    assert out.shape == (b, w, hq, d)
    for i in range(w):
        # Row i sees positions < q_off + i + 1 — decode_attention takes
        # that extent directly as cache_len.
        ref = decode_attention(q[:, i:i + 1], kc, vc, q_off + i + 1,
                               window=window, layout="bshd")
        np.testing.assert_allclose(np.asarray(out[:, i:i + 1]),
                                   np.asarray(ref), atol=1e-6)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2)])
@pytest.mark.parametrize("window", [0, 7])
def test_paged_verify_kernel_matches_eager(hq, hkv, window):
    """Pallas paged verify kernel == eager verify attention to 1e-5
    through the page-table indirection, mixed per-slot offsets."""
    from repro.kernels import paged_verify_attention
    from repro.models.layers import verify_attention
    from repro.serving.kv_cache import gather_pages

    b, d, ps, n_pages, w = 3, 16, 8, 5, 4
    nprng = np.random.default_rng(4)
    q = jnp.asarray(nprng.normal(size=(b, w, hq, d)).astype(np.float32))
    k_pool = jnp.asarray(nprng.normal(
        size=(1 + b * n_pages, hkv, ps, d)).astype(np.float32))
    v_pool = jnp.asarray(nprng.normal(
        size=(1 + b * n_pages, hkv, ps, d)).astype(np.float32))
    q_off = np.array([5, 17, 33], np.int32)
    table = np.zeros((b, n_pages), np.int32)
    nxt = 1
    for i in range(b):
        for j in range(-(-(int(q_off[i]) + w) // ps)):
            table[i, j] = nxt
            nxt += 1
    table, q_off = jnp.asarray(table), jnp.asarray(q_off)

    out = paged_verify_attention(q, k_pool, v_pool, table, q_off,
                                 window=window)
    kc = gather_pages(k_pool, table, layout="bshd")
    vc = gather_pages(v_pool, table, layout="bshd")
    ref = verify_attention(q, kc, vc, q_off, window=window, layout="bshd")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=1e-5)
    # Idle slots (offset 0, NULL table row): finite zeros, no NaNs.
    out0 = paged_verify_attention(q, k_pool, v_pool,
                                  jnp.zeros_like(table),
                                  jnp.zeros((b,), jnp.int32))
    assert np.all(np.isfinite(np.asarray(out0)))


# ---------------------------------------------------- rollback allocator

def test_rollback_extent_frees_exclusive_tail():
    cfg = _cfg("qwen1.5-0.5b")
    kv = PagedKVCache(cfg, slots=2, max_len=64, page_size=16)
    kv.ensure(0, 60)                            # 4 pages
    assert kv.pages_in_use == 4
    dropped = kv.rollback_extent(0, 20)         # keep 2
    assert dropped == 2 and kv.pages_in_use == 2
    assert np.count_nonzero(
        np.asarray(kv.page_table)[0] != NULL_PAGE) == 2
    kv.assert_page_accounting()
    # Shrinking to the same extent is a no-op; growing again reuses the
    # freed pages.
    assert kv.rollback_extent(0, 32) == 0
    kv.ensure(0, 60)
    assert kv.pages_in_use == 4
    kv.assert_page_accounting()


def test_rollback_extent_refuses_shared_pages():
    """The guard satellite: a rollback that would free a shared or
    tree-owned page is a custody bug, not a cleanup — it must trip the
    allocator's assertion instead of corrupting the radix tree."""
    cfg = _cfg("qwen1.5-0.5b")
    kv = PagedKVCache(cfg, slots=2, max_len=64, page_size=16)
    pages = kv.ensure(0, 32)                    # 2 pages
    kv.adopt_shared(1, int(pages[-1]))          # slot 1 shares the tail
    with pytest.raises(AssertionError, match="rollback"):
        kv.rollback_extent(0, 1)
    kv.release(1)
    kv.mark_tree(int(pages[-1]))                # tree owns the tail
    with pytest.raises(AssertionError, match="rollback"):
        kv.rollback_extent(0, 1)


# --------------------------------------------------------------- engine

@pytest.mark.parametrize("name", list(CONFIGS))
def test_speculative_bitmatch(name):
    """Speculative greedy tokens == non-speculative greedy tokens, for
    dense / GQA / sliding-window configs, with real accepts happening on
    the repetitive traffic and the verify program compiling at most
    three times (the W ladder)."""
    cfg = CONFIGS[name]()
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = _repetitive_prompts(cfg)
    _, base = _run(cfg, params, prompts)
    e, spec = _run(cfg, params, prompts, speculative=True, draft_len=4,
                   check_pages=True)
    assert spec == base
    m = e.metrics
    assert m["verify_dispatches"] > 0
    assert m["spec_tokens"] >= m["verify_dispatches"]   # >= 1 token/dispatch
    assert m["verify_traces"] <= 3                      # the W ladder
    if name != "swa":
        # gpt2/llama random weights collapse to repetition, so n-gram
        # drafting provably fires; the swa smoke weights stay aperiodic
        # (zero drafts is then CORRECT — and still bit-matches above).
        assert m["draft_tokens"] > 0
    e.kv.assert_page_accounting()


def test_zero_acceptance_worst_case():
    """Incompressible traffic: drafts are wrong (or absent), every
    dispatch still delivers exactly row 0's token, outputs bit-match,
    and rollback returns every speculatively provisioned page."""
    cfg = _cfg("gpt2")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, n, dtype=np.int32)
               for n in (21, 13)]
    _, base = _run(cfg, params, prompts, new_tokens=8)
    e, spec = _run(cfg, params, prompts, new_tokens=8, speculative=True,
                   draft_len=4, check_pages=True)
    assert spec == base
    m = e.metrics
    # Worst case still makes forward progress at >= 1 token per dispatch.
    assert m["spec_tokens"] >= m["verify_dispatches"] > 0
    assert m["dispatches_per_token"] <= 1.0
    e.kv.assert_page_accounting()
    # All slots retired: no page is slot-referenced (tree-cached pages
    # are counted separately and are fine to keep).
    assert e.kv.pages_in_use == 0


def test_rollback_next_to_cow_shared_prefix():
    """Bootstrap-admitted repeat traffic: the slot decodes speculatively
    right on top of COW-shared prefix pages.  The COW swap plus verify
    appends plus rollback must leave the cached tree pages untouched and
    the outputs identical to the plain engine."""
    cfg = _cfg("llama3-8b", num_heads=8, num_kv_heads=4, head_dim=8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    # Page-aligned prompt (bootstrap full hits are page-granular), sized
    # off a probe engine's resolved page size.
    ps = ServingEngine(cfg, params, batch_slots=1, max_len=96,
                       prefix_bootstrap=True).kv.page_size
    prompt = np.array(([3, 1, 4, 1, 5, 9, 2, 6] * 16)[:2 * ps], np.int32)
    # Same prompt twice on ONE slot, so the runs serialize: the second
    # admits fully cached (bootstrap) and speculates over the shared
    # tail page post-COW.
    _, base = _run(cfg, params, [prompt, prompt], new_tokens=10,
                   batch_slots=1, prefix_bootstrap=True)
    e, spec = _run(cfg, params, [prompt, prompt], new_tokens=10,
                   batch_slots=1, prefix_bootstrap=True, speculative=True,
                   draft_len=4, check_pages=True)
    assert spec == base
    assert e.metrics["prefix_bootstraps"] >= 1
    assert e.metrics["cow_copies"] >= 1
    e.kv.assert_page_accounting()


def test_mixed_speculative_and_chunked_prefill():
    """A burst wider than the slot count: chunked prefill of late
    arrivals interleaves with speculative verify dispatches over the
    early ones — parked mid-prefill slots ride the verify window on NULL
    routing, and every request's tokens still bit-match."""
    cfg = _cfg("gpt2")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    prompts = [np.array([1, 2, 3, 4] * 8, np.int32),
               rng.integers(1, cfg.vocab_size, 41, dtype=np.int32),
               np.array([7, 7, 8, 9] * 7, np.int32),
               rng.integers(1, cfg.vocab_size, 9, dtype=np.int32),
               np.array(([5, 6] * 20)[:33], np.int32)]
    _, base = _run(cfg, params, prompts, new_tokens=8)
    e, spec = _run(cfg, params, prompts, new_tokens=8, speculative=True,
                   draft_len=4, check_pages=True)
    assert spec == base
    assert e.metrics["prefill_chunks"] > 0      # prefill really interleaved
    assert e.metrics["verify_dispatches"] > 0
    e.kv.assert_page_accounting()


@pytest.mark.slow
def test_rollback_churn_soak():
    """Admission / speculate / rollback / retire churn over more waves
    than slots, page accounting audited after EVERY rollback (the debug
    hook) and at the end."""
    cfg = _cfg("gpt2")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(6)
    prompts = []
    for i in range(7):
        if i % 2 == 0:
            prompts.append(np.array(([2, 4, 6, 8] * 10)[:17 + i], np.int32))
        else:
            prompts.append(rng.integers(1, cfg.vocab_size, 11 + 3 * i,
                                        dtype=np.int32))
    _, base = _run(cfg, params, prompts, new_tokens=11)
    e, spec = _run(cfg, params, prompts, new_tokens=11, speculative=True,
                   draft_len=4, check_pages=True)
    assert spec == base
    assert e.metrics["rollbacks"] > 0           # churn actually rolled back
    e.kv.assert_page_accounting()


@multi
def test_sharded_speculative_matches_single_device():
    """Forced 8-device mesh: the speculative engine's fused verify
    dispatch runs under shard_map (kv_heads over the model axis) and its
    tokens match the single-device non-speculative engine exactly."""
    from repro.launch.mesh import make_mesh

    cfg = CONFIGS["gqa"]()
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = _repetitive_prompts(cfg)
    _, base = _run(cfg, params, prompts, new_tokens=8)
    mesh = make_mesh((2, 4), ("data", "model"))
    e, spec = _run(cfg, params, prompts, new_tokens=8, speculative=True,
                   draft_len=4, batch_slots=4, mesh=mesh)
    assert spec == base
    lp = e.plan.layer("attn")
    assert lp.verify_attn.fused
    assert e.plan.summary()["sharding"]["attn"]["verify_attn"] == {
        "batch": "data", "kv_heads": "model"}
    e.kv.assert_page_accounting()
