"""Prefix cache subsystem: radix-tree page sharing + copy-on-write.

Five layers of coverage (DESIGN.md §10):

  * Radix tree units — walk/insert/claim/evict over a real allocator:
    refcount moves, LRU-leaf eviction order, eviction under allocation
    pressure, and the free-list accounting invariant
    (``assert_page_accounting``) catching a seeded corruption.
  * COW primitives — ``paged_append`` / ``place_chunk_pages`` with
    ``cow_src``/``cow_dst``: the shared page survives the divergent
    write bit-for-bit; a model-level ``prefill_chunk`` drive shows the
    partial-last-page COW through the whole stack, starting at a nonzero
    page offset against a pre-populated table row.
  * Engine exactness — two requests sharing a page-aligned prefix
    physically share those pages (same physical ids in both table rows,
    refcount 2, pool bytes counted once) and greedy tokens bit-match the
    cold-start engine for dense, GQA, and sliding-window configs; the
    bootstrap mode's mid-page COW divergence never mutates the cached
    run.
  * Scheduler knobs — ``admission="sjf"|"prefix"`` orderings and the
    adaptive decode block (floored at the static value, bounded compiled
    program count, token-exact).
  * Churn soak — random join/leave over shared prefixes with the
    accounting invariant checked between waves; an allocator failure
    mid-chunked-prefill fails that request alone and returns its
    already-placed pages exactly once.

The 8-virtual-device test (sharded pools + replicated table + per-shard
bytes counting shared pages once) skips without forced host devices,
exactly like ``tests/test_sharded_serving.py``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params, prefill, prefill_chunk
from repro.models.params import cache_leaf_kind, cache_leaf_name
from repro.serving import (PagedKVCache, PrefixCache, ServingEngine,
                           gather_pages, paged_append, place_chunk_pages)
from repro.serving.kv_cache import NULL_PAGE, stage_chunk

multi = pytest.mark.skipif(len(jax.devices()) < 8,
                           reason="needs 8 forced host devices")


@pytest.fixture(scope="module")
def rng():
    return jax.random.PRNGKey(0)


def _cfg(arch="qwen1.5-0.5b", **over):
    cfg = get_config(arch).reduced()
    return dataclasses.replace(cfg, **over) if over else cfg


def _kv(slots=4, max_len=64, ps=4):
    return PagedKVCache(_cfg(), slots=slots, max_len=max_len, page_size=ps)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, _cfg().vocab_size, n).astype(np.int32)


def _engine(cfg, params, **over):
    kw = dict(batch_slots=2, max_len=64, decode_block=4, page_size=4,
              prefill_chunk=8)
    kw.update(over)
    return ServingEngine(cfg, params, **kw)


# ------------------------------------------------------ radix tree units

def test_radix_walk_insert_and_rewalk():
    kv = _kv()
    pc = PrefixCache(kv, chunk=8)
    p = _prompt(16, 1)
    kv.ensure(0, 16)                              # 4 exclusive pages
    assert pc.insert(0, p) == 4 and pc.nodes == 4
    assert pc.lookup_pages(p) == 4
    assert pc.insert(0, p) == 0                   # idempotent
    # A prompt diverging at page 2 matches exactly the first 2 chunks.
    q = p.copy()
    q[9] += 1
    assert pc.lookup_pages(q) == 2
    # Duplicate token chunks under DIFFERENT parents are distinct nodes.
    r = np.concatenate([p[4:8], p[4:8], p[8:]]).astype(np.int32)
    assert pc.lookup_pages(r) == 0
    kv.assert_page_accounting()


def test_claim_moves_refcounts_and_release_keeps_pages_cached():
    kv = _kv()
    pc = PrefixCache(kv, chunk=8)
    p = _prompt(16, 2)
    kv.ensure(0, 16)
    pc.insert(0, p)
    pages = list(kv.slot_pages(0))
    kv.release(0)
    pc.release_slot(0)
    assert kv.pages_in_use == 0 and kv.pages_cached == 4
    kv.assert_page_accounting()
    # Claim: chunk-aligned cap at plen-1 -> 16 tokens claims 8 (1 chunk).
    hit = pc.claim(1, p)
    assert hit.prefill_start == 8 and hit.hit_pages == 2
    assert hit.prompt_pages == 4 and hit.cow is None and not hit.full
    assert list(kv.slot_pages(1)) == pages[:2]
    assert list(kv.table_row(1)[:2]) == pages[:2]
    assert all(kv.page_refs(pg) == 1 for pg in pages[:2])
    assert kv.pages_in_use == 2 and kv.pages_cached == 2
    kv.release(1)
    pc.release_slot(1)
    assert kv.pages_in_use == 0 and kv.pages_cached == 4
    kv.assert_page_accounting()


def test_evict_lru_leaf_order_and_pressure():
    # Pool: 2 slots x 8 pages; cache two 4-page prompts, then demand the
    # whole pool — eviction must reclaim all cached pages, LRU first.
    kv = PagedKVCache(_cfg(), slots=2, max_len=32, page_size=4)
    pc = PrefixCache(kv, chunk=4)
    pa, pb = _prompt(16, 3), _prompt(16, 4)
    kv.ensure(0, 16)
    pc.insert(0, pa)
    kv.release(0)
    pc.release_slot(0)
    kv.ensure(0, 16)
    pc.insert(0, pb)
    kv.release(0)
    pc.release_slot(0)
    assert kv.pages_cached == 8 and pc.nodes == 8
    # pa's leaf is older than pb's: first eviction takes pa's deepest...
    # (leaf-only: the deepest cached chunk of the LRU chain).
    assert pc.evict_lru_leaf()
    assert pc.nodes == 7 and pc.evictions == 1
    assert pc.lookup_pages(pa) == 3 and pc.lookup_pages(pb) == 4
    kv.assert_page_accounting()
    # Allocation pressure: both slots want full capacity; every cached
    # page is reclaimed through the evictor hook, nothing raises.
    kv.ensure(0, 32)
    kv.ensure(1, 32)
    assert kv.pages_cached == 0 and pc.nodes == 0
    assert kv.pages_in_use == 16 and not kv._free
    kv.assert_page_accounting()
    # Fully referenced pool: eviction cannot help; ensure now raises...
    with pytest.raises(ValueError, match="slot capacity"):
        kv.ensure(0, 33)
    kv.release(0)
    kv.release(1)
    kv.assert_page_accounting()


def test_eviction_prunes_interior_pages_pinned_by_suffix_claims():
    """Regression: ``extend_claim`` lets a request adopt only a SUFFIX
    of a chain, so unreferenced ancestors can sit above referenced
    descendants; leaf-only eviction then found nothing and allocation
    failed while reclaimable cached pages sat pinned.  Eviction must
    prune the unreferenced subtree — freeing the cached ancestors and
    merely disowning the still-referenced suffix pages."""
    kv = PagedKVCache(_cfg(), slots=2, max_len=32, page_size=4)
    pc = PrefixCache(kv, chunk=4)
    pa = _prompt(32, 21)                           # 8 full pages
    kv.ensure(0, 32)
    pc.insert(0, pa)
    a_pages = list(kv.slot_pages(0))
    # Same-wave slot 1 computed pages 0..3 itself, then caught up and
    # adopted only the suffix nodes 4..6 (chunk-capped at plen-1).
    kv.ensure(1, 16)
    off, caught = pc.extend_claim(1, pa, 16)
    assert off == 28 and caught == 3
    kv.release(0)
    pc.release_slot(0)
    assert kv.pages_cached == 5                    # nodes 0..3 + node 7
    # Pressure: slot 0 wants full capacity again.  Free list holds 4
    # (16 - 8 - 4); the rest must come from eviction, which has to
    # prune through the referenced suffix' unreferenced ancestors —
    # leaf-only eviction would raise here with 4 reclaimable pages
    # pinned.  Eviction frees only what the demand needs, so at most
    # one cached page may survive.
    kv.ensure(0, 32)
    assert kv.pages_in_use == 15                   # 8 + 4 + 3 adopted
    assert kv.pages_cached + len(kv._free) == 1
    kv.assert_page_accounting()
    # Slot 1's adopted suffix pages survived as disowned references...
    for pg in a_pages[4:7]:
        assert kv.page_refs(pg) == 1
    kv.release(1)
    kv.release(0)
    kv.assert_page_accounting()
    # A not-yet-needed cached ancestor may legitimately survive the
    # pressure (eviction frees only what demand asked for).
    assert kv.pages_in_use == 0 and kv.pages_cached <= 1


def test_accounting_invariant_catches_corruption():
    kv = _kv()
    kv.ensure(0, 16)
    kv.assert_page_accounting()
    kv._free.append(kv._owned[0][0])              # seed a double-free
    with pytest.raises(AssertionError, match="referenced page"):
        kv.assert_page_accounting()


def test_release_is_exact_once_and_idempotent():
    kv = _kv()
    pc = PrefixCache(kv, chunk=8)
    p = _prompt(16, 5)
    kv.ensure(0, 16)
    pc.insert(0, p)
    free_before = len(kv._free)
    kv.release(0)
    # Tree pages stay cached: NOT pushed to the free list (the old
    # unconditional extend would have double-freed them at eviction).
    assert len(kv._free) == free_before
    kv.release(0)                                 # idempotent no-op
    assert len(kv._free) == free_before
    kv.assert_page_accounting()


# ------------------------------------------------------- COW primitives

def test_paged_append_cow_preserves_shared_page():
    ps, h, hd = 4, 2, 8
    nprng = np.random.default_rng(6)
    pool = jnp.asarray(nprng.normal(size=(4, h, ps, hd)).astype(np.float32))
    shared = np.asarray(pool[1])
    # Slot 0 diverges at position 2 inside shared page 1 -> COW to page 3.
    table = jnp.asarray([[3, 2]], np.int32)       # already redirected
    new = jnp.full((1, 1, h, hd), 9.0, jnp.float32)
    out = paged_append(pool, table, jnp.asarray([2], np.int32), new,
                       layout="bshd", cow_src=jnp.asarray([1], np.int32),
                       cow_dst=jnp.asarray([3], np.int32))
    np.testing.assert_array_equal(np.asarray(out[1]), shared)   # intact
    np.testing.assert_array_equal(np.asarray(out[3][:, :2]), shared[:, :2])
    np.testing.assert_array_equal(np.asarray(out[3][:, 2]), 9.0)
    # NULL pair no-ops for idle slots.
    out2 = paged_append(pool, table, jnp.asarray([2], np.int32), new,
                        layout="bshd",
                        cow_src=jnp.asarray([NULL_PAGE], np.int32),
                        cow_dst=jnp.asarray([NULL_PAGE], np.int32))
    np.testing.assert_array_equal(np.asarray(out2[1]), shared)


def test_place_chunk_pages_cow_preserves_shared_page():
    ps, h, hd = 4, 2, 8
    nprng = np.random.default_rng(7)
    pool = jnp.asarray(nprng.normal(size=(4, h, ps, hd)).astype(np.float32))
    shared = np.asarray(pool[2])
    chunk = jnp.asarray(nprng.normal(size=(1, ps, h, hd)).astype(np.float32))
    out = place_chunk_pages(pool, chunk, jnp.asarray([3], np.int32),
                            layout="bshd", cow_src=jnp.int32(2),
                            cow_dst=jnp.int32(3))
    np.testing.assert_array_equal(np.asarray(out[2]), shared)   # intact
    np.testing.assert_array_equal(np.asarray(out[3]),
                                  np.asarray(chunk[0]).transpose(1, 0, 2))


def test_prefill_chunk_cow_partial_last_page(rng):
    """The partial-last-page COW through the whole stack: prompt B is a
    mid-page prefix of cached prompt A; B claims A's pages INCLUDING the
    tail page, then runs ONE final chunk at a nonzero page offset against
    the pre-populated row, copy-on-writing the tail page.  B's logits
    match its whole-prompt prefill and A's page is untouched."""
    cfg = _cfg(dtype="float32")
    params = init_params(rng, cfg)
    ps, chunk, max_len = 4, 4, 32
    kv = PagedKVCache(cfg, slots=2, max_len=max_len, page_size=ps)
    pc = PrefixCache(kv, chunk=chunk, bootstrap=True)
    pa = _prompt(16, 8)                            # 4 full pages
    pb = pa[:11]                                   # ends mid-page (3 in 3rd)

    # Prefill A chunk-by-chunk into slot 0 (the engine's recipe).
    cache = kv.init_cache()
    step = jax.jit(
        lambda p, t, c, row, cp, off, li, cs, cd: prefill_chunk(
            p, cfg, t, c, row, cp, off, li, cs, cd), donate_argnums=(2,))
    for k in range(4):
        off = k * chunk
        kv.ensure(0, off + chunk)
        row = kv.table_row(0)
        toks, cpages, last = stage_chunk(pa, off, chunk, row, ps)
        _, _, cache = step(params, jnp.asarray(toks)[None], cache,
                           jnp.asarray(row), jnp.asarray(cpages),
                           jnp.int32(off), jnp.int32(last),
                           jnp.int32(NULL_PAGE), jnp.int32(NULL_PAGE))
    pc.insert(0, pa)

    # B: full-page walk matches 2 pages, tail (tokens 8..10) matches the
    # cached 3rd chunk -> bootstrap claim takes it as a COW candidate.
    hit = pc.claim(1, pb)
    assert hit.full and hit.cow == 2 and hit.hit_pages == 3
    a_page = int(kv.slot_pages(1)[2])      # the claimed (shared) page
    a_rows = np.asarray(
        jax.tree_util.tree_leaves(cache)[0][0, a_page])   # snapshot

    # Drive B's final chunk at offset 8 — nothing of B was computed yet:
    # the chunk attends to the CLAIMED pages through the row.
    cow_src, cow_dst = kv.cow_page(1, 2)
    assert cow_src == a_page and cow_dst != a_page
    kv.ensure(1, 12)
    row = kv.table_row(1)
    toks, cpages, last = stage_chunk(pb, 8, chunk, row, ps)
    assert cpages[0] == cow_dst
    nt, lg, cache = step(params, jnp.asarray(toks)[None], cache,
                         jnp.asarray(row), jnp.asarray(cpages),
                         jnp.int32(8), jnp.int32(last),
                         jnp.int32(cow_src), jnp.int32(cow_dst))

    whole_lg, _ = jax.jit(lambda p, b: prefill(p, cfg, b))(
        params, {"tokens": jnp.asarray(pb)[None]})
    np.testing.assert_allclose(np.asarray(lg), np.asarray(whole_lg),
                               atol=1e-5)
    assert int(np.asarray(nt)[0, 0]) == int(jnp.argmax(whole_lg, -1)[0, 0])
    # A's shared page is bit-identical after B's divergent write.
    np.testing.assert_array_equal(
        np.asarray(jax.tree_util.tree_leaves(cache)[0][0, a_page]), a_rows)
    kv.assert_page_accounting()


# ------------------------------------------- engine: sharing exactness

@pytest.mark.parametrize("arch", ["gpt2", "llama3-8b", "gemma3-4b"])
def test_shared_prefix_bit_matches_cold_engine(rng, arch):
    """Dense (learned positions), GQA, and sliding-window: a hot engine
    (prefix cache warm from an earlier wave) produces bit-identical
    greedy tokens to a cold engine for prompts sharing a k-page prefix,
    while prefilling fewer chunks."""
    cfg = _cfg(arch)
    params = init_params(rng, cfg)
    nprng = np.random.default_rng(9)
    shared = nprng.integers(1, cfg.vocab_size, 24, dtype=np.int32)
    mk = lambda tail: np.concatenate(
        [shared, nprng.integers(1, cfg.vocab_size, tail,
                                dtype=np.int32)]).astype(np.int32)
    warm, p1, p2 = mk(5), mk(7), mk(3)

    cold = _engine(cfg, params, prefix_cache=False)
    ref = cold.generate([p1, p2], max_new_tokens=5)

    hot = _engine(cfg, params)
    hot.generate([warm], max_new_tokens=2)          # populate the tree
    chunks0 = hot.metrics["prefill_chunks"]
    out = hot.generate([p1, p2], max_new_tokens=5)
    for a, b in zip(ref, out):
        assert a.out_tokens == b.out_tokens, "hot engine diverged"
    m = hot.metrics
    assert m["prefix_hit_pages"] >= 2 * 4            # >= 2 chunks each
    assert m["prefix_hit_rate"] > 0
    # The shared 24-token prefix (3 chunks) is claimed, not recomputed:
    # each hot request prefills at least 2 chunks fewer than cold.
    assert (m["prefill_chunks"] - chunks0
            <= cold.metrics["prefill_chunks"] - 4)
    hot.kv.assert_page_accounting()
    assert hot.kv.pages_in_use == 0 and hot.kv.pages_cached > 0


def test_two_requests_physically_share_pages(rng):
    """The acceptance contract: both table rows carry the SAME physical
    ids for the shared prefix (refcount 2 while both are live), pool
    bytes count the shared pages once, and both requests bit-match their
    cold references."""
    cfg = _cfg()
    params = init_params(rng, cfg)
    nprng = np.random.default_rng(10)
    shared = nprng.integers(1, cfg.vocab_size, 24, dtype=np.int32)
    mk = lambda tail, s: np.concatenate(
        [shared, np.random.default_rng(s).integers(
            1, cfg.vocab_size, tail, dtype=np.int32)]).astype(np.int32)
    p1, p2 = mk(7, 1), mk(5, 2)

    rows, refs, in_use = {}, {}, {}

    class Probe(ServingEngine):
        def _dispatch_chunk(self, slot, r, *a):
            if r.rid not in rows:
                rows[r.rid] = self.kv.table_row(slot).copy()
                refs[r.rid] = self.kv._refs.copy()
                in_use[r.rid] = self.kv.pages_in_use
            return super()._dispatch_chunk(slot, r, *a)

    cold = _engine(cfg, params, prefix_cache=False)
    ref_out = cold.generate([p1, p2], max_new_tokens=5)

    eng = Probe(cfg, params, batch_slots=2, max_len=64, decode_block=4,
                page_size=4, prefill_chunk=8)
    eng.generate([p1[:26]], max_new_tokens=2)       # warm the prefix
    rows.clear(), refs.clear(), in_use.clear()
    out = eng.generate([p1, p2], max_new_tokens=5)
    assert [r.out_tokens for r in out] == [r.out_tokens for r in ref_out]
    # Both admissions claimed the same 6 physical pages (the 24-token
    # shared prefix) straight into their table rows...
    k = 6
    assert list(rows[0][:k]) == list(rows[1][:k])
    assert NULL_PAGE not in rows[0][:k]
    # ...with refcount 2 while both were live — counted ONCE in the pool
    # (at either snapshot at most one slot has any exclusive pages yet).
    assert all(refs[1][pg] == 2 for pg in rows[1][:k])
    assert in_use[0] == k and in_use[1] <= k + 2
    # Pool-bytes-counted-once shows up as a lower allocation peak than
    # the cold engine serving the identical wave.
    assert eng.kv.peak_pages < cold.kv.peak_pages
    eng.kv.assert_page_accounting()


def test_bootstrap_cow_divergence_never_mutates_other_slot(rng):
    """Bootstrap mode: a fully-cached prompt skips prefill (decode-path
    first token, COW on the shared last page — both the page-aligned and
    the mid-page variants) and its divergent decode writes never touch
    the cached run, which replays bit-identically afterwards."""
    cfg = _cfg()
    params = init_params(rng, cfg)
    plong = _prompt(32, 11)                          # page-aligned
    pmid = plong[:27].copy()                         # ends mid-page

    def cold(p):
        e = _engine(cfg, params, batch_slots=1, prefix_cache=False)
        return e.generate([p], max_new_tokens=6)[0].out_tokens

    boot = _engine(cfg, params, batch_slots=1, prefix_bootstrap=True)
    boot.generate([plong], max_new_tokens=6)         # cold: fills tree
    r1 = boot.generate([plong], max_new_tokens=6)    # page-aligned hit
    assert boot.metrics["prefix_bootstraps"] == 1
    assert boot.metrics["cow_copies"] == 1
    assert r1[0].out_tokens == cold(plong)
    r2 = boot.generate([pmid], max_new_tokens=6)     # mid-page tail hit
    assert boot.metrics["prefix_bootstraps"] == 2
    assert boot.metrics["cow_copies"] == 2
    assert r2[0].out_tokens == cold(pmid)
    # The COW'd divergences (r1 and r2 decoded into private copies) left
    # the cached pages intact: plong replays exactly.
    r3 = boot.generate([plong], max_new_tokens=6)
    assert r3[0].out_tokens == cold(plong)
    boot.kv.assert_page_accounting()
    assert boot.kv.pages_in_use == 0


# ------------------------------------------------------ scheduler knobs

def test_admission_policy_validation(rng):
    cfg = _cfg()
    params = init_params(rng, cfg)
    with pytest.raises(ValueError, match="admission policy"):
        _engine(cfg, params, admission="lifo")
    with pytest.raises(ValueError, match="requires prefix_cache"):
        _engine(cfg, params, admission="prefix", prefix_cache=False)
    with pytest.raises(ValueError, match="requires chunked"):
        _engine(cfg, params, chunked=False, prefix_cache=True)
    with pytest.raises(ValueError, match="requires prefix_cache"):
        _engine(cfg, params, prefix_cache=False, prefix_bootstrap=True)


def test_admission_sjf_serves_short_first(rng):
    cfg = _cfg()
    params = init_params(rng, cfg)
    long_p, short_p = _prompt(40, 12), _prompt(6, 13)
    eng = _engine(cfg, params, batch_slots=1, admission="sjf")
    reqs = eng.generate([long_p, short_p], max_new_tokens=3)
    assert all(r.done and not r.failed for r in reqs)
    assert reqs[1].first_token_at < reqs[0].first_token_at


def test_admission_prefix_serves_cached_first(rng):
    cfg = _cfg()
    params = init_params(rng, cfg)
    cached, fresh = _prompt(24, 14), _prompt(24, 15)
    eng = _engine(cfg, params, batch_slots=1, admission="prefix")
    eng.generate([cached], max_new_tokens=2)
    reqs = eng.generate([fresh, cached], max_new_tokens=3)
    assert all(r.done and not r.failed for r in reqs)
    # The hot prompt jumps the queue: its prefill is mostly free.
    assert reqs[1].first_token_at < reqs[0].first_token_at
    assert eng.metrics["prefix_hit_pages"] > 0


def test_adaptive_decode_block_grows_with_active_slots(rng):
    cfg = _cfg()
    params = init_params(rng, cfg)
    nprng = np.random.default_rng(16)
    prompts = [nprng.integers(1, cfg.vocab_size, n, dtype=np.int32)
               for n in (6, 8, 10, 12)]
    base = ServingEngine(cfg, params, batch_slots=4, max_len=64,
                         decode_block=2, page_size=4)
    ref = base.generate(prompts, max_new_tokens=12)
    eng = ServingEngine(cfg, params, batch_slots=4, max_len=64,
                        decode_block=2, page_size=4,
                        adaptive_decode_block=True)
    out = eng.generate(prompts, max_new_tokens=12)
    assert [r.out_tokens for r in out] == [r.out_tokens for r in ref]
    # 4 efficient slots scale the block to the 4x cap; the floor is the
    # static value; the power-of-two ladder bounds compiles at 3.
    assert eng.metrics["decode_block"] == 2
    assert eng.metrics["decode_block_last"] in (2, 4, 8)
    assert eng._decode_block_size(0) == 2
    assert eng.metrics["decode_traces"] <= 3
    assert eng.metrics["dispatches"] <= base.metrics["dispatches"]


def test_decode_block_size_ladder(rng):
    cfg = _cfg()
    params = init_params(rng, cfg)
    eng = ServingEngine(cfg, params, batch_slots=8, max_len=32,
                        decode_block=4, adaptive_decode_block=True)
    eng.decode_eff = 1.0
    assert eng._decode_block_size(1) == 4          # floor
    assert eng._decode_block_size(2) == 8
    assert eng._decode_block_size(8) == 16         # 4x cap
    eng.decode_eff = 0.3                           # wasted ticks pull back
    assert eng._decode_block_size(4) == 4
    eng2 = ServingEngine(cfg, params, batch_slots=8, max_len=32,
                         decode_block=4)
    eng2.decode_eff = 1.0
    assert eng2._decode_block_size(8) == 4         # knob off: static


# -------------------------------------------------- churn / failure soak

def test_midprefill_failure_returns_pages_exactly_once(rng):
    """An allocator failure between chunks fails THAT request, returns
    its already-placed pages exactly once, and the stream keeps serving
    (the old engine would have raised mid-generate with pages held)."""
    cfg = _cfg()
    params = init_params(rng, cfg)
    eng = _engine(cfg, params, batch_slots=2)
    good, doomed = _prompt(6, 17), _prompt(40, 18)

    calls = {"n": 0}
    orig = eng.kv.alloc_page

    def failing_alloc():
        calls["n"] += 1
        if calls["n"] > 6:                        # mid-prefill of doomed
            raise RuntimeError("KV page pool exhausted (injected)")
        return orig()

    eng.kv.alloc_page = failing_alloc
    reqs = eng.generate([doomed, good], max_new_tokens=4)
    eng.kv.alloc_page = orig
    assert reqs[0].failed and "exhausted" in reqs[0].error
    assert reqs[1].done and not reqs[1].failed and reqs[1].out_tokens
    assert eng.metrics["rejected"] == 1
    eng.kv.assert_page_accounting()
    assert eng.kv.pages_in_use == 0


def test_decode_cow_pool_exhaustion_fails_one_request(rng):
    """Regression: a fully-referenced pool plus a pending bootstrap COW
    (which needs one transient extra page while src and dst are both
    live) used to raise straight through ``generate()``, stranding every
    active request.  It must fail only the slot whose COW cannot be
    satisfied; the retired slot's pages fall back to cached and unblock
    the neighbour's COW."""
    cfg = _cfg()
    params = init_params(rng, cfg)
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=16,
                        decode_block=4, page_size=4, prefill_chunk=4,
                        prefix_bootstrap=True)
    p, q = _prompt(16, 30), _prompt(16, 31)
    eng.generate([p], max_new_tokens=2)           # cache all 4 pages
    eng.generate([q], max_new_tokens=2)           # ...and the other 4
    reqs = eng.generate([p, q], max_new_tokens=2)
    # Both full-hit: 8/8 pages referenced, no page free for slot 0's
    # COW -> it fails gracefully; slot 1 then evicts slot 0's returned
    # pages for its own COW and completes.
    assert reqs[0].failed and "exhausted" in reqs[0].error
    assert reqs[1].done and not reqs[1].failed and reqs[1].out_tokens
    eng.kv.assert_page_accounting()
    assert eng.kv.pages_in_use == 0


@pytest.mark.slow
@pytest.mark.parametrize("bootstrap", [False, True])
def test_churn_soak_accounting_invariants(rng, bootstrap):
    """Random join/leave over a small pool of shared prefixes: after
    every wave the refcount/free-list partition holds, no page leaks,
    and every request completes."""
    cfg = _cfg()
    params = init_params(rng, cfg)
    eng = ServingEngine(cfg, params, batch_slots=3, max_len=48,
                        decode_block=4, page_size=4, prefill_chunk=8,
                        prefix_bootstrap=bootstrap)
    nprng = np.random.default_rng(19)
    bases = [nprng.integers(1, cfg.vocab_size, 16, dtype=np.int32)
             for _ in range(3)]
    for wave in range(4):
        prompts = []
        for _ in range(5):
            base = bases[nprng.integers(0, len(bases))]
            cut = int(nprng.integers(4, 17))
            tail = nprng.integers(
                1, cfg.vocab_size, int(nprng.integers(0, 9)),
                dtype=np.int32)
            prompts.append(np.concatenate([base[:cut], tail])
                           .astype(np.int32)[:40])
        reqs = eng.generate(prompts,
                            max_new_tokens=int(nprng.integers(2, 7)))
        assert all(r.done and not r.failed for r in reqs)
        eng.kv.assert_page_accounting()
        assert eng.kv.pages_in_use == 0
    assert eng.metrics["prefix_hit_pages"] > 0
    assert eng.metrics["prefix_hit_rate"] > 0


# ------------------------------------------------------------- sharded

@multi
def test_sharded_shared_pages_counted_once(rng):
    """Under a ('data','model') mesh the shared pages live in the
    kv_heads-sharded pools unchanged (the table is replicated), greedy
    tokens match the single-device hot engine, and per-shard byte
    accounting counts a shared page once."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh

    cfg = _cfg("llama3-8b", dtype="float32", use_fused_kernels=True,
               num_heads=8, num_kv_heads=4, head_dim=8)
    params = init_params(rng, cfg)
    nprng = np.random.default_rng(20)
    shared = nprng.integers(1, cfg.vocab_size, 24, dtype=np.int32)
    p1 = np.concatenate([shared, nprng.integers(
        1, cfg.vocab_size, 7, dtype=np.int32)]).astype(np.int32)

    outs, peaks = {}, {}
    for name, mesh in (("single", None),
                       ("sharded", make_mesh((2, 4), ("data", "model")))):
        eng = _engine(cfg, params, mesh=mesh)
        eng.generate([p1[:26]], max_new_tokens=2)      # warm
        reqs = eng.generate([p1, p1], max_new_tokens=4)
        outs[name] = [r.out_tokens for r in reqs]
        peaks[name] = eng.metrics["kv_bytes_peak"]
        assert eng.metrics["prefix_hit_pages"] > 0
        eng.kv.assert_page_accounting()
        if mesh is not None:
            assert eng.kv.kv_shards == 4
            # Replicated table, kv_heads-sharded pools.
            assert eng.kv.page_table.sharding.spec == P(None, None)

            def claims_model(spec):
                return any(e == "model" or (isinstance(e, tuple)
                                            and "model" in e)
                           for e in spec)

            kv_specs = [leaf.sharding.spec for path, leaf in
                        jax.tree_util.tree_flatten_with_path(
                            eng._slot_cache)[0]
                        if cache_leaf_kind(cache_leaf_name(path)) == "kv"]
            assert kv_specs and all(claims_model(s) for s in kv_specs)
            # Shared pages counted once, then split across shards.
            assert (eng.kv.peak_bytes_per_shard
                    == eng.kv.peak_bytes_in_use // 4)
    assert outs["single"] == outs["sharded"]
    assert peaks["single"] == peaks["sharded"]
