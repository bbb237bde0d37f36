"""Mesh-aware StreamPlan + sharded serving tests (DESIGN.md §9).

The multi-device tier needs forced host devices — run with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI ``sharded``
job does); without it those tests skip, exactly like
``tests/test_distributed.py``.  The scheduler / KV-traffic-bound unit
tests at the bottom run everywhere.

Contract pinned here (ISSUE 4 acceptance): with a ('data','model') mesh
the engine's fused prefill-chunk + paged-decode path runs under shard_map
(asserted via the plan's stage records and the layers dispatch probe —
no eager fallback), the KV page pools carry a ``kv_heads``-sharded
``NamedSharding``, and greedy tokens match the single-device engine
exactly for dense, GQA, and sliding-window configs.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import init_params, layers as L, resolve_plan
from repro.models.params import cache_leaf_kind, cache_leaf_name
from repro.serving import ServingEngine

multi = pytest.mark.skipif(len(jax.devices()) < 8,
                           reason="needs 8 forced host devices")

SLOTS, MAX_LEN, DECODE_BLOCK, NEW_TOKENS = 4, 96, 4, 6


def _mesh():
    return make_mesh((2, 4), ("data", "model"))


def _cfg(arch, **over):
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32",
                              use_fused_kernels=True)
    return dataclasses.replace(cfg, **over)


# Dense MHA (layernorm, learned positions, block_matmul qkv), GQA
# (rmsnorm_matmul qkv), and sliding-window (local:global pattern).  Head
# counts are chosen so kv_heads divides the 4-way model axis.
CONFIGS = {
    "dense": lambda: _cfg("gpt2"),
    "gqa": lambda: _cfg("llama3-8b", num_heads=8, num_kv_heads=4,
                        head_dim=8),
    "swa": lambda: _cfg("gemma3-4b", num_heads=8, num_kv_heads=4,
                        head_dim=8),
}


def _prompts(cfg, n=3):
    rng = np.random.default_rng(7)
    return [rng.integers(1, cfg.vocab_size, ln, dtype=np.int32)
            for ln in (11, 37, 7)[:n]]


def _kv_pool_shardings(engine):
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            engine._slot_cache)[0]:
        if cache_leaf_kind(cache_leaf_name(path)) == "kv":
            out.append(leaf.sharding)
    return out


# ---------------------------------------------------------- plan records

@multi
def test_plan_records_sharding():
    cfg = CONFIGS["gqa"]()
    plan = resolve_plan(cfg, SLOTS, kv_len=MAX_LEN, mesh=_mesh())
    assert dict(plan.mesh_axes) == {"data": 2, "model": 4}
    lp = plan.layer("attn")
    for stage in (lp.attention, lp.decode_attn):
        assert stage.fused
        assert dict(stage.sharding)["kv_heads"] == "model"
    assert dict(lp.qkv.sharding).get("out") == "model"
    assert dict(lp.ffn.sharding).get("d_ff") == "model"
    # Post-shard block feedback: the ffn tile target is clipped toward
    # d_ff / 4 but never below the 128-lane floor (smoke d_ff is tiny;
    # the wrapper's pick_block handles the true per-shard extent).
    assert dict(lp.ffn.blocks)["block_f"] <= max(128, cfg.d_ff // 4)
    s = plan.summary()
    assert s["sharding"]["attn"]["decode_attn"] == {"batch": "data",
                                                    "kv_heads": "model"}


@multi
def test_plan_replicates_when_quantum_does_not_divide():
    """kv_heads=2 on a 4-way model axis cannot shard — the fallback is
    replication (no kv_heads claim), NEVER eager (stages stay fused)."""
    cfg = _cfg("llama3-8b")          # reduced: 4 q heads over 2 kv heads
    plan = resolve_plan(cfg, SLOTS, kv_len=MAX_LEN, mesh=_mesh())
    lp = plan.layer("attn")
    assert lp.attention.fused and lp.decode_attn.fused
    assert "kv_heads" not in dict(lp.attention.sharding)
    assert "kv_heads" not in dict(lp.decode_attn.sharding)


# ------------------------------------------------- serving exactness

@multi
@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_sharded_engine_matches_single_device(name):
    cfg = CONFIGS[name]()
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = _prompts(cfg)

    ref = ServingEngine(cfg, params, batch_slots=SLOTS, max_len=MAX_LEN,
                        decode_block=DECODE_BLOCK)
    ref_reqs = ref.generate(prompts, max_new_tokens=NEW_TOKENS)

    L.reset_dispatch_records()
    eng = ServingEngine(cfg, params, batch_slots=SLOTS, max_len=MAX_LEN,
                        decode_block=DECODE_BLOCK, mesh=_mesh())
    reqs = eng.generate(prompts, max_new_tokens=NEW_TOKENS)

    # Plan stage records: the serving path's stages are fused AND carry
    # the kv_heads sharding claim — no eager fallback anywhere.
    for kind, lp in eng.plan.layers:
        if kind not in ("attn", "local_attn", "global_attn"):
            continue
        assert lp.attention.fused and lp.decode_attn.fused
        assert dict(lp.decode_attn.sharding)["kv_heads"] == "model"
    # ... and the traced dispatches actually went through shard_map.
    assert L.DISPATCH_RECORDS["shard_map"] > 0
    assert L.DISPATCH_RECORDS["single"] == 0

    # KV page pools carry a kv_heads-sharded NamedSharding (model axis on
    # the Hkv dim of [G, P, Hkv, page_size, hd]); 4 shards of the pool.
    assert eng.kv.kv_shards == 4
    for s in _kv_pool_shardings(eng):
        assert s.spec[2] == "model", s.spec
    assert eng.metrics["sharded"] == 1

    # Greedy tokens match the single-device engine exactly.
    for a, b in zip(ref_reqs, reqs):
        assert not a.failed and not b.failed
        assert a.out_tokens == b.out_tokens


@multi
def test_sharded_engine_replicated_heads_still_matches():
    """Non-divisible kv_heads: pools replicate but the fused path still
    serves (and matches) — the fallback chain never reaches eager."""
    cfg = _cfg("llama3-8b")          # kv_heads=2, model axis 4
    params = init_params(jax.random.PRNGKey(1), cfg)
    prompts = _prompts(cfg, n=2)
    ref = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                        decode_block=DECODE_BLOCK)
    r1 = ref.generate(prompts, max_new_tokens=4)
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                        decode_block=DECODE_BLOCK, mesh=_mesh())
    assert eng.kv.kv_shards == 1     # replicated pools
    r2 = eng.generate(prompts, max_new_tokens=4)
    for a, b in zip(r1, r2):
        assert a.out_tokens == b.out_tokens


# ------------------------------------------------ sharded fused training

@multi
def test_mixer_dispatches_under_shard_map():
    """Regression: the mixer call sites must pass the plan's shard claim
    — every fused wrapper traced under the mesh goes through shard_map
    (RWKV reduced: wkv mixer + streamed-xent head), none single."""
    from repro.models import forward_train
    from repro.distributed.context import use_mesh

    cfg = dataclasses.replace(get_config("rwkv6-7b").reduced(),
                              dtype="float32", use_fused_kernels=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (4, 64)).astype(np.int32)
    batch = {"tokens": toks, "labels": toks}
    l1 = float(jax.jit(lambda p, b: forward_train(p, cfg, b))(params, batch))
    L.reset_dispatch_records()
    with use_mesh(_mesh()):
        l8 = float(jax.jit(lambda p, b: forward_train(p, cfg, b))(
            params, batch))
    assert L.DISPATCH_RECORDS["shard_map"] > 0
    assert L.DISPATCH_RECORDS["single"] == 0
    assert abs(l1 - l8) < 1e-5

@multi
def test_sharded_fused_train_matches_single_device():
    """The mesh-routed train step with ``use_fused_kernels``: shard_map'd
    kernels (row-parallel FFN psum, psum'd streamed-xent parts) with the
    eager-recompute VJP must reproduce the single-device fused loss."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import ShapeConfig
    from repro.distributed import make_train_step
    from repro.distributed.optimizer import init_opt_state

    cfg = CONFIGS["gqa"]()
    batch_np = {
        "tokens": np.random.default_rng(0).integers(
            0, cfg.vocab_size, (4, 64)).astype(np.int32),
        "labels": np.random.default_rng(1).integers(
            0, cfg.vocab_size, (4, 64)).astype(np.int32),
    }

    def run(mesh):
        fn, p_specs, o_specs, b_fn = make_train_step(cfg, mesh)
        params = init_params(jax.random.PRNGKey(0), cfg)
        params = jax.device_put(params, jax.tree.map(
            lambda s: NamedSharding(mesh, s), p_specs,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))
        opt = init_opt_state(params)
        specs = b_fn(batch_np)
        batch = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
                 for k, v in batch_np.items()}
        params, opt, metrics = fn(params, opt, batch)
        return float(metrics["loss"])

    l1 = run(make_mesh((1, 1), ("data", "model")))
    l8 = run(_mesh())
    assert abs(l1 - l8) < 1e-5


# ---------------------------------------- adaptive prefill budget (unit)

def test_adaptive_prefill_budget():
    cfg = _cfg("llama3-8b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, batch_slots=4, max_len=64,
                        decode_block=4)
    assert eng.chunked
    c = eng.chunk

    class _R:          # stand-in request
        pass

    # No waiting slots -> no prefill budget.
    assert eng._prefill_budget([None] * 4, [False] * 4) == 0
    # All four slots waiting, none decoding -> full share.
    act = [_R(), _R(), _R(), _R()]
    assert eng._prefill_budget(act, [False] * 4) == 4 * c
    # One waiting against a saturated decode backlog (eff == 1): the
    # backlog lends nothing — budget stays at the waiting share.
    eng.decode_eff = 1.0
    assert eng._prefill_budget(act, [True, True, True, False]) == c
    # Same split with a draining decode stream (recent-EMA eff == 0.25):
    # the three decoding slots lend 75% of their share to prefill.
    eng.decode_eff = 0.25
    assert (eng._prefill_budget(act, [True, True, True, False])
            == int(c * (1 + 0.75 * 3)))
    # Budget never exceeds the all-slots share.
    eng.decode_eff = 0.0
    assert (eng._prefill_budget(act, [True, True, True, False]) == 4 * c)
    assert eng.metrics["sched_budget"] == 4 * c


# ------------------------------- offset flash kernel: live-prefix clamp

def test_offset_flash_kv_clamp_numerics():
    """The meta[1] index-map clamp re-fetches a live block for dead KV
    blocks; pl.when already discards their compute, so results must be
    unchanged even when kv_len covers a small prefix of the extent."""
    from repro.kernels import flash_attention
    from repro.models.layers import streaming_attention
    rng = jax.random.PRNGKey(3)
    b, sq, skv, h, d = 1, 8, 64, 2, 16
    q, k, v = (jax.random.normal(r, s, jnp.float32) for r, s in zip(
        jax.random.split(rng, 3),
        ((b, sq, h, d), (b, skv, h, d), (b, skv, h, d))))
    for kv_len in (9, 16, 24):       # dead tail >> live prefix
        off = jnp.int32(kv_len - sq)
        out = flash_attention(q, k, v, causal=True,
                              q_offset=off, kv_len=jnp.int32(kv_len),
                              block_q=8, block_kv=8)
        ref = streaming_attention(q, k, v, causal=True,
                                  q_offset=kv_len - sq, kv_len=kv_len)
        np.testing.assert_allclose(out, ref, atol=1e-5)
