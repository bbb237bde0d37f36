"""Static stream verifier (DESIGN.md §15).

Two halves:

  * **Golden seeded-bad fixtures** — five deliberately-broken plans /
    pool schemas / dispatch signatures, each asserting the verifier
    produces the expected diagnostic (pass, stage, severity, code)
    without ever tracing a kernel.
  * **Registry sweep** — every shipped config × {none, kv_int8, w8_kv8}
    × {single-device, 8-device AbstractMesh} builds its StreamPlan and
    verifies *clean* (no errors, no warnings; info-level fallback notes
    are fine) — the strict-by-default engine hook depends on this.

Plus unit coverage for the itensor reconstruction (elem_shape == the
plan's blocks, tripcounts == the stage grid), the ``_DTYPE_BYTES``
extension (fp8 variants, fractional int4), and the engine hook itself.
"""

import copy
import dataclasses

import numpy as np
import pytest

from repro.analysis import (Diagnostic, PlanVerificationError, clean,
                            errors, stage_itensors, verify_plan)
from repro.analysis.effects import check_effects
from repro.configs import ARCHS, get_config
from repro.core.itensor import dtype_bytes
from repro.core.stream_plan import (EAGER, KernelChoice, LayerPlan,
                                    StreamPlan, build_stream_plan)
from repro.models.layers import DISPATCH_EFFECTS
from repro.serving.kv_cache import paged_cache_defs

QUANTS = ("none", "kv_int8", "w8_kv8")


def _cfg(arch="llama3-8b", **over):
    cfg = get_config(arch).reduced()
    over.setdefault("use_fused_kernels", True)
    return dataclasses.replace(cfg, **over)


def _plan(cfg, tokens=4, kv_len=64, mesh=None):
    return build_stream_plan(cfg, tokens=tokens, kv_len=kv_len, mesh=mesh)


def _mesh8():
    from jax.sharding import AbstractMesh
    return AbstractMesh((2, 4), ("data", "model"))


def _find(diags, code):
    return [d for d in diags if d.code == code]


# ------------------------------------------------- seeded-bad fixtures

def test_bad_non_divisible_block():
    """Fixture 1: an lm_head block_v that doesn't divide the vocab is
    flagged (the wrapper would silently clip it)."""
    cfg = _cfg()
    plan = _plan(cfg)
    bad = dataclasses.replace(plan, lm_head=KernelChoice(
        "streamed_xent", (("block_t", plan.tokens), ("block_v", 192))))
    diags = verify_plan(bad, cfg)
    hits = _find(diags, "non-divisible-block")
    assert hits, [str(d) for d in diags]
    d = hits[0]
    assert d.severity == "warning" and d.pass_name == "kernel"
    assert d.stage == "final.lm_head"
    assert "192" in d.message and d.fix_hint


def test_bad_over_vmem_tile():
    """Fixture 2: a full-size FFN tile that cannot fit in VMEM is a hard
    error — the hand-built plan is never traced."""
    cfg = dataclasses.replace(get_config("llama3-8b"),
                              use_fused_kernels=True)
    lp = LayerPlan(kind="attn", ffn=KernelChoice(
        "streamed_ffn", (("block_t", 512), ("block_f", cfg.d_ff))))
    plan = StreamPlan(
        arch=cfg.name, tokens=512, kv_len=512, platform="TPU-v5e",
        default_tile_size=128, overall_unroll_size=64,
        layers=(("attn", lp),), quant=cfg.quant)
    diags = verify_plan(plan, cfg)
    hits = _find(diags, "vmem-exceeded")
    assert hits, [str(d) for d in diags]
    d = hits[0]
    assert d.severity == "error" and d.pass_name == "kernel"
    assert d.stage == "attn.ffn" and "MiB" in d.message


def test_bad_mismatched_psum_axes():
    """Fixture 3: column-parallel qkv reducing over 'model' while the
    row-parallel FFN psums over 'data' is a coherence error."""
    cfg = dataclasses.replace(get_config("llama3-8b"),
                              use_fused_kernels=True)
    lp = LayerPlan(
        kind="attn",
        qkv=KernelChoice("rmsnorm_matmul",
                         (("block_t", 128), ("block_n", 128)),
                         (("tokens", "data"), ("out", "model"))),
        ffn=KernelChoice("streamed_ffn",
                         (("block_t", 128), ("block_f", 128)),
                         (("d_ff", "data"),)))
    plan = StreamPlan(
        arch=cfg.name, tokens=256, kv_len=256, platform="TPU-v5e",
        default_tile_size=128, overall_unroll_size=64,
        layers=(("attn", lp),), quant=cfg.quant,
        mesh_axes=(("data", 2), ("model", 4)))
    diags = verify_plan(plan, cfg)
    hits = _find(diags, "psum-mismatch")
    assert hits, [str(d) for d in diags]
    d = hits[0]
    assert d.severity == "error" and d.pass_name == "sharding"
    assert d.stage == "attn.ffn"
    assert "'model'" in d.message and "'data'" in d.message


def test_bad_missing_scale_pool():
    """Fixture 4: a quantized pool tree missing a _scale sibling."""
    cfg = _cfg(quant="kv_int8")
    plan = _plan(cfg)
    defs = paged_cache_defs(cfg, 2, 64, 16)
    victim = None
    for group in defs["blocks"] + defs["rest"]:
        for name in list(group):
            if name.endswith("_scale"):
                victim = name
                del group[name]
                break
        if victim:
            break
    assert victim is not None
    diags = check_effects(plan, cfg, page_size=16, cache_defs=defs)
    hits = _find(diags, "missing-scale-pool")
    assert hits
    d = hits[0]
    assert d.severity == "error" and d.pass_name == "effects"
    assert d.stage == f"pool.{victim[:-len('_scale')]}"
    # The intact schema verifies clean.
    good = paged_cache_defs(cfg, 2, 64, 16)
    assert not errors(check_effects(plan, cfg, page_size=16,
                                    cache_defs=good))


def test_bad_cow_self_alias():
    """Fixture 5: a decode signature whose copy-on-write step loses the
    fresh-dst allocator guarantee."""
    cfg = _cfg()
    plan = _plan(cfg)
    sigs = copy.deepcopy(DISPATCH_EFFECTS)
    sigs["decode"]["ops"][0]["cow"]["fresh_dst"] = False
    diags = check_effects(plan, cfg, signatures=sigs)
    hits = _find(diags, "cow-self-alias")
    assert hits
    d = hits[0]
    assert d.severity == "error" and d.pass_name == "effects"
    assert d.stage == "dispatch.decode"
    # The shipped signatures carry no such bug.
    assert not errors(check_effects(plan, cfg))


def test_bad_donated_read_after_write():
    """Reordering a dispatch's ops so the initial-contents read follows
    a write to the donated buffer is rejected."""
    cfg = _cfg()
    plan = _plan(cfg)
    sigs = copy.deepcopy(DISPATCH_EFFECTS)
    sigs["decode"]["ops"] = tuple(reversed(sigs["decode"]["ops"]))
    diags = check_effects(plan, cfg, signatures=sigs)
    hits = _find(diags, "donated-read-after-write")
    assert hits and hits[0].severity == "error"
    assert hits[0].stage == "dispatch.decode"


def test_bad_scale_lockstep_and_null_routing():
    """Dropping updates_scales (under KV quant) or null_routed from a
    page-indexed write is rejected."""
    cfg = _cfg(quant="kv_int8")
    plan = _plan(cfg)
    sigs = copy.deepcopy(DISPATCH_EFFECTS)
    op = dict(sigs["prefill"]["ops"][1])
    op["updates_scales"] = False
    op["null_routed"] = False
    sigs["prefill"]["ops"] = (sigs["prefill"]["ops"][0], op)
    diags = check_effects(plan, cfg, signatures=sigs)
    assert _find(diags, "scale-lockstep")
    assert _find(diags, "unguarded-null-page")
    assert all(d.stage == "dispatch.prefill" for d in errors(diags))


def test_bad_quant_mismatch_and_unknown_kernel():
    cfg = _cfg(quant="kv_int8")
    plan = _plan(_cfg(quant="none"))           # plan from the wrong mode
    diags = verify_plan(plan, cfg)
    assert any(d.code == "quant-mismatch"
               and d.severity == "error" for d in diags)
    bad = dataclasses.replace(
        plan, lm_head=KernelChoice("warp_gemm", (("block_t", 4),)))
    hits = _find(verify_plan(bad, _cfg(quant="none")), "unknown-kernel")
    assert hits and hits[0].severity == "error"


def test_mesh_mismatch():
    """A plan built for one mesh verified against another is an error."""
    cfg = _cfg()
    plan = _plan(cfg, mesh=_mesh8())
    from jax.sharding import AbstractMesh
    other = AbstractMesh((4, 2), ("data", "model"))
    diags = verify_plan(plan, cfg, mesh=other)
    hits = _find(diags, "mesh-mismatch")
    assert hits and hits[0].severity == "error"


# ------------------------------------------------------- registry sweep

@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_registry_verifies_clean(arch):
    """Every shipped config × quant mode × mesh verifies clean — the
    invariant that makes verify='strict' safe as the engine default."""
    for quant in QUANTS:
        cfg = _cfg(arch, quant=quant)
        for mesh in (None, _mesh8()):
            plan = _plan(cfg, mesh=mesh)
            diags = verify_plan(plan, cfg, mesh, slots=2, max_len=64)
            assert clean(diags), (
                f"{arch}/{quant}/mesh={mesh is not None}: "
                + "; ".join(str(d) for d in diags if d.severity != "info"))
            assert plan.with_verification(True, ()).verified is True


# --------------------------------------------- itensor reconstruction

def test_stage_itensors_mirror_blocks():
    """Reconstructed itensors are the type-level twin of the BlockSpec:
    elem_shape == effective blocks, tripcounts == the stage grid."""
    cfg = _cfg("gpt2")
    plan = _plan(cfg, tokens=8, kv_len=64)
    its = stage_itensors(plan, cfg)
    assert its, "no fused stages reconstructed"
    for (kind, stage), it in its.items():
        assert it.is_exact_tiling()
        for elem, trips, extent in zip(it.elem_shape, it.tripcounts,
                                       it.data_shape):
            assert elem * trips == extent
    # The qkv stage's token tile is its block_t target (post-clip).
    for kind, lp in plan.layers:
        if lp.qkv.fused and (kind, "qkv") in its:
            it = its[(kind, "qkv")]
            assert it.elem_shape[0] <= max(lp.qkv.block("block_t"),
                                           plan.tokens)


def test_plan_summary_records_verification():
    cfg = _cfg()
    plan = _plan(cfg)
    assert plan.summary()["verified"] is None
    v = plan.with_verification(True, ("[info] x",))
    s = v.summary()
    assert s["verified"] is True and s["diagnostics"] == ["[info] x"]


# ----------------------------------------------------- dtype coverage

def test_dtype_bytes_extended():
    assert dtype_bytes("float8_e5m2") == 1
    assert dtype_bytes("float8_e4m3fn") == 1
    assert dtype_bytes("bfloat16") == 2
    assert dtype_bytes("int4") == 0.5
    assert dtype_bytes("uint4") == 0.5
    with pytest.raises(ValueError):
        dtype_bytes("tf32x9")


# ---------------------------------------------------------- engine hook

def test_engine_verify_strict_default(rng_params):
    import jax

    from repro.serving import ServingEngine
    cfg, params = rng_params
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=64)
    assert eng.verify_mode == "strict"
    assert eng.plan is not None and eng.plan.verified is True
    assert eng.metrics["verified"] == 1
    assert eng.plan.summary()["verified"] is True


def test_engine_verify_rejects_bad_mode(rng_params):
    from repro.serving import ServingEngine
    cfg, params = rng_params
    with pytest.raises(ValueError, match="verify mode"):
        ServingEngine(cfg, params, batch_slots=2, max_len=64,
                      verify="paranoid")


def test_engine_verify_off_skips(rng_params):
    from repro.serving import ServingEngine
    cfg, params = rng_params
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                        verify="off")
    assert eng.plan.verified is None and eng.metrics["verified"] == 0


@pytest.fixture(scope="module")
def rng_params():
    import jax

    from repro.models import init_params
    cfg = _cfg("qwen1.5-0.5b")
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def test_diagnostic_validation():
    with pytest.raises(ValueError):
        Diagnostic("fatal", "kernel", "x", "c", "m")
    with pytest.raises(ValueError):
        Diagnostic("error", "vibes", "x", "c", "m")
    d = Diagnostic("error", "kernel", "attn.ffn", "code", "msg", "hint")
    assert "kernel:code" in str(d) and "fix: hint" in str(d)
    err = PlanVerificationError([d])
    assert d in err.diagnostics and "1 error" in str(err)
