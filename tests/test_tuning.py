"""Measured-latency autotuner (DESIGN.md §16).

Four clusters:

  * **Table store** — round-trip persistence, atomic concurrent writers,
    and graceful degradation: a corrupt file, a schema-version mismatch,
    and a backend-fingerprint mismatch each load as an EMPTY table with
    the matching warning ``Diagnostic`` (never an exception, never stale
    entries) so a damaged table degrades to re-tuning, not a crash.
  * **Tuner mechanics** — candidate enumeration (original first, dedup
    by effective block), lint pruning (illegal lattice points are never
    scored), strict-min determinism, frozen-table reproducibility, and
    the measured/analytic provenance stamping.
  * **DSE plumbing** — ``CostSource`` overrides the kernel-latency term,
    ``evaluate_trial`` records per-kernel breakdowns, and
    ``explore(seed_trials=...)`` warm-starts deterministically.
  * **Engine integration** — ``ServingEngine(autotune=path)``: first
    start populates the table, second start performs zero measurement
    dispatches and resolves a bit-identical plan, and greedy tokens are
    unchanged by tuning (block sizes never change kernel math).
"""

import dataclasses
import json
import os
import threading

import numpy as np
import pytest

from repro.configs import get_config
from repro.core.dse import CostSource, evaluate_trial, explore
from repro.core.platforms import TPU_V5E
from repro.core.stream_plan import build_stream_plan, plan_for
from repro.core.trace import trace_block
from repro.tuning import (SCHEMA_VERSION, TuneEntry, TuneTable, Tuner,
                          backend_fingerprint, enumerate_candidates,
                          make_key, measure, measure_candidate,
                          resolve_tuner, use_tuner)


def _cfg(arch="gpt2", **over):
    cfg = get_config(arch).reduced()
    over.setdefault("use_fused_kernels", True)
    return dataclasses.replace(cfg, **over)


def _plan(cfg, tokens=4, kv_len=64, **kw):
    return build_stream_plan(cfg, tokens=tokens, kv_len=kv_len, **kw)


# ------------------------------------------------------- table store

def test_table_round_trip(tmp_path):
    path = str(tmp_path / "t.json")
    t = TuneTable(path=path)
    key = make_key("streamed_ffn", shape=(("t", 4), ("d", 64)),
                   dtype="float32", quant="none", mesh_axes=(),
                   blocks=(("block_t", 256), ("block_f", 128)))
    t.put(key, TuneEntry(latency_s=1.5e-4, source="measured"))
    t.save()
    back = TuneTable.load(path)
    assert not back.diagnostics
    assert len(back) == 1
    got = back.get(key)
    assert got is not None
    assert got.latency_s == pytest.approx(1.5e-4)
    assert got.source == "measured"
    assert back.hits == 1 and back.misses == 0
    assert back.get("no-such-key") is None
    assert back.misses == 1


def test_table_key_is_order_insensitive():
    a = make_key("k", shape=(("t", 4), ("d", 8)), dtype="f32",
                 quant="none", mesh_axes=(), blocks=(("x", 1), ("y", 2)))
    b = make_key("k", shape=(("d", 8), ("t", 4)), dtype="f32",
                 quant="none", mesh_axes=(), blocks=(("y", 2), ("x", 1)))
    assert a == b


def test_table_concurrent_writers_leave_valid_json(tmp_path):
    """Atomic replace: racing saves must each leave a complete, parseable
    file — a reader can never observe a half-written table."""
    path = str(tmp_path / "t.json")
    errs = []

    def writer(i):
        try:
            t = TuneTable(path=path)
            for j in range(20):
                t.put(f"w{i}.e{j}", TuneEntry(latency_s=float(j + 1)))
                t.save()
        except Exception as e:         # pragma: no cover - failure path
            errs.append(e)

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errs
    back = TuneTable.load(path)
    assert not back.diagnostics          # parseable, version/backend ok
    assert len(back) == 20               # one writer's complete last save
    assert not os.listdir(str(tmp_path)) == []  # no tmp litter check below
    assert [f for f in os.listdir(str(tmp_path))] == ["t.json"]


def test_table_corrupt_file_degrades_with_warning(tmp_path):
    path = str(tmp_path / "t.json")
    with open(path, "w") as f:
        f.write("{ this is not json")
    t = TuneTable.load(path)
    assert len(t) == 0
    assert any(d.code == "table-corrupt" and d.severity == "warning"
               for d in t.diagnostics)
    # A degraded table still works: fill + save overwrites the wreck.
    t.put("k", TuneEntry(latency_s=1.0))
    t.save()
    assert not TuneTable.load(path).diagnostics


def test_table_schema_version_mismatch(tmp_path):
    path = str(tmp_path / "t.json")
    blob = {"version": SCHEMA_VERSION + 1,
            "backend": backend_fingerprint(),
            "entries": {"k": {"latency_s": 1.0, "source": "measured",
                              "samples": 1}}}
    with open(path, "w") as f:
        json.dump(blob, f)
    t = TuneTable.load(path)
    assert len(t) == 0                   # stale-schema entries dropped
    assert any(d.code == "table-version" for d in t.diagnostics)


def test_table_backend_mismatch(tmp_path):
    path = str(tmp_path / "t.json")
    blob = {"version": SCHEMA_VERSION,
            "backend": "tpu:compiled",   # not this host's fingerprint
            "entries": {"k": {"latency_s": 1.0, "source": "measured",
                              "samples": 1}}}
    with open(path, "w") as f:
        json.dump(blob, f)
    t = TuneTable.load(path)
    assert len(t) == 0                   # foreign measurements dropped
    assert any(d.code == "table-backend" for d in t.diagnostics)


def test_frozen_table_rejects_writes(tmp_path):
    t = TuneTable(path=str(tmp_path / "t.json"), frozen=True)
    with pytest.raises(RuntimeError):
        t.put("k", TuneEntry(latency_s=1.0))
    with pytest.raises(RuntimeError):
        t.save()


# --------------------------------------------------- tuner mechanics

def test_enumerate_candidates_original_first_and_deduped():
    cfg = _cfg()
    plan = _plan(cfg)
    for kind, stage, choice in plan.stage_choices():
        if not choice.fused or stage == "verify_attn":
            continue
        cands = enumerate_candidates(cfg, plan, stage, choice)
        assert cands[0] == choice        # analytic fallback always present
        # Dedup: no two candidates share an effective-block signature.
        from repro.tuning.autotune import _signature
        sigs = [_signature(cfg, plan, stage, c) for c in cands]
        assert len(sigs) == len(set(sigs))
        # Tuning varies stream granularity only — never math flags.
        for c in cands:
            assert c.implementation == choice.implementation
            assert c.block("fuse_norm") == choice.block("fuse_norm")
            assert c.block("w8") == choice.block("w8")


def test_lint_pruning_rejects_illegal_candidates():
    """Full-size gpt2: block 512 does not divide the 768-wide qkv dim, so
    that lattice point survives dedup but must be pruned by the lint —
    never scored, never picked."""
    cfg = dataclasses.replace(get_config("gpt2"), use_fused_kernels=True)
    plan = build_stream_plan(cfg, tokens=256, kv_len=256)
    tuner = Tuner()
    tuned = tuner.tune_plan(cfg, plan)
    assert tuner.stats.pruned > 0
    assert tuner.stats.candidates >= tuner.stats.pruned
    # The winner at every tuned stage is lint-clean or the original.
    from repro.analysis.kernel_lint import check_kernels
    base_dirty = {(d.stage, d.code)
                  for d in check_kernels(plan, cfg, TPU_V5E)
                  if d.severity in ("error", "warning")}
    tuned_dirty = {(d.stage, d.code)
                   for d in check_kernels(tuned, cfg, TPU_V5E)
                   if d.severity in ("error", "warning")}
    assert tuned_dirty <= base_dirty     # tuning never dirties a plan


def test_tuned_registry_plan_verifies_clean():
    """The reduced-config sweep contract: a tuned plan passes the static
    verifier exactly as strictly as the analytic plan it came from."""
    from repro.analysis import clean, verify_plan
    for arch in ("gpt2", "llama3-8b", "qwen3-0.6b"):
        cfg = _cfg(arch)
        plan = _plan(cfg, tune=True)
        diags = verify_plan(plan, cfg, None, slots=2, max_len=64)
        assert clean(diags), (arch, [str(d) for d in diags])


def test_tuner_deterministic_and_frozen_table_reproducible(tmp_path):
    path = str(tmp_path / "t.json")
    cfg = _cfg()
    p1 = _plan(cfg, tune=Tuner(TuneTable(path=path)))
    # Frozen reload: scoring is table-only lookups, plans bit-identical.
    frozen = TuneTable.load(path)
    frozen.frozen = True
    t2 = Tuner(frozen)
    t3 = Tuner(TuneTable.load(path))
    p2 = _plan(cfg, tune=t2)
    p3 = _plan(cfg, tune=t3)
    assert p1 == p2 == p3
    assert t2.stats.measured == 0        # frozen run never measures
    assert t2.table.hits > 0


def test_tuner_stamps_sources_and_syncs_verify_pages():
    cfg = _cfg("llama3-8b")
    plan = _plan(cfg, tokens=8, kv_len=64)
    tuner = Tuner(force_measure=True)    # wall-clock even in interpret
    tuned = tuner.tune_plan(cfg, plan)
    assert tuned.cost_source in ("measured", "hybrid")
    srcs = {f"{k}.{s}": c.source for k, s, c in tuned.stage_choices()
            if c.fused}
    assert any(v == "measured" for v in srcs.values())
    # verify_attn mirrors decode_attn's page size (same paged pool).
    for kind, lp in tuned.layers:
        if lp.verify_attn.fused and lp.decode_attn.fused:
            assert (lp.verify_attn.block("page_size")
                    == lp.decode_attn.block("page_size"))
    # summary carries the provenance satellites.
    summ = tuned.summary()
    assert summ["plan_source"] == tuned.cost_source
    assert summ["stage_sources"]         # measured stages are listed


def test_measure_candidate_interpret_falls_back_to_analytic():
    cfg = _cfg()
    plan = _plan(cfg)
    for kind, stage, choice in plan.stage_choices():
        if not choice.fused:
            continue
        lat, src = measure_candidate(
            cfg, plan, kind, stage, choice, platform=TPU_V5E)
        assert src == "analytic" and lat > 0.0
        break


def test_measure_candidate_failed_timing_raises(monkeypatch):
    """A timing run that fails is an error, never an analytic score: on
    the chip a kernel the compiler refuses must not look tuned."""
    import importlib
    measure_mod = importlib.import_module("repro.tuning.measure")
    cfg = _cfg()
    plan = _plan(cfg)
    kind, stage, choice = next(c for c in plan.stage_choices()
                               if c[2].fused)

    def refused(fn, **kw):
        raise RuntimeError("Mosaic refused the block shape")

    monkeypatch.setattr(measure_mod, "measure", refused)
    with pytest.raises(RuntimeError, match="refused"):
        measure_candidate(cfg, plan, kind, stage, choice,
                          platform=TPU_V5E, force=True)


def test_measure_wall_clock_path():
    calls = []

    def fn():
        calls.append(1)
        return np.zeros(1)

    lat = measure(fn, reps=3, warmup=1)
    assert lat >= 0.0
    assert len(calls) == 4               # warmup + reps


def test_resolve_tuner_specs(tmp_path):
    cfg = _cfg()
    assert resolve_tuner(None, cfg) is None
    assert resolve_tuner(False, cfg) is None
    t = Tuner()
    assert resolve_tuner(t, cfg) is t
    tt = resolve_tuner(str(tmp_path / "x.json"), cfg)
    assert tt.table.path == str(tmp_path / "x.json")
    td = resolve_tuner(str(tmp_path), cfg)
    assert td.table.path == str(tmp_path / f"{cfg.name}.json")
    with pytest.raises(TypeError):
        resolve_tuner(123, cfg)


def test_use_tuner_context_reaches_plan_for():
    cfg = _cfg()
    plan_for.cache_clear()
    tuner = Tuner()
    with use_tuner(tuner):
        plan = plan_for(cfg, 4, 64)
    assert tuner.stats.stages > 0        # plan_for consulted the tuner
    assert plan == tuner.tune_plan(cfg, plan_for(cfg, 4, 64))


# ------------------------------------------------------ DSE plumbing

def _ops(cfg):
    return trace_block(cfg, tokens=8, kv_len=64)


def test_evaluate_trial_records_breakdown():
    cfg = _cfg()
    trial = evaluate_trial(_ops(cfg), TPU_V5E, 64, 64)
    assert trial.breakdown                # per-kernel timing terms
    for name, row in trial.breakdown.items():
        assert row["kernel_s"] >= 0.0 and row["source"] == "analytic"
    assert trial.dma_s > 0.0
    assert trial.cost_source == "analytic"


def test_cost_source_overrides_kernel_latency():
    cfg = _cfg()
    ops = _ops(cfg)
    base = evaluate_trial(ops, TPU_V5E, 64, 64)
    slow = CostSource(mode="measured", lookup=lambda name: 1.0)
    trial = evaluate_trial(ops, TPU_V5E, 64, 64, cost_source=slow)
    assert trial.cost_source == "measured"
    assert trial.latency_s > base.latency_s
    assert all(r["source"] == "measured"
               for r in trial.breakdown.values())
    # Hybrid: misses are filled through the fill callback.
    filled = []
    hy = CostSource(mode="hybrid", lookup=lambda name: None,
                    fill=lambda name, s: filled.append(name) or s)
    evaluate_trial(ops, TPU_V5E, 64, 64, cost_source=hy)
    assert filled                         # every kernel went through fill
    with pytest.raises(ValueError):
        CostSource(mode="bogus")


def test_explore_seed_trials_deterministic():
    cfg = _cfg()
    ops = _ops(cfg)
    r1 = explore(ops, TPU_V5E, budget=6, seed_trials=[(64, 32)])
    r2 = explore(ops, TPU_V5E, budget=6, seed_trials=[(64, 32)])
    assert r1.seed_trials == r2.seed_trials == ((64, 32),)
    assert r1.best.params == r2.best.params
    assert [t.params for t in r1.trials] == [t.params for t in r2.trials]
    # Seeding the known winner reproduces it even with zero random budget.
    r3 = explore(ops, TPU_V5E, budget=1,
                 seed_trials=[tuple(r1.best.params.values())])
    assert r3.best.params == r1.best.params


# ------------------------------------------------- engine integration

@pytest.mark.slow
def test_engine_autotune_build_once_reuse(tmp_path):
    import jax

    from repro.models import init_params
    from repro.serving.engine import ServingEngine

    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    path = str(tmp_path / "gpt2.json")
    prompts = [np.arange(1, 9, dtype=np.int32)]

    eng1 = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                         autotune=path)
    out1 = eng1.generate([p.copy() for p in prompts], max_new_tokens=6)
    assert os.path.exists(path)
    assert eng1.tuner.stats.measured > 0
    assert eng1.metrics["autotuned"] == 1
    assert eng1.metrics["tune_table"] == path
    assert eng1.metrics["tune_entries"] > 0
    assert eng1.metrics["plan_source"] in ("analytic", "measured",
                                           "hybrid")

    plan_for.cache_clear()               # fresh-process stand-in
    eng2 = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                         autotune=path)
    out2 = eng2.generate([p.copy() for p in prompts], max_new_tokens=6)
    assert eng2.tuner.stats.measured == 0   # everything served from disk
    assert eng2.metrics["tune_hits"] > 0
    assert eng1.plan == eng2.plan           # bit-identical resolution
    assert out1[0].out_tokens == out2[0].out_tokens


@pytest.mark.slow
def test_engine_autotune_matches_untuned_tokens(tmp_path):
    """Tuning changes stream granularity, never kernel math: greedy
    tokens from a tuned engine equal the untuned engine's."""
    import jax

    from repro.models import init_params
    from repro.serving.engine import ServingEngine

    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = [np.arange(1, 9, dtype=np.int32),
               np.arange(5, 12, dtype=np.int32)]

    plan_for.cache_clear()
    base = ServingEngine(cfg, params, batch_slots=2, max_len=64)
    ref = base.generate([p.copy() for p in prompts], max_new_tokens=6)
    assert base.metrics["autotuned"] == 0
    assert base.metrics["plan_source"] == "analytic"

    plan_for.cache_clear()
    tuned = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                          autotune=str(tmp_path / "t.json"))
    got = tuned.generate([p.copy() for p in prompts], max_new_tokens=6)
    for a, b in zip(ref, got):
        assert a.out_tokens == b.out_tokens


def test_engine_warns_on_degraded_table(tmp_path):
    import jax

    from repro.models import init_params
    from repro.serving.engine import ServingEngine

    path = str(tmp_path / "t.json")
    with open(path, "w") as f:
        f.write("not json at all")
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    plan_for.cache_clear()
    with pytest.warns(UserWarning, match="autotune table degraded"):
        ServingEngine(cfg, params, batch_slots=2, max_len=64,
                      autotune=path)
