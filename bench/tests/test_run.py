"""CPU rehearsal of a benchmark run at tiny size, and its faults.

The test's own switch (``check_device``) skips the look for a TPU; the
rest of a run is driven as on the chip: weights from the seed, the engine
over the fused plan (Pallas in interpret mode), the warm-up batch, the
window, the trace and the reference check.  The CPU's trace holds no
device plane, so a traced rehearsal lays the ops and programs of a
recorded TPU v5e trace (``data/v5e_trace_names.json``) over its batch.
Each fault that a serving cell can have is planted under the timed path
and must turn ``correct`` false, as must a kernel that the trace can no
longer name.
"""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).parents[1]
RECORDED = json.loads((Path(__file__).parent / "data"
                       / "v5e_trace_names.json").read_text())
REPO = BENCH.parent
CELL = "tiny.chat"
LIMIT = 2e-4          # mean logit gap at tiny widths: sound runs read 0 to
#                       1.04e-4, the int8 control 2.4e-4 to 3.8e-4 (below)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout holding one tiny cell: qwen3 at widths 64/16, 2 layers."""
    root = tmp_path_factory.mktemp("checkout")
    for d in ("configs", "traffic", "limits"):
        (root / "bench" / d).mkdir(parents=True)
    cfg = json.loads((BENCH / "configs" / "qwen3-0.6b.json").read_text())
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, intermediate_size=128, num_hidden_layers=2,
               vocab_size=512)
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((BENCH / "traffic" / "chat.json").read_text())
    mix.update(slots=2, max_len=128, batch=2, new_tokens=6,
               check_requests=2,
               prompt={"law": "uniform", "min": 20, "max": 60})
    (root / "bench/traffic/tiny.json").write_text(json.dumps(mix))
    shutil.copy(BENCH / "traffic" / "batches.py", root / "bench/traffic")
    (root / "bench/limits" / f"{CELL}.json").write_text(
        json.dumps({"mean_logit_gap": LIMIT}))
    (root / "bench" / "metrics").symlink_to(BENCH / "metrics")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [dict(spec["configs"][0], name="tiny",
                            file="bench/configs/tiny.json")]
    spec["workloads"] = [dict(name=CELL, config="tiny", traffic="tiny",
                              chips=1, why="tiny rehearsal")]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture
def own_cache(tmp_path, monkeypatch):
    """The run's compile cache in a temporary directory, and JAX's cache
    settings restored after it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])
    compilation_cache.reset_cache()


def fake_tpu(chips):
    return {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


@pytest.fixture
def recorded_device(monkeypatch):
    """``trace.load`` with the recorded TPU ops and programs laid end to
    end over the traced batch; returns the list of op names to edit."""
    from bench import trace

    real = trace.load
    names = [o["name"] for o in RECORDED["xla_ops"]]

    def load(path):
        tr = real(path)
        ann = [e for e in tr.host if e.name.startswith("bench batch ")]
        lo, hi = ann[0].start, ann[0].end
        step = (hi - lo) / (len(names) + 1)
        tr.ops = [trace.Event(n, lo + i * step, 0.9 * step)
                  for i, n in enumerate(names)]
        mods = [m["name"] for m in RECORDED["xla_modules"]][:2]
        tr.modules = [trace.Event(m, lo + k * (hi - lo) / 2, (hi - lo) / 2)
                      for k, m in enumerate(mods)]
        tr.devices = 1
        return tr
    monkeypatch.setattr(trace, "load", load)
    return names


def run(root, trace=False, seed=2**33 + 11):
    from bench import harness
    return harness.run(root, CELL, seed, 0.2, trace, time.perf_counter(),
                       check_device=fake_tpu)


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_rehearsal_prints_the_contract_keys(root, own_cache, recorded_device,
                                            trace):
    out = run(root, trace)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 2
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}
    if trace:
        assert {"busy_s", "window_s"} <= set(out["device"])
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
        spec = json.loads((root / "BENCHMARK.json").read_text())
        assert set(out["metrics"]) == {m["name"] for m in spec["per_layer"]}
        assert out["checks"]["unnamed_kernel_ops"]["value"] == 0
    else:
        assert set(out["metrics"]) == {"output_tok_per_s", "ttft_p90_ms",
                                       "tpot_p90_ms", "setup_s"}
        assert all(m["value"] > 0 for m in out["metrics"].values())
    json.dumps(out, allow_nan=False)


def test_a_token_altered_where_produced_is_not_correct(root, own_cache,
                                                       monkeypatch):
    from repro.serving import engine

    real = engine.ServingEngine.generate

    def altered(self, prompts, max_new_tokens=16):
        reqs = real(self, prompts, max_new_tokens)
        if max_new_tokens > 2:          # the window's batches, not warm-up
            for r in reqs:
                r.out_tokens[2] = (r.out_tokens[2] + 1) % 512
        return reqs
    monkeypatch.setattr(engine.ServingEngine, "generate", altered)
    out = run(root)
    assert out["correct"] is False
    assert out["checks"]["mean_logit_gap"]["value"] > LIMIT


def test_a_decode_step_that_keeps_its_state_is_not_correct(root, own_cache,
                                                           monkeypatch):
    from repro.serving import engine

    real = engine.decode_step

    def stale(p, cfg, tok, cache, *a, **kw):
        nt, lg, _ = real(p, cfg, tok, cache, *a, **kw)
        return nt, lg, cache            # the new K/V is never written
    monkeypatch.setattr(engine, "decode_step", stale)
    out = run(root)
    assert out["correct"] is False
    assert out["checks"]["mean_logit_gap"]["value"] > LIMIT


def test_a_kernel_the_trace_cannot_name_is_not_correct(root, own_cache,
                                                       recorded_device):
    """A paged kernel whose operands changed reads as an unnamed Pallas
    call: its metrics read nothing, and the run says so."""
    for i, n in enumerate(recorded_device):
        recorded_device[i] = n.replace("s32[16,128]{1,0}", "s32[16,128,1]")
    out = run(root, trace=True)
    assert out["correct"] is False
    assert out["checks"]["unnamed_kernel_ops"]["value"] == 1
    assert out["checks"]["per_layer_unread"]["value"] >= 2
    assert "paged_attention_roofline" not in out["metrics"]


def test_without_a_tpu_the_command_exits_nonzero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "qwen3-0.6b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_control_fails_the_tiny_limit(root, seed):
    """The control, the reference at int8 in the program's place, read at
    every position of 512-token rows at the tiny cell's widths, is judged
    by ``bench/control.py`` as a run is judged, against the tiny cell's
    limit file: not correct."""
    import numpy as np

    from bench import check, control, harness, weights

    cfg = json.loads((root / "bench/configs/tiny.json").read_text())
    params = weights.make_params(harness.program_config(cfg), seed,
                                 cfg["initializer_range"])
    ref = check.Reference(cfg, 512, 512)
    row = np.random.default_rng(seed).integers(1, 512, 512)
    req = type("R", (), {"prompt": row[:1], "out_tokens": list(row[1:]) + [1],
                         "rid": 0})()
    limits = json.loads((root / "bench/limits" / f"{CELL}.json").read_text())
    row = control.verdict("control", ref.gaps(params, [req], control=True),
                          0, limits)
    assert row["control_correct"] is False
    assert row["control_mean_gap"] > LIMIT
