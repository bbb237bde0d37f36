"""The engine's host phases on the profiler's timeline, and the three
scheduler metrics read from them (CPU only)."""

import dataclasses
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import spans, trace
from bench.trace import Event


def _metric(name):
    path = Path(__file__).parents[1] / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_span_names_are_the_program_vocabulary():
    from repro.obs import events

    assert spans.HOST_PHASES == events.HOST_PHASES
    assert spans.DEVICE_WAITS == events.DEVICE_WAITS
    assert spans.ENQUEUES == events.ENQUEUES
    assert spans.FIRST_TOKEN == events.REQ_FIRST_TOKEN
    assert not any(n.startswith(spans.BATCH) for n in
                   spans.HOST_PHASES + spans.DEVICE_WAITS)


def test_one_generate_is_tiled_by_leaf_spans(tmp_path):
    """A chunked, prefix-caching engine serves one batch under the
    profiler: the engine's spans carry vocabulary names, and its leaves
    cover at least 95% of the batch annotation."""
    import jax

    from repro.configs import get_config
    from repro.models import init_params
    from repro.obs import events
    from repro.serving import ServingEngine

    cfg = dataclasses.replace(get_config("qwen3-0.6b").reduced(),
                              dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, batch_slots=4, max_len=128,
                        decode_block=4, prefill_chunk=16)
    rng = np.random.default_rng(0)
    shared = rng.integers(1, cfg.vocab_size, 32)
    prompts = [np.concatenate([shared, rng.integers(1, cfg.vocab_size, n)])
               for n in (5, 20, 40, 9, 33)]
    eng.generate([p.copy() for p in prompts], 6)       # compiles
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench batch 0"):
        reqs = eng.generate([p.copy() for p in prompts], 6)
    jax.profiler.stop_trace()
    assert all(len(r.out_tokens) == 6 for r in reqs)

    tr = trace.load(str(next(tmp_path.rglob("*.xplane.pb"))))
    (batch,) = [e for e in tr.host if e.name == "bench batch 0"]
    lo, hi = batch.start, batch.end
    vocab = {v for k, v in vars(events).items()
             if k.isupper() and isinstance(v, str)}
    engine = {e.name for e in trace.clip(tr.host, lo, hi)
              if e.name.split(".")[0] in ("engine", "wait", "dispatch",
                                          "req")}
    assert engine <= vocab
    assert {"engine.admit", "engine.chunk_stage", "dispatch.prefill_chunk",
            "engine.prefix_insert", "wait.first_token", "engine.decode_prep",
            "dispatch.decode", "engine.decode_enqueue", "wait.decode",
            "engine.harvest", "engine.retire", "req.first_token"} <= engine
    firsts = [e for e in tr.host if e.name == spans.FIRST_TOKEN]
    assert len(firsts) == len(prompts)
    covered = spans.length(trace.union(spans.named(
        tr.host, spans.HOST_PHASES + spans.DEVICE_WAITS, lo, hi)))
    assert covered >= 0.95 * (hi - lo), covered / (hi - lo)


# One batch over [0, 100) ns: admission, a chunk staged and enqueued, its
# program on the device over [12, 18); a decode block prepared, enqueued and
# waited for, its program over [31, 79); the harvest; an admission pass
# that runs past the window's end.  First tokens are stamped at 21 and 85.
HOST = [Event("bench batch 0", 0, 100), Event("engine.admit", 0, 10),
        Event("engine.chunk_stage", 10, 5),
        Event("dispatch.prefill_chunk", 15, 5),
        Event("req.first_token", 21, 1),
        Event("engine.decode_prep", 20, 5), Event("dispatch.decode", 25, 55),
        Event("engine.decode_enqueue", 25, 5), Event("wait.decode", 30, 50),
        Event("engine.harvest", 80, 10), Event("req.first_token", 85, 1),
        Event("engine.admit", 95, 10)]
OPS = [Event("%fusion.1 = f32[2] fusion(f32[2] %a), kind=kLoop", 12, 6),
       Event("%fusion.2 = f32[2] fusion(f32[2] %a), kind=kLoop", 31, 48)]
MODULES = [Event("jit__chunk_fwd(1)", 12, 6),
           Event("jit__decode_n(2)", 31, 48)]


def _reading(host, ops=OPS, modules=MODULES):
    return SimpleNamespace(trace=trace.Trace(ops=ops, modules=modules,
                                             host=host),
                           window_ns=(0.0, 100.0))


def test_scheduler_readers_by_hand():
    r = _reading(HOST)
    # host work: [0, 30) (admission to the decode enqueue), the harvest
    # [80, 90) and the admission's part [95, 100); two enqueues
    got = _metric("engine_host_ms_per_dispatch").read(r)
    assert got == pytest.approx(45 / 2 / 1e6)
    # idle [0, 12), [18, 31), [79, 100) inside that work: 12 + 12 + 10 + 5
    got = _metric("device_idle_engine_share").read(r)
    assert got == pytest.approx(39.0)
    assert got <= _metric("device_idle_share").read(r)
    # batch start to the last first token's end (86): decode ran 48
    got = _metric("ttft_decode_share").read(r)
    assert got == pytest.approx(100 * 48 / 86)


def test_a_wait_nested_in_a_host_phase_is_not_host_work():
    """A whole-prompt prefill inside admission: its enqueue counts, its
    read-back does not."""
    host = [Event("bench batch 0", 0, 100), Event("engine.admit", 0, 100),
            Event("dispatch.prefill", 10, 80),
            Event("engine.prefill_enqueue", 10, 10),
            Event("wait.prefill", 20, 70)]
    r = _reading(host)
    assert _metric("engine_host_ms_per_dispatch").read(r) == pytest.approx(
        30 / 1 / 1e6)
    # idle [0, 12), [18, 20) and [90, 100) lie in host work
    assert _metric("device_idle_engine_share").read(r) == pytest.approx(24.0)


def test_a_program_without_the_spans_is_read_from_runtime_events():
    """A program older than the spans: host work is the batch outside the
    read-backs, a dispatch is a program run, and the last first token
    comes at the end of the last prefill program."""
    host = [Event("bench batch 0", 0, 100),
            Event("np.asarray(jax.Array)", 19, 2),
            Event("np.asarray(jax.Array)", 30, 50),
            Event("CommonPjRtBuffer::ToLiteral", 31, 48)]
    modules = MODULES + [Event("jit__chunk_fwd(1)", 81, 3)]
    ops = OPS + [Event("%fusion.1 = f32[2] fusion(f32[2] %a), kind=kLoop",
                       81, 3)]
    r = _reading(host, ops, modules)
    # host work [0, 19), [21, 30), [80, 100); three program runs
    assert _metric("engine_host_ms_per_dispatch").read(r) == pytest.approx(
        48 / 3 / 1e6)
    # idle [0, 12), [18, 19), [21, 30), [80, 81), [84, 100) in that work
    got = _metric("device_idle_engine_share").read(r)
    assert got == pytest.approx(39.0)
    assert got <= _metric("device_idle_share").read(r)
    # batch start to the last chunk's end (84): decode ran 48
    assert _metric("ttft_decode_share").read(r) == pytest.approx(
        100 * 48 / 84)


def test_a_trace_without_spans_or_programs_reads_nothing():
    r = _reading([Event("bench batch 0", 0, 100)], ops=[], modules=[])
    for name in ("engine_host_ms_per_dispatch", "device_idle_engine_share",
                 "ttft_decode_share"):
        assert _metric(name).read(r) is None, name
