"""Observability subsystem (DESIGN.md §17): events, metrics, exporters.

Three tiers:

  * pure-unit — histogram bucket/percentile math, counter/gauge/window
    semantics, recorder span ordering under a fake clock, the disabled
    recorder's zero-allocation contract;
  * golden — byte-exact Chrome-trace / JSONL / Prometheus exports of a
    fixed scenario driven by ``ManualClock`` (regenerate with
    ``python tests/test_obs.py --regen`` after INTENDED format changes);
  * engine integration (``slow``) — a mixed chunked+speculative burst
    with telemetry on: the event timeline must agree with the engine's
    own counters and trace-time compile probes, tokens must be
    bit-identical with telemetry off, and the lifetime vs
    ``last_generate`` snapshot views must window correctly.
"""

import dataclasses
import json
import math
import os

import numpy as np
import pytest

from repro.obs import (
    DEVICE_WAITS,
    DISPATCH_PREFILL_CHUNK,
    DISPATCH_VERIFY,
    HOST_PHASES,
    NULL_RECORDER,
    Counter,
    Gauge,
    Histogram,
    ManualClock,
    MetricsView,
    NullRecorder,
    Recorder,
    Registry,
    REQ_ADMITTED,
    REQ_FINISHED,
    REQ_FIRST_TOKEN,
    REQ_QUEUED,
    REQ_REJECTED,
    TRACE_DECODE,
    TRACE_PREFILL,
    TRACE_VERIFY,
    chrome_trace,
    events_jsonl,
    log_buckets,
    prometheus_text,
    resolve_recorder,
    slot_track,
    TRACK_ENGINE,
    validate_chrome_trace,
)

DATA = os.path.join(os.path.dirname(__file__), "data", "obs")


# ------------------------------------------------------------ histograms

def test_log_buckets_cover_range():
    b = log_buckets(1e-3, 10.0, 4)
    assert b[0] == pytest.approx(1e-3)
    assert b[-1] >= 10.0
    # log-spaced: constant ratio between consecutive bounds
    ratios = [b[i + 1] / b[i] for i in range(len(b) - 1)]
    assert all(r == pytest.approx(10 ** 0.25) for r in ratios)
    for bad in ((0, 1, 4), (1, 1, 4), (1e-3, 10, 0)):
        with pytest.raises(ValueError):
            log_buckets(*bad)


def test_histogram_bucket_edges_and_units():
    h = Histogram("lat", lo=1e-3, hi=1.0, per_decade=1, unit="s")
    assert h.bounds == pytest.approx((1e-3, 1e-2, 1e-1, 1.0))
    # an observation exactly ON a bound lands in that bound's bucket
    # (le semantics), one epsilon above lands in the next
    h.observe(1e-2)
    h.observe(1e-2 * 1.0001)
    h.observe(5.0)                       # overflow bucket
    assert h.counts() == [0, 1, 1, 0, 1]
    assert h.count() == 3
    assert h.sum() == pytest.approx(1e-2 + 1e-2 * 1.0001 + 5.0)
    assert h.mean() == pytest.approx(h.sum() / 3)


def test_histogram_skips_non_finite():
    h = Histogram("lat")
    h.observe(float("nan"))
    h.observe(float("inf"))
    h.observe(0.5)
    assert h.count() == 1
    assert math.isnan(Histogram("empty").percentile(0.5))


def test_histogram_percentiles_ordered_and_clamped():
    rng = np.random.default_rng(7)
    h = Histogram("lat", lo=1e-5, hi=100.0, per_decade=4)
    vals = np.exp(rng.normal(-2.0, 2.0, size=500))
    for v in vals:
        h.observe(float(v))
    p50, p90, p99 = (h.percentile(q) for q in (0.5, 0.9, 0.99))
    assert p50 <= p90 <= p99
    assert vals.min() <= p50 and p99 <= vals.max()
    # estimates land within a bucket width of the exact quantile
    for q, est in ((0.5, p50), (0.9, p90), (0.99, p99)):
        exact = float(np.quantile(vals, q))
        assert est / exact < 10 ** 0.25 + 1e-9
        assert exact / est < 10 ** 0.25 + 1e-9
    with pytest.raises(ValueError):
        h.percentile(0.0)


def test_histogram_window_views():
    h = Histogram("lat", lo=1e-3, hi=1.0, per_decade=2)
    h.observe(0.001)
    h.observe(0.002)
    h.mark()
    h.observe(0.9)
    assert h.count("lifetime") == 3
    assert h.count("last_generate") == 1
    # window percentiles come from the windowed bucket counts: the
    # estimate lands inside the bucket holding 0.9 (bucket resolution,
    # not exact recovery), far from the lifetime median
    assert 0.316 < h.percentile(0.5, "last_generate") <= 1.0
    assert h.percentile(0.5, "lifetime") < 0.1


def test_counter_and_gauge_semantics():
    c = Counter("n")
    c.inc()
    c.inc(4)
    c.mark()
    c.inc(2)
    assert c.value("lifetime") == 7
    assert c.value("last_generate") == 2
    with pytest.raises(ValueError):
        c.inc(-1)
    g = Gauge("peak")
    g.set(5)
    g.max(3)
    assert g.value() == 5
    g.max(9)
    assert g.value("last_generate") == 9      # gauges are view-independent


def test_registry_kind_mismatch_and_view():
    reg = Registry()
    reg.counter("generated", "tokens out")
    reg.histogram("ttft_s", "time to first token")
    with pytest.raises(TypeError):
        reg.gauge("generated")
    assert reg.counter("generated") is reg["generated"]  # get-or-create
    reg["generated"].inc(3)
    reg["ttft_s"].observe(0.25)
    view = MetricsView(reg)
    assert view["generated"] == 3
    assert view["ttft_s_count"] == 1
    assert view["ttft_s_p50"] == pytest.approx(0.25)
    assert "generated" in dict(view) and "ttft_s_p99" in dict(view)
    with pytest.raises(KeyError):
        view["nope"]
    with pytest.raises(KeyError):
        view["ttft_s"]                  # histograms only expose suffixes
    snap = reg.snapshot("last_generate")
    assert snap["generated"] == 3 and snap["ttft_s_count"] == 1
    reg.mark()
    assert reg.snapshot("last_generate")["generated"] == 0
    assert reg.snapshot("lifetime")["generated"] == 3
    with pytest.raises(ValueError):
        reg.snapshot("bogus")


# --------------------------------------------------------------- events

def test_manual_clock_never_returns_start():
    clk = ManualClock()
    assert clk() > 0.0                  # 0.0 is the engine's unset sentinel
    t1, t2 = clk(), clk()
    assert t1 < t2
    clk.advance(1.0)
    assert clk() > t2 + 1.0


def test_recorder_span_nesting_and_ordering():
    rec = Recorder(ManualClock(tick=1.0))
    with rec.span("outer", track="engine", a=1):
        rec.instant("mid", track="engine")
        with rec.span("inner", track="engine"):
            pass
    # spans emit at EXIT: mid, inner, outer
    assert [e.name for e in rec.events] == ["mid", "inner", "outer"]
    mid, inner, outer = rec.events
    assert outer.ts < mid.ts < inner.ts
    assert inner.end <= outer.end
    assert outer.dur > inner.dur > 0
    assert outer.args == {"a": 1}
    assert rec.count("inner") == 1 and rec.count("nope") == 0


def test_recorder_complete_and_max_events():
    rec = Recorder(ManualClock(tick=1.0), max_events=2)
    rec.complete("d", 1.0, 0.5, track="engine", n=3)
    assert rec.events[0].kind == "span" and rec.events[0].end == 1.5
    rec.instant("a")
    rec.instant("b")                    # past the cap: dropped, counted
    assert len(rec.events) == 2 and rec.dropped == 1
    rec.clear()
    assert rec.events == [] and rec.dropped == 0


def test_null_recorder_is_inert_and_allocation_free():
    nr = NULL_RECORDER
    assert not nr.enabled and nr.events == ()
    nr.instant("x", track="engine", a=1)
    nr.complete("y", 0.0, 1.0)
    assert nr.events == () and nr.count("x") == 0
    # span returns ONE shared context — the hot path allocates nothing
    assert nr.span("a") is nr.span("b")
    with nr.span("a"):
        pass


def test_resolve_recorder():
    assert resolve_recorder(None) is NULL_RECORDER
    assert resolve_recorder(False) is NULL_RECORDER
    clk = ManualClock()
    rec = resolve_recorder(True, clock=clk)
    assert isinstance(rec, Recorder) and rec.clock is clk
    mine = Recorder()
    assert resolve_recorder(mine) is mine
    assert resolve_recorder(mine, clock=clk).clock is clk  # rebound
    assert isinstance(resolve_recorder(NullRecorder()), NullRecorder)
    with pytest.raises(TypeError):
        resolve_recorder("yes")


# --------------------------------------------------------------- goldens

def _golden_events():
    """A fixed mini-lifecycle on a deterministic clock."""
    rec = Recorder(ManualClock(tick=0.001))
    rec.instant(REQ_QUEUED, track="engine", rid=0, prompt_len=12)
    rec.instant(REQ_ADMITTED, track=slot_track(0), rid=0)
    rec.complete("prefill", 0.002, 0.010, track=slot_track(0), rid=0,
                 tokens=12)
    rec.instant(REQ_FIRST_TOKEN, track=slot_track(0), rid=0,
                ttft_s=0.011)
    rec.complete("decode", 0.012, 0.004, track="engine", block=4,
                 slots=1)
    rec.instant("page.alloc", track="kv", page=3, free=5)
    rec.instant(REQ_FINISHED, track=slot_track(0), rid=0, tokens=4,
                failed=False)
    return rec.events


def _golden_registry():
    reg = Registry()
    reg.counter("generated", "tokens generated").inc(4)
    reg.counter("dispatches", "decode dispatches").inc(1)
    reg.gauge("pages_in_use", "allocated KV pages").set(3)
    h = reg.histogram("ttft_s", "time to first token",
                      lo=1e-3, hi=10.0, per_decade=2)
    for v in (0.011, 0.02, 0.5):
        h.observe(v)
    reg.info("quant", "KV quantization mode", value="none")
    reg.info("plan_source", "plan provenance", value="analytic")
    return reg


def _golden(name, text, regen):
    path = os.path.join(DATA, name)
    if regen:
        os.makedirs(DATA, exist_ok=True)
        with open(path, "w") as fh:
            fh.write(text)
        return
    with open(path) as fh:
        assert text == fh.read(), (
            f"{name} drifted from golden — if the format change is "
            f"intended, regenerate: python tests/test_obs.py --regen")


def test_chrome_trace_golden():
    trace = chrome_trace(_golden_events())
    assert validate_chrome_trace(trace) == []
    _golden("trace.json", json.dumps(trace, indent=1) + "\n", False)


def test_events_jsonl_golden():
    _golden("events.jsonl", events_jsonl(_golden_events()), False)


def test_prometheus_golden():
    text = prometheus_text(_golden_registry())
    _golden("metrics.prom", text, False)
    # structural spot-checks, independent of the golden bytes
    assert "repro_generated_total 4" in text
    assert 'repro_ttft_s_bucket{le="+Inf"} 3' in text
    assert "repro_ttft_s_count 3" in text
    assert 'repro_info{quant="none",plan_source="analytic"} 1' in text
    p = [float(line.split()[-1]) for line in text.splitlines()
         if line.startswith("repro_ttft_s_p")]
    assert len(p) == 3 and p[0] <= p[1] <= p[2]


def test_chrome_trace_tracks_stable():
    trace = chrome_trace(_golden_events())
    meta = [e for e in trace["traceEvents"] if e["ph"] == "M"]
    names = [e["args"]["name"] for e in meta]
    # named tracks first (first-seen), then slots sorted numerically
    assert names == ["engine", "kv", "slot0"]
    assert [e["tid"] for e in meta] == [1, 2, 3]


def test_validate_chrome_trace_catches_problems():
    assert validate_chrome_trace({}) == ["traceEvents missing or empty"]
    bad = {"traceEvents": [
        {"ph": "Z", "pid": 1, "tid": 1},
        {"ph": "X", "pid": 1, "tid": 9, "name": "d", "ts": -1.0},
    ]}
    errs = validate_chrome_trace(bad)
    assert any("bad ph" in e for e in errs)
    assert any("bad ts" in e for e in errs)
    assert any("no thread_name" in e for e in errs)


# ---------------------------------------------- engine integration (slow)

def _engine(telemetry=False, clock=None, **kw):
    import jax

    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving import ServingEngine

    cfg = dataclasses.replace(get_config("gpt2").reduced(),
                              dtype="float32", use_fused_kernels=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    kw.setdefault("batch_slots", 2)
    kw.setdefault("max_len", 96)
    kw.setdefault("decode_block", 4)
    eng = ServingEngine(cfg, params, telemetry=telemetry, clock=clock,
                        **kw)
    return cfg, eng


def _burst_prompts(cfg):
    v = cfg.vocab_size
    return [
        np.array(([1, 2, 3, 4, 5, 6, 7, 8] * 4)[:30], np.int32) % v,
        np.array([9, 8, 7, 6, 5], np.int32) % v,
        np.array([1, 2, 3, 4] * 5, np.int32) % v,
    ]


@pytest.mark.slow
def test_engine_burst_timeline_matches_counters():
    """Mixed chunked+speculative burst: the event timeline, the metric
    counters, and the trace-time compile probes must all agree, and
    telemetry must not perturb the greedy tokens."""
    kw = dict(chunked=True, prefill_chunk=16, speculative=True,
              draft_len=4)
    cfg, eng = _engine(telemetry=True, clock=ManualClock(tick=1e-4), **kw)
    prompts = _burst_prompts(cfg)
    reqs = eng.generate([p.copy() for p in prompts], max_new_tokens=10)

    # event counts == the engine's own counters
    n = len(prompts)
    for name, want in ((REQ_QUEUED, n), (REQ_ADMITTED, n),
                       (REQ_FIRST_TOKEN, n), (REQ_FINISHED, n)):
        assert eng.obs.count(name) == want, name
    assert (eng.obs.count(DISPATCH_PREFILL_CHUNK)
            == eng.metrics["prefill_chunks"])
    assert (eng.obs.count(DISPATCH_VERIFY)
            == eng.metrics["verify_dispatches"])
    # event counts == the trace-time compile probes (same bump sites)
    for name, probe in ((TRACE_PREFILL, "prefill"),
                        (TRACE_DECODE, "decode"),
                        (TRACE_VERIFY, "verify")):
        assert eng.obs.count(name) == eng._traces[probe]

    # per-request lifecycle ordering on the shared clock
    by_rid = {}
    for e in eng.obs.events:
        if e.name.startswith("req."):
            by_rid.setdefault(e.args["rid"], {})[e.name] = e.ts
    for r in reqs:
        t = by_rid[r.rid]
        assert (t[REQ_QUEUED] <= t[REQ_ADMITTED]
                <= t[REQ_FIRST_TOKEN] <= t[REQ_FINISHED])
        assert t[REQ_QUEUED] == r.submitted_at
        assert t[REQ_FINISHED] == r.finished_at
        assert r.ttft_s == pytest.approx(
            t[REQ_FIRST_TOKEN] - t[REQ_QUEUED])
        assert 0.0 <= r.queue_wait_s <= r.ttft_s <= r.latency_s
        assert r.tpot_s >= 0.0

    # every dispatch span sits inside the generate() window
    t_lo = min(e.ts for e in eng.obs.events)
    t_hi = max(e.end for e in eng.obs.events)
    for e in eng.obs.events:
        assert t_lo <= e.ts <= e.end <= t_hi

    # trace export is loadable; per-slot tracks exist
    trace = chrome_trace(eng.obs.events)
    assert validate_chrome_trace(trace) == []
    lanes = {e["args"]["name"] for e in trace["traceEvents"]
             if e["ph"] == "M"}
    assert {"engine", "slot0", "slot1"} <= lanes

    # pure observer: identical tokens with telemetry off, zero events
    _, off = _engine(telemetry=False, **kw)
    reqs_off = off.generate([p.copy() for p in prompts],
                            max_new_tokens=10)
    assert [r.out_tokens for r in reqs] == [r.out_tokens
                                            for r in reqs_off]
    assert off.obs.events == () and not off.obs.enabled


def test_engine_host_phases_reach_the_profiler_and_the_recorder(tmp_path):
    """Each host phase is emitted once to both sinks: the engine spans on
    the profiler's timeline carry the names of the recorder's engine-track
    spans, and the first-token mark is the recorder's instant."""
    import jax

    cfg, eng = _engine(telemetry=True, chunked=True, prefill_chunk=16,
                       speculative=True, draft_len=4)
    prompts = _burst_prompts(cfg)
    eng.generate([p.copy() for p in prompts], max_new_tokens=6)  # compiles
    eng.obs.clear()
    jax.profiler.start_trace(str(tmp_path))
    eng.generate([p.copy() for p in prompts], max_new_tokens=6)
    jax.profiler.stop_trace()
    pd = jax.profiler.ProfileData.from_file(
        str(next(tmp_path.rglob("*.xplane.pb"))))
    profiled = {e.name for plane in pd.planes if plane.name == "/host:CPU"
                for line in plane.lines for e in line.events
                if e.name.split(".")[0] in ("engine", "wait", "dispatch",
                                            "req")}
    recorded = {e.name for e in eng.obs.events
                if e.kind == "span" and e.track == TRACK_ENGINE}
    assert profiled - {REQ_FIRST_TOKEN} == recorded
    assert eng.obs.count(REQ_FIRST_TOKEN) == len(prompts)
    assert set(HOST_PHASES + DEVICE_WAITS) & recorded >= {
        "engine.admit", "engine.chunk_stage", "dispatch.prefill_chunk",
        "wait.first_token", "engine.decode_prep", "engine.verify_enqueue",
        "wait.verify", "engine.harvest", "engine.retire"}
    assert REQ_FIRST_TOKEN in profiled


@pytest.mark.slow
def test_engine_rejection_nan_semantics_and_event():
    """Admission-rejected requests: finite latency, nan ttft, a
    REQ_REJECTED event, and a windowed ``rejected`` counter."""
    cfg, eng = _engine(telemetry=True, clock=ManualClock(tick=1e-4))
    good = np.array([1, 2, 3, 4, 5], np.int32)
    bad = np.ones(97, np.int32)                  # > max_len
    reqs = eng.generate([good, bad], max_new_tokens=4)
    r = reqs[1]
    assert r.failed and math.isnan(r.ttft_s) and math.isnan(r.tpot_s)
    assert math.isnan(r.queue_wait_s)            # never admitted
    assert r.latency_s >= 0.0                    # failed AT a real time
    assert eng.obs.count(REQ_REJECTED) == 1
    assert eng.metrics["rejected"] == 1
    assert eng.metrics["ttft_s_count"] == 1      # nan never observed
    # the good request is untouched
    assert reqs[0].out_tokens and not reqs[0].failed


@pytest.mark.slow
def test_engine_snapshot_windows():
    """lifetime accumulates across generate() calls; last_generate
    covers exactly the most recent one."""
    cfg, eng = _engine(telemetry=True, clock=ManualClock(tick=1e-4))
    p = [np.array([1, 2, 3, 4, 5, 6], np.int32)]
    eng.generate([q.copy() for q in p], max_new_tokens=4)
    g1 = eng.metrics["generated"]
    eng.generate([q.copy() for q in p], max_new_tokens=4)
    life = eng.snapshot("lifetime")
    win = eng.snapshot("last_generate")
    assert life["generated"] == 2 * g1
    assert win["generated"] == g1
    assert life["ttft_s_count"] == 2 and win["ttft_s_count"] == 1
    # gauges are point-in-time in both views
    assert life["pages_in_use"] == win["pages_in_use"]
    # the back-compat mapping is the lifetime view
    assert eng.metrics["generated"] == life["generated"]
    assert dict(eng.metrics)["generated"] == life["generated"]


if __name__ == "__main__":
    import sys
    if "--regen" in sys.argv:
        os.makedirs(DATA, exist_ok=True)
        _golden("trace.json",
                json.dumps(chrome_trace(_golden_events()), indent=1)
                + "\n", True)
        _golden("events.jsonl", events_jsonl(_golden_events()), True)
        _golden("metrics.prom", prometheus_text(_golden_registry()), True)
        print(f"regenerated goldens under {DATA}")
    else:
        raise SystemExit(pytest.main([__file__, "-v"] + sys.argv[1:]))
