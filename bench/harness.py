"""One run of one benchmark cell: set-up, measured window, check, result.

The cell (``BENCHMARK.json`` ``workloads`` entry) names a configuration
(``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``, whose ``generator`` is a module of
``bench/traffic/``); its correctness limit is ``bench/limits/<cell>.json``;
each per-layer metric is read by ``bench/metrics/<metric>.py``.  Nothing
here names a cell, configuration, mix or metric.

Set-up (``setup_s``, from process start to the first measured submission)
draws the weights on the device, builds the ``ServingEngine`` with the
fused plan, and serves one warm-up batch of the cell's own traffic, which
compiles (or loads from the persistent cache) the prefill-chunk and decode
programs and, where the mix shares prefixes, fills the prefix cache.

The window is a closed loop of batches (see ``traffic/batches.py``) that
starts batches until ``--seconds`` have passed and ends when the last
returns; with ``--trace 1`` it is exactly one batch, under the profiler.
After it, the peak device memory is read, the engine is freed, and a
sample of the finished requests goes through the float32 reference
(``check.py``).  A traced run also holds its yardstick to account: a
per-layer metric of the cell that reads nothing, or a Pallas call that no
``bench/kernels/*.json`` signature names, is counted among the checks
(limit 0), so a program change that hides a kernel from the trace turns
``correct`` false instead of dropping the metric unseen.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COUNTERS = ("generated", "prefills", "scan_ticks", "ticks",
            "prefix_hit_pages", "prompt_pages", "prefill_traces",
            "decode_traces")
SPANS = ("decode_dispatch_s",)


class Refused(RuntimeError):
    """The run cannot measure here (no TPU, too few chips, interpret
    mode, a file missing): exit non-zero and print no result."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise Refused(f"missing module {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> Dict:
    if not path.is_file():
        raise Refused(f"missing file {path}")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    """Everything a run reads from files, by the names in BENCHMARK.json."""
    name: str
    spec: Dict          # the workloads entry
    cfg: Dict           # configuration file
    mix: Dict           # traffic mix file
    limits: Dict        # correctness limits
    metrics: List[Dict]  # end_to_end or per_layer entries for this cell

    @classmethod
    def load(cls, root: Path, workload: str, trace: bool) -> "Cell":
        bench = read_json(root / "BENCHMARK.json")
        spec = next((w for w in bench["workloads"] if w["name"] == workload),
                    None)
        if spec is None:
            raise Refused(f"no workload {workload!r} in BENCHMARK.json")
        entry = next(c for c in bench["configs"]
                     if c["name"] == spec["config"])
        kind = "per_layer" if trace else "end_to_end"
        metrics = [m for m in bench[kind]
                   if workload in m.get("workloads", [workload])]
        return cls(workload, spec, read_json(root / entry["file"]),
                   read_json(root / "bench" / "traffic"
                             / f"{spec['traffic']}.json"),
                   read_json(root / "bench" / "limits" / f"{workload}.json"),
                   metrics)


def require_tpu(chips: int) -> Dict:
    """The device summary, or ``Refused`` where JAX finds no TPU, fewer
    chips than the cell asks for, or Pallas would run in interpret mode."""
    import jax
    from repro.kernels.common import interpret_default

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX sees "
                      f"{len(devs)}")
    if interpret_default():
        raise Refused("Pallas would run in interpret mode")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def program_config(cfg: Dict):
    """The engine's ``ModelConfig`` for a configuration file: the program's
    own preset, with every size the file states written over it."""
    from repro.configs import get_config

    from bench.reference import reference_module

    try:
        fields = reference_module(cfg).program_fields(cfg)
    except ValueError as e:         # the program cannot compute it
        raise Refused(str(e)) from e
    return dataclasses.replace(
        get_config(cfg["program_arch"]), **fields,
        use_fused_kernels=True, dtype=cfg["torch_dtype"], quant="none")


def serve_setup(root: Path, cell: Cell, seed: int):
    """Set-up of a run: the served weights from ``seed``, the engine over
    the fused plan, the traffic generator, and the warm-up batch (which
    compiles or loads both programs and fills the prefix cache where the
    mix shares prefixes).  Returns ``(params, engine, gen)``."""
    import jax
    from repro.serving import ServingEngine

    from bench import weights

    t = [time.perf_counter()]

    def lap() -> float:
        t.append(time.perf_counter())
        return t[-1] - t[-2]

    pcfg = program_config(cell.cfg)
    mix = cell.mix
    params = weights.make_params(pcfg, seed, cell.cfg["initializer_range"])
    jax.block_until_ready(params)
    w_s = lap()
    engine = ServingEngine(pcfg, params, batch_slots=int(mix["slots"]),
                           max_len=int(mix["max_len"]))
    gen = load_module(root / "bench" / "traffic" / f"{mix['generator']}.py",
                      "bench_traffic_gen").make(mix, pcfg.vocab_size, seed)
    e_s = lap()
    engine.generate(gen.warmup(), int(mix["warmup_new_tokens"]))
    log(f"set-up: weights {w_s:.3f} s, engine {e_s:.3f} s, warm-up batch "
        f"{lap():.3f} s")
    return params, engine, gen


def percentile(values: List[float], q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between
    order statistics (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def registry_values(engine) -> Dict[str, float]:
    reg = engine.registry
    out = {k: float(reg[k].value()) for k in COUNTERS}
    for k in SPANS:
        out[k] = float(reg[k].sum())
        out[k + "_count"] = float(reg[k].count())
    return out


def judge(checks: Dict[str, Dict]) -> bool:
    """``correct``: every number compared is there and within its limit."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


def gap_check(gap: Optional[float], limits: Dict) -> Dict:
    """The logit-gap entry of a run's checks (None: nothing was read)."""
    return {"value": gap, "limit": float(limits["mean_logit_gap"])}


class Pauses:
    """Python's garbage-collector pauses while open (``gc.callbacks``): a
    diagnostic printed beside each run, since a pause stalls the engine's
    host loop."""

    def __init__(self):
        self.n, self.total, self.longest, self._t = 0, 0.0, 0.0, None

    def __call__(self, phase, _info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            dt = time.perf_counter() - self._t
            self.n, self.total = self.n + 1, self.total + dt
            self.longest = max(self.longest, dt)

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *_exc):
        gc.callbacks.remove(self)


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader gets."""
    cfg: Dict
    mix: Dict
    counters: Dict[str, float]          # registry deltas over the window
    window_s: float                     # host clock, first submit to return
    reqs: List                          # finished Request objects
    page_size: int
    chips: int
    peaks: Dict[str, float]
    trace: Optional[object] = None      # bench.trace.Trace, clipped
    window_ns: tuple = (0.0, 0.0)


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, *, check_device=require_tpu) -> Dict:
    """One run; returns the result object of the last stdout line."""
    cell = Cell.load(root, workload, trace)
    device = check_device(int(cell.spec["chips"]))

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    from bench import check, peaks

    log(f"{workload}: {device}; compile cache {enable_compile_cache()}; "
        f"{time.perf_counter() - t_start:.3f} s to the device")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    lowerings = [0]

    def on_event(name, _secs, **_kw):
        if name == LOWERING_EVENT:
            lowerings[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)

    mix = cell.mix
    params, engine, gen = serve_setup(root, cell, seed)
    before = registry_values(engine)
    low0 = lowerings[0]

    tmp = tempfile.TemporaryDirectory() if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp.name, profiler_options=opts)
    reqs: List = []
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    deadline = t0 + seconds
    i = 0
    with Pauses() as gcp:
        while i == 0 or not (trace or time.perf_counter() >= deadline):
            prompts = gen.batch()
            with jax.profiler.TraceAnnotation(f"bench batch {i}"):
                reqs += engine.generate(prompts, gen.new_tokens)
            i += 1
    t1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    window_s = t1 - t0
    after = registry_values(engine)
    counters = {k: after[k] - before[k] for k in after}
    lowered = lowerings[0] - low0
    longest = {k: engine.registry[k].percentile(1.0)      # the max
               for k in ("decode_dispatch_s", "chunk_latency_s")}
    log(f"window {t1 - t0:.3f} s, {i} batches; gc pauses {gcp.n}, "
        f"{gcp.total:.4f} s, longest {gcp.longest:.4f} s; longest engine "
        f"dispatch since start {longest}")
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    page_size = engine.kv.page_size
    del engine
    gc.collect()

    new = gen.new_tokens
    done = [r for r in reqs if not r.failed and len(r.out_tokens) == new]
    failed = len(reqs) - len(done)
    metrics: Dict[str, Dict] = {}
    yardstick: Dict[str, Dict] = {}
    if trace:
        from bench import trace as tr_mod
        import glob
        pb = glob.glob(f"{tmp.name}/**/*.xplane.pb", recursive=True)
        trc = tr_mod.load(pb[0])
        tmp.cleanup()
        lo, hi = _window_ns(trc)
        trc.ops = tr_mod.clip(trc.ops, lo, hi)
        trc.modules = tr_mod.clip(trc.modules, lo, hi)
        reading = Reading(cell.cfg, mix, counters, window_s, done, page_size,
                          int(cell.spec["chips"]), peaks.peaks(device["kind"]),
                          trc, (lo, hi))
        device["busy_s"] = tr_mod.busy_ns(trc.ops) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        unread = []
        for m in cell.metrics:
            mod = load_module(root / "bench" / "metrics" / f"{m['name']}.py",
                              "bench_metric_" + m["name"].replace(".", "_"))
            v = mod.read(reading)
            if v is None:
                unread.append(m["name"])
            else:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        unnamed = [e for e in trc.ops if tr_mod.op_kind(e.name)
                   == tr_mod.UNNAMED]
        for e in unnamed[:3]:
            log(f"a Pallas call no bench/kernels signature names: "
                f"{e.name[:600]}")
        if unread:
            log(f"per-layer metrics that read nothing: {unread}")
        yardstick = {
            "per_layer_unread": {"value": len(unread), "limit": 0},
            "unnamed_kernel_ops": {"value": len(unnamed), "limit": 0},
        }
        log("device time by program: " + ", ".join(
            f"{k} {v / 1e9:.6f} s" for k, v in
            sorted(_by_program(trc).items(), key=lambda kv: -kv[1])))
        breakdown = _breakdown(trc, lo, hi)
    else:
        e2e = end_to_end(done, window_s, setup_s)
        for m in cell.metrics:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
        breakdown = None

    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
    sample = check.sample(done, int(mix["check_requests"]), rng)
    gap = None                          # no finished request: not correct
    if sample:
        ref = check.Reference(cell.cfg, int(mix["max_len"]), new)
        try:
            gap = ref.program_gap(params, sample)
        except ValueError as e:         # a token outside the vocabulary
            log(f"check: {e}")
    checks = {
        "mean_logit_gap": gap_check(gap, cell.limits),
        "requests_failed": {"value": failed, "limit": 0},
        "lowerings_in_window": {"value": lowered, "limit": 0},
        "programs_traced_in_window": {
            "value": int(counters["prefill_traces"]
                         + counters["decode_traces"]), "limit": 0},
        **yardstick,
    }
    correct = judge(checks)
    out = {"correct": correct, "attempted": len(reqs),
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    for k, c in checks.items():
        log(f"check {k} {c['value']} limit {c['limit']}")
    return out


def end_to_end(reqs: List, window_s: float, setup_s: float) -> Dict:
    """The end-to-end metrics over every request of the window."""
    ttft = [r.first_token_at - r.submitted_at for r in reqs]
    tpot = [(r.finished_at - r.first_token_at) / (len(r.out_tokens) - 1)
            for r in reqs if len(r.out_tokens) > 1]
    return {
        "output_tok_per_s": sum(len(r.out_tokens) for r in reqs) / window_s,
        "ttft_p90_ms": 1e3 * percentile(ttft, 90),
        "tpot_p90_ms": 1e3 * percentile(tpot, 90) if tpot else math.nan,
        "setup_s": setup_s,
    }


def _window_ns(trc) -> tuple:
    """The traced window: from the first batch annotation's start to the
    last one's end."""
    ann = [e for e in trc.host if e.name.startswith("bench batch ")]
    if not ann:
        raise RuntimeError("the trace holds no batch annotation")
    return min(e.start for e in ann), max(e.end for e in ann)


def _by_program(trc) -> Dict[str, float]:
    """Device time (ns) of the window's program runs, by jitted program."""
    out: Dict[str, float] = {}
    for m in trc.modules:
        name = m.name.split("(", 1)[0]
        out[name] = out.get(name, 0.0) + m.dur
    return out


def _breakdown(trc, lo: float, hi: float) -> Dict:
    from bench import trace as tr_mod

    ops = sorted(tr_mod.time_by_kind(trc.ops).items(), key=lambda kv: -kv[1])
    idle = sorted(tr_mod.gaps(trc.ops, lo, hi), key=lambda g: g[0] - g[1])
    host = [e for e in trc.host if not e.name.startswith("bench batch ")]
    return {
        "device_ops": [[k, v / 1e9] for k, v in ops[:10]],
        "idle_gaps": [[tr_mod.host_label(host, a, b), (b - a) / 1e9]
                      for a, b in idle[:10]],
    }


def main(argv, t_start: float, root: Path) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")
    try:
        out = run(root, args.workload, args.seed, args.seconds,
                  bool(args.trace), t_start)
    except Refused as e:
        log(f"refused: {e}")
        return 2
    print(json.dumps(out), flush=True)
    return 0
