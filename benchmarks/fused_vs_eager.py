"""Eager vs StreamPlan-fused execution benchmark -> BENCH_fused.json.

Measures the three model entry points under both execution paths:

  * ``forward_train`` — streamed-CE loss latency (tokens/s),
  * ``prefill``       — prompt ingestion latency,
  * decode            — engine tokens/s through the continuous-batching
    block-decode fast path, CONTIGUOUS vs PAGED KV cache (tokens/s, peak
    cache bytes-in-use vs reserved, dispatch count).  The paged run is the
    engine default (page-table indirection + plan-selected Pallas paged
    decode attention under ``fused``); the contiguous run keeps the PR-1
    slots x max_len cache on the same scheduler for a like-for-like A/B.
  * prefill burst     — a mixed-length burst (>= 4 distinct prompt
    lengths) through a FRESH engine, CHUNKED prefill (one compiled
    program for the whole mix) vs the per-length-compile baseline:
    aggregate TTFT and the prefill compile count (the engine's
    trace-time probe).  The compile storm is the cost being measured, so
    no warmup run precedes the burst.
  * shared prefix     — the prefix-cache subsystem (DESIGN.md §10): a
    second request reusing a long cached prompt prefix vs the cold run
    on the same (pre-compiled) engine — TTFT, prefill chunk count,
    prefix hit rate, and the KV bytes NOT recomputed/restored; plus the
    bootstrap mode's decode-path first token for a fully cached prompt.
  * speculative       — self-speculative decoding (DESIGN.md §11):
    draft-then-verify vs the plain decode scan on REPETITIVE traffic
    (periodic prompts — the n-gram/prefix draft sources' home turf):
    accept rate, sequential model evaluations per generated token
    (plain = 1 scan tick per token; speculative = 1 verify dispatch per
    1..k+1 tokens), compiled verify-program count (the <=3-rung W
    ladder), tokens/s, and a greedy-token equality check.  The
    evaluations-per-token ratio is backend-independent; the tokens/s
    delta on CPU carries the interpret-mode caveat below.
  * sharded decode    — the mesh-aware StreamPlan (DESIGN.md §9): the
    fused engine on a (2, 4) ('data', 'model') mesh vs single-device,
    tokens/s plus KV bytes PER SHARD (the pools split over kv_heads) and
    a greedy-token equality check.  Needs >= 8 (forced) devices — run
    under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
    ``sharded`` job does); skipped gracefully otherwise.
  * quantized         — quantized serving (DESIGN.md §14): the same
    engine under ``quant=kv_int8 / kv_fp8 / w8_kv8`` vs ``none`` — KV
    bytes per token, pages per slot, effective KV itemsize, plus the
    accuracy gate's max-logit-error and greedy-vs-f32 equality per mode.
  * latency distribution — the telemetry subsystem (DESIGN.md §17): a
    mixed chunked+speculative burst with the event recorder ON vs OFF —
    TTFT/TPOT/queue-wait p50/p90/p99 from the windowed metric snapshot,
    the recorded event count, the wall-clock overhead ratio (telemetry
    must stay under a few percent) and a greedy-token equality check
    (telemetry is a pure observer).

``interpret_mode`` is reported ONCE at the report's top level (every
fused number in the file shares the same backend).

Run on CPU the Pallas kernels execute in *interpret mode* (the kernel body
runs in Python per grid step), so fused numbers here validate the dispatch
plumbing and measure the perf *trajectory*, not the TPU speedup — on TPU
the same plan dispatches compiled MXU kernels.  Every fused result embeds
``interpret_mode`` so a fused-slower-than-eager row on CPU is read as the
interpreter tax, not a kernel regression; the decode section's cache-bytes
numbers are backend-independent.

    PYTHONPATH=src python benchmarks/fused_vs_eager.py [--quick] \
        [--out BENCH_fused.json]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.kernels.common import interpret_default
from repro.launch.compile_cache import enable_compile_cache
from repro.models import (forward_train, init_params, prefill, resolve_plan,
                          supports_chunked_prefill, supports_speculative)
from repro.serving import ServingEngine
from repro.serving.accuracy import run_accuracy, supports_quantized_serving

ARCHS = ("gpt2", "llama3-8b")        # layernorm/GELU-MLP and RMSNorm/SwiGLU-GQA


def _timed(fn: Callable[[], Any], iters: int) -> float:
    """Median wall-clock seconds over ``iters`` runs (post-warmup)."""
    jax.block_until_ready(fn())                  # compile + warm caches
    jax.block_until_ready(fn())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def bench_sharded_decode(base, *, batch: int, max_len: int,
                         decode_block: int, new_tokens: int) -> Dict[str, Any]:
    """Sharded vs single-device fused decode through the serving engine.

    Uses a head layout whose kv_heads divide the 4-way model axis (the
    reduced configs' GQA ratio often doesn't) so the KV pools actually
    split; reports per-shard KV bytes — the number that scales capacity.
    """
    if len(jax.devices()) < 8:
        return {"skipped": "needs 8 (forced) host devices — run under "
                           "XLA_FLAGS=--xla_force_host_platform_device_"
                           "count=8"}
    from repro.launch.mesh import make_mesh
    cfg = dataclasses.replace(base, use_fused_kernels=True, num_heads=8,
                              num_kv_heads=4, head_dim=8)
    params = init_params(jax.random.PRNGKey(2), cfg)
    nprng = np.random.default_rng(5)
    prompts = [nprng.integers(1, cfg.vocab_size, n, dtype=np.int32)
               for n in (max_len // 2, max_len // 4)][:batch]
    out: Dict[str, Any] = {}
    tokens = {}
    for name, mesh in (("single", None),
                       ("sharded", make_mesh((2, 4), ("data", "model")))):
        eng = ServingEngine(cfg, params, batch_slots=batch, max_len=max_len,
                            decode_block=decode_block, mesh=mesh,
                            prefix_cache=False)      # measure cold prefill
        eng.generate(prompts, max_new_tokens=2)      # compile
        t0 = time.perf_counter()
        reqs = eng.generate(prompts, max_new_tokens=new_tokens)
        wall = time.perf_counter() - t0
        generated = sum(len(r.out_tokens) for r in reqs)
        tokens[name] = [r.out_tokens for r in reqs]
        out[name] = {
            "decode_s": wall,
            "decode_tokens_per_s": generated / wall,
            "kv_shards": eng.metrics["kv_shards"],
            "kv_bytes_peak": eng.metrics["kv_bytes_peak"],
            "kv_bytes_peak_per_shard": eng.kv.peak_bytes_per_shard,
        }
        if mesh is not None:
            out[name]["plan_sharding"] = eng.plan.summary()["sharding"]
    out["tokens_equal"] = tokens["single"] == tokens["sharded"]
    return out


def bench_quantized(base, params, *, max_len: int, decode_block: int,
                    new_tokens: int) -> Dict[str, Any]:
    """Quantized serving (DESIGN.md §14): kv_int8/kv_fp8/w8_kv8 vs none.

    Same engine, same prompts, one run per mode: KV bytes per token and
    pages per slot (the capacity numbers halving the page itemsize
    buys), the effective KV itemsize (codes + f32 scale rows), and —
    from the teacher-forced accuracy harness — max logit error vs f32
    and greedy-token equality per mode.
    """
    if not supports_quantized_serving(base):
        return {"skipped": f"{base.name}: no paged attention KV "
                           "(quantized pages ride on it)"}
    modes = ("kv_int8", "kv_fp8", "w8_kv8")
    acc = run_accuracy(base, modes=modes, steps=6)
    nprng = np.random.default_rng(33)
    prompts = [nprng.integers(1, base.vocab_size, n, dtype=np.int32)
               for n in (max_len // 2, max_len // 4)]
    out: Dict[str, Any] = {}
    for quant in ("none",) + modes:
        eng = ServingEngine(base, params, batch_slots=len(prompts),
                            max_len=max_len, decode_block=decode_block,
                            quant=quant, prefix_cache=False)
        eng.generate([p.copy() for p in prompts],
                     max_new_tokens=2)               # absorb compiles
        t0 = time.perf_counter()
        reqs = eng.generate([p.copy() for p in prompts],
                            max_new_tokens=new_tokens)
        wall = time.perf_counter() - t0
        generated = sum(len(r.out_tokens) for r in reqs)
        cached = sum(len(p) for p in prompts) + generated
        peak = eng.metrics["kv_bytes_peak"]
        row: Dict[str, Any] = {
            "decode_tokens_per_s": generated / wall,
            "kv_bytes_peak": int(peak),
            "kv_bytes_per_token": peak / cached,
            "pages_per_slot": (peak / eng.kv.page_bytes) / len(prompts),
            "kv_itemsize_effective":
                eng.metrics["kv_itemsize_effective"],
        }
        if quant != "none":
            row["max_logit_err"] = acc[quant]["max_logit_err"]
            row["tokens_equal_f32"] = bool(acc[quant]["tokens_equal"])
        out[quant] = row
    out["kv_int8_over_none_bytes"] = (
        out["kv_int8"]["kv_bytes_peak"]
        / max(out["none"]["kv_bytes_peak"], 1))
    return out


def bench_prefix_serving(base, params, *, max_len: int,
                         decode_block: int) -> Dict[str, Any]:
    """Hot-prefix vs cold serving TTFT through the prefix cache.

    One engine serves three waves: a token-distinct warmup (absorbs the
    chunk/decode compiles and shares nothing), a COLD request, then a HOT
    request reusing the cold one's long prefix — so the TTFT delta is
    pure prefill work, not compile noise.  KV bytes saved = pages claimed
    instead of recomputed-and-restored, times the page byte size.  A
    second engine measures ``prefix_bootstrap`` on a fully cached prompt
    (first token through the decode path alone).
    """
    if not supports_chunked_prefill(base):
        return {"skipped": f"{base.name}: no chunked prefill "
                           "(prefix cache rides on it)"}
    # Fine stream granules so the shared prefix spans many chunks (the
    # eager default chunk of 4 pages x 16 would swallow it whole), and a
    # page-aligned prompt so the bootstrap leg gets a full hit.
    ps, chunk, pairs = 8, 16, 3
    nprng = np.random.default_rng(21)
    plen = (3 * max_len // 4) // ps * ps
    prefix_len = plen - ps

    def mk(prefix, tail_seed):
        tail = np.random.default_rng(tail_seed).integers(
            1, base.vocab_size, ps, dtype=np.int32)
        return np.concatenate([prefix, tail]).astype(np.int32)

    warmup = nprng.integers(1, base.vocab_size, plen, dtype=np.int32)
    eng = ServingEngine(base, params, batch_slots=2, max_len=max_len,
                        decode_block=decode_block, page_size=ps,
                        prefill_chunk=chunk)
    eng.generate([warmup], max_new_tokens=2)       # absorb the compiles
    ttft_cold, ttft_hot, chunks = [], [], []
    for i in range(pairs):                         # fresh prefix per pair
        prefix = nprng.integers(1, base.vocab_size, prefix_len,
                                dtype=np.int32)
        c0 = eng.metrics["prefill_chunks"]
        cold = eng.generate([mk(prefix, 2 * i)], max_new_tokens=4)[0]
        c1 = eng.metrics["prefill_chunks"]
        hot = eng.generate([mk(prefix, 2 * i + 1)], max_new_tokens=4)[0]
        c2 = eng.metrics["prefill_chunks"]
        ttft_cold.append(cold.ttft_s)
        ttft_hot.append(hot.ttft_s)
        chunks.append((c1 - c0, c2 - c1))
    tc, th = float(np.median(ttft_cold)), float(np.median(ttft_hot))
    out: Dict[str, Any] = {
        "prompt_len": plen,
        "shared_prefix_len": prefix_len,
        "ttft_cold_s": tc,
        "ttft_hot_s": th,
        "hot_over_cold_ttft": th / max(tc, 1e-9),
        "prefill_chunks_cold": chunks[-1][0],
        "prefill_chunks_hot": chunks[-1][1],
        "prefix_hit_rate": eng.metrics["prefix_hit_rate"],
        "prefix_hit_pages": int(eng.metrics["prefix_hit_pages"]),
        "kv_bytes_saved": int(eng.metrics["prefix_hit_pages"]
                              * eng.kv.page_bytes),
        "kv_bytes_cached": int(eng.metrics["kv_bytes_cached"]),
        "kv_itemsize_effective": eng.metrics["kv_itemsize_effective"],
    }
    boot = ServingEngine(base, params, batch_slots=2, max_len=max_len,
                         decode_block=decode_block, page_size=ps,
                         prefill_chunk=chunk, prefix_bootstrap=True)
    boot.generate([warmup], max_new_tokens=2)        # compile
    cached_p = mk(warmup[:prefix_len], 99)
    boot.generate([cached_p], max_new_tokens=4)      # cache the prompt
    tts = []
    for _ in range(pairs):                           # fully cached replays
        tts.append(boot.generate([cached_p],
                                 max_new_tokens=4)[0].ttft_s)
    out["ttft_bootstrap_s"] = float(np.median(tts))
    out["bootstraps"] = int(boot.metrics["prefix_bootstraps"])
    out["cow_copies"] = int(boot.metrics["cow_copies"])
    return out


def bench_speculative(base, params, *, max_len: int, decode_block: int,
                      new_tokens: int) -> Dict[str, Any]:
    """Speculative vs plain decode on repetitive ("agentic") traffic.

    The comparison that matters is SEQUENTIAL MODEL EVALUATIONS per
    generated token — the quantity a real accelerator's decode latency
    scales with.  Plain decode pays one scan tick per token PER SLOT
    (``scan_ticks / generated``; batching amortizes a tick over the
    slots, so the value sits below 1 with several slots active);
    speculative decode pays one verify dispatch per 1..draft_len+1
    tokens per slot (``verify_dispatches / spec_tokens``).  Both count
    sequential steps over tokens delivered across the whole batch, so
    the ratio is like-for-like.  Both engines run the same prompts and
    the greedy tokens must be identical — speculation is a pure perf
    knob.
    """
    if not supports_speculative(base):
        return {"skipped": f"{base.name}: no speculative decoding "
                           "(recurrent state cannot roll back)"}
    cfg = dataclasses.replace(base, use_fused_kernels=True)
    # Periodic prompts: random-weight reduced models collapse onto
    # repeating cycles on these, so n-gram prompt-lookup drafting fires
    # the way it does on real looping/agentic traffic.
    periods = ((1, 2, 3, 4), (7, 8, 9), (5, 6))
    prompts = [np.array((p * max_len)[:max_len // 3], np.int32)
               for p in periods]
    out: Dict[str, Any] = {}
    tokens = {}
    for name in ("plain", "speculative"):
        eng = ServingEngine(cfg, params, batch_slots=2, max_len=max_len,
                            decode_block=decode_block,
                            speculative=(name == "speculative"),
                            draft_len=4)
        eng.generate([p.copy() for p in prompts],
                     max_new_tokens=2)               # absorb compiles
        m0 = dict(eng.metrics)
        t0 = time.perf_counter()
        reqs = eng.generate([p.copy() for p in prompts],
                            max_new_tokens=new_tokens)
        wall = time.perf_counter() - t0
        generated = sum(len(r.out_tokens) for r in reqs)
        tokens[name] = [r.out_tokens for r in reqs]
        row: Dict[str, Any] = {
            "decode_s": wall,
            "decode_tokens_per_s": generated / wall,
            "generated": generated,
        }
        if name == "speculative":
            spec = eng.metrics["spec_tokens"] - m0["spec_tokens"]
            disp = (eng.metrics["verify_dispatches"]
                    - m0["verify_dispatches"])
            drafted = eng.metrics["draft_tokens"] - m0["draft_tokens"]
            accepted = (eng.metrics["accepted_tokens"]
                        - m0["accepted_tokens"])
            row.update({
                "evals_per_token": disp / max(spec, 1),
                "accept_rate": accepted / max(drafted, 1),
                "draft_tokens": drafted,
                "accepted_tokens": accepted,
                "rollback_pages": int(eng.metrics["rollback_pages"]
                                      - m0["rollback_pages"]),
                # Programs built across BOTH runs: the ladder cap, not a
                # per-run delta.
                "verify_compiles": int(eng.metrics["verify_traces"]),
            })
        else:
            ticks = eng.metrics["scan_ticks"] - m0["scan_ticks"]
            gen = eng.metrics["generated"] - m0["generated"]
            row["evals_per_token"] = ticks / max(gen, 1)
        out[name] = row
    out["tokens_equal"] = tokens["plain"] == tokens["speculative"]
    out["plain_over_speculative_evals"] = (
        out["plain"]["evals_per_token"]
        / max(out["speculative"]["evals_per_token"], 1e-9))
    if interpret_default():
        out["note"] = ("CPU interpret mode: tokens/s measures dispatch "
                       "plumbing; the evals-per-token ratio is the "
                       "backend-independent speculative win.")
    return out


def bench_autotune(base, params, *, max_len: int, decode_block: int,
                   new_tokens: int) -> Dict[str, Any]:
    """Autotuned vs analytic serving (DESIGN.md §16).

    Three engines over identical prompts: the analytic baseline, a COLD
    autotuned start (tunes every fused stage, persists the table), and a
    WARM start against the same table — which must perform zero
    measurement dispatches and resolve a bit-identical plan.  Records
    tuned-vs-analytic decode tokens/s and TTFT, the candidate/pruned/
    measured counters, and the plan provenance.  Deviceless runs score
    candidates with the analytic surrogate, so the deltas are noise —
    the section's value there is exercising the whole tune/persist/
    reload pipeline on every benchmark run.
    """
    import tempfile

    from repro.core.stream_plan import plan_for

    nprng = np.random.default_rng(47)
    prompts = [nprng.integers(1, base.vocab_size, n, dtype=np.int32)
               for n in (max_len // 2, max_len // 4)]

    def serve(**engine_kw) -> Dict[str, Any]:
        eng = ServingEngine(base, params, batch_slots=len(prompts),
                            max_len=max_len, decode_block=decode_block,
                            prefix_cache=False, **engine_kw)
        eng.generate([p.copy() for p in prompts],
                     max_new_tokens=2)               # absorb compiles
        t0 = time.perf_counter()
        reqs = eng.generate([p.copy() for p in prompts],
                            max_new_tokens=new_tokens)
        wall = time.perf_counter() - t0
        generated = sum(len(r.out_tokens) for r in reqs)
        return {
            "engine": eng,
            "tokens": [r.out_tokens for r in reqs],
            "decode_tokens_per_s": generated / wall,
            "ttft_s": float(np.nanmean([r.ttft_s for r in reqs])),
        }

    with tempfile.TemporaryDirectory(prefix="repro_tune_") as d:
        plan_for.cache_clear()
        analytic = serve()
        plan_for.cache_clear()
        cold = serve(autotune=d)
        plan_for.cache_clear()
        warm = serve(autotune=d)
        e_cold, e_warm = cold["engine"], warm["engine"]
        out: Dict[str, Any] = {
            "analytic": {k: v for k, v in analytic.items()
                         if k in ("decode_tokens_per_s", "ttft_s")},
            "tuned_cold": {
                "decode_tokens_per_s": cold["decode_tokens_per_s"],
                "ttft_s": cold["ttft_s"],
                "candidates": e_cold.tuner.stats.candidates,
                "pruned_by_lint": e_cold.tuner.stats.pruned,
                "measured": e_cold.tuner.stats.measured,
                "stages_tuned": e_cold.tuner.stats.stages,
                "table_entries": e_cold.metrics["tune_entries"],
            },
            "tuned_warm": {
                "decode_tokens_per_s": warm["decode_tokens_per_s"],
                "ttft_s": warm["ttft_s"],
                "measured": e_warm.tuner.stats.measured,
                "table_hits": e_warm.metrics["tune_hits"],
            },
            "plan_source": e_warm.metrics["plan_source"],
            "plans_identical": e_cold.plan == e_warm.plan,
            "tokens_equal_analytic":
                cold["tokens"] == analytic["tokens"] == warm["tokens"],
            "tuned_over_analytic_decode":
                warm["decode_tokens_per_s"]
                / max(analytic["decode_tokens_per_s"], 1e-9),
        }
    plan_for.cache_clear()       # drop tuned plans from the shared cache
    return out


def bench_latency_distribution(base, params, *, max_len: int,
                               decode_block: int,
                               new_tokens: int) -> Dict[str, Any]:
    """Telemetry on vs off on a mixed chunked+speculative burst
    (DESIGN.md §17).

    Two engines over the same mixed-length repetitive burst: one with
    the observability subsystem recording the full event stream, one
    with the no-op recorder.  Both are warmed first so the walls
    compare steady-state dispatch loops, not compiles.  Records the
    TTFT/TPOT/queue-wait percentile fields from the windowed snapshot
    (``snapshot("last_generate")`` — the measured burst only), the
    event count, the median-of-3 wall-clock overhead ratio, and a
    greedy-token equality check: telemetry must be a pure observer.
    """
    if not (supports_chunked_prefill(base) and supports_speculative(base)):
        return {"skipped": f"{base.name}: needs chunked prefill and "
                           "speculative decoding"}
    cfg = dataclasses.replace(base, use_fused_kernels=True)
    periods = ((1, 2, 3, 4), (7, 8, 9), (5, 6), (2, 9))
    prompts = [np.array((p * max_len)[:n], np.int32)
               for p, n in zip(periods, (max_len // 3, max_len // 6,
                                         max_len // 2, max_len // 4))]

    def serve(telemetry: bool) -> Dict[str, Any]:
        eng = ServingEngine(cfg, params, batch_slots=2, max_len=max_len,
                            decode_block=decode_block, chunked=True,
                            prefill_chunk=max(8, max_len // 8),
                            speculative=True, draft_len=4,
                            telemetry=telemetry)
        eng.generate([p.copy() for p in prompts],
                     max_new_tokens=2)               # absorb compiles
        walls = []
        for _ in range(3):
            t0 = time.perf_counter()
            reqs = eng.generate([p.copy() for p in prompts],
                                max_new_tokens=new_tokens)
            walls.append(time.perf_counter() - t0)
        return {"engine": eng, "wall_s": float(np.median(walls)),
                "tokens": [r.out_tokens for r in reqs]}

    on, off = serve(True), serve(False)
    eng = on["engine"]
    snap = eng.snapshot("last_generate")             # the last burst only
    out: Dict[str, Any] = {
        "wall_on_s": on["wall_s"],
        "wall_off_s": off["wall_s"],
        "overhead_ratio": on["wall_s"] / max(off["wall_s"], 1e-9),
        "tokens_equal": on["tokens"] == off["tokens"],
        "events": len(eng.obs.events),
    }
    for h in ("ttft_s", "tpot_s", "queue_wait_s"):
        out[h] = {k: snap[f"{h}_{k}"]
                  for k in ("count", "mean", "p50", "p90", "p99")}
    return out


def bench_config(arch: str, *, quick: bool) -> Dict[str, Any]:
    batch, seq = (2, 64) if quick else (2, 128)
    iters = 3 if quick else 7
    new_tokens = 16 if quick else 32
    decode_block = 8
    max_len = seq + new_tokens + decode_block

    base = dataclasses.replace(get_config(arch).reduced(), dtype="float32")
    rng = jax.random.PRNGKey(0)
    params = init_params(rng, base)
    toks = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                              base.vocab_size)
    train_batch = {"tokens": toks, "labels": toks}
    # Heterogeneous prompt lengths: the continuous engine places each at
    # its own offset, and the paged cache only allocates the pages each
    # one actually needs (the contiguous cache reserves max_len for both).
    prompts = [np.asarray(toks[i][:seq if i % 2 == 0 else seq // 2])
               for i in range(batch)]

    result: Dict[str, Any] = {
        "arch": base.name, "batch": batch, "seq": seq,
        "new_tokens": new_tokens, "decode_block": decode_block,
    }
    fused_cfg = dataclasses.replace(base, use_fused_kernels=True)
    plan = resolve_plan(fused_cfg, batch * seq)
    # Static verification (DESIGN.md §15): BENCH_fused.json records
    # whether the plan it benchmarked passed the stream verifier.
    from repro.analysis import errors as _diag_errors, verify_plan
    diags = verify_plan(plan, fused_cfg)
    plan = plan.with_verification(not _diag_errors(diags),
                                  tuple(str(d) for d in diags))
    result["plan"] = plan.summary()

    losses = {}
    for mode in ("eager", "fused"):
        cfg = dataclasses.replace(base, use_fused_kernels=(mode == "fused"))
        train_fn = jax.jit(lambda p, b: forward_train(p, cfg, b))
        prefill_fn = jax.jit(lambda p, b: prefill(p, cfg, b))

        train_s = _timed(lambda: train_fn(params, train_batch), iters)
        prefill_s = _timed(lambda: prefill_fn(params, train_batch)[0], iters)
        losses[mode] = float(train_fn(params, train_batch))

        decode: Dict[str, Any] = {}
        for paged in (False, True):
            # prefix_cache off: the warmup generate would otherwise cache
            # these prompts and make the measured run prefill-hot — the
            # prefix win is measured in its own section below.
            engine = ServingEngine(cfg, params, batch_slots=batch,
                                   max_len=max_len,
                                   decode_block=decode_block, paged=paged,
                                   prefix_cache=False)
            engine.generate(prompts, max_new_tokens=2)  # compile
            d0 = engine.metrics["dispatches"]
            g0 = engine.metrics["generated"]
            t0 = time.perf_counter()
            reqs = engine.generate(prompts, max_new_tokens=new_tokens)
            decode_s = time.perf_counter() - t0
            generated = sum(len(r.out_tokens) for r in reqs)
            decode["paged" if paged else "contiguous"] = {
                "decode_s": decode_s,
                "decode_tokens_per_s": generated / decode_s,
                "ttft_s": float(np.mean([r.ttft_s for r in reqs])),
                "dispatches": engine.metrics["dispatches"] - d0,
                "generated": engine.metrics["generated"] - g0,
                "kv_bytes_reserved": engine.metrics["kv_bytes_reserved"],
                "kv_bytes_peak": engine.metrics["kv_bytes_peak"],
                "kv_itemsize_effective":
                    engine.metrics["kv_itemsize_effective"],
                "page_size": engine.metrics["page_size"],
            }
        decode["paged_over_contiguous_bytes"] = (
            decode["paged"]["kv_bytes_peak"]
            / max(decode["contiguous"]["kv_bytes_peak"], 1))

        # Mixed-length prefill burst: chunked (one program) vs per-length
        # (one program per distinct length).  Fresh engines, no warmup —
        # compile latency IS the number under test.  Archs outside the
        # chunked gate (SSM/RWKV/mrope) skip the section rather than
        # crash the report.
        burst_lens = sorted({max(4, seq // 4), seq // 2,
                             max(8, 3 * seq // 4), seq})
        nprng = np.random.default_rng(12)
        burst_prompts = [nprng.integers(1, base.vocab_size, n,
                                        dtype=np.int32)
                         for n in burst_lens]
        burst: Dict[str, Any] = {"lengths": burst_lens}
        modes = ((("chunked", True),) if supports_chunked_prefill(base)
                 else ()) + (("per_length", False),)
        for bname, chunk_mode in modes:
            eng = ServingEngine(cfg, params, batch_slots=batch,
                                max_len=max_len,
                                decode_block=decode_block,
                                chunked=chunk_mode)
            t0 = time.perf_counter()
            breqs = eng.generate(burst_prompts, max_new_tokens=4)
            wall = time.perf_counter() - t0
            ttfts = [r.ttft_s for r in breqs]
            burst[bname] = {
                "wall_s": wall,
                "ttft_mean_s": float(np.nanmean(ttfts)),
                "ttft_max_s": float(np.nanmax(ttfts)),
                "prefill_compiles": int(eng.metrics["prefill_traces"]),
                "prefill_chunks": int(eng.metrics["prefill_chunks"]),
                "prefill_chunk": int(eng.metrics["prefill_chunk"]),
            }
        if "chunked" in burst:
            burst["chunked_over_per_length_ttft"] = (
                burst["chunked"]["ttft_mean_s"]
                / max(burst["per_length"]["ttft_mean_s"], 1e-9))

        result[mode] = {
            "prefill_burst": burst,
            "train_s": train_s,
            "train_tokens_per_s": batch * seq / train_s,
            "prefill_s": prefill_s,
            "prefill_tokens_per_s": batch * seq / prefill_s,
            # Headline decode numbers come from the engine default (paged).
            "decode_s": decode["paged"]["decode_s"],
            "decode_tokens_per_s": decode["paged"]["decode_tokens_per_s"],
            "ttft_s": decode["paged"]["ttft_s"],
            "decode_dispatches": decode["paged"]["dispatches"],
            "decode": decode,
        }
        if mode == "fused":
            if interpret_default():
                result[mode]["note"] = (
                    "Pallas kernels ran in interpret mode (no TPU): "
                    "fused-slower-than-eager here is interpreter tax, "
                    "not a kernel regression.")
    result["loss_abs_diff"] = abs(losses["eager"] - losses["fused"])
    result["fused_over_eager_train"] = (result["fused"]["train_s"]
                                        / result["eager"]["train_s"])
    result["prefix_serving"] = bench_prefix_serving(
        base, params, max_len=max_len, decode_block=decode_block)
    result["speculative"] = bench_speculative(
        base, params, max_len=max_len, decode_block=decode_block,
        new_tokens=new_tokens)
    result["sharded_decode"] = bench_sharded_decode(
        base, batch=batch, max_len=max_len, decode_block=decode_block,
        new_tokens=new_tokens)
    result["quantized"] = bench_quantized(
        base, params, max_len=max_len, decode_block=decode_block,
        new_tokens=new_tokens)
    result["autotune"] = bench_autotune(
        fused_cfg, params, max_len=max_len, decode_block=decode_block,
        new_tokens=new_tokens)
    result["latency_distribution"] = bench_latency_distribution(
        base, params, max_len=max_len, decode_block=decode_block,
        new_tokens=new_tokens)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI mode: smaller shapes, fewer iterations")
    ap.add_argument("--out", default="BENCH_fused.json")
    ap.add_argument("--archs", default=",".join(ARCHS))
    args = ap.parse_args(argv)
    enable_compile_cache()

    report: Dict[str, Any] = {
        "backend": jax.default_backend(),
        # ONE top-level flag: every fused number below shares the same
        # backend, so per-section copies only invited drift.
        "interpret_mode": interpret_default(),
        "quick": args.quick,
        "configs": [],
    }
    for arch in args.archs.split(","):
        t0 = time.perf_counter()
        r = bench_config(arch, quick=args.quick)
        r["bench_seconds"] = time.perf_counter() - t0
        report["configs"].append(r)
        e, f = r["eager"], r["fused"]
        dc = e["decode"]
        pb = e["prefill_burst"]
        if "chunked" in pb:
            burst_note = (
                f"burst ttft {pb['chunked']['ttft_mean_s']*1e3:.0f}ms "
                f"({pb['chunked']['prefill_compiles']} compile) vs "
                f"{pb['per_length']['ttft_mean_s']*1e3:.0f}ms "
                f"({pb['per_length']['prefill_compiles']} compiles)")
        else:
            burst_note = (
                f"burst ttft {pb['per_length']['ttft_mean_s']*1e3:.0f}ms "
                f"({pb['per_length']['prefill_compiles']} compiles, "
                "no chunked support)")
        px = r["prefix_serving"]
        if "skipped" in px:
            prefix_note = "prefix serving skipped"
        else:
            prefix_note = (
                f"prefix ttft {px['ttft_hot_s']*1e3:.0f}ms hot / "
                f"{px['ttft_cold_s']*1e3:.0f}ms cold "
                f"(hit rate {px['prefix_hit_rate']:.2f}, "
                f"{px['kv_bytes_saved']} B saved, "
                f"bootstrap {px['ttft_bootstrap_s']*1e3:.0f}ms)")
        sp = r["speculative"]
        if "skipped" in sp:
            spec_note = "speculative skipped"
        else:
            spec_note = (
                f"spec {sp['speculative']['evals_per_token']:.2f} vs "
                f"{sp['plain']['evals_per_token']:.2f} evals/tok "
                f"(x{sp['plain_over_speculative_evals']:.1f}, accept "
                f"{sp['speculative']['accept_rate']:.2f}, "
                f"{sp['speculative']['verify_compiles']} verify "
                f"compiles, tokens_equal={sp['tokens_equal']})")
        sd = r["sharded_decode"]
        if "skipped" in sd:
            shard_note = "sharded decode skipped (<8 devices)"
        else:
            shard_note = (
                f"sharded {sd['sharded']['decode_tokens_per_s']:.1f} tok/s "
                f"x{sd['sharded']['kv_shards']} shards "
                f"({sd['sharded']['kv_bytes_peak_per_shard']} B/shard, "
                f"tokens_equal={sd['tokens_equal']})")
        qz = r["quantized"]
        if "skipped" in qz:
            quant_note = "quantized skipped"
        else:
            q8 = qz["kv_int8"]
            quant_note = (
                f"kv_int8 {q8['kv_bytes_per_token']:.0f} B/tok "
                f"(x{qz['kv_int8_over_none_bytes']:.2f} bytes, itemsize "
                f"{q8['kv_itemsize_effective']:.2f}B, max|dlogit| "
                f"{q8['max_logit_err']:.3g}, "
                f"tokens_equal={q8['tokens_equal_f32']})")
        at = r["autotune"]
        tune_note = (
            f"autotune x{at['tuned_over_analytic_decode']:.2f} decode "
            f"({at['tuned_cold']['candidates']} cands, "
            f"{at['tuned_cold']['pruned_by_lint']} pruned, warm "
            f"measured={at['tuned_warm']['measured']}, "
            f"identical={at['plans_identical']})")
        ld = r["latency_distribution"]
        if "skipped" in ld:
            lat_note = "latency distribution skipped"
        else:
            lat_note = (
                f"telemetry overhead x{ld['overhead_ratio']:.3f} "
                f"({ld['events']} events, ttft p50/p90/p99 "
                f"{ld['ttft_s']['p50']*1e3:.0f}/"
                f"{ld['ttft_s']['p90']*1e3:.0f}/"
                f"{ld['ttft_s']['p99']*1e3:.0f}ms, "
                f"tokens_equal={ld['tokens_equal']})")
        print(f"{r['arch']}: train {e['train_s']*1e3:.1f}ms eager / "
              f"{f['train_s']*1e3:.1f}ms fused | decode "
              f"{e['decode_tokens_per_s']:.1f} vs "
              f"{f['decode_tokens_per_s']:.1f} tok/s | "
              f"kv peak {dc['paged']['kv_bytes_peak']} paged / "
              f"{dc['contiguous']['kv_bytes_peak']} contiguous bytes | "
              f"{burst_note} | {prefix_note} | {spec_note} | "
              f"{shard_note} | {quant_note} | {tune_note} | "
              f"{lat_note} | loss diff {r['loss_abs_diff']:.2e}",
              flush=True)

    with open(args.out, "w") as fh:
        json.dump(report, fh, indent=2)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
