"""Chip smoke: serve qwen3-0.6b at its published widths on one TPU.

    python chip_smoke.py [--seed 0]            # one chip
    python chip_smoke.py --chips 4 [--seed 0]  # sharded serving on four

Drives the main path once, through the entry points a user calls:
``ServingEngine`` over the StreamPlan's fused Pallas kernels (chunked
prefill through the offset flash kernel, paged decode through
``paged_attention``, ``rmsnorm_matmul`` and ``streamed_ffn`` for the
projections), all 28 layers, random weights from ``--seed``.

One chip, three phases:

1. **correctness** (float32, ``default_matmul_precision("highest")``):
   a fused engine and an eager (``jax.numpy``) engine greedily extend a
   ~256- and a ~1,000-token prompt; teacher-forced logits of both paths
   over prompt + eager tokens must agree within ``TOL_F32``, and the
   fused tokens may leave the eager ones only at a step whose eager
   top-2 margin is within ``2 * TOL_F32``.
2. **serving** (bfloat16, engine defaults): 16 requests of 128-1024
   prompt tokens, 64 new tokens each, through 8 slots of 2048 tokens.
   Every request gets its 64 tokens, each dispatch program is traced
   once, and the plan dispatched the four fused stages.
3. **report**: the last stdout line is the JSON result.

``--chips 4`` runs only the sharded phase: the bf16 engine on a (1, 4)
('data', 'model') mesh, KV pools split over kv heads, against a
one-chip engine on the same prompts (tokens agree under ``TOL_BF16``'s
margin rule).

Exits non-zero, printing no result, when JAX finds no TPU or any phase
fails.  Timings printed on the way are smoke readings, not benchmarks.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Fused-vs-eager float32 logit tolerance.  Both paths compute in f32: XLA
# dots run at HIGHEST precision, and Mosaic lowers f32 Pallas dots to the
# MXU's f32 format.  They differ only in summation order (block-tiled
# reductions, the online-softmax rescale, the norm recomputed per tile),
# which costs O(sqrt(K) * 2^-24) ~ 1e-6 relative per contraction of depth
# K <= 3072.  Over 28 layers and a 1024-wide lm_head that stays near
# 1e-4 on logits of O(1) (the random init keeps them roughly unit
# normal).  2e-3 leaves an order of magnitude of headroom, and still sits
# an order of magnitude under the ~2e-2 a silently bf16 contraction
# would leave.
TOL_F32 = 2e-3
# Sharded-vs-one-chip bf16 tolerance for the token margin rule: the
# row-parallel FFN sums bf16 partials across shards in another order, a
# 2^-8 relative rounding per layer that can move O(1) logits by ~0.1.
TOL_BF16 = 0.1

ARCH = "qwen3-0.6b"
SERVE_REQUESTS, SERVE_NEW, SERVE_SLOTS, SERVE_MAX_LEN = 16, 64, 8, 2048
CHECK_PROMPTS, CHECK_NEW = (256, 1024), 64
LEN_RANGE = (128, 1024)
FUSED_STAGES = ("paged_attention", "flash_attention", "rmsnorm_matmul",
                "streamed_ffn")


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _prompts(cfg, lengths, rng):
    return [rng.integers(1, cfg.vocab_size, int(n), dtype=np.int32)
            for n in lengths]


def _logits_fn(cfg):
    """Teacher-forced logits [B, L, vocab] through the model's own entry
    point (fused or eager, per ``cfg.use_fused_kernels``)."""
    from repro.models import forward_hidden

    def fn(params, toks):
        h = forward_hidden(params, cfg, {"tokens": toks}, remat=False)
        head = params["lm_head"].astype(h.dtype)
        return (h @ head).astype(jnp.float32)[..., :cfg.vocab_size]
    return jax.jit(fn)


def _forced_rows(prompts, outs, block: int) -> np.ndarray:
    """Prompt + generated tokens (the last one is never an input), one row
    per request, zero-padded to a shared length that is a whole number of
    kernel blocks (causal: the pad never reaches earlier positions)."""
    seqs = [np.concatenate([p, np.asarray(o[:-1], np.int32)])
            for p, o in zip(prompts, outs)]
    width = _round_up(max(len(s) for s in seqs), block)
    rows = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        rows[i, :len(s)] = s
    return rows


def _top2_margin(logits: jax.Array) -> np.ndarray:
    top = jax.lax.top_k(logits, 2)[0]
    return np.asarray(top[..., 0] - top[..., 1])


def _first_divergence(prompt, ref, got, margin, tol: float):
    """(step, margin) of the first token where ``got`` leaves ``ref``, or
    None.  A divergence is a failure only where the reference's top-2
    margin exceeds ``2 * tol``: there a logit error within tolerance
    cannot have flipped the argmax."""
    for i, (a, b) in enumerate(zip(ref, got)):
        if a != b:
            m = float(margin[len(prompt) - 1 + i])
            check(m <= 2 * tol,
                  f"token {i} differs ({b} vs {a}) where the reference "
                  f"top-2 margin {m:.4g} exceeds 2*tol={2 * tol:g}")
            return i, m
    return None


def _generate(engine, prompts, new_tokens):
    reqs = engine.generate(prompts, max_new_tokens=new_tokens)
    for r in reqs:
        check(not r.failed, f"request {r.rid} failed: {r.error}")
        check(len(r.out_tokens) == new_tokens,
              f"request {r.rid} got {len(r.out_tokens)} of {new_tokens} "
              "tokens")
    return reqs


def correctness_phase(cfg, params, prompts, *, new_tokens: int,
                      max_len: int, tol: float = TOL_F32,
                      block: int = 128) -> dict:
    """Fused vs eager in float32: teacher-forced logits within ``tol`` and
    greedy tokens equal up to the margin rule."""
    from repro.serving import ServingEngine

    fused_cfg = dataclasses.replace(cfg, dtype="float32",
                                    use_fused_kernels=True)
    eager_cfg = dataclasses.replace(fused_cfg, use_fused_kernels=False)
    out = {}
    with jax.default_matmul_precision("highest"):
        toks = {}
        for name, c in (("fused", fused_cfg), ("eager", eager_cfg)):
            eng = ServingEngine(c, params, batch_slots=len(prompts),
                                max_len=max_len)
            t0 = time.perf_counter()
            toks[name] = [r.out_tokens
                          for r in _generate(eng, prompts, new_tokens)]
            log(f"{name} f32 engine generated in "
                f"{time.perf_counter() - t0:.1f}s")
            del eng
            gc.collect()
        rows = _forced_rows(prompts, toks["eager"], block)
        fused_logits, eager_logits = _logits_fn(fused_cfg), \
            _logits_fn(eager_cfg)
        max_err, divergences = 0.0, []
        for i, p in enumerate(prompts):
            n = len(p) + new_tokens - 1
            row = jnp.asarray(rows[i:i + 1])
            ref = eager_logits(params, row)
            err = float(jnp.max(jnp.abs(fused_logits(params, row) - ref)
                                [:, :n]))
            max_err = max(max_err, err)
            check(err <= tol, f"prompt {i} ({len(p)} tokens): max "
                  f"|fused - eager| logit {err:.4g} > tol {tol:g}")
            div = _first_divergence(p, toks["eager"][i], toks["fused"][i],
                                    _top2_margin(ref[0]), tol)
            divergences.append(div)
            del ref
    out.update(max_abs_logit_err=max_err, tol=tol,
               token_divergence=divergences)
    return out


def serving_phase(cfg, params, prompts, warmup, *, new_tokens: int,
                  slots: int, max_len: int) -> dict:
    """bf16 engine, engine defaults: every request served in full, one
    program per dispatch kind, the fused stages on the plan."""
    from repro.serving import ServingEngine

    t0 = time.perf_counter()
    eng = ServingEngine(cfg, params, batch_slots=slots, max_len=max_len)
    # Warm both dispatch programs on one request of its own, so the timed
    # run below is serving rather than compiling.
    _generate(eng, [warmup], 2)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    reqs = _generate(eng, prompts, new_tokens)
    wall_s = time.perf_counter() - t0
    m = eng.metrics
    check(m["prefill_traces"] == 1 and m["decode_traces"] == 1,
          f"programs traced more than once: prefill_traces="
          f"{m['prefill_traces']}, decode_traces={m['decode_traces']}")
    stages = set(eng.plan.summary()["stages"]["attn"].values())
    missing = [s for s in FUSED_STAGES if s not in stages]
    check(not missing, f"plan did not dispatch {missing}: {stages}")
    return {
        "requests": len(reqs), "new_tokens": new_tokens,
        "compile_and_warmup_s": compile_s, "serve_wall_s": wall_s,
        "tokens_per_s": sum(len(r.out_tokens) for r in reqs) / wall_s,
        "ttft_p50_s": float(np.median([r.ttft_s for r in reqs])),
        "stages": sorted(stages),
        "tokens": [r.out_tokens for r in reqs],
    }


def sharded_phase(cfg, params, prompts, *, new_tokens: int, slots: int,
                  max_len: int, mesh, tol: float = TOL_BF16,
                  block: int = 128) -> dict:
    """The bf16 engine on ``mesh`` against one chip: KV pools split over
    the model axis and greedy tokens equal up to the margin rule."""
    from repro.serving import ServingEngine

    one = ServingEngine(cfg, params, batch_slots=slots, max_len=max_len)
    ref = [r.out_tokens for r in _generate(one, prompts, new_tokens)]
    del one
    gc.collect()
    eng = ServingEngine(cfg, params, batch_slots=slots, max_len=max_len,
                        mesh=mesh)
    check(eng.kv.kv_shards == int(mesh.shape["model"]),
          f"kv_shards={eng.kv.kv_shards}, want {mesh.shape['model']}")
    got = [r.out_tokens for r in _generate(eng, prompts, new_tokens)]
    kv_shards = eng.kv.kv_shards
    del eng
    gc.collect()
    rows = _forced_rows(prompts, ref, block)
    logits = _logits_fn(cfg)
    divergences = []
    for i, p in enumerate(prompts):
        margin = _top2_margin(logits(params, jnp.asarray(rows[i:i + 1]))[0])
        divergences.append(_first_divergence(p, ref[i], got[i], margin,
                                             tol))
    return {"kv_shards": kv_shards, "tol": tol,
            "token_divergence": divergences,
            "identical": sum(d is None for d in divergences)}


def _device_summary():
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-serving phase")
    args = ap.parse_args(argv)

    dev = _device_summary()
    if dev["platform"] != "tpu":
        print(f"[smoke] no TPU: JAX runs on {dev['platform']}; "
              "there is no CPU fallback", file=sys.stderr)
        return 2
    from repro.kernels.common import interpret_default
    from repro.launch.compile_cache import enable_compile_cache
    if interpret_default():
        print("[smoke] Pallas would run in interpret mode", file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print(f"[smoke] --chips {args.chips} but JAX sees {dev['count']}",
              file=sys.stderr)
        return 2
    log(f"device {dev['kind']} x{dev['count']}; compile cache "
        f"{enable_compile_cache()}")

    from repro.configs import get_config
    from repro.models import init_params

    cfg = dataclasses.replace(get_config(ARCH), use_fused_kernels=True)
    rng = np.random.default_rng(args.seed)
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    results = {}
    try:
        if args.chips == 1:
            t0 = time.perf_counter()
            results["correctness"] = correctness_phase(
                cfg, params, _prompts(cfg, CHECK_PROMPTS, rng),
                new_tokens=CHECK_NEW, max_len=SERVE_MAX_LEN)
            log(f"correctness ok in {time.perf_counter() - t0:.1f}s: "
                f"{json.dumps(results['correctness'])}")
        # The served weights: bf16, cast once from the same seeded init.
        params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
        gc.collect()
        lengths = rng.integers(LEN_RANGE[0], LEN_RANGE[1] + 1,
                               SERVE_REQUESTS)
        prompts = _prompts(cfg, lengths, rng)
        if args.chips == 1:
            warmup = _prompts(cfg, LEN_RANGE[:1], rng)[0]
            r = serving_phase(cfg, params, prompts, warmup,
                              new_tokens=SERVE_NEW, slots=SERVE_SLOTS,
                              max_len=SERVE_MAX_LEN)
            r.pop("tokens")
            results["serving"] = r
            log("serving ok (smoke reading, not a benchmark): "
                + json.dumps(r))
        else:
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((1, args.chips), ("data", "model"))
            results["sharded"] = sharded_phase(
                cfg, params, prompts, new_tokens=SERVE_NEW,
                slots=SERVE_SLOTS, max_len=SERVE_MAX_LEN, mesh=mesh)
            log(f"sharded ok: {json.dumps(results['sharded'])}")
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr)
        return 1
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak device memory {stats.get('peak_bytes_in_use')} bytes "
        "(smoke reading)")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
