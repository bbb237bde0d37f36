"""Measurement harness — wall-clock candidate timing, analytic off-chip.

One candidate = one fused ``KernelChoice`` (implementation + block
targets) at one op-shape context.  ``measure_candidate`` returns the
latency the tuner should score it with, plus the PROVENANCE of that
number:

  * On a real TPU the candidate's kernel family is compiled and timed in
    isolation — wall-clock median-of-k after a warmup dispatch, through
    a per-family driver that builds representative operands from the
    config's own dimensions (``source="measured"``).
  * In interpret mode (deviceless CI) wall-clock would time the Python
    Pallas interpreter, which says nothing about the MXU — so the
    harness scores with ``analytic_estimate``, a block-sensitive
    surrogate (``source="analytic"``) that keeps the tuner's argmin
    deterministic without a device.  It is a test fixture, never a
    stand-in for a measurement on the chip: a timing run that fails
    there raises instead of being scored as if it had worked.

The surrogate models what block sizes actually change on a weight-
streaming dataflow kernel: every token-block restreams the stage's
weights once (so bigger token tiles amortize HBM traffic) and every
grid step pays a fixed pipeline-fill overhead (so bigger feature tiles
mean fewer steps), on top of the compute/memory roofline.  Candidates
the kernel lint rejects never reach this module — legality pruning
happens in ``autotune.py`` BEFORE anything is compiled or scored.

Families without an isolation driver (the paged/verify decode kernels,
whose operands are pool + page-table state, and the MoE/SSM/RWKV
mixers) fall back to the surrogate even on device — a documented
follow-on, not a silent gap: ``measure_candidate`` reports the source.
"""

from __future__ import annotations

import math
import statistics
import time
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..core.itensor import dtype_bytes
from ..core.platforms import Platform
from ..core.stream_plan import KernelChoice, StreamPlan
from ..kernels.common import LANE, interpret_default, pick_block, round_up
from ..kernels.paged_attention import pages_per_block

# Median-of-k protocol: one warmup dispatch absorbs compilation, then k
# timed dispatches; the median is robust to a stray scheduling hiccup.
WARMUP = 1
REPS = 5

# Pipeline-fill overhead charged per grid step by the surrogate — the
# same fixed stage-fill depth ``Platform.kernel_timing`` models.
_PIPELINE_DEPTH = 32.0


def measure(fn: Callable[[], object], *, reps: int = REPS,
            warmup: int = WARMUP) -> float:
    """Wall-clock median-of-``reps`` of ``fn`` after ``warmup`` calls."""
    for _ in range(max(0, warmup)):
        jax.block_until_ready(fn())
    samples = []
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        samples.append(time.perf_counter() - t0)
    return float(statistics.median(samples))


def _eff(extent: int, target: int) -> int:
    """Effective block after the wrapper's ``pick_block`` clip."""
    return pick_block(max(1, int(extent)), max(1, int(target)))


def _cdiv(a: int, b: int) -> int:
    return -(-int(a) // max(1, int(b)))


def analytic_estimate(cfg: ModelConfig, plan: StreamPlan, stage: str,
                      choice: KernelChoice, platform: Platform) -> float:
    """Block-sensitive latency surrogate for one candidate (seconds).

    roofline(flops, streamed bytes) + grid_steps * pipeline fill.  The
    streamed-bytes term restreams the stage's weights once per token
    block — the dominant effect a token tile has on a weight-streaming
    kernel — so the argmin over a candidate lattice is meaningful even
    though the absolute number is a model, not a measurement.
    """
    impl = choice.implementation
    dt = dtype_bytes(cfg.dtype)
    t = max(1, plan.tokens)
    s = max(1, plan.kv_len)
    d = cfg.d_model
    flops = 0.0
    stream = 0.0
    steps = 1

    if impl in ("rmsnorm_matmul", "block_matmul"):
        n = max(1, min(cfg.q_dim, cfg.kv_dim))
        bt = _eff(t, choice.block("block_t", t))
        bn = _eff(n, choice.block("block_n", n))
        restreams = _cdiv(t, bt)
        steps = restreams * _cdiv(n, bn)
        flops = 2.0 * t * d * n
        stream = restreams * d * n * dt + t * d * dt
    elif impl in ("streamed_ffn", "streamed_mlp"):
        f = max(1, cfg.d_ff)
        mats = 3 if impl == "streamed_ffn" else 2
        bt = _eff(t, choice.block("block_t", t))
        bf = _eff(f, choice.block("block_f", f))
        restreams = _cdiv(t, bt)
        steps = restreams * _cdiv(f, bf)
        flops = 2.0 * mats * t * d * f
        stream = restreams * mats * d * f * dt + t * d * dt
    elif impl == "moe_experts":
        f = max(1, cfg.d_ff)
        e = max(1, cfg.num_experts)
        bt = _eff(t, choice.block("block_t", t))
        restreams = _cdiv(t, bt)
        steps = restreams * e
        flops = 2.0 * 3 * t * d * f
        stream = restreams * 3 * d * f * e * dt + t * d * dt
    elif impl == "flash_attention":
        dp = round_up(max(1, cfg.head_dim_), LANE)
        h = max(1, cfg.num_heads)
        bq = _eff(t, choice.block("block_q", t))
        bkv = _eff(s, choice.block("block_kv", s))
        qb = _cdiv(t, bq)
        steps = h * qb * _cdiv(s, bkv)
        flops = 4.0 * h * t * s * dp
        stream = qb * 2.0 * h * s * dp * dt + h * t * dp * dt
    elif impl in ("paged_attention", "verify_attention"):
        dp = round_up(max(1, cfg.head_dim_), LANE)
        hkv = max(1, cfg.num_kv_heads)
        ps = max(1, choice.block("page_size", 16))
        pages = _cdiv(s, ps)
        steps = _cdiv(pages, pages_per_block(ps, pages))
        flops = 4.0 * max(1, cfg.num_heads) * s * dp
        stream = 2.0 * hkv * s * dp * dt
    elif impl in ("mamba2_scan", "rwkv6_wkv"):
        # Chunked recurrences: within-chunk work is quadratic in the
        # chunk length while the sequential state carry costs one
        # pipeline fill per chunk — the lattice has a real interior
        # tradeoff, unlike the monotone matmul tiles.
        width = max(1, cfg.d_inner if impl == "mamba2_scan" else d)
        q = _eff(t, choice.block("chunk", t))
        steps = _cdiv(t, q)
        flops = 4.0 * t * q * width
        stream = 2.0 * t * width * dt
    elif impl == "streamed_xent":
        v = max(1, cfg.vocab_size)
        bt = _eff(t, choice.block("block_t", t))
        bv = _eff(v, choice.block("block_v", v))
        restreams = _cdiv(t, bt)
        steps = restreams * _cdiv(v, bv)
        flops = 2.0 * t * d * v
        stream = restreams * d * v * dt + t * d * dt
    else:
        # Unknown family: a flat (block-insensitive) floor — the tuner
        # keeps the original choice on ties.
        flops = 2.0 * t * d * d
        stream = t * d * dt

    roofline = max(flops / platform.peak_flops, stream / platform.hbm_bw)
    return roofline + steps * (_PIPELINE_DEPTH / platform.freq_hz)


# --------------------------------------------------------------------- #
# Isolation drivers: build representative operands from the config's own
# dimensions and dispatch the candidate's kernel family with its blocks.
# --------------------------------------------------------------------- #

def _np_dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, dtype=jnp.float32).astype(dtype)


def _driver(cfg: ModelConfig, plan: StreamPlan, stage: str,
            choice: KernelChoice) -> Optional[Callable[[], object]]:
    """A zero-arg jitted dispatch of this candidate, or None when the
    family has no isolation driver (caller falls back to the surrogate)."""
    impl = choice.implementation
    dtype = _np_dtype(cfg)
    t = max(1, plan.tokens)
    s = max(1, plan.kv_len)
    d = cfg.d_model
    k0, k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 4)

    if impl in ("rmsnorm_matmul", "block_matmul"):
        n = max(1, min(cfg.q_dim, cfg.kv_dim))
        x = _rand(k0, (t, d), dtype)
        w = _rand(k1, (d, n), dtype)
        if impl == "rmsnorm_matmul":
            from ..kernels.rmsnorm_matmul import rmsnorm_matmul
            scale = jnp.ones((d,), dtype)
            bt, bn = choice.block("block_t", 256), choice.block("block_n", 512)
            return jax.jit(lambda: rmsnorm_matmul(
                x, scale, w, block_t=bt, block_n=bn))
        from ..kernels.block_matmul import block_matmul
        bm, bn = choice.block("block_t", 256), choice.block("block_n", 256)
        return jax.jit(lambda: block_matmul(x, w, block_m=bm, block_n=bn))

    if impl == "flash_attention":
        from ..kernels.flash_attention import flash_attention_2d
        hq = max(1, cfg.num_heads)
        hkv = max(1, cfg.num_kv_heads)
        dp = max(1, cfg.head_dim_)
        q = _rand(k0, (hq, t, dp), dtype)
        kk = _rand(k1, (hkv, s, dp), dtype)
        v = _rand(k2, (hkv, s, dp), dtype)
        bq, bkv = choice.block("block_q", 512), choice.block("block_kv", 512)
        return jax.jit(lambda: flash_attention_2d(
            q, kk, v, causal=True, kv_group=hq // hkv,
            block_q=bq, block_kv=bkv))

    if impl in ("streamed_ffn", "streamed_mlp"):
        f = max(1, cfg.d_ff)
        x = _rand(k0, (t, d), dtype)
        wu = _rand(k1, (d, f), dtype)
        wd = _rand(k2, (f, d), dtype)
        bt, bf = choice.block("block_t", 256), choice.block("block_f", 512)
        if impl == "streamed_ffn":
            from ..kernels.streamed_ffn import streamed_ffn
            wg = _rand(k3, (d, f), dtype)
            return jax.jit(lambda: streamed_ffn(
                x, wg, wu, wd, block_t=bt, block_f=bf))
        from ..kernels.streamed_ffn import streamed_mlp
        return jax.jit(lambda: streamed_mlp(
            x, wu, wd, block_t=bt, block_f=bf))

    if impl == "streamed_xent":
        from ..kernels.streamed_xent import streamed_xent_loss
        v = max(1, cfg.vocab_size)
        hid = _rand(k0, (t, d), dtype)
        head = _rand(k1, (d, v), dtype)
        labels = jax.random.randint(k2, (t,), 0, v)
        bt, bv = choice.block("block_t", 256), choice.block("block_v", 2048)
        return jax.jit(lambda: streamed_xent_loss(
            hid, head, labels, vocab_size=v, block_t=bt, block_v=bv))

    return None     # paged/verify/moe/ssm/rwkv: surrogate-only for now


def measure_candidate(cfg: ModelConfig, plan: StreamPlan, kind: str,
                      stage: str, choice: KernelChoice, *,
                      platform: Platform, force: bool = False,
                      reps: int = REPS, warmup: int = WARMUP
                      ) -> Tuple[float, str]:
    """Latency for one lint-legal candidate: ``(seconds, source)``.

    Interpret mode (no TPU) scores with the analytic surrogate unless
    ``force=True`` — forcing in interpret mode times the Python Pallas
    interpreter, which is only useful to exercise the wall-clock path in
    tests.  A failed timing run (a kernel the compiler refuses, VMEM or
    HBM exhaustion) propagates: a candidate that cannot run is never
    scored as if it could.
    """
    if interpret_default() and not force:
        return analytic_estimate(cfg, plan, stage, choice, platform), \
            "analytic"
    fn = _driver(cfg, plan, stage, choice)
    if fn is None:
        return analytic_estimate(cfg, plan, stage, choice, platform), \
            "analytic"
    return measure(fn, reps=reps, warmup=warmup), "measured"
