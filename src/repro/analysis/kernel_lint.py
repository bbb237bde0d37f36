"""Pass 2 — kernel lint: block legality, VMEM budgets, quant mode.

Checks every fused ``KernelChoice`` against the model dimensions and the
platform model in ``core/platforms.py`` WITHOUT tracing a kernel:

  * implementation names must be known kernels (a plan naming a kernel
    the runtime doesn't have dispatches nothing);
  * feature-dim block targets honor the 128-lane floor and either divide
    their extent or clip (``kernels/common.pick_block``) to an
    MXU-aligned divisor — a clip below the lane width on a lane-sized
    extent would hand the MXU an illegal tile;
  * a per-kernel VMEM footprint estimate (operand blocks resident per
    grid step, f32 accumulators, w8 scale rows) must fit the platform's
    on-chip memory;
  * the plan's recorded quant mode must agree with the config it is
    verified against — a cached plan from a different QuantMode would
    pick wrong kernel twins.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

from ..configs.base import ModelConfig
from ..core.itensor import dtype_bytes
from ..core.platforms import Platform
from ..core.stream_plan import KernelChoice, StreamPlan
from ..kernels.common import LANE, pick_block, round_up
from ..kernels.paged_attention import pages_per_block
from .diagnostics import Diagnostic

# Every implementation name a KernelChoice may carry -> the block names
# it understands.  (Extra block entries like "fuse_norm"/"w8" are flags.)
KNOWN_KERNELS: Dict[str, Tuple[str, ...]] = {
    "eager": (),
    "rmsnorm_matmul": ("block_t", "block_n", "w8"),
    "block_matmul": ("block_t", "block_n"),
    "flash_attention": ("block_q", "block_kv"),
    "paged_attention": ("page_size",),
    "verify_attention": ("page_size",),
    "streamed_ffn": ("block_t", "block_f", "fuse_norm", "w8"),
    "streamed_mlp": ("block_t", "block_f", "fuse_norm", "w8"),
    "moe_experts": ("block_t",),
    "mamba2_scan": ("chunk",),
    "rwkv6_wkv": ("chunk",),
    "streamed_xent": ("block_t", "block_v"),
}

def _feature_blocks(cfg: ModelConfig, stage: str, choice: KernelChoice,
                    kv_len: int) -> List[Tuple[str, int]]:
    """(block_name, extent) for the LANE-sensitive dims of a stage."""
    impl = choice.implementation
    if stage == "qkv":
        return [("block_n", min(cfg.q_dim, cfg.kv_dim))]
    if stage == "attention":
        return [("block_kv", kv_len)]
    if stage == "ffn" and impl in ("streamed_ffn", "streamed_mlp"):
        return [("block_f", cfg.d_ff)]
    if stage == "lm_head":
        return [("block_v", cfg.vocab_size)]
    return []


def _shard_div(choice: KernelChoice, mesh_axes: Dict[str, int],
               dim: str) -> int:
    ax = choice.claim(dim)
    if ax is None:
        return 1
    axes = ax if isinstance(ax, tuple) else (ax,)
    n = 1
    for a in axes:
        n *= int(mesh_axes.get(a, 1))
    return max(1, n)


def vmem_estimate(cfg: ModelConfig, plan: StreamPlan, stage: str,
                  choice: KernelChoice) -> Optional[float]:
    """Resident bytes one grid step of the stage's kernel holds in VMEM:
    operand blocks + f32 accumulators/scratch (+ w8 codes and scales).
    ``None`` for eager stages.  Uses the EFFECTIVE blocks (post
    ``pick_block`` clip) and post-shard extents — what one program on
    one shard actually streams."""
    if not choice.fused:
        return None
    impl = choice.implementation
    dt = dtype_bytes(cfg.dtype)
    mesh = dict(plan.mesh_axes)
    d = cfg.d_model
    tokens = max(1, plan.tokens)
    kv_len = max(1, plan.kv_len)
    w8 = bool(choice.block("w8"))

    def eff(extent: int, name: str, default: int) -> int:
        return pick_block(max(1, extent), choice.block(name, default)
                          or default)

    if impl in ("rmsnorm_matmul", "block_matmul"):
        n = min(cfg.q_dim, cfg.kv_dim) // _shard_div(choice, mesh, "out")
        bt = eff(tokens, "block_t", tokens)
        bn = eff(n, "block_n", n)
        wbytes = d * bn * (1 if w8 else dt) + (bn * 4 if w8 else 0)
        return bt * d * dt + wbytes + bt * bn * 4
    if impl in ("streamed_ffn", "streamed_mlp"):
        f = cfg.d_ff // _shard_div(choice, mesh, "d_ff")
        bt = eff(tokens, "block_t", tokens)
        bf = eff(f, "block_f", f)
        mats = 3 if impl == "streamed_ffn" else 2
        per_mat = d * bf * (1 if w8 else dt) + (bf * 4 if w8 else 0)
        return (bt * d * dt + mats * per_mat
                + bt * bf * 4 + bt * d * 4)
    if impl == "moe_experts":
        bt = eff(tokens, "block_t", tokens)
        return (bt * d * dt + 3 * d * cfg.d_ff * dt
                + bt * cfg.d_ff * 4 + bt * d * 4)
    if impl == "flash_attention":
        dp = round_up(cfg.head_dim_, LANE)
        bq = eff(tokens, "block_q", tokens)
        bkv = eff(kv_len, "block_kv", kv_len)
        return (bq + 2 * bkv) * dp * dt + bq * (dp + 2) * 4
    if impl in ("paged_attention", "verify_attention"):
        # Per slot: double-buffered K and V blocks (pages_per_block pages
        # of every kv head on the shard), the q and output blocks, the
        # f32 running (m, l, acc), and one K/V block as f32 while it folds.
        dp = round_up(cfg.head_dim_, LANE)
        hkv = max(1, cfg.num_kv_heads
                  // _shard_div(choice, mesh, "kv_heads"))
        g = max(1, cfg.num_heads // max(1, cfg.num_kv_heads))
        rows = g
        if impl == "verify_attention":
            rows = g * plan.verify_window(plan.decode_page_size())
        ps = max(1, choice.block("page_size", 16))
        bt = pages_per_block(ps, -(-kv_len // ps)) * ps
        return (4 * hkv * bt * dp * dt + 2 * hkv * rows * dp * dt
                + hkv * rows * (dp + 2) * 4 + 2 * hkv * bt * dp * 4)
    if impl == "mamba2_scan":
        chunk = eff(tokens, "chunk", tokens)
        return 4.0 * chunk * max(cfg.d_inner, 1) * dt
    if impl == "rwkv6_wkv":
        chunk = eff(tokens, "chunk", tokens)
        return 4.0 * chunk * d * dt
    if impl == "streamed_xent":
        v = cfg.vocab_size // _shard_div(choice, mesh, "vocab")
        bt = eff(tokens, "block_t", tokens)
        bv = eff(v, "block_v", v)
        return bt * d * dt + d * bv * dt + bt * bv * 4 + 8 * bt
    return None     # unknown kernel: reported separately


def check_kernels(plan: StreamPlan, cfg: ModelConfig,
                  platform: Platform) -> List[Diagnostic]:
    diags: List[Diagnostic] = []

    if plan.quant != cfg.quant:
        diags.append(Diagnostic(
            "error", "kernel", "plan", "quant-mismatch",
            f"plan was built under quant mode {plan.quant!r} but is "
            f"verified against a config in mode {cfg.quant!r} — kernel "
            "twins and pool dtypes would disagree",
            "rebuild the plan with the config's quant mode "
            "(plans are cached per config)"))

    for kind, stage, choice in plan.stage_choices():
        if not choice.fused:
            continue
        where = f"{kind}.{stage}"
        impl = choice.implementation

        if impl not in KNOWN_KERNELS:
            diags.append(Diagnostic(
                "error", "kernel", where, "unknown-kernel",
                f"implementation {impl!r} is not a known Pallas kernel",
                f"one of {sorted(k for k in KNOWN_KERNELS if k != 'eager')}"))
            continue

        # w8 flags must agree with the config's weight-quant mode.
        if choice.block("w8") and not cfg.weight_quant:
            diags.append(Diagnostic(
                "error", "kernel", where, "w8-without-weight-quant",
                f"{impl} carries the w8 flag but cfg.quant={cfg.quant!r} "
                "has no weight quantization — the wrapper would "
                "quantize weights the checkpoint math doesn't expect",
                "drop the w8 block flag or set quant=w8/w8_kv8"))

        # Feature-dim block targets: lane floor + divisibility.
        for bname, extent in _feature_blocks(cfg, stage, choice,
                                             plan.kv_len):
            target = choice.block(bname)
            if target <= 0 or extent <= 0:
                continue
            if extent >= LANE and target < LANE:
                diags.append(Diagnostic(
                    "error", "kernel", where, "lane-floor",
                    f"{bname}={target} is below the {LANE}-lane floor "
                    f"for a {extent}-wide dim — the MXU tile would be "
                    "lane-misaligned",
                    f"raise {bname} to a multiple of {LANE}"))
                continue
            if target <= extent and extent % target != 0:
                eff = pick_block(extent, target)
                diags.append(Diagnostic(
                    "warning", "kernel", where, "non-divisible-block",
                    f"{bname}={target} does not divide the {extent}-wide "
                    f"dim; the wrapper will clip it to {eff}",
                    f"use a {bname} that divides {extent} so the plan's "
                    "tile is the tile that runs"))
                if extent >= LANE and eff % LANE != 0:
                    diags.append(Diagnostic(
                        "error", "kernel", where, "unaligned-block",
                        f"no lane-aligned divisor of {extent} exists at "
                        f"or below {bname}={target}; the clip lands on "
                        f"{eff}, an MXU-illegal tile",
                        f"pad the dim to a multiple of {LANE} or pick a "
                        "dividing block"))

        # Paged stream granule sanity.
        if impl in ("paged_attention", "verify_attention"):
            ps = choice.block("page_size", 0)
            if ps <= 0:
                diags.append(Diagnostic(
                    "error", "kernel", where, "bad-page-size",
                    f"{impl} carries page_size={ps}",
                    "page_size must be a positive KV stream granule"))

        # VMEM footprint vs the platform budget.
        est = vmem_estimate(cfg, plan, stage, choice)
        if est is not None:
            if est > platform.onchip_bytes:
                diags.append(Diagnostic(
                    "error", "kernel", where, "vmem-exceeded",
                    f"{impl} needs ~{est / 2**20:.1f} MiB of VMEM per "
                    f"grid step; {platform.name} has "
                    f"{platform.onchip_bytes / 2**20:.0f} MiB",
                    "shrink the stage's block targets"))
            elif est > platform.fusion_budget(0.5):
                diags.append(Diagnostic(
                    "warning", "kernel", where, "vmem-pressure",
                    f"{impl} needs ~{est / 2**20:.1f} MiB of VMEM per "
                    "grid step — over half the on-chip budget, leaving "
                    "no room for double-buffering",
                    "shrink the stage's block targets"))
    return diags
