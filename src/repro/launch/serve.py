"""Serving launcher: batched greedy generation with the serving engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
        [--requests 8] [--prompt-len 32] [--new-tokens 16]

The engine runs the StreamPlan's fused Pallas kernels, the path
``chip_smoke.py`` checks on the chip.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import numpy as np

from ..configs import ARCHS, get_config
from ..models import init_params
from ..serving.engine import ServingEngine
from .compile_cache import enable_compile_cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--contiguous", action="store_true",
                    help="contiguous slots*max_len KV cache instead of "
                         "the paged default")
    ap.add_argument("--page-size", type=int, default=None,
                    help="KV page size (default: StreamPlan tile / 16)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = dataclasses.replace(get_config(args.arch), use_fused_kernels=True)
    if args.smoke:
        cfg = cfg.reduced()
    if cfg.encoder_only:
        ap.error(f"{args.arch} is encoder-only: no decode step")
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    engine = ServingEngine(cfg, params, batch_slots=args.slots,
                           max_len=args.prompt_len + args.new_tokens + 8,
                           paged=not args.contiguous,
                           page_size=args.page_size)
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(1, cfg.vocab_size, args.prompt_len,
                            dtype=np.int32)
               for _ in range(args.requests)]
    t0 = time.perf_counter()
    reqs = engine.generate(prompts, max_new_tokens=args.new_tokens)
    dt = time.perf_counter() - t0
    total = sum(len(r.out_tokens) for r in reqs)
    ttft = np.mean([r.ttft_s for r in reqs])
    m = engine.metrics
    print(f"[serve] {len(reqs)} requests, {total} tokens in {dt:.2f}s "
          f"({total/dt:.1f} tok/s), mean TTFT {ttft*1e3:.1f}ms")
    print(f"[serve] kv cache: {'paged' if m['paged'] else 'contiguous'}, "
          f"peak {m['kv_bytes_peak']} / reserved {m['kv_bytes_reserved']} "
          f"bytes, block efficiency {m['ticks']}/{m['scan_ticks']} ticks")
    for r in reqs[:2]:
        print(f"  req {r.rid}: {r.out_tokens[:8]}...")
    return 0


if __name__ == "__main__":
    sys.exit(main())
