"""Readings that set a cell's correctness limit: program and control.

    python bench/control.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--out chiprun_out/control.json]

For each seed, in one process: the run's set-up (weights from the seed,
engine, warm-up batch), then one batch of the cell's own traffic through
the engine, then the same sample of finished requests that a run draws
(``check.sample``), read against the float32 reference: the program's
per-position logit gaps.  For each control seed, the same rows read by
the int8 reference in the program's place (``check.Reference.gaps`` with
``control=True``).  Each row, program and control, is judged as a run
judges it: its mean gap against the cell's limit
(``bench/limits/<cell>.json``) through ``harness.judge``, so the program's
rows must read ``correct`` true and the control's false.  The lower
reading of a limit is the largest program mean gap over the seeds, the
upper one the smallest control mean gap; the widest gaps and the share of
positions flipped are printed beside them.  Needs a TPU, as a run does.
The benchmark's own runs never run the control.
"""

import gc
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs under /tmp
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def verdict(name: str, gaps, failed: int, limits: dict) -> dict:
    """One row's readings from its per-position ``gaps`` and whether a run
    that served them would read ``correct``, judged as a run judges it."""
    from bench import harness

    mean = float(gaps.mean()) if gaps.size else None
    return {
        f"{name}_gap": float(gaps.max(initial=0.0)),
        f"{name}_mean_gap": mean,
        f"{name}_flipped": float((gaps > 0).mean()) if gaps.size else 0.0,
        f"{name}_correct": harness.judge(
            {"mean_logit_gap": harness.gap_check(mean, limits),
             "requests_failed": {"value": failed, "limit": 0}}),
    }


def readings(root: Path, workload: str, seed: int, control: bool,
             check_device=None) -> dict:
    import jax
    import numpy as np

    from bench import check, harness

    cell = harness.Cell.load(root, workload, trace=False)
    (check_device or harness.require_tpu)(int(cell.spec["chips"]))
    mix = cell.mix
    params, engine, gen = harness.serve_setup(root, cell, seed)
    reqs = engine.generate(gen.batch(), gen.new_tokens)
    del engine
    gc.collect()
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
    sample = check.sample(reqs, int(mix["check_requests"]), rng)
    ref = check.Reference(cell.cfg, int(mix["max_len"]), gen.new_tokens)
    out = {"seed": seed, "requests": len(reqs), "sampled": len(sample),
           "failed": sum(r.failed for r in reqs)}
    for name, ctl in (("program", False), ("control", True)):
        if ctl and not control:
            continue
        out.update(verdict(name, ref.gaps(params, sample, control=ctl),
                           out["failed"], cell.limits))
    del params
    jax.clear_caches()
    gc.collect()
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    from bench import harness
    from bench.harness import Refused, log
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    try:
        for s in sorted(set(seeds) | control):
            t = time.perf_counter()
            rows.append(readings(ROOT, args.workload, s, s in control))
            log(f"{json.dumps(rows[-1])} in {time.perf_counter() - t:.1f}s")
    except Refused as e:
        log(f"refused: {e}")
        return 2
    prog = [r["program_mean_gap"] for r in rows]
    ctrl = [r["control_mean_gap"] for r in rows if "control_mean_gap" in r]
    limit = harness.Cell.load(ROOT, args.workload, False).limits
    out = {"workload": args.workload, "rows": rows,
           "lower": max(prog), "upper": min(ctrl) if ctrl else None,
           "limit": limit["mean_logit_gap"],
           "program_correct": all(r["program_correct"] for r in rows),
           "control_correct_on_any_seed": any(
               r["control_correct"] for r in rows if "control_correct" in r)}
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
