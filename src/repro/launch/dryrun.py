import os
# Deviceless by design: 512 forced host devices stand in for the pods, and
# the CPU platform is pinned so the dry-run never claims an attached chip.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: ``jax.jit(step).lower(**abstract_inputs).compile()`` must succeed
on the 16x16 single-pod mesh and the 2x16x16 multi-pod mesh for every cell,
and the compiled artifact yields the roofline terms
(``cost_analysis``/``memory_analysis`` + collective bytes parsed from the
HLO) recorded in EXPERIMENTS.md.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
        --shape train_4k [--multi-pod] [--out results.json]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--out dir/]
"""

import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax

from ..configs import ALL_SHAPES, ARCHS, ASSIGNED_ARCHS, cells, get_config, \
    get_shape, skipped_cells
from .hlo_analysis import analyze_hlo
from .mesh import make_production_mesh
from .roofline import roofline_from_compiled


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             perf: bool = False, verbose: bool = True) -> dict:
    """Lower + compile one cell; return the dry-run record."""
    from ..distributed.steps import lower_cell   # jax initialized by now

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "chips": chips, "kind": shape.kind, "perf": perf,
    }
    t0 = time.perf_counter()
    lowered = lower_cell(cfg, shape, mesh, perf=perf)
    rec["lower_s"] = round(time.perf_counter() - t0, 2)

    t0 = time.perf_counter()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.perf_counter() - t0, 2)

    mem = compiled.memory_analysis()
    def _m(attr):
        return int(getattr(mem, attr, 0) or 0) if mem is not None else 0
    rec["memory"] = {
        "argument_bytes": _m("argument_size_in_bytes"),
        "output_bytes": _m("output_size_in_bytes"),
        "temp_bytes": _m("temp_size_in_bytes"),
        "alias_bytes": _m("alias_size_in_bytes"),
    }
    rec["memory"]["peak_bytes"] = (rec["memory"]["argument_bytes"]
                                   + rec["memory"]["output_bytes"]
                                   + rec["memory"]["temp_bytes"]
                                   - rec["memory"]["alias_bytes"])
    cost = compiled.cost_analysis() or {}
    # Raw XLA numbers (while bodies counted ONCE — kept for reference).
    rec["cost_xla_raw"] = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
    }
    # Loop-aware analysis: while bodies scaled by known_trip_count.
    hlo_text = compiled.as_text()
    t0 = time.perf_counter()
    analysis = analyze_hlo(hlo_text)
    rec["analyze_s"] = round(time.perf_counter() - t0, 2)
    rec["cost"] = {
        "flops": analysis["flops"],
        "bytes_accessed": analysis["bytes_accessed"],
    }
    rec["collectives"] = {
        **analysis["collective_link_bytes"],
        "total": analysis["collective_link_total"],
        "operand_total": analysis["collective_operand_total"],
        "counts": analysis["collective_count"],
    }
    rec["roofline"] = roofline_from_compiled(cfg, shape, rec, chips=chips)
    if verbose:
        m = rec["memory"]
        r = rec["roofline"]
        print(f"[dryrun] {arch} x {shape_name} mesh={rec['mesh']}  "
              f"compile={rec['compile_s']}s  "
              f"args/dev={m['argument_bytes']/2**30:.2f}GiB "
              f"temp/dev={m['temp_bytes']/2**30:.2f}GiB  "
              f"compute={r['compute_s']*1e3:.2f}ms "
              f"memory={r['memory_s']*1e3:.2f}ms "
              f"collective={r['collective_s']*1e3:.2f}ms "
              f"bound={r['bound']}", flush=True)
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(ALL_SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--perf", nargs="?", const="all", default=False,
                    choices=["all", "embed", "sp"],
                    help="apply the §Perf optimization set "
                         "(pin mode: all|embed|sp)")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) cell on this mesh")
    ap.add_argument("--both-meshes", action="store_true",
                    help="with --all: run single-pod AND multi-pod")
    ap.add_argument("--out", default=None, help="JSON output path or dir")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args(argv)

    results, failures = [], []

    def save(rec, tag):
        if args.out:
            outdir = Path(args.out)
            outdir.mkdir(parents=True, exist_ok=True)
            (outdir / f"{tag}.json").write_text(json.dumps(rec, indent=1))

    if args.all:
        meshes = [False, True] if args.both_meshes else [args.multi_pod]
        todo = [(cfg.name, shape.name, mp)
                for mp in meshes for cfg, shape in cells()]
        for arch, shape_name, mp in todo:
            tag = (f"{arch}__{shape_name}__{'pod2' if mp else 'pod1'}"
                   + (f"__perf_{args.perf}" if args.perf else ""))
            if args.skip_existing and args.out and \
                    (Path(args.out) / f"{tag}.json").exists():
                print(f"[dryrun] skip existing {tag}", flush=True)
                continue
            try:
                rec = run_cell(arch, shape_name, multi_pod=mp,
                               perf=args.perf)
                results.append(rec)
                save(rec, tag)
            except Exception as e:   # record and continue
                traceback.print_exc()
                failures.append({"arch": arch, "shape": shape_name,
                                 "multi_pod": mp, "error": repr(e)})
                save({"arch": arch, "shape": shape_name, "multi_pod": mp,
                      "error": repr(e)}, tag + "__FAILED")
        for arch, shape, reason in skipped_cells():
            print(f"[dryrun] SKIP {arch} x {shape}: {reason}", flush=True)
        print(f"[dryrun] done: {len(results)} ok, {len(failures)} failed",
              flush=True)
        return 1 if failures else 0

    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --all)")
    rec = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   perf=args.perf)
    if args.out:
        save(rec, f"{args.arch}__{args.shape}__"
                  f"{'pod2' if args.multi_pod else 'pod1'}"
             + (f"__perf_{args.perf}" if args.perf else ""))
    else:
        print(json.dumps(rec, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
