import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()
# The two lines above MUST run before any other import (jax locks the device
# count at first init) — do not move them.

"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production meshes and record memory/cost/roofline evidence.
(No ``from __future__`` here: the XLA_FLAGS lines must stay first.)

Usage:
  python -m repro.launch.dryrun                      # all cells, both meshes
  python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
  python -m repro.launch.dryrun --multi-pod-only --json out.json

Success criterion (deliverable e): .lower().compile() succeeds for the
16×16 single-pod mesh AND the 2×16×16 multi-pod mesh for every assigned
cell; the printed memory_analysis proves the state fits per device and
cost_analysis feeds §Roofline.
"""

import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, List, Optional

import jax

from repro.configs import base as cfgs

# Per-arch train-cell settings from the S-Perf hillclimb: sequence
# parallelism wins for MoE/VL (and is required for their HBM fit);
# dense/recurrent archs fit better via grad accumulation alone (SP
# regressed their collective term, catastrophically so for RWKV's
# time-scan).  Serve cells are tuned inside build_cell.
TRAIN_POLICY = {
    "phi3.5-moe-42b-a6.6b": {"sp": True},
    "qwen2-moe-a2.7b": {"sp": True},
    "qwen2-vl-7b": {"sp": True},
    "minitron-8b": {"grad_accum": 8},
    "qwen3-32b": {"grad_accum": 16},
    "command-r-35b": {"grad_accum": 16},
    "stablelm-12b": {"grad_accum": 8},
    "whisper-base": {"grad_accum": 8},
    "rwkv6-1.6b": {"grad_accum": 4},
    "recurrentgemma-9b": {"grad_accum": 4},
}
from repro.launch import roofline as rl
from repro.launch.mesh import PRODUCTION_DEVICE_KIND, make_production_mesh
from repro.launch.steps import build_cell


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             grad_accum: Optional[int] = None, remat: bool = True,
             sp: bool = False, verbose: bool = True) -> Dict[str, Any]:
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.perf_counter()
    cell = build_cell(arch, shape_name, mesh, grad_accum=grad_accum,
                      remat=remat, sp=sp)
    with mesh:
        lowered = cell.lower()
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
    roof = rl.analyze(cell, lowered=lowered, compiled=compiled,
                      device_kind=PRODUCTION_DEVICE_KIND)
    dt = time.perf_counter() - t0
    rec = roof.to_dict()
    rec.update({"ok": True, "compile_s": dt, "multi_pod": multi_pod})
    if verbose:
        print(f"[OK] {arch} × {shape_name} × {rec['mesh']} "
              f"({dt:.1f}s compile)")
        if mem is not None:
            print(f"     memory/device: args={_gb(mem.argument_size_in_bytes)} "
                  f"out={_gb(mem.output_size_in_bytes)} "
                  f"temp={_gb(mem.temp_size_in_bytes)}")
        print("     " + rl.fmt_row(roof))
    return rec


def _gb(b) -> str:
    return f"{b/2**30:.2f}GiB"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--grad-accum", type=int, default=None)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--sp", action="store_true",
                    help="sequence-parallel layer carry for train cells")
    ap.add_argument("--optimized", action="store_true",
                    help="per-arch tuned settings from the perf pass")
    ap.add_argument("--json", default=None, help="append records to file")
    args = ap.parse_args(argv)

    archs = [args.arch] if args.arch else list(cfgs.ARCH_IDS)
    meshes = []
    if not args.multi_pod_only:
        meshes.append(False)
    if not args.single_pod_only:
        meshes.append(True)

    records: List[Dict[str, Any]] = []
    failures: List[str] = []
    for arch in archs:
        shapes = ([cfgs.SHAPE_BY_NAME[args.shape]] if args.shape
                  else cfgs.cells(arch))
        for (s, reason) in cfgs.skipped_cells(arch):
            if args.shape and s.name != args.shape:
                continue
            records.append({"arch": arch, "shape": s.name, "ok": True,
                            "skipped": reason})
            print(f"[SKIP] {arch} × {s.name}: {reason}")
        for shape in shapes:
            for mp in meshes:
                try:
                    pol = (TRAIN_POLICY.get(arch, {})
                           if args.optimized and shape.kind == "train"
                           else {})
                    records.append(run_cell(
                        arch, shape.name, multi_pod=mp,
                        grad_accum=args.grad_accum or pol.get("grad_accum"),
                        remat=not args.no_remat,
                        sp=pol.get("sp", args.sp and shape.kind == "train")))
                except Exception as e:  # noqa: BLE001 — report, keep going
                    failures.append(f"{arch}×{shape.name}×mp={mp}: {e}")
                    traceback.print_exc()
                    records.append({"arch": arch, "shape": shape.name,
                                    "multi_pod": mp, "ok": False,
                                    "error": str(e)})
    if args.json:
        existing = []
        try:
            with open(args.json) as f:
                existing = json.load(f)
        except (FileNotFoundError, json.JSONDecodeError):
            pass
        with open(args.json, "w") as f:
            json.dump(existing + records, f, indent=1)
    print(f"\n{sum(1 for r in records if r.get('ok'))}/{len(records)} cells OK")
    if failures:
        print("FAILURES:")
        for f in failures:
            print("  " + f)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
