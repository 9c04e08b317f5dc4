"""Readings that a cell's limits are set from, on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seconds <s> --seeds 1 2 3 ...

One process runs the cell once per seed (engine, warm-up, a window at the
cell's own load, the check), and beside each check the lower-precision
controls of the cell's float32 reference (``reference/common.py``) on the
same prompts and tokens (int8 and fp8).  One JSON line per seed on
standard output, and all of them in
``chiprun_out/calibrate_<cell>.jsonl``: the program's numbers, each
control's, whether the run and each control came out correct under the
limits in force, and every sampled gap of each stream.  Exits 1 where a
control came out correct on any seed: the limits do not hold it off.
The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    if jax.default_backend() not in ("tpu", "gpu"):
        print("calibrate: needs an accelerator", file=sys.stderr)
        return 2
    from chipbench import harness
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"calibrate_{args.workload}.jsonl")
    passed = []
    for seed in args.seeds:
        out = harness.run(args.workload, seed, args.seconds, False,
                          controls=("int8", "fp8"))
        line = {"seed": seed, "correct": out["correct"],
                "failed": out["failed"], "attempted": out["attempted"],
                "program": {k: v["value"] for k, v in out["checks"].items()},
                "control": out["control"],
                "control_correct": out["control_correct"],
                "metrics": {k: v["value"] for k, v in out["metrics"].items()},
                "memory_peak_bytes": out["device"]["memory_peak_bytes"],
                "gaps": out["gaps"]}
        print(json.dumps(line), flush=True)
        with open(path, "a") as f:
            f.write(json.dumps(line) + "\n")
        passed += [(seed, c) for c, ok in out["control_correct"].items() if ok]
    if passed:
        print(f"calibrate: controls came out correct: {passed}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
