"""The benchmark's one entry point.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell named in ``BENCHMARK.json`` on the chip, warms up every
shape its traffic reaches (set-up), drives the program's serving path for
``--seconds`` seconds, checks what it served against the float32
reference, and prints one JSON object as the last line of standard
output: the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics from a profiler trace with ``--trace 1``.  The numbers compared
for ``correct`` are the last lines of standard error and the last key of
that object.  Without an accelerator, or with fewer chips than the cell
asks for, or a device whose peaks are unknown, it exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    if jax.default_backend() not in ("tpu", "gpu"):
        print(f"chipbench: needs an accelerator; JAX found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2
    from chipbench import correct, harness
    from chipbench.peaks import peaks
    from repro.launch import compile_cache
    peaks(jax.devices()[0].device_kind)          # unknown device: error
    harness.log("compile cache:", compile_cache.enable())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = harness.run(args.workload, args.seed, args.seconds,
                      bool(args.trace), started=STARTED)
    correct.print_checks(out["checks"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
