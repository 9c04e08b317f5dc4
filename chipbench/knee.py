"""The highest rate an open-loop cell's system sustains, found once.

    python3 chipbench/knee.py --workload <cell> --seed <n> --seconds <s> --rates 0.5 1 2 ...

Builds and warms the cell once, then offers its traffic at each rate in
turn for ``--seconds`` (the pipeline's result cache emptied between
rates, so each rate starts as a run does) and prints one JSON line per
rate: latency quantiles of the queries due, and how far the last tenth's
median latency stands above the first tenth's -- a backlog that grows
through the window shows there.  The cell's ``rate_qps`` is then set
below the knee by hand; the benchmark's own runs never search for one.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    if jax.default_backend() not in ("tpu", "gpu"):
        print("knee: needs an accelerator", file=sys.stderr)
        return 2
    import time
    from chipbench import generator, harness
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload)
    device = jax.devices()[0]
    base = generator.generate(cell.mix, args.seed, args.seconds, cell.max_seq)
    engine, serving = harness.build(cell, base, args.seed, device)
    harness.warm_up(engine, serving, base, cell.max_seq)
    for rate in args.rates:
        mix = dict(cell.mix, rate_qps=rate)
        work = generator.generate(mix, args.seed, args.seconds, cell.max_seq)
        serving.pipeline.clear_cache()
        t0 = time.perf_counter()
        records, late = harness.run_open(serving, work, t0, args.seconds)
        lat = harness.latencies(records, t0, args.seconds)
        tenth = max(len(lat) // 10, 1)
        print(json.dumps({
            "rate_qps": rate, "due": int(len(lat)),
            "failed": sum(not r.ok for r in records),
            "p50_s": float(np.percentile(lat, 50)),
            "p95_s": float(np.percentile(lat, 95)),
            "max_s": float(lat.max()),
            "first_tenth_p50_s": float(np.median(lat[:tenth])),
            "last_tenth_p50_s": float(np.median(lat[-tenth:])),
            "late_max_s": float(max(late)),
            "by_shape_p50_s": {s: float(np.median([r.end - r.start
                                                   for r in records
                                                   if r.query.shape == s]))
                               for s in {r.query.shape for r in records}},
        }), flush=True)
    harness.free(engine, serving)
    return 0


if __name__ == "__main__":
    sys.exit(main())
