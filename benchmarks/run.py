"""Run every paper-reproduction benchmark (one per table/figure).

    PYTHONPATH=src python -m benchmarks.run [--skip-serving]

Artifacts land in results/*.json; the printed tables mirror the paper's
Figures 9-12 and Tables 2-4 plus the §5.4 aggregation optimization and a
§2 serving-throughput check on the real JAX engine.  After a full run
the per-bench headline metrics are folded into `BENCH_trajectory.json`
at the repo root, keyed by git SHA, so the perf trajectory across PRs
stays inspectable.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAJECTORY = os.path.join(REPO_ROOT, "BENCH_trajectory.json")


def _git_sha() -> str:
    try:
        proc = subprocess.run(["git", "rev-parse", "HEAD"],
                              capture_output=True, text=True,
                              cwd=REPO_ROOT, timeout=10)
        sha = proc.stdout.strip()
        return sha if proc.returncode == 0 and sha else "unknown"
    except OSError:
        return "unknown"


def _headline(payload) -> dict:
    """Scalar top-level fields only — the trajectory tracks headline
    numbers, not full artifacts (those stay in results/*.json)."""
    return {k: v for k, v in payload.items()
            if isinstance(v, (int, float, str, bool)) or v is None}


def update_trajectory() -> str:
    """Fold every results/*.json headline into BENCH_trajectory.json,
    keyed by the current git SHA (re-running on the same SHA replaces
    that SHA's entry instead of appending a duplicate)."""
    from benchmarks.common import RESULTS_DIR
    benches = {}
    if os.path.isdir(RESULTS_DIR):
        for fname in sorted(os.listdir(RESULTS_DIR)):
            if not fname.endswith(".json"):
                continue
            try:
                with open(os.path.join(RESULTS_DIR, fname)) as f:
                    payload = json.load(f)
            except (OSError, ValueError):
                continue
            if isinstance(payload, dict):
                benches[fname[:-len(".json")]] = _headline(payload)
    sha = _git_sha()
    entries = []
    if os.path.exists(TRAJECTORY):
        try:
            with open(TRAJECTORY) as f:
                entries = json.load(f).get("entries", [])
        except (OSError, ValueError):
            entries = []
    entries = [e for e in entries if e.get("sha") != sha]
    entries.append({"sha": sha, "recorded_at": time.strftime(
        "%Y-%m-%dT%H:%M:%SZ", time.gmtime()), "benches": benches})
    with open(TRAJECTORY, "w") as f:
        json.dump({"entries": entries}, f, indent=1)
        f.write("\n")
    return TRAJECTORY


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-serving", action="store_true",
                    help="skip the real-engine serving benchmark (slow)")
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    compile_cache.enable()
    from benchmarks import (bench_adaptive, bench_agg_shortcircuit,
                            bench_cascade, bench_concurrent, bench_http,
                            bench_hybrid_join, bench_index,
                            bench_join_placement, bench_join_rewrite,
                            bench_learned, bench_predicate_reorder,
                            bench_streaming)
    benches = [
        ("Fig 9 predicate reordering", bench_predicate_reorder.main),
        ("adaptive re-optimization (learned stats)", bench_adaptive.main),
        ("learned cost model v2 (kNN transfer + plan memo)",
         bench_learned.main),
        ("streaming partition-parallel LIMIT + top-k", bench_streaming.main),
        ("semantic index: join blocking + kernel gate", bench_index.main),
        ("concurrent multi-tenant serving", bench_concurrent.main),
        ("Fig 10 join placement", bench_join_placement.main),
        ("Table 2 / Fig 11 cascades", bench_cascade.main),
        ("Tables 3-4 / Fig 12 join rewrite", bench_join_rewrite.main),
        ("S5.4 agg short-circuit", bench_agg_shortcircuit.main),
        ("beyond-paper: hybrid k-pass join", bench_hybrid_join.main),
        ("HTTP serving front-end + NL2SQL",
         lambda: bench_http.main(["--quick"])),
    ]
    if not args.skip_serving:
        from benchmarks import bench_backend, bench_obs, bench_serving
        benches.append(("S2 serving throughput", bench_serving.main))
        benches.append(("S2 decode backend: continuous vs static batching",
                        lambda: bench_backend.main(["--quick"])))
        benches.append(("observability: overhead + billing reconciliation",
                        lambda: bench_obs.main(["--quick"])))

    t0 = time.perf_counter()
    for name, fn in benches:
        print(f"\n######## {name} ########")
        fn()
    path = update_trajectory()
    print(f"\nall benchmarks done in {time.perf_counter() - t0:.1f}s "
          f"(trajectory -> {os.path.relpath(path, REPO_ROOT)})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
