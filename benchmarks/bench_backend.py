"""Decode-backend benchmark: continuous batching vs static batching.

The workload is the serving pattern the paper's §2 platform actually
faces: a burst of AI_COMPLETE generations of wildly mixed lengths (a few
long tails among many short answers) followed by a queue of short
AI_FILTER scores.  Static batching drains each batch to its longest
member and only then starts the filters; the continuous backend retires
finished sequences every step, back-fills the freed slots, and chunk-
prefills incoming prompts between decode steps.

Gates (``--check``, on by default):
  * result rows byte-identical between the two backends;
  * total credits conserved (identical per-request metering);
  * >= 2x tokens/sec and lower p95 latency for continuous batching.

The results JSON includes the backend telemetry (step counts, slot
occupancy, KV-block peaks).  It runs the CPU smoke preset, so its times
are host timings, not device numbers.
"""
from __future__ import annotations

import argparse
import copy
import sys
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from benchmarks.common import fmt_table, save_result
from repro.inference.backend import COMPLETE, SCORE, Request, Result
from repro.inference.engine import JaxInferenceEngine

ARCH = "proxy-8b"


def _mixed_workload(n_complete: int = 32, n_score: int = 16,
                    long_every: int = 8, long_tokens: int = 96,
                    short_tokens: int = 4) -> List[Request]:
    """Short completions with a long tail every ``long_every`` requests
    (so every static chunk drains to the long one), then short filters
    queued behind all of them."""
    reqs: List[Request] = []
    rid = 0
    for i in range(n_complete):
        rid += 1
        mt = long_tokens if i % long_every == 0 else short_tokens
        reqs.append(Request(
            f"summarize support ticket {i}: the product arrived late and",
            ARCH, COMPLETE, max_tokens=mt, request_id=rid))
    for i in range(n_score):
        rid += 1
        reqs.append(Request(
            f"is review {i} about shipping delays and refunds?",
            ARCH, SCORE, request_id=rid))
    return reqs


def _prefill_heavy_workload(n: int = 24) -> List[Request]:
    """Long prompts, single-pass scores plus tiny completions — the step
    mix is dominated by chunked prefill."""
    body = ("the customer writes a long and detailed account of the "
            "delivery problem, the packaging damage and the support calls "
            "that followed, asking for a refund. ")
    reqs: List[Request] = []
    for i in range(n):
        kind = SCORE if i % 3 else COMPLETE
        reqs.append(Request(
            f"[case {i}] {body} is this case about shipping?", ARCH, kind,
            max_tokens=4, request_id=i + 1))
    return reqs


def _row_key(r: Result) -> Tuple:
    return (r.request_id, r.kind, r.text, r.score, r.tokens_in,
            r.tokens_out, r.credits)


def _serve(engine: JaxInferenceEngine, reqs: List[Request]
           ) -> Tuple[float, List[Result]]:
    batch = [copy.deepcopy(r) for r in reqs]
    t0 = time.perf_counter()
    out = engine.submit_batch(batch)
    return time.perf_counter() - t0, out


def _measure(engine: JaxInferenceEngine, reqs: List[Request],
             repeats: int = 3) -> Dict[str, Any]:
    _serve(engine, reqs)                      # warm every jit key
    dt, out = min((_serve(engine, reqs) for _ in range(repeats)),
                  key=lambda p: p[0])         # best-of-N rides out load spikes
    toks = sum(r.tokens_in + r.tokens_out for r in out)
    lat = np.asarray([r.latency_s for r in out])
    return {
        "wall_s": dt, "tokens": toks, "tokens_per_s": toks / dt,
        "p50_ms": float(np.percentile(lat, 50) * 1e3),
        "p95_ms": float(np.percentile(lat, 95) * 1e3),
        "credits": sum(r.credits for r in out),
        "rows": [_row_key(r) for r in out],
        "backend": engine.backend_stats(),
    }


def run(check: bool = True, quick: bool = False) -> Dict[str, Any]:
    mixes = {
        "decode_heavy": _mixed_workload(
            n_complete=24 if quick else 32, n_score=8 if quick else 16),
        "prefill_heavy": _prefill_heavy_workload(12 if quick else 24),
    }
    results: Dict[str, Any] = {}
    table = []
    for mix_name, reqs in mixes.items():
        static = JaxInferenceEngine(ARCH, smoke=True, max_seq=192,
                                    backend="static", seed=0)
        cont = JaxInferenceEngine(ARCH, smoke=True, max_seq=192,
                                  backend="continuous", seed=0)
        ms = _measure(static, reqs)
        mc = _measure(cont, reqs)
        identical = ms["rows"] == mc["rows"]
        speedup = mc["tokens_per_s"] / ms["tokens_per_s"]
        results[mix_name] = {
            "requests": len(reqs),
            "static": {k: v for k, v in ms.items() if k != "rows"},
            "continuous": {k: v for k, v in mc.items() if k != "rows"},
            "rows_identical": identical,
            "credits_conserved": ms["credits"] == mc["credits"],
            "tokens_per_s_speedup": speedup,
            "p95_ratio": mc["p95_ms"] / ms["p95_ms"],
        }
        for name, m in (("static", ms), ("continuous", mc)):
            table.append({
                "mix": mix_name, "backend": name,
                "tok/s": round(m["tokens_per_s"], 1),
                "p50_ms": round(m["p50_ms"], 1),
                "p95_ms": round(m["p95_ms"], 1),
                "identical": identical,
            })
        if check:
            assert identical, f"{mix_name}: result rows differ"
            assert ms["credits"] == mc["credits"], \
                f"{mix_name}: credits not conserved"
        if check and mix_name == "decode_heavy":
            assert speedup >= 2.0, \
                f"{mix_name}: continuous speedup {speedup:.2f}x < 2x"
            assert mc["p95_ms"] < ms["p95_ms"], \
                f"{mix_name}: continuous p95 not lower"
    print(fmt_table(table, ["mix", "backend", "tok/s", "p50_ms", "p95_ms",
                            "identical"]))
    path = save_result("bench_backend", results)
    print(f"saved {path}")
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller workload (CI smoke)")
    ap.add_argument("--no-check", action="store_true",
                    help="skip the speedup/identity gates")
    args = ap.parse_args(argv)
    run(check=not args.no_check, quick=args.quick)
    return 0


if __name__ == "__main__":
    sys.exit(main())
