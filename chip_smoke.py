"""Bring-up check: the served AISQL path on a TPU at proxy-8b's widths.

    python chip_smoke.py              # one chip (what a checkout must pass)
    python chip_smoke.py --chips 4    # four one-chip replicas vs one replica

One process, no children.  It builds ``proxy-8b`` at every published width
(d_model 4096, 32 heads, 8 KV heads, head_dim 128, d_ff 14336, vocab
128256) with 16 of its 32 layers, about 9.1 GB of bf16 weights drawn from
``--seed``, and serves SQL queries through ``ServingEngine`` -- the path
``repro.serve.http`` wraps -- over ``launch/serve.build_catalog``'s seeded
tables.  It checks the rows and the kernels on the way.  The times and
bytes it prints are bring-up readings, not benchmark numbers.

The last line of standard output is one JSON object naming the device.
Where JAX finds no TPU, or any check fails, the script exits non-zero and
prints no such line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
LAYERS = 16                 # depth cut: 16 of proxy-8b's 32 layers
ROWS = 16                   # rows per seeded catalog table
# AI_FILTER queries as (table, text column, prompt template).  SCORE
# requests: chunked prefill through the continuous backend.  The weights
# are random, so which rows pass is arbitrary: each filter's rows are
# checked against static one-shot prefill's scores, not for being many.
FILTERS = {
    "filter": ("reviews", "text", "Is this review positive? {0}"),
    # longer prompts (about 300 tokens): a wider paged-cache gather
    "filter_long": ("articles", "body", "Is this article about business? {0}"),
}
QUERIES = {
    name: f"SELECT t.id FROM {table} AS t WHERE AI_FILTER(PROMPT('{tmpl}', "
          f"t.{col}))" for name, (table, col, tmpl) in FILTERS.items()}
QUERIES.update({
    # COMPLETE requests: decode steps through the Pallas flash-decode kernel
    "complete": "SELECT r.id, AI_COMPLETE(PROMPT('Summarize in one line: "
                "{0}', r.text), max_tokens => 16) AS summary "
                "FROM reviews AS r LIMIT 8",
    # CLASSIFY requests: static label scoring
    "classify": "SELECT a.id, AI_CLASSIFY(a.body, ['business', 'science', "
                "'sports', 'politics']) AS topic FROM articles AS a LIMIT 8",
})
# Pallas flash-decode vs decode_attention_ref: the kernel writes bfloat16
# (8 significant bits, so one ulp is 2^-7 relative at worst) and the two
# sides round the softmax weights at different points; 2^-6 relative plus
# absolute is a few bf16 ulps, while a masking or block-skipping error
# moves outputs by O(0.1).
DECODE_TOL = 2.0 ** -6
# similarity_topk Pallas vs its reference: both see the same float32 unit
# vectors, and the MXU may round their entries to bfloat16 (2^-9 relative
# each); summed over D=768 products of size ~1/768 that is ~1e-4 on a
# cosine.  1e-3 still fails a wrong block, mask or tie-break by far.
TOPK_TOL = 1e-3
# Continuous (chunked) vs static one-shot prefill SCOREs: both run bfloat16
# activations (8 significant bits) through 16 layers, and the order in
# which the MXU sums follows each step's shapes.  check_filter reads both
# sides of that on every run: static prefill against itself at batch 1 and
# at a full batch (0.012 on a v5e), and chunked prefill against static
# prefill at batch 1 (0.006).  3e-2 is 2.5 times the larger, while changing
# one word of the prompt moved scores by up to 0.18.
SCORE_TOL = 3e-2


FAILED = []                 # checks that failed; the run goes on to report all


def _reading(label: str, value) -> None:
    print(f"[bring-up reading] {label}: {value}", flush=True)


def _check(ok: bool, what: str) -> None:
    if not ok:
        FAILED.append(what)
    print(f"[check] {what}: {'ok' if ok else 'FAILED'}", flush=True)


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, duration_secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration_secs
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def build_engine(cfg, seed: int, device):
    import jax
    from repro.inference.engine import JaxInferenceEngine
    t0 = time.perf_counter()
    engine = JaxInferenceEngine(cfg, engine_id=f"{cfg.name}#0", seed=seed,
                                device=device)
    jax.block_until_ready(engine.params)
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(engine.params))
    _reading("model", f"{cfg.name}: {cfg.num_layers} of 32 layers, d_model "
             f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads} "
             f"x {cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}")
    _reading("param bytes", n_bytes)
    _reading("engine build s (init compile included)",
             time.perf_counter() - t0)
    _check(engine.backend == "continuous",
           "the engine serves SCORE/COMPLETE through continuous batching")
    return engine


def check_flash_decode(cfg, seed: int, smax: int = 2048) -> None:
    """Pallas flash-decode against the jnp reference at the model's widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.decode_attention.ops import flash_decode
    from repro.kernels.decode_attention.ref import decode_attention_ref
    B, H, KV, hd = 8, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kq, kk, kv, kl = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(kq, (B, 1, H, hd), jnp.bfloat16)
    k = jax.random.normal(kk, (B, smax, KV, hd), jnp.bfloat16)
    v = jax.random.normal(kv, (B, smax, KV, hd), jnp.bfloat16)
    lengths = jax.random.randint(kl, (B,), 1, smax + 1, jnp.int32)
    got = np.asarray(flash_decode(q, k, v, lengths, impl="pallas"),
                     np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(decode_attention_ref(
            q[:, 0].astype(jnp.float32),
            jnp.swapaxes(k, 1, 2).astype(jnp.float32),
            jnp.swapaxes(v, 1, 2).astype(jnp.float32), lengths))[:, None]
    err = float(np.max(np.abs(got - want) / (1.0 + np.abs(want))))
    _reading("flash_decode max err / (1 + |ref|)", err)
    _check(np.isfinite(got).all() and err <= DECODE_TOL,
           f"flash_decode (pallas) matches decode_attention_ref at H={H} "
           f"KV={KV} hd={hd} Smax={smax} within {DECODE_TOL}")


def check_similarity_topk(seed: int, n: int = 100_000, d: int = 768,
                          q: int = 64, k: int = 10) -> None:
    """The semantic index's top-k kernel at arctic-embed-m's width.  No JAX
    engine hosts an embedding model yet, so AI_EMBED cannot be served:
    the kernel runs directly on seeded vectors."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.similarity_topk.ops import similarity_topk
    from repro.kernels.similarity_topk.ref import l2_normalize
    kq, kc = jax.random.split(jax.random.PRNGKey(seed + 1))
    queries = jax.random.normal(kq, (q, d), jnp.float32)
    corpus = jax.random.normal(kc, (n, d), jnp.float32)
    vals, idx = similarity_topk(queries, corpus, k, impl="pallas")
    with jax.default_matmul_precision("highest"):
        ref_vals, _ = similarity_topk(queries, corpus, k, impl="reference")
        sims = l2_normalize(queries) @ l2_normalize(corpus).T
        true = jnp.take_along_axis(sims, idx, axis=1)
    vals, ref_vals, true = (np.asarray(x) for x in (vals, ref_vals, true))
    err = max(float(np.max(np.abs(vals - ref_vals))),
              float(np.max(np.abs(vals - true))))
    _reading("similarity_topk max err vs reference", err)
    _check(err <= TOPK_TOL and np.all(np.diff(vals, axis=1) <= 0),
           f"similarity_topk (pallas) returns the reference's top-{k} of "
           f"N={n} D={d} within {TOPK_TOL} (AI_EMBED is not served: no JAX "
           f"engine hosts arctic-embed-m)")


def serve_queries(scheduler, model: str):
    """Run every query through a fresh ServingEngine over ``scheduler``,
    one at a time; returns ``{name: [row tuples]}``."""
    from repro.core.serving import ServingConfig, ServingEngine
    from repro.launch.serve import build_catalog
    cfg = ServingConfig(default_model=model, proxy_model=model)
    rows = {}
    with ServingEngine(build_catalog(ROWS), scheduler, cfg=cfg) as serving:
        for name, sql in QUERIES.items():
            ticket = serving.submit("smoke", sql)
            table = ticket.result()
            _check(ticket.exception() is None, f"query {name!r} succeeded")
            if name not in FILTERS:
                _check(table.num_rows > 0, f"query {name!r} returned rows "
                       f"({table.num_rows})")
            _reading(f"query {name!r} wall s", ticket.wall_s)
            rows[name] = [tuple(r.values()) for r in table.rows()]
    return rows


def check_decode_step_has_kernel(engine) -> None:
    batcher = engine._batcher
    keys = [k for k in batcher._step_specs if k[0] == "cb_decode"]
    _check(bool(keys), "the served queries ran decode steps")
    _, _, sds = batcher._step_specs[keys[0]]
    hlo = engine._jit_cache[keys[0]].lower(*sds).compile().as_text()
    _check("tpu_custom_call" in hlo,
           "the compiled decode step contains the Pallas tpu_custom_call")


def check_filter(engine, name: str, served_rows) -> None:
    """One AI_FILTER's SCOREs, continuous (chunked) against static one-shot
    prefill of one row at a time, and its served rows against those static
    scores' verdicts (a row whose static score lies within SCORE_TOL of the
    0.5 cut may go either way).  Static prefill of full batches is read
    too: how far it moves from batch 1 is the rounding spread SCORE_TOL
    rests on."""
    import numpy as np
    from repro.inference.backend import SCORE, Request
    from repro.launch.serve import build_catalog
    table_name, col, tmpl = FILTERS[name]
    table = build_catalog(ROWS).table(table_name)
    reqs = [Request(tmpl.format(t), engine.arch, SCORE, request_id=i + 1)
            for i, t in enumerate(table.column(col))]
    cont = np.asarray([r.score for r in engine.submit_batch(reqs)])
    static = np.asarray([engine._score_batch([r])[0].score for r in reqs])
    batched = np.asarray(
        [r.score for i in range(0, len(reqs), engine.max_batch)
         for r in engine._score_batch(reqs[i:i + engine.max_batch])])
    spread = float(np.max(np.abs(batched - static)))
    err = float(np.max(np.abs(cont - static)))
    _reading(f"{name}: static SCORE batch {engine.max_batch} vs batch 1 "
             f"max abs diff", spread)
    _reading(f"{name}: continuous vs static batch 1 SCORE max abs diff", err)
    _check(spread <= SCORE_TOL, f"{name}: static prefill's SCOREs move "
           f"less than {SCORE_TOL} with the batch's shape")
    _check(err <= SCORE_TOL, f"{name}: continuous SCOREs match static "
           f"one-shot prefill within {SCORE_TOL}")
    ids = np.asarray(table.column("id"))
    want = set(ids[static >= 0.5].tolist())
    either = set(ids[np.abs(static - 0.5) <= SCORE_TOL].tolist())
    got = {int(r[0]) for r in served_rows}
    _reading(f"{name}: rows served / expected / near the cut",
             f"{len(got)} / {len(want)} / {len(either)} of {len(ids)}")
    _check(not (got ^ want) - either,
           f"{name}: the served rows are the rows static prefill passes")


def run_one_chip(cfg, seed: int) -> None:
    import jax
    from repro.inference.scheduler import Scheduler
    device = jax.devices()[0]
    engine = build_engine(cfg, seed, device)
    check_flash_decode(cfg, seed)
    check_similarity_topk(seed)
    sched = Scheduler()
    sched.register(engine)
    rows = serve_queries(sched, engine.arch)
    check_decode_step_has_kernel(engine)
    for name in FILTERS:
        check_filter(engine, name, rows[name])
    _reading("backend stats", engine.backend_stats())
    stats = device.memory_stats() or {}
    _reading("peak_bytes_in_use", stats.get("peak_bytes_in_use",
                                            "not reported"))


def run_four_chips(cfg, seed: int) -> None:
    """Four one-chip replicas behind the Scheduler give the rows of one."""
    import jax
    from repro.inference.api import make_engine_client
    from repro.inference.scheduler import Scheduler
    devices = jax.devices()
    _check(len(devices) == 4, f"four devices ({len(devices)} found)")
    t0 = time.perf_counter()
    client = make_engine_client((cfg,), replicas=4, seed=seed)
    engines = client.scheduler.replicas(cfg.name)
    jax.block_until_ready([e.params for e in engines])
    _reading("4 replicas build s", time.perf_counter() - t0)
    for e, dev in zip(engines, devices):
        _reading(f"{e.engine_id} on device {dev.id}: bytes_in_use",
                 (dev.memory_stats() or {}).get("bytes_in_use",
                                                "not reported"))
        arrays = jax.tree.leaves((e.params, e._batcher.kv.pool))
        _check(all(a.devices() == {dev} for a in arrays),
               f"{e.engine_id}'s params and KV pool live on device {dev.id}")
    rows4 = serve_queries(client.scheduler, cfg.name)
    served = [e.total_requests for e in engines]
    _reading("requests served per replica", served)
    _check(all(n > 0 for n in served),
           "least-loaded routing sent work to every replica")
    one = Scheduler()
    one.register(engines[0])
    before = engines[0].total_requests
    rows1 = serve_queries(one, cfg.name)
    _check(engines[0].total_requests - before == sum(served),
           "one replica served the same requests again, uncached")
    _check(rows4 == rows1, "four replicas return the rows of one replica")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: compare four one-chip replicas with one")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights and the data")
    args = ap.parse_args(argv)
    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {jax.default_backend()!r}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import base as cfgs
    from repro.launch import compile_cache
    _reading("compile cache dir", compile_cache.enable())
    meter = CompileMeter()
    dev = jax.devices()[0]
    _reading("device", f"{dev.platform} {dev.device_kind} x "
             f"{len(jax.devices())}")
    cfg = cfgs.depth_cut("proxy-8b", LAYERS)
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(cfg, args.seed)
    else:
        run_one_chip(cfg, args.seed)
    _reading("backend compiles", meter.compiles)
    _reading("backend compile s", meter.seconds)
    _reading("persistent cache hits", meter.cache_hits)
    _reading("total s", time.perf_counter() - t0)
    if FAILED:
        print(f"chip_smoke: {len(FAILED)} check(s) failed: {FAILED}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
