"""Semantic-operator runtime: RequestPipeline coalescing / dedup / flush,
load-aware scheduling, and eager-vs-pipelined end-to-end equivalence."""
import threading

import numpy as np
import pytest

from repro.core import AisqlEngine, Catalog, CascadeConfig, ExecConfig
from repro.data import datasets as D
from repro.inference.api import CortexClient, make_simulated_client
from repro.inference.backend import CLASSIFY, COMPLETE, SCORE, Request
from repro.inference.pipeline import (PipelineConfig, RequestPipeline,
                                      ResultFuture)
from repro.inference.scheduler import Scheduler
from repro.inference.simulator import SimulatedBackend


def make_pipeline(max_batch=512, dedup=True, seed=0, models=None):
    sched = Scheduler()
    sched.register(SimulatedBackend(models=models, seed=seed))
    pipe = RequestPipeline(sched, PipelineConfig(max_batch=max_batch,
                                                 dedup=dedup))
    return sched, pipe


def score_reqs(n, model="proxy-8b", prefix="row"):
    return [Request(f"{prefix} {i}", model, SCORE) for i in range(n)]


# ---------------------------------------------------------------------------
# RequestPipeline unit tests
# ---------------------------------------------------------------------------


def test_micro_batches_coalesce_into_one_submit():
    sched, pipe = make_pipeline()
    futures = []
    for lo in range(0, 100, 10):          # ten 10-row micro-batches
        futures.extend(pipe.submit_many(score_reqs(10, prefix=f"b{lo}")))
    assert sched.submits == 0             # nothing dispatched yet
    assert not futures[0].done()
    first = futures[0].result()           # barrier flush
    assert 0.0 <= first.score <= 1.0
    assert sched.submits == 1             # all 100 in one engine batch
    assert all(f.done() for f in futures)
    assert pipe.stats.batch_size_hist == {100: 1}
    assert pipe.stats.flushes_on_barrier == 1
    assert pipe.stats.queue_wait_s >= 0.0


def test_flush_on_size_threshold():
    sched, pipe = make_pipeline(max_batch=32)
    futs = pipe.submit_many(score_reqs(80))
    # 80 enqueued at once: the size flush drains the whole queue in
    # right-sized batches of <= 32
    assert sched.submits == 3
    assert all(f.done() for f in futs)
    assert pipe.stats.flushes_on_size == 1
    assert sorted(pipe.stats.batch_size_hist) == [16, 32]


def test_per_model_queues_dispatch_separately():
    sched, pipe = make_pipeline()
    fa = pipe.submit_many(score_reqs(5, model="proxy-8b"))
    fb = pipe.submit_many(score_reqs(5, model="oracle-70b"))
    fa[0].result()
    # the barrier is scoped to the demanded future's model queue: the
    # other model's queue keeps coalescing until its own barrier
    assert sched.submits == 1
    assert all(f.done() for f in fa)
    assert not any(f.done() for f in fb)
    fb[0].result()
    assert sched.submits == 2             # one model-pure batch each
    assert all(f.done() for f in fa + fb)


def test_dedup_inflight_and_memo_cache():
    sched, pipe = make_pipeline()
    f1 = pipe.submit(Request("same prompt", "proxy-8b", SCORE))
    f2 = pipe.submit(Request("same prompt", "proxy-8b", SCORE))
    r1, r2 = f1.result(), f2.result()
    assert r1.score == r2.score
    assert pipe.stats.dispatched == 1     # one engine execution
    assert pipe.stats.inflight_hits == 1
    # a third arrival after completion is served from the memo cache
    f3 = pipe.submit(Request("same prompt", "proxy-8b", SCORE))
    assert f3.done()                      # resolved without any dispatch
    assert pipe.stats.cache_hits == 1
    assert pipe.stats.dedup_hits == 2
    assert sched.submits == 1


class _Gated(SimulatedBackend):
    """A simulated backend whose batches wait for ``gate``; ``entered`` is
    set once a batch reached it."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.entered = threading.Event()
        self.gate = threading.Event()

    def submit_batch(self, requests):
        self.entered.set()
        assert self.gate.wait(30)
        return super().submit_batch(requests)


def _mid_dispatch():
    """A pipeline whose one queued request is being dispatched, held in
    the backend, by another thread; returns the pieces and that thread."""
    sched = Scheduler()
    backend = _Gated(seed=0)
    sched.register(backend)
    pipe = RequestPipeline(sched, PipelineConfig())
    f1 = pipe.submit(Request("same prompt", "proxy-8b", SCORE))
    flusher = threading.Thread(target=pipe.flush)
    flusher.start()
    assert backend.entered.wait(30)
    return sched, backend, pipe, f1, flusher


def test_duplicate_mid_dispatch_attaches_and_resolves():
    sched, backend, pipe, f1, flusher = _mid_dispatch()
    # the dispatch holds no lock: a duplicate arriving now attaches
    f2 = pipe.submit(Request("same prompt", "proxy-8b", SCORE))
    assert pipe.stats.inflight_hits == 1 and not f2.done()
    backend.gate.set()
    flusher.join(30)
    assert f2.result().score == f1.result().score
    assert sched.submits == 1 and pipe.stats.dispatched == 1


def test_result_waits_for_another_threads_dispatch():
    sched, backend, pipe, f1, flusher = _mid_dispatch()
    got = []
    waiter = threading.Thread(target=lambda: got.append(f1.result()))
    waiter.start()
    waiter.join(0.2)
    assert waiter.is_alive() and not got   # blocked, not failed
    backend.gate.set()
    waiter.join(30)
    flusher.join(30)
    assert got and got[0] is f1.result()
    assert sched.submits == 1              # the waiter dispatched nothing


def test_lru_hot_key_survives_cache_pressure():
    """Regression: FIFO eviction used to drop the oldest *half* of the
    cache including hot keys — eviction is LRU now (hits move a key to
    the recent end), so a constantly-hit key outlives churn."""
    sched, pipe = make_pipeline()
    pipe.cfg.cache_size = 4
    pipe.submit(Request("HOT", "proxy-8b", SCORE)).result()
    for i in range(8):                     # 2x the capacity in cold keys
        pipe.submit(Request("HOT", "proxy-8b", SCORE))     # keep it hot
        pipe.submit(Request(f"cold {i}", "proxy-8b", SCORE)).result()
    dispatched = pipe.stats.dispatched
    f = pipe.submit(Request("HOT", "proxy-8b", SCORE))
    assert f.done()                        # still a cache hit
    assert pipe.stats.dispatched == dispatched
    # the cache never exceeds its cap and the hot key is the freshest
    assert len(pipe.cache_keys()) <= 4


def test_lru_evicts_the_least_recently_used_key():
    sched, pipe = make_pipeline()
    pipe.cfg.cache_size = 2
    pipe.submit(Request("a", "proxy-8b", SCORE)).result()
    pipe.submit(Request("b", "proxy-8b", SCORE)).result()
    pipe.submit(Request("a", "proxy-8b", SCORE))       # refresh a
    pipe.submit(Request("c", "proxy-8b", SCORE)).result()  # evicts b
    d0 = pipe.stats.dispatched
    assert pipe.submit(Request("a", "proxy-8b", SCORE)).done()
    assert pipe.stats.dispatched == d0                 # a survived
    pipe.submit(Request("b", "proxy-8b", SCORE)).result()
    assert pipe.stats.dispatched == d0 + 1             # b was evicted


def test_cache_ttl_expires_memoized_results():
    import time as _time
    sched, pipe = make_pipeline()
    pipe.cfg.cache_ttl_s = 0.03
    pipe.submit(Request("p", "proxy-8b", SCORE)).result()
    assert pipe.submit(Request("p", "proxy-8b", SCORE)).done()  # fresh hit
    _time.sleep(0.04)
    f = pipe.submit(Request("p", "proxy-8b", SCORE))
    assert not f.done()                    # expired: goes back to the queue
    f.result()
    assert pipe.stats.cache_expired == 1
    assert pipe.stats.dispatched == 2


def test_dedup_respects_fingerprint_fields():
    sched, pipe = make_pipeline()
    futs = [pipe.submit(Request("p", "proxy-8b", SCORE)),
            pipe.submit(Request("p", "oracle-70b", SCORE)),      # model
            pipe.submit(Request("p", "proxy-8b", COMPLETE)),     # kind
            pipe.submit(Request("p", "proxy-8b", CLASSIFY,
                                labels=("a", "b")))]             # labels
    [f.result() for f in futs]
    assert pipe.stats.dedup_hits == 0
    assert pipe.stats.dispatched == 4


def test_client_meters_only_dispatched_work():
    sched = Scheduler()
    sched.register(SimulatedBackend(seed=0))
    client = CortexClient(sched, pipeline=PipelineConfig())
    s = client.filter_scores(["dup", "dup", "dup"], model="oracle-70b")
    assert s.shape == (3,) and len(set(s.tolist())) == 1
    assert client.ai_calls == 1           # two were deduplicated
    assert client.pipeline.stats.dedup_hits == 2


def test_sync_wrappers_match_eager_results():
    prompts = [f"is row {i} good?" for i in range(40)]
    eager = make_simulated_client()
    piped = make_simulated_client(pipelined=True)
    np.testing.assert_allclose(eager.filter_scores(prompts),
                               piped.filter_scores(prompts))
    assert piped.scheduler.submits == 1


# ---------------------------------------------------------------------------
# Scheduler: least-loaded routing, batch splitting, id collisions
# ---------------------------------------------------------------------------


def test_scheduler_least_loaded_balances_replicas():
    sched = Scheduler()
    a = SimulatedBackend(models=["proxy-8b"], seed=0)
    b = SimulatedBackend(models=["proxy-8b"], seed=1)
    sched.register(a)
    sched.register(b)
    for i in range(6):
        sched.submit([Request(f"q{i}", "proxy-8b", SCORE, request_id=1)])
    served = [sum(e.calls_by_model.values()) for e in (a, b)]
    assert min(served) > 0                # both replicas took traffic
    # artificially load one replica: new work routes to its peer
    sched._busy_s[id(a)] += 100.0
    before = sum(b.calls_by_model.values())
    sched.submit([Request("q-extra", "proxy-8b", SCORE, request_id=1)])
    assert sum(b.calls_by_model.values()) == before + 1


def test_scheduler_splits_oversized_batch_across_replicas():
    sched = Scheduler()
    a = SimulatedBackend(models=["proxy-8b"], seed=0, batch_parallelism=2)
    b = SimulatedBackend(models=["proxy-8b"], seed=0, batch_parallelism=2)
    sched.register(a)
    sched.register(b)
    # capacity hint per replica = 2 * 32 = 64; 200 requests -> split in two
    reqs = [Request(f"r{i}", "proxy-8b", SCORE, request_id=i + 1)
            for i in range(200)]
    res = sched.submit(reqs)
    assert len(res) == 200
    assert [r.request_id for r in res] == [q.request_id for q in reqs]
    assert sched.splits >= 1
    assert sum(a.calls_by_model.values()) > 0
    assert sum(b.calls_by_model.values()) > 0


def test_scheduler_handles_request_id_collisions():
    sched = Scheduler()
    sched.register(SimulatedBackend(seed=0))
    reqs = [Request(f"prompt {i}", "proxy-8b", SCORE) for i in range(5)]
    assert all(r.request_id == 0 for r in reqs)    # the all-zero default
    res = sched.submit(reqs)
    assert len(res) == 5                  # nothing silently dropped
    assert all(r.request_id == 0 for r in reqs)    # caller ids restored
    assert all(r.request_id == 0 for r in res)
    scores = [r.score for r in res]
    assert len(set(scores)) > 1           # distinct per-prompt results


def test_engine_classify_empty_labels_metered():
    pytest.importorskip("jax")
    from repro.inference.engine import JaxInferenceEngine
    eng = JaxInferenceEngine("proxy-8b", smoke=True, max_seq=64)
    res = eng.submit_batch([Request("no labels here", "proxy-8b", CLASSIFY,
                                    labels=(), request_id=3)])
    assert res[0].label is None
    assert res[0].engine_id == eng.engine_id
    assert res[0].tokens_in > 0
    assert res[0].credits > 0
    assert eng.total_credits > 0
    # a coalesced batch mixing labeled and label-less classify requests
    mixed = eng.submit_batch([
        Request("pick one", "proxy-8b", CLASSIFY, labels=("a", "b"),
                request_id=1),
        Request("nothing to pick", "proxy-8b", CLASSIFY, labels=(),
                request_id=2)])
    assert mixed[0].label in ("a", "b")
    assert mixed[1].label is None and mixed[1].credits > 0


# ---------------------------------------------------------------------------
# End-to-end: eager vs pipelined equivalence + fewer scheduler submits
# ---------------------------------------------------------------------------

_SQL = ("SELECT r.id, AI_CLASSIFY(PROMPT('sentiment of {0}', r.text), "
        "['positive','negative']) AS sentiment "
        "FROM reviews AS r WHERE "
        "AI_FILTER(PROMPT('does {0} express positive sentiment?', r.text)) "
        "AND AI_FILTER(PROMPT('is {0} about a movie?', r.text))")


def _run(pipelined: bool):
    cat = Catalog({"reviews": D.cascade_table("IMDB", rows=600)})
    client = make_simulated_client(pipelined=pipelined)
    eng = AisqlEngine(cat, client)
    out = eng.sql(_SQL)
    rows = sorted(zip(out.column("r.id").tolist(),
                      out.column("sentiment").tolist()))
    return rows, client, eng


def test_pipelined_query_identical_rows_fewer_submits():
    rows_e, client_e, _ = _run(pipelined=False)
    rows_p, client_p, eng_p = _run(pipelined=True)
    assert rows_e == rows_p               # identical result set
    assert len(rows_p) > 0
    assert client_p.scheduler.submits < client_e.scheduler.submits
    rep = eng_p.last_report
    assert rep.pipeline is not None
    assert rep.pipeline["batches"] == client_p.scheduler.submits
    assert rep.pipeline["dispatched"] == rep.ai_calls


def test_repeated_cascade_query_hits_dedup_cache():
    cat = Catalog({"ds": D.cascade_table("NQ", rows=600)})
    client = make_simulated_client(pipelined=True)
    eng = AisqlEngine(cat, client,
                      executor=ExecConfig(use_cascade=True,
                                          cascade=CascadeConfig(seed=0)))
    sql = ("SELECT * FROM ds AS d WHERE "
           "AI_FILTER(PROMPT('answers? {0}', d.text))")
    out1 = eng.sql(sql)
    first = eng.last_report
    assert first.ai_calls > 0
    out2 = eng.sql(sql)                   # the production repeat-query case
    second = eng.last_report
    assert sorted(out1.column("d.id").tolist()) == \
        sorted(out2.column("d.id").tolist())
    assert second.pipeline["dedup_hits"] > 0
    assert second.pipeline["cache_hits"] > 0
    assert second.ai_calls == 0           # fully served from the memo cache
    assert second.ai_credits == 0.0
