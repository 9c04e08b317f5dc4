"""The program's spans on the profiler's clock, and the dispatch locks' waits.

Every span the program opens is also a ``jax.profiler`` annotation, with
tracing off as with it on, so a profiler trace names what the host did
in each stretch the device sat idle.  A thread that blocks on the request
pipeline's or the scheduler's lock is timed: per pipeline, per query, and
as a span.  Neither lock is held across an engine call, so sessions'
dispatches overlap.
"""
import copy
import glob
import os
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import Catalog, ServingConfig, ServingEngine
from repro.inference.backend import (CLASSIFY, COMPLETE, EMBED, SCORE,
                                     Request)
from repro.inference.engine import JaxInferenceEngine
from repro.inference.scheduler import Scheduler
from repro.inference.simulator import SimulatedBackend
from repro.obs import SPAN_KINDS, Observability, TickClock, walk_spans
from repro.tables.table import Table
from _loop_join import join_mid_loop

FILTER = ("SELECT t.id FROM t WHERE "
          "AI_FILTER(PROMPT('is this interesting? {0}', t.text))")


def _catalog(n=8):
    rng = np.random.default_rng(0)
    return Catalog({"t": Table({
        "id": np.arange(n),
        "text": [f"row {i} says something" for i in range(n)],
        "_truth": rng.random(n) < 0.5,
        "_difficulty": np.full(n, 0.05),
    }, name="t")})


def _host_event_names(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    return {ev.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


def _hold(lock, entered, seconds):
    with lock:
        entered.set()
        time.sleep(seconds)


def _contend(lock, call, seconds=0.05):
    """``call()`` while another thread holds ``lock`` for ``seconds``."""
    entered = threading.Event()
    holder = threading.Thread(target=_hold, args=(lock, entered, seconds))
    holder.start()
    assert entered.wait(10)
    call()
    holder.join(10)
    assert not holder.is_alive()


def test_spans_reach_the_profiler_with_tracing_off(tmp_path):
    cont = JaxInferenceEngine("proxy-8b", smoke=True, max_seq=96, seed=3)
    static = JaxInferenceEngine("proxy-8b", smoke=True, max_seq=96, seed=3,
                                backend="static")
    wave = [Request("finish this sentence", "proxy-8b", COMPLETE,
                    max_tokens=3, request_id=1),
            Request("is the sky blue?", "proxy-8b", SCORE, request_id=2),
            Request("pick a colour", "proxy-8b", CLASSIFY,
                    labels=("red", "blue"), request_id=3),
            Request("embed me", "proxy-8b", EMBED, request_id=4)]
    sched = Scheduler()
    sched.register(cont)
    serving = ServingEngine(_catalog(), sched, cfg=ServingConfig(
        default_model="proxy-8b", proxy_model="proxy-8b",
        obs=Observability(enabled=False)))
    try:
        with jax.profiler.trace(str(tmp_path)):
            # a second caller joins the first's step loop: engine.join
            join_mid_loop(cont, wave, wave[:2])
            static.submit_batch(copy.deepcopy(wave[:2]))
            ticket = serving.submit("acme", FILTER)
            ticket.result(timeout=300)
            _contend(serving.pipeline._lock, serving.pipeline.flush)
            _contend(sched._lock, lambda: sched.submit(
                [Request("is it?", "proxy-8b", SCORE, request_id=5)]))
    finally:
        serving.close()
    assert ticket.report.trace is None          # nothing was recorded
    names = _host_event_names(str(tmp_path))
    new = {k for k in SPAN_KINDS
           if k.startswith("engine.") or k.endswith(".lock_wait")}
    assert len(new) == 15
    assert new <= names, sorted(new - names)
    assert {"query", "execute", "pipeline.dispatch",
            "dispatch.replica"} <= names


class _Straggler(SimulatedBackend):
    """A simulated backend that takes ``straggle_s`` per batch."""
    straggle_s = 0.5

    def submit_batch(self, requests):
        time.sleep(self.straggle_s)
        return super().submit_batch(requests)


def _serving(workers, obs=None):
    sched = Scheduler()
    sched.register(_Straggler(seed=0))
    return ServingEngine(_catalog(), sched, cfg=ServingConfig(
        workers=workers, obs=obs or Observability(enabled=False)))


def _contending_pair(serving):
    """Two tenants' queries at once, each tenant's session made before;
    returns their tickets and the pair's wall seconds."""
    for tenant in ("acme", "globex"):
        serving.submit(tenant, FILTER.replace("interesting", "dull")).result(
            timeout=60)
    t0 = time.perf_counter()
    tickets = [serving.submit(t, FILTER.replace("interesting",
                                                f"interesting to {t}"))
               for t in ("acme", "globex")]
    for t in tickets:
        t.result(timeout=60)
    return tickets, time.perf_counter() - t0


def test_contending_sessions_wait_on_the_pipeline_lock():
    """Two sessions' straggling dispatches overlap: neither lock is held
    across the engine call, so neither query waits out the other's."""
    straggle = _Straggler.straggle_s
    with _serving(workers=2) as serving:
        tickets, wall = _contending_pair(serving)
        stats = serving.pipeline.stats_snapshot()
    assert all(t.report.ai_calls > 0 for t in tickets)
    assert wall < 1.6 * straggle            # serialized: 2 x straggle
    waits = [t.report.lock_wait_s for t in tickets]
    assert max(waits) < 0.1 * straggle
    assert stats["lock_wait_s"] < 0.1 * straggle


def test_lone_query_waits_for_no_lock():
    with _serving(workers=1) as serving:
        ticket = serving.submit("acme", FILTER)
        ticket.result(timeout=60)
        stats = serving.pipeline.stats_snapshot()
    assert ticket.report.lock_wait_s == 0.0
    assert stats["lock_waits"] == 0 and stats["lock_wait_s"] == 0.0


class _Watched:
    """A reentrant lock that notes when another thread finds it held."""

    def __init__(self):
        self._lock = threading.RLock()
        self.contended = threading.Event()

    def acquire(self, blocking=True, timeout=-1):
        if self._lock.acquire(blocking=False):
            return True
        self.contended.set()
        return blocking and self._lock.acquire(timeout=timeout)

    def release(self):
        self._lock.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()


def test_lock_wait_is_in_the_waiting_query_span_tree():
    """A query that finds the pipeline's lock held, here by this thread,
    records the wait as a span in its own tree."""
    obs = Observability(clock=TickClock)
    with _serving(workers=2, obs=obs) as serving:
        serving.submit("acme", FILTER).result(timeout=60)   # session made
        lock = serving.pipeline._lock = _Watched()
        with lock:
            ticket = serving.submit("acme", FILTER.replace("interesting",
                                                           "odd"))
            assert lock.contended.wait(30)
            time.sleep(0.01)
        ticket.result(timeout=60)
    assert ticket.report.lock_wait_s > 0
    kinds = [s["kind"] for s in walk_spans(ticket.report.trace)]
    assert "pipeline.lock_wait" in kinds
