"""The readers of the engine's step-loop counters and the dispatch-lock
waits, on run data made by hand; and each reads nothing, without raising,
from a program that lacks what it reads."""
from types import SimpleNamespace

import pytest

from chipbench import harness

SLOTS = 8


def _run(backend=({}, {}), reports=(), t0=100.0, seconds=10.0):
    records = [harness.Record(query=None, start=start, sent=start, ok=ok,
                              ticket=SimpleNamespace(report=rep))
               for start, ok, rep in reports]
    return harness.RunData(
        cell=None, shape=None, peaks={}, t0=t0, seconds=seconds,
        records=records, dispatches=[], steps=[], backend=backend,
        pipeline=({}, {}), slots=SLOTS, prefill_chunk=32, trace=None,
        tracer=None)


def _counters(**kw):
    base = {"prefill_steps": 0, "decode_steps": 0, "prefill_rows": 0,
            "prefill_tokens": 0, "loop_s": 0.0, "readback_s": 0.0}
    return {**base, **kw}


def test_prefill_slot_share():
    read = harness.load_reader("engine.prefill_slot_share")
    run = _run((_counters(prefill_steps=10, prefill_rows=30),
                _counters(prefill_steps=110, prefill_rows=530)))
    # 500 prefilling rows over 100 steps of 8 slots
    assert read(run) == pytest.approx(62.5)
    assert read(_run((_counters(), _counters()))) is None   # no steps


def test_host_ms_per_step():
    read = harness.load_reader("engine.host_ms_per_step.dashboard")
    run = _run((_counters(prefill_steps=5, decode_steps=20, loop_s=1.0,
                          readback_s=0.5),
                _counters(prefill_steps=25, decode_steps=220, loop_s=5.0,
                          readback_s=4.1)))
    # (4.0 - 3.6) s of host work over 220 steps
    assert read(run) == pytest.approx(1e3 * 0.4 / 220)
    assert read(_run((_counters(), _counters()))) is None


def test_lock_wait_p50_reads_answered_queries_due_in_the_window():
    read = harness.load_reader("pipeline.lock_wait_p50_s.dashboard")

    def rep(w):
        return SimpleNamespace(lock_wait_s=w)

    run = _run(reports=[(101.0, True, rep(0.1)), (102.0, True, rep(0.3)),
                        (103.0, True, rep(0.2)),
                        (104.0, False, rep(9.0)),     # failed
                        (120.0, True, rep(9.0)),      # due after the window
                        (105.0, True, None)])         # no report
    assert read(run) == pytest.approx(0.2)
    assert read(_run()) is None


@pytest.mark.parametrize("metric", [
    "engine.prefill_slot_share", "engine.host_ms_per_step.dashboard"])
def test_step_readers_read_nothing_without_the_counters(metric):
    old = {"prefill_steps": 3, "decode_steps": 4, "prefill_tokens": 9}
    assert harness.load_reader(metric)(_run((old, old))) is None


def test_lock_wait_reads_nothing_from_reports_without_it():
    read = harness.load_reader("pipeline.lock_wait_p50_s.dashboard")
    run = _run(reports=[(101.0, True, SimpleNamespace(ai_calls=3))])
    assert read(run) is None
