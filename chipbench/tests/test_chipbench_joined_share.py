"""The readers of the engine's joined-admission counter, on run data made
by hand; and each reads nothing, without raising, from a program that
lacks the counter."""
import pytest

from chipbench import harness

READERS = ["engine.joined_share", "engine.joined_share.dashboard"]


def _run_data(before, after):
    return harness.RunData(
        cell=None, shape=None, peaks={}, t0=100.0, seconds=10.0, records=[],
        dispatches=[], steps=[], backend=(before, after), pipeline=({}, {}),
        slots=8, prefill_chunk=32, trace=None, tracer=None)


@pytest.mark.parametrize("metric", READERS)
def test_joined_share(metric):
    read = harness.load_reader(metric)
    run = _run_data({"admitted": 40, "joined": 10},
                    {"admitted": 140, "joined": 85})
    # 75 of the 100 sequences admitted in the window joined a running loop
    assert read(run) == pytest.approx(75.0)
    none = {"admitted": 40, "joined": 10}
    assert read(_run_data(none, none)) is None          # none admitted


@pytest.mark.parametrize("metric", READERS)
def test_joined_share_reads_nothing_without_the_counter(metric):
    old = {"prefill_steps": 3, "decode_steps": 4, "admitted": 7}
    assert harness.load_reader(metric)(_run_data(old, old)) is None
