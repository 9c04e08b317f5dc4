"""The long-document cell's readers on run data made by hand: a recorded
trace reduced to a few operations, the batcher's counters and one served
request, at Trinity-Mini's published widths; and each reads nothing,
without raising, from a program that lacks what it reads."""
import json
from types import SimpleNamespace

import pytest

from chipbench import flops, harness, tracereduce
from chipbench.reference import afmoe
from chipbench.tests import tiny

PEAKS = {"flops": 197e12, "hbm_bytes_per_s": 819e9}
SHAPE = afmoe.Shape.of(json.loads(
    (tiny.REPO / "chipbench/configs/trinity-mini.json").read_text()))
GROUPED = ('%ragged-dot-none.7 = f32[2048,1024]{1,0} custom-call(%a, %b), '
           'custom_call_target="tpu_custom_call"')
META = ('%ragged-dot-metadata = (s32[129], s32[131]) custom-call(%g), '
        'custom_call_target="tpu_custom_call"')


def _trace(grouped: bool = True):
    """Ten traced steps of 0.25 s in all: two grouped-matmul calls of
    0.09 s and 0.01 s, and three fusions."""
    text = {"_prefill_fn/fusion.3": "%fusion.3 = f32[256,128] fusion(%x)",
            "_prefill_fn/fusion.4": "%fusion.4 = bf16[256,2048] fusion(%y)",
            "_prefill_fn/fusion.5": "%fusion.5 = bf16[8,32,4096] fusion(%z)"}
    ops = {"_prefill_fn/fusion.3": 0.01, "_prefill_fn/fusion.4": 0.02,
           "_prefill_fn/fusion.5": 0.12}
    if grouped:
        text["_prefill_fn/ragged-dot-none.7"] = GROUPED
        text["_prefill_fn/ragged-dot-metadata"] = META
        ops["_prefill_fn/ragged-dot-none.7"] = 0.09
        ops["_prefill_fn/ragged-dot-metadata"] = 0.01
    return tracereduce.Reduced(window_s=1.0, busy_s=0.25,
                               modules={"_prefill_fn": (10, 0.25)},
                               ops=ops, text=text, gaps=[])


def _run(trace=None, backend=({}, {}), dispatches=()):
    cell = SimpleNamespace(max_seq=8192)
    return harness.RunData(
        cell=cell, shape=SHAPE, peaks=PEAKS, t0=0.0, seconds=10.0,
        records=[], dispatches=list(dispatches), steps=[], backend=backend,
        pipeline=({}, {}), slots=8, prefill_chunk=32, trace=trace,
        tracer=None)


STEPS = ({"prefill_steps": 100, "prefill_tokens": 1000},
         {"prefill_steps": 300, "prefill_tokens": 49000})


def _least(kernel_s):
    # 10 traced steps of 200 in the window, 48,000 real tokens among them
    tokens = 48000 * 10 / 200
    f = 6.0 * 2048 * 1024 * tokens * 8 * 4
    b = 3.0 * 128 * 2048 * 1024 * 2 * 4 * 10
    return 100.0 * max(f / PEAKS["flops"], b / PEAKS["hbm_bytes_per_s"]) \
        / kernel_s


def test_expert_roofline_reads_the_grouped_matmuls():
    read = harness.load_reader("moe.expert_roofline.longdoc")
    # the expert weights, 4 MoE layers x 10 steps x 1.61 GB, bound it
    assert read(_run(_trace(), STEPS)) == pytest.approx(_least(0.1))
    assert read(_run(_trace(grouped=False), STEPS)) is None


def test_moe_device_share_reads_the_grouped_matmuls():
    read = harness.load_reader("moe.device_share.longdoc")
    # 0.09 + 0.01 s of the step programs' 0.25 s
    assert read(_run(_trace(), STEPS)) == pytest.approx(40.0)
    assert read(_run(_trace(grouped=False), STEPS)) is None


def test_window_outside_share():
    read = harness.load_reader("attention.window_outside_share.longdoc")
    run = _run(backend=({"window_blocks": 100, "window_blocks_outside": 10},
                        {"window_blocks": 1100,
                         "window_blocks_outside": 260}))
    assert read(run) == pytest.approx(25.0)


def test_mfu_counts_the_afmoe_work_of_the_requests():
    from repro.inference.backend import SCORE, Request, Result
    read = harness.load_reader("mfu.longdoc")
    req = Request("x" * 4999, "trinity-mini", SCORE)
    d = harness.Dispatch(t0=1.0, t1=2.0, requests=[req],
                         results=[Result(0, "trinity-mini", SCORE)])
    want = flops.score(SHAPE, 5000) / (10.0 * PEAKS["flops"]) * 100.0
    assert read(_run(dispatches=[d])) == pytest.approx(want)
    # past the window, a sliding layer reads 2048 keys and a full one all
    assert SHAPE.keys(4999, 1, True) == 2048
    assert SHAPE.keys(4999, 1, False) == 5000


@pytest.mark.parametrize("metric", [
    "moe.expert_roofline.longdoc", "moe.device_share.longdoc",
    "attention.window_outside_share.longdoc"])
def test_readers_read_nothing_from_a_program_without_them(metric):
    """The parent program: no window counters, no expert operations."""
    old = {"prefill_steps": 3, "prefill_tokens": 90}
    empty = tracereduce.Reduced(window_s=1.0, busy_s=0.5,
                                modules={"_prefill_fn": (3, 0.5)},
                                ops={"_prefill_fn/fusion.1": 0.5},
                                text={"_prefill_fn/fusion.1": "%fusion.1"},
                                gaps=[])
    read = harness.load_reader(metric)
    assert read(_run(empty, (old, old))) is None
    assert read(_run(None, (old, old))) is None
