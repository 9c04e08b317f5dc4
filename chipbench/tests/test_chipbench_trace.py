"""The trace reduction against hand-counted traces."""
import json
from pathlib import Path

import pytest

from chipbench import tracereduce as tr

DATA = Path(__file__).resolve().parent / "data"
DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(line, name, start, dur, plane=DEV):
    return tr.Event(plane, line, name, float(start), float(dur))


def test_busy_is_the_union_of_op_intervals():
    events = [
        ev(tr.MODULES_LINE, "jit__prefill_fn(7)", 100, 400),
        ev(tr.OPS_LINE, "fusion.1", 100, 200),
        ev(tr.OPS_LINE, "fusion.2", 250, 100),       # overlaps fusion.1
        ev(tr.OPS_LINE, "_decode_kernel", 400, 100),
        ev(tr.MODULES_LINE, "jit__decode_fn(9)", 700, 200),
        ev(tr.OPS_LINE, "_decode_kernel", 700, 200),
        ev("python", "PjitFunction(_decode_fn)", 560, 120, plane=HOST),
    ]
    r = tr.reduce(events, window=(0.0, 1000.0))
    # [100, 350] + [400, 500] + [700, 900] = 250 + 100 + 200 ns
    assert r.busy_s == pytest.approx(550e-9)
    assert r.window_s == pytest.approx(1000e-9)
    assert r.idle_share == pytest.approx(0.45)
    assert r.module("_prefill_fn") == (1, pytest.approx(400e-9))
    assert r.module("_decode_fn") == (1, pytest.approx(200e-9))
    assert r.op_seconds(r"_decode_kernel") == pytest.approx(300e-9)
    assert r.op_seconds(r"_decode_kernel", "_decode_fn") == pytest.approx(
        200e-9)
    assert r.ops["_prefill_fn/fusion.1"] == pytest.approx(200e-9)
    # gaps: [0,100] [350,400] [500,700] [900,1000]; the longest is named
    # after the host event that covers most of it
    assert r.gaps[0] == ("PjitFunction(_decode_fn)", pytest.approx(200e-9))
    assert sorted(g for _, g in r.gaps) == pytest.approx(
        [50e-9, 100e-9, 100e-9, 200e-9])


def test_default_window_spans_the_device_events():
    events = [ev(tr.MODULES_LINE, "jit_f(1)", 50, 100),
              ev(tr.OPS_LINE, "x", 60, 40)]
    r = tr.reduce(events)
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(40e-9)


def test_window_clips_and_planes_average():
    events = [ev(tr.OPS_LINE, "a", -50, 100), ev(tr.OPS_LINE, "b", 90, 20),
              ev(tr.OPS_LINE, "c", 0, 40, plane="/device:TPU:1")]
    r = tr.reduce(events, window=(0.0, 100.0))
    # TPU:0 busy [0, 50] + [90, 100] = 60 ns, TPU:1 40 ns: mean 50 ns
    assert r.busy_s == pytest.approx(50e-9)


def test_module_names():
    assert tr.module_name("jit__prefill_fn(123)") == "_prefill_fn"
    assert tr.module_name("jit__seqlp_fn") == "_seqlp_fn"


def test_no_device_operations_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce([ev("python", "x", 0, 10, plane=HOST)])


def test_breakdown_lists_at_most_ten():
    events = [ev(tr.OPS_LINE, f"op{i}", i * 10, 5) for i in range(30)]
    b = tr.breakdown(tr.reduce(events, window=(0.0, 300.0)))
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10


def _recorded():
    raw = json.loads((DATA / "trace_small.json").read_text())
    return [tr.Event(**e) for e in raw]


def test_recorded_chip_trace():
    """Two chunked-prefill steps of qwen3-32b on a v5e, as the profiler
    recorded them, against counts made here by other means."""
    import numpy as np
    events = _recorded()
    r = tr.reduce(events)
    ops = [e for e in events if e.line == tr.OPS_LINE]
    lo = min(e.start_ns for e in events if e.plane.startswith("/device"))
    hi = max(e.end_ns for e in events if e.plane.startswith("/device"))
    # busy: a 10 ns timeline marked op by op
    line = np.zeros(int((hi - lo) / 10) + 1, bool)
    for e in ops:
        line[int((e.start_ns - lo) / 10):int((e.end_ns - lo) / 10)] = True
    assert r.window_s == pytest.approx((hi - lo) * 1e-9)
    assert r.busy_s == pytest.approx(line.sum() * 10e-9, rel=1e-3)
    assert 0.0 < r.idle_share < 1.0
    steps = [e for e in events if e.line == tr.MODULES_LINE]
    assert r.module("_prefill_fn") == (
        len(steps), pytest.approx(sum(e.dur_ns for e in steps) * 1e-9))
    # every listed operation ran inside a prefill step
    assert all(k.startswith("_prefill_fn/") for k in r.ops)
    # the MLP's fused matmuls, by their HLO text
    mlp = [e for e in ops if "bf16[8,32,25600]" in e.name.split(" = ")[1][:40]]
    assert mlp and r.op_seconds(r"^\S+ = bf16\[8,32,25600\]") == pytest.approx(
        sum(e.dur_ns for e in mlp) * 1e-9)
