"""A whole run on the CPU at a tiny size, sound and with the timed path
broken underneath: ``correct`` must follow.  And the entry point itself
refuses to run without an accelerator."""
import os
import subprocess
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import harness, tokenizer  # noqa: E402
from chipbench.tests import tiny  # noqa: E402

# Limits for the tiny model on the CPU: its gaps read up to ~5e-3 (see
# test_chipbench_reference.py), so none lies over the 0.01 tolerance; the
# faults below move them by 0.5 or more.
LIMITS = {
    "qwen3-8b.filter": {"sample": {"score": 24}, "tolerance": {"score": 0.01},
                        "limits": {"rows_wrong": 0, "score_gap": 0.02,
                                   "score_gap_share": 0.25}},
    "qwen3-32b.dashboard": {
        "sample": {"score": 12, "complete": 8, "classify": 6},
        "limits": {"rows_wrong": 0, "score_gap": 0.02, "token_gap": 0.02,
                   "label_lp_gap": 0.02}},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("checkout"), LIMITS)


def run(root, workload, seconds=4.0):
    return harness.run(workload, 2**31 + 21, seconds, False, root=root,
                       chip_peaks=tiny.PEAKS)


@pytest.mark.parametrize("workload", sorted(LIMITS))
def test_a_sound_run_is_correct(root, workload):
    out = run(root, workload)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    names = {m["name"] for m in harness.load_cell(workload, root).end_to_end}
    assert set(out["metrics"]) == names
    assert all(v["value"] > 0 for v in out["metrics"].values())


def _alter_score(monkeypatch):
    """A SCORE altered where it is produced: the yes logit of the step's
    readback moved before the score is taken."""
    from repro.inference.continuous import ContinuousBatcher
    finish = ContinuousBatcher._finish_prefill

    def altered(self, s, row, *a, **kw):
        row = np.array(row)
        row[tokenizer.YES_ID] += 1.0
        return finish(self, s, row, *a, **kw)

    monkeypatch.setattr(ContinuousBatcher, "_finish_prefill", altered)


def _alter_token(monkeypatch):
    """A COMPLETE token altered where it is produced: the greedy pick of a
    decode step replaced by the next id."""
    from repro.inference.continuous import ContinuousBatcher
    consume = ContinuousBatcher._consume

    def altered(self, s, *a, **kw):
        if s.req.kind == "complete" and len(s.out) == 3:
            s.cur = (s.cur + 1) % self.engine.cfg.vocab_size
        return consume(self, s, *a, **kw)

    monkeypatch.setattr(ContinuousBatcher, "_consume", altered)


def _alter_label(monkeypatch):
    """AI_CLASSIFY label scores altered where they are produced."""
    from repro.inference.engine import JaxInferenceEngine
    seqlp = JaxInferenceEngine._sequence_logprob

    def altered(self, prompts, continuations):
        lps, used = seqlp(self, prompts, continuations)
        return [lp - 0.5 * (i % 2) for i, lp in enumerate(lps)], used

    monkeypatch.setattr(JaxInferenceEngine, "_sequence_logprob", altered)


@pytest.mark.parametrize("workload,fault,number", [
    ("qwen3-8b.filter", _alter_score, "score_gap"),
    ("qwen3-32b.dashboard", _alter_token, "token_gap"),
    ("qwen3-32b.dashboard", _alter_label, "label_lp_gap"),
], ids=["score", "token", "label"])
def test_an_altered_answer_is_not_correct(root, monkeypatch, workload, fault,
                                          number):
    fault(monkeypatch)
    out = run(root, workload)
    assert not out["correct"]
    check = out["checks"][number]
    assert check["value"] > check["limit"]


def test_entry_point_refuses_without_an_accelerator(tmp_path):
    """Exits non-zero and prints no result when JAX finds only the CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(tiny.REPO / "chipbench/run.py"), "--workload",
         "qwen3-8b.filter", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "accelerator" in proc.stderr


def test_a_control_is_judged_by_the_same_limits(root):
    """fp8 matrix products read score gaps of ~0.03 or more on the tiny
    model, over the 0.02 the run's limits allow: the control comes out not
    correct there, and correct under limits loose enough to let it by."""
    out = harness.run("qwen3-8b.filter", 2**31 + 21, 4.0, False, root=root,
                      chip_peaks=tiny.PEAKS, controls=("fp8",))
    assert out["correct"] and out["control_correct"] == {"fp8": False}
    assert out["control"]["fp8"]["score_gap"] > 0.02
    assert len(out["gaps"]["fp8"]["score"]) == len(
        out["gaps"]["served"]["score"]) > 0
    loose = tiny.make_root(root.parent / "loose", {"qwen3-8b.filter": {
        "sample": {"score": 24},
        "limits": {"rows_wrong": 0, "score_gap": 1.0}}})
    out = harness.run("qwen3-8b.filter", 2**31 + 21, 4.0, False, root=loose,
                      chip_peaks=tiny.PEAKS, controls=("fp8",))
    assert out["control_correct"] == {"fp8": True}


@pytest.mark.parametrize("control_correct,rc", [(False, 0), (True, 1)])
def test_calibration_fails_where_a_control_passes(monkeypatch, tmp_path,
                                                  control_correct, rc):
    import jax
    from chipbench import calibrate
    from repro.launch import compile_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(compile_cache, "enable", lambda: None)
    monkeypatch.setattr(jax.config, "update", lambda *a: None)
    monkeypatch.setattr(calibrate, "ROOT", str(tmp_path))

    def fake_run(workload, seed, seconds, trace, *, controls):
        return {"correct": True, "failed": 0, "attempted": 1,
                "checks": {"score_gap": {"value": 0.01, "limit": 0.1}},
                "control": {c: {"score_gap": 0.5} for c in controls},
                "control_correct": {c: control_correct and c == "int8"
                                    for c in controls},
                "metrics": {}, "device": {"memory_peak_bytes": 1},
                "gaps": {}}

    monkeypatch.setattr(harness, "run", fake_run)
    assert calibrate.main(["--workload", "w", "--seconds", "1",
                           "--seeds", "1", "2"]) == rc
    lines = (tmp_path / "chiprun_out/calibrate_w.jsonl").read_text().splitlines()
    assert len(lines) == 2


def test_a_compile_in_the_window_is_not_correct(root, monkeypatch):
    """A step shape the warm-up left out compiles inside the window."""
    monkeypatch.setattr(harness, "warm_up", lambda *a, **kw: None)
    out = run(root, "qwen3-8b.filter")
    assert not out["correct"]
    assert out["checks"]["window_compiles"]["value"] > 0
