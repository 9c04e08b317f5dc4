"""An architecture is a file: the harness loads ``reference/<model_type>.py``
from the checkout a cell's files came from, so a configuration of a new
``model_type`` is added with its module and no edit to the harness."""
import dataclasses
import json
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from chipbench import correct, harness  # noqa: E402
from chipbench.reference import qwen3  # noqa: E402
from chipbench.tests import tiny  # noqa: E402

FILTER = {"sample": {"score": 24}, "tolerance": {"score": 0.01},
          "limits": {"rows_wrong": 0, "score_gap": 0.02,
                     "score_gap_share": 0.25}}


def add_config(root, name: str, model_type: str) -> str:
    """A configuration of ``model_type`` and its filter cell, added to the
    checkout at ``root`` as a later PR would add them: files and entries."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    conf = dict(tiny.conf(), model_type=model_type)
    (root / f"chipbench/configs/{name}.json").write_text(json.dumps(conf))
    spec["configs"].append({"name": name, "source": "tiny",
                            "file": f"chipbench/configs/{name}.json",
                            "reduced": [], "why": "tiny"})
    workload = f"{name}.filter"
    spec["workloads"].append({"name": workload, "config": name,
                              "traffic": "filter", "chips": 1, "why": "tiny"})
    for m in spec["end_to_end"]:
        if "qwen3-8b.filter" in m.get("workloads", ()):
            m["workloads"].append(workload)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (root / f"chipbench/limits/{workload}.json").write_text(json.dumps(FILTER))
    return workload


def test_a_new_architecture_is_added_by_files_alone(tmp_path, monkeypatch):
    """A copy of Qwen3's module under another ``model_type``, present only
    in this checkout, runs a tiny cell to ``correct``; its check numbers
    equal Qwen3's reference on the same served answers and seed."""
    root = tiny.make_root(tmp_path, {})
    (root / "chipbench/reference/tinyqwen.py").write_bytes(
        (tiny.REPO / "chipbench/reference/qwen3.py").read_bytes())
    workload = add_config(root, "tiny-copy", "tinyqwen")
    cell = harness.load_cell(workload, root)
    assert cell.arch.__file__ == str(root / "chipbench/reference/tinyqwen.py")
    check, by_qwen3 = correct.check, []

    def both(cell, seed, records, served, **kw):
        by_qwen3.append(check(dataclasses.replace(cell, arch=qwen3), seed,
                              records, served, **kw))
        return check(cell, seed, records, served, **kw)

    monkeypatch.setattr(correct, "check", both)
    out = harness.run(workload, 2**31 + 21, 4.0, False, root=root,
                      chip_peaks=tiny.PEAKS)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and set(out["metrics"]) == {"rows_per_s",
                                                            "setup_s"}
    assert "score_gap" in out["checks"]
    assert out["checks"] == by_qwen3[0].checks


def test_an_unknown_model_type_names_the_missing_module(tmp_path):
    root = tiny.make_root(tmp_path, {})
    workload = add_config(root, "tiny-none", "nosuchmodel")
    want = root / "chipbench/reference/nosuchmodel.py"
    with pytest.raises(FileNotFoundError, match=str(want)):
        harness.load_cell(workload, root)
