"""A checkout-shaped directory whose cells run the real harness on the CPU
at a size a test can hold: the benchmark's own cells, traffic and
architecture modules, with each Qwen3 configuration cut to two layers of
width 64."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
TINY = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "intermediate_size": 160, "vocab_size": 512,
        "num_hidden_layers": 2}
PEAKS = {"flops": 1e12, "hbm_bytes_per_s": 1e11}   # a test's stand-in


def conf(max_seq: int = 256) -> dict:
    c = json.loads((REPO / "chipbench/configs/qwen3-8b.json").read_text())
    c.update(TINY, serving={"max_seq": max_seq})
    return c


def make_root(path: Path, limits: dict) -> Path:
    """``limits``: workload -> its limits file's object."""
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    (path / "chipbench/configs").mkdir(parents=True)
    (path / "chipbench/limits").mkdir()
    shutil.copytree(REPO / "chipbench/traffic", path / "chipbench/traffic")
    shutil.copytree(REPO / "chipbench/reference", path / "chipbench/reference",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (path / "chipbench/configs/tiny.json").write_text(json.dumps(conf()))
    for c in spec["configs"]:
        c["file"] = "chipbench/configs/tiny.json"
    (path / "BENCHMARK.json").write_text(json.dumps(spec))
    for name, obj in limits.items():
        (path / f"chipbench/limits/{name}.json").write_text(json.dumps(obj))
    return path
