"""Roofline analysis from the compiled dry-run artifact (no hardware).

Three terms per (arch × shape × mesh), all in seconds:

  compute    = HLO_FLOPs_per_device / peak_FLOP/s
  memory     = HLO_bytes_per_device / HBM_bw
  collective = Σ_ops collective_bytes_per_device(op) / link_bw

Sources: ``compiled.cost_analysis()`` for FLOPs/bytes (XLA reports the
per-partition module after SPMD partitioning).  Collective bytes are NOT in
cost_analysis — we parse the post-optimization HLO text and sum the wire
bytes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute, applying ring-algorithm factors over the actual
replica-group size parsed per op.

Hardware constants: one table of published per-chip peaks keyed by
``jax.Device.device_kind`` (:data:`PEAKS`).  A device that is not in it
is an error, never a default.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Any, Dict, List, Optional, Tuple

# Published peaks of one chip, keyed by ``device_kind``.  Source: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, and
# 1,600 Gbit/s of chip-to-chip interconnect over four links (50 GB/s each).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """Peak FLOP/s, HBM bytes/s and ICI bytes/s per link of one chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

# "bf16[2048,4096]{1,0}" -> bytes
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_COLL_RE = re.compile(
    r"^\s*(?:%)?(\S+)\s*=\s*((?:\([^)]*\)|\S+))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(",
    re.MULTILINE)

_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_ITOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


@dataclasses.dataclass
class CollectiveOp:
    kind: str
    result_bytes: int
    group_size: int

    @property
    def wire_bytes(self) -> float:
        """Ring-algorithm bytes over the slowest link, per device."""
        n = max(self.group_size, 1)
        if n == 1:
            return 0.0
        b = self.result_bytes
        if self.kind == "all-reduce":
            # reduce-scatter + all-gather: 2(n-1)/n × full buffer
            return 2.0 * (n - 1) / n * b
        if self.kind == "all-gather":
            # result is the gathered buffer; each device receives (n-1)/n
            return (n - 1) / n * b
        if self.kind == "reduce-scatter":
            # result is the scattered shard; wire = (n-1) shards
            return (n - 1) * b
        if self.kind == "all-to-all":
            return (n - 1) / n * b
        if self.kind == "collective-permute":
            return float(b)
        return float(b)


def parse_collectives(hlo_text: str) -> List[CollectiveOp]:
    out: List[CollectiveOp] = []
    for m in _COLL_RE.finditer(hlo_text):
        shape_str, kind = m.group(2), m.group(3)
        line_end = hlo_text.find("\n", m.end())
        line = hlo_text[m.start():line_end if line_end > 0 else None]
        gsize = 1
        gm = _GROUPS_RE.search(line)
        if gm:
            gsize = len([x for x in gm.group(1).split(",") if x.strip()])
        else:
            gm2 = _GROUPS_ITOTA_RE.search(line)
            if gm2:
                gsize = int(gm2.group(2))
        out.append(CollectiveOp(kind, _shape_bytes(shape_str), gsize))
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    device_kind: str             # key into PEAKS
    hlo_flops: float             # per device
    hlo_bytes: float             # per device
    collective_bytes: float      # per device (wire)
    model_flops: float           # analytic 6ND / 2ND (global)
    collectives: Dict[str, int] = dataclasses.field(default_factory=dict)
    memory_per_device: Optional[Dict[str, float]] = None
    detail: Optional[Dict[str, Any]] = None

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / peaks(self.device_kind)["flops"]

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / peaks(self.device_kind)["hbm_bw"]

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / peaks(self.device_kind)["ici_bw"]

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flop_ratio(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs × chips): how much compiled compute is
        'useful' — catches remat/redundancy waste."""
        total = self.hlo_flops * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def mfu_bound(self) -> float:
        """Model-FLOPs utilisation *upper bound* at the roofline: useful
        FLOPs / (chips × peak × bound-time)."""
        denom = self.chips * peaks(self.device_kind)["flops"] * self.t_bound
        return self.model_flops / denom if denom else 0.0

    def to_dict(self) -> Dict[str, Any]:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips, "device_kind": self.device_kind,
            "hlo_flops_per_dev": self.hlo_flops,
            "hlo_bytes_per_dev": self.hlo_bytes,
            "collective_bytes_per_dev": self.collective_bytes,
            "model_flops": self.model_flops,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flop_ratio": self.useful_flop_ratio,
            "mfu_bound": self.mfu_bound,
            "collectives": self.collectives,
            "memory_per_device": self.memory_per_device,
            "detail": self.detail,
        }


def analyze(cell, lowered=None, compiled=None, *,
            device_kind: str) -> Roofline:
    """Run the lower/compile (if not supplied) and extract the terms.

    FLOPs/bytes/collectives come from the trip-count-aware HLO walk
    (``hlo_cost``) because XLA's ``cost_analysis()`` counts while-loop
    bodies once (verified empirically) — scan-over-layers models would be
    undercounted by ~num_layers.  The raw cost_analysis numbers are kept
    in the record for cross-reference.
    """
    from repro.launch.hlo_cost import analyze_hlo
    if lowered is None:
        lowered = cell.lower()
    if compiled is None:
        compiled = lowered.compile()
    chips = cell.mesh.size
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    hc = analyze_hlo(hlo)
    flops = hc.flops
    byts = hc.bytes
    wire = hc.collective_bytes
    counts = dict(hc.collective_counts)
    by_path = {
        "collective_by_path": dict(sorted(hc.collective_by_path.items(),
                                          key=lambda kv: -kv[1])[:8]),
        "flops_by_path": dict(sorted(hc.flops_by_path.items(),
                                     key=lambda kv: -kv[1])[:8]),
        "xla_cost_analysis_flops": float(cost.get("flops", 0.0)),
        "xla_cost_analysis_bytes": float(cost.get("bytes accessed", 0.0)),
    }
    mem = None
    try:
        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": float(getattr(ma, "argument_size_in_bytes", 0)),
            "output_bytes": float(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": float(getattr(ma, "temp_size_in_bytes", 0)),
            "peak_bytes": float(
                getattr(ma, "peak_memory_in_bytes",
                        getattr(ma, "temp_size_in_bytes", 0))),
        }
    except Exception:
        pass
    mesh_name = "x".join(str(s) for s in cell.mesh.devices.shape)
    return Roofline(
        arch=cell.arch, shape=cell.shape.name, mesh=mesh_name, chips=chips,
        device_kind=device_kind, hlo_flops=flops, hlo_bytes=byts,
        collective_bytes=wire,
        model_flops=cell.model_flops, collectives=counts,
        memory_per_device=mem, detail=by_path)


def fmt_row(r: Roofline) -> str:
    return (f"{r.arch:22s} {r.shape:12s} {r.mesh:9s} "
            f"C={r.t_compute*1e3:9.2f}ms M={r.t_memory*1e3:9.2f}ms "
            f"X={r.t_collective*1e3:9.2f}ms -> {r.bottleneck:10s} "
            f"useful={r.useful_flop_ratio:6.2%} mfu_bound={r.mfu_bound:6.2%}")
