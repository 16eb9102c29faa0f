"""Roofline terms from compiled artifacts (see EXPERIMENTS.md §Roofline).

All three terms are *per-chip seconds* on TPU v5e constants:

  compute_s    = flops_per_chip / 197e12
  memory_s     = bytes_accessed_per_chip / 819e9
  collective_s = collective_bytes_per_chip / 50e9   (1 ICI link, worst case)

``cost_analysis()`` on a partitioned compile reports per-chip numbers
(SPMD = one program per chip), which is what we want.

Scan bodies are cost-counted once by XLA, so totals are assembled from
unrolled *probe* compiles (launch/dryrun.py): a base compile with one
unit per stack and one with two; per-unit delta x unit count + base =
exact post-optimization totals.  ``combine_costs`` implements that.

``model_flops`` is the brief's useful-work definition (6·N·D train /
2·N·D inference, N = active params), used for the usefulness ratio
MODEL_FLOPS / HLO_FLOPs.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from repro.configs.base import InputShape, ModelConfig
from repro.core.energy_model import TPU_V5E, HardwareSpec
from repro.roofline.hlo import buffer_traffic_bytes, collective_bytes


@dataclasses.dataclass
class CellCosts:
    """Per-chip costs of one compiled step.

    ``hbm_bytes`` is the buffer-traffic model (top-level result buffers of
    the optimized HLO, write+read — see roofline.hlo); ``bytes_accessed``
    is XLA's unfused upper bound, kept for reference.
    """
    flops: float
    hbm_bytes: float
    coll_bytes: float
    bytes_accessed: float = 0.0
    coll_by_kind: Dict[str, float] = dataclasses.field(default_factory=dict)

    def __add__(self, other: "CellCosts") -> "CellCosts":
        kinds = set(self.coll_by_kind) | set(other.coll_by_kind)
        return CellCosts(
            self.flops + other.flops,
            self.hbm_bytes + other.hbm_bytes,
            self.coll_bytes + other.coll_bytes,
            self.bytes_accessed + other.bytes_accessed,
            {k: self.coll_by_kind.get(k, 0) + other.coll_by_kind.get(k, 0)
             for k in kinds})

    def scaled(self, a: float) -> "CellCosts":
        return CellCosts(self.flops * a, self.hbm_bytes * a,
                         self.coll_bytes * a, self.bytes_accessed * a,
                         {k: v * a for k, v in self.coll_by_kind.items()})


def costs_from_compiled(compiled) -> CellCosts:
    ca = compiled.cost_analysis() or {}
    text = compiled.as_text()
    stats = collective_bytes(text)
    return CellCosts(
        flops=float(ca.get("flops", 0.0)),
        hbm_bytes=buffer_traffic_bytes(text),
        coll_bytes=stats.total_bytes,
        bytes_accessed=float(ca.get("bytes accessed", 0.0)),
        coll_by_kind=dict(stats.bytes_by_kind))


def combine_costs(base: CellCosts,
                  deltas: List[Tuple[CellCosts, int]],
                  corrections: Optional[CellCosts] = None) -> CellCosts:
    """base + sum((probe2 - base) * (count - 1)) + analytic corrections."""
    total = base
    for probe2, count in deltas:
        delta = CellCosts(
            max(0.0, probe2.flops - base.flops),
            max(0.0, probe2.hbm_bytes - base.hbm_bytes),
            max(0.0, probe2.coll_bytes - base.coll_bytes),
            max(0.0, probe2.bytes_accessed - base.bytes_accessed),
            {k: max(0.0, v - base.coll_by_kind.get(k, 0.0))
             for k, v in probe2.coll_by_kind.items()})
        total = total + delta.scaled(count - 1)
    if corrections is not None:
        total = total + corrections
    return total


# -- useful-work model -----------------------------------------------------------

def model_flops(cfg: ModelConfig, shape: InputShape) -> float:
    """6·N_active·D (train) / 2·N_active·D (inference), D = tokens."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    tokens = shape.global_batch            # one new token per row
    return 2.0 * n * tokens


# -- report ------------------------------------------------------------------------

@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    costs: CellCosts
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    step_s: float                     # max of the three (no-overlap bound)
    model_flops: float
    useful_ratio: float               # MODEL_FLOPS / global HLO flops
    roofline_fraction: float          # compute_s / step_s
    note: str = ""

    def row(self) -> str:
        return (f"{self.arch:18s} {self.shape:12s} {self.mesh:10s} "
                f"C={self.compute_s:9.4f}s M={self.memory_s:9.4f}s "
                f"X={self.collective_s:9.4f}s dom={self.dominant:10s} "
                f"useful={self.useful_ratio:6.3f} "
                f"roofline={self.roofline_fraction:6.3f}")


def roofline_report(arch: str, shape: InputShape, mesh_name: str,
                    chips: int, costs: CellCosts, cfg: ModelConfig,
                    hw: HardwareSpec = TPU_V5E, note: str = ""
                    ) -> RooflineReport:
    compute_s = costs.flops / hw.peak_flops
    memory_s = costs.hbm_bytes / hw.hbm_bw
    collective_s = costs.coll_bytes / hw.ici_bw
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    step_s = max(terms.values())
    mf = model_flops(cfg, shape)
    global_flops = costs.flops * chips
    return RooflineReport(
        arch=arch, shape=shape.name, mesh=mesh_name, chips=chips,
        costs=costs, compute_s=compute_s, memory_s=memory_s,
        collective_s=collective_s, dominant=dominant, step_s=step_s,
        model_flops=mf,
        useful_ratio=mf / global_flops if global_flops else 0.0,
        roofline_fraction=compute_s / step_s if step_s else 0.0,
        note=note)
