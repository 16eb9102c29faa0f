"""Analytical energy model for accelerators without a power API.

This is the TPU-native adaptation of the paper's "built-in sensor" idea
(DESIGN.md §2): where NVML exposes measured watts, a TPU chip exposes an
exact *compiled cost profile* (XLA ``cost_analysis()``), and energy is
modeled from it:

    E_step = flops * pj_per_flop
           + hbm_bytes * pj_per_hbm_byte
           + ici_bytes * pj_per_ici_byte        (dynamic energy)
    E_wall = idle_w * seconds * chips           (static energy)
    E      = E_wall + E_step_total

The same FLOPs/bytes terms feed the roofline analysis (repro.roofline), so
the §Roofline deliverable and the energy numbers are one set of facts.

Coefficients are order-of-magnitude literature values for a 5nm-class
accelerator, and are explicitly *modeled* quantities — every consumer of
this module carries the ``kind="modeled"`` label.  A site with physical
calibration (the paper's PowerSensor2 role) can construct a custom
:class:`EnergyModel`.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Per-chip performance envelope (roofline peaks) + power envelope."""

    name: str
    peak_flops: float          # FLOP/s (bf16 matmul)
    hbm_bw: float              # bytes/s
    ici_bw: float              # bytes/s per link
    hbm_bytes: float           # HBM capacity per chip
    idle_w: float              # static board power
    peak_w: float              # max sustained board power


# Published v5e peaks (Google Cloud documentation, "TPU v5e"): 197 TFLOP/s
# bf16, 819 GB/s HBM, 16 GB HBM per chip; ~50 GB/s/link ICI.
TPU_V5E = HardwareSpec(
    name="tpu-v5e",
    peak_flops=197e12,
    hbm_bw=819e9,
    ici_bw=50e9,
    hbm_bytes=16 * 2 ** 30,
    idle_w=60.0,
    peak_w=200.0,
)

# Chips keyed by ``jax.Device.device_kind`` as the TPU runtime reports it.
HARDWARE_BY_DEVICE_KIND = {
    "TPU v5 lite": TPU_V5E,
    "TPU v5e": TPU_V5E,
}


def hardware_for(device_kind: str) -> HardwareSpec:
    """The :class:`HardwareSpec` of a device kind.  A kind not in the
    table is an error, never a default: peaks of another chip would
    model its joules wrong without a sign."""
    try:
        return HARDWARE_BY_DEVICE_KIND[device_kind]
    except KeyError:
        raise ValueError(
            f"no HardwareSpec for device kind {device_kind!r} (known: "
            f"{sorted(HARDWARE_BY_DEVICE_KIND)})") from None


@dataclasses.dataclass(frozen=True)
class EnergyModel:
    """Energy coefficients on top of a :class:`HardwareSpec`."""

    hw: HardwareSpec = TPU_V5E
    pj_per_flop: float = 0.55       # bf16 MXU FLOP, incl. datapath
    pj_per_hbm_byte: float = 15.0   # HBM3-class access energy
    pj_per_ici_byte: float = 30.0   # serdes + switch energy

    def dynamic_joules(self, flops: float, hbm_bytes: float,
                       ici_bytes: float = 0.0) -> float:
        """Dynamic (activity-proportional) energy of one step, one chip."""
        return (flops * self.pj_per_flop
                + hbm_bytes * self.pj_per_hbm_byte
                + ici_bytes * self.pj_per_ici_byte) * 1e-12

    def static_joules(self, seconds: float, chips: int = 1) -> float:
        """Idle-floor energy over a wall-clock interval."""
        return self.hw.idle_w * seconds * chips

    def step_joules(self, flops: float, hbm_bytes: float, ici_bytes: float,
                    seconds: float, chips: int = 1) -> float:
        """Total modeled energy for a step spanning ``seconds`` wall time.

        The dynamic component is capped so implied average power never
        exceeds the board envelope — the model must not claim power the
        hardware cannot draw.
        """
        dyn = self.dynamic_joules(flops, hbm_bytes, ici_bytes)
        static = self.static_joules(seconds, chips)
        if seconds > 0:
            cap = (self.hw.peak_w - self.hw.idle_w) * seconds * chips
            dyn = min(dyn, cap)
        return static + dyn

    def step_watts(self, flops: float, hbm_bytes: float, ici_bytes: float,
                   seconds: float, chips: int = 1) -> float:
        if seconds <= 0:
            return self.hw.idle_w * chips
        return self.step_joules(flops, hbm_bytes, ici_bytes, seconds,
                                chips) / seconds
