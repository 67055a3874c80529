"""TPU-native hardware menu and cost model.

The paper provisions over a heterogeneous CPU/K80 menu (§6, "CPU costs were
computed by dividing the total hourly cost of an instance by the number of
CPUs ..."). We adapt the menu to a TPU-native fleet (see DESIGN.md §2): a
CPU host core and v5e slices of 1/4/8 chips. The Planner only requires that
hardware has a *total ordering of latency across all batch sizes* (§9) —
the menu below preserves that ordering.

All constants used by the analytic profile backend and the roofline
analysis live here so there is exactly one source of truth.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

# --- TPU v5e chip constants (also used by roofline/analysis.py); the
# device kinds they apply to are mapped in HARDWARE_BY_DEVICE_KIND -------
PEAK_FLOPS_BF16 = 197e12      # FLOP/s per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link
VMEM_BYTES = 128 * 1024**2    # ~128 MiB VMEM per chip
HBM_BYTES = 16 * 1024**3      # 16 GiB per v5e chip

# CPU host core (measured-profile fallback / non-acceleratable stages)
CPU_PEAK_FLOPS = 0.15e12      # effective fp32 FLOP/s for one host core
CPU_MEM_BW = 25e9             # bytes/s effective


@dataclasses.dataclass(frozen=True)
class HardwareType:
    """One entry in the provisioning menu."""

    name: str
    chips: int                 # accelerator chips (0 => CPU)
    peak_flops: float          # FLOP/s aggregate
    mem_bw: float              # bytes/s aggregate (HBM or host DRAM)
    ici_bw: float              # bytes/s per link between chips (0 if n/a)
    cost_per_hr: float         # $/hr, marginal-cost accounting as in §6
    # Fixed per-batch overhead (dispatch + RPC + PCIe/ICI latency floor).
    overhead_s: float

    @property
    def cost_per_s(self) -> float:
        return self.cost_per_hr / 3600.0

    def is_accelerator(self) -> bool:
        return self.chips > 0


# Menu ordered by descending capability; BestHardware == first entry.
# Prices follow public v5e on-demand pricing shape ($1.20/chip-hr) and a
# $0.05/core-hr host CPU (paper's marginal-cost accounting).
HARDWARE_MENU: Tuple[HardwareType, ...] = (
    # 4x4 ICI slice — the smallest slice that holds >=140 GB of bf16
    # weights (qwen2-72b) with cache headroom.
    HardwareType("tpu-v5e-16", 16, 16 * PEAK_FLOPS_BF16, 16 * HBM_BW,
                 ICI_BW, cost_per_hr=16 * 1.20, overhead_s=0.0022),
    HardwareType("tpu-v5e-8", 8, 8 * PEAK_FLOPS_BF16, 8 * HBM_BW, ICI_BW,
                 cost_per_hr=8 * 1.20, overhead_s=0.0018),
    HardwareType("tpu-v5e-4", 4, 4 * PEAK_FLOPS_BF16, 4 * HBM_BW, ICI_BW,
                 cost_per_hr=4 * 1.20, overhead_s=0.0015),
    HardwareType("tpu-v5e-1", 1, PEAK_FLOPS_BF16, HBM_BW, 0.0,
                 cost_per_hr=1.20, overhead_s=0.0012),
    HardwareType("cpu-1", 0, CPU_PEAK_FLOPS, CPU_MEM_BW, 0.0,
                 cost_per_hr=0.05, overhead_s=0.0005),
)

HARDWARE_BY_NAME: Dict[str, HardwareType] = {h.name: h for h in HARDWARE_MENU}


def get_hardware(name: str) -> HardwareType:
    try:
        return HARDWARE_BY_NAME[name]
    except KeyError:
        raise KeyError(
            f"unknown hardware {name!r}; menu: {sorted(HARDWARE_BY_NAME)}"
        ) from None


# jax ``Device.device_kind`` -> the menu entry one such device provides.
# A kind missing here has unmeasured constants: an error, never a default.
HARDWARE_BY_DEVICE_KIND: Dict[str, str] = {
    "cpu": "cpu-1",
    "TPU v5 lite": "tpu-v5e-1",     # TPU v5e as JAX reports it
}


def hardware_for_device(device) -> str:
    """Menu name for one jax device (e.g. ``jax.devices()[0]``)."""
    try:
        return HARDWARE_BY_DEVICE_KIND[device.device_kind]
    except KeyError:
        raise KeyError(
            f"unknown device kind {device.device_kind!r}; known: "
            f"{sorted(HARDWARE_BY_DEVICE_KIND)}") from None


def cheaper_hardware(name: str) -> Tuple[str, ...]:
    """Hardware strictly cheaper than `name`, most capable first.

    Used by the Planner's DowngradeHW action.
    """
    cur = get_hardware(name)
    return tuple(
        h.name for h in HARDWARE_MENU if h.cost_per_hr < cur.cost_per_hr
    )
