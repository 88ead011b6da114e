"""Hardware constants and memory math.

These numbers drive (a) the workload classifier — the TPU analogue of the
paper's `S = w_s * n  vs  M` rule — and (b) the roofline analysis.
"""
from __future__ import annotations

import dataclasses

import jax


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Per-chip hardware model used by the planner and the roofline."""

    name: str
    peak_flops_bf16: float  # FLOP/s
    hbm_bytes: int          # per chip
    hbm_bw: float           # bytes/s
    vmem_bytes: int         # per core
    ici_bw_per_link: float  # bytes/s per link
    ici_links: int          # links per chip (torus)

    @property
    def arithmetic_intensity_knee(self) -> float:
        """FLOPs/byte at which compute and HBM rooflines intersect."""
        return self.peak_flops_bf16 / self.hbm_bw


# Target hardware for this reproduction (per task constants):
#   197 TFLOP/s bf16 per chip; 819 GB/s HBM; ~50 GB/s/link ICI.
TPU_V5E = HardwareSpec(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    hbm_bytes=16 * 1024**3,
    hbm_bw=819e9,
    vmem_bytes=128 * 1024**2,
    ici_bw_per_link=50e9,
    ici_links=4,
)

# jax.devices()[0].device_kind -> spec. A TPU kind missing here is an
# error, never a silent v5e.
HARDWARE_BY_KIND = {
    "TPU v5 lite": TPU_V5E,
}


def detect_hardware() -> HardwareSpec:
    """The spec of the TPU this process runs on, looked up by
    ``device_kind``. Off the TPU (the CPU test backend) the planner
    models the v5e, the deployment target."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return TPU_V5E
    try:
        return HARDWARE_BY_KIND[dev.device_kind]
    except KeyError:
        raise ValueError(
            f"no HardwareSpec for TPU kind {dev.device_kind!r}; add one "
            "to repro.utils.mem.HARDWARE_BY_KIND"
        ) from None


def bytes_to_human(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f} {unit}"
        n /= 1024.0
    return f"{n:.2f} PiB"
