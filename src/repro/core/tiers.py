"""Memory-system specifications for heterogeneous KV-cache placement.

The paper (Table I) models an NVIDIA GH200: HBM3 + NVLink-C2C attached
LPDDR5X. We keep the spec as data so the same latency model runs for the
paper-faithful GH200 configuration (used to validate the paper's claims)
and for TPU-native tier constants (used by the serving stack + roofline).

All bandwidths are bytes/second, capacities in bytes.
"""

from __future__ import annotations

import dataclasses

GB = 1024**3
TB = 1024**4
GBps = 1e9  # vendor bandwidth figures are decimal
TBps = 1e12


@dataclasses.dataclass(frozen=True)
class MemorySystemSpec:
    """Two-tier memory system: HBM + off-package DRAM behind a serial link.

    Attributes mirror the paper's Table I / Section III-A symbols:
      hbm_bw          B_h  — HBM bandwidth
      hbm_capacity         — HBM bytes available to the KV cache
                             (model weights already subtracted)
      link_bw         B_k  — uni-directional serial-link bandwidth
                             (NVLink-C2C / PCIe); full duplex
      dram_bw         B_d  — internal DDR/LPDDR channel bandwidth
      dram_capacity        — off-package DRAM capacity ("sufficiently
                             large" per the paper; enforced anyway)
    """

    name: str
    hbm_bw: float
    hbm_capacity: float
    link_bw: float
    dram_bw: float
    dram_capacity: float

    @property
    def effective_dram_read_bw(self) -> float:
        # Reads from off-package DRAM traverse both the DRAM channels and
        # the serial link; Eq. (4) charges them at min(B_k, B_d).
        return min(self.link_bw, self.dram_bw)

    @property
    def bw_ratio(self) -> float:
        """HBM : effective-DRAM read bandwidth ratio (paper: ~order of 1)."""
        return self.hbm_bw / self.effective_dram_read_bw

    def with_kv_budget(self, kv_bytes: float) -> "MemorySystemSpec":
        """Spec with HBM capacity replaced by an explicit KV budget."""
        return dataclasses.replace(self, hbm_capacity=kv_bytes)


# --- Paper-faithful configuration (Table I) --------------------------------
# "Bandwidth 4.9 TB/s, Capacity 24 GB, Link 900 GB/s, DRAM 500 GB/s,
#  Capacity 480 GB".  The evaluation then says LLaMA-3.1-8B weights (~16 GB)
# leave ~8 GB of HBM for KV cache; we model that by re-budgeting capacity at
# simulation setup (`with_kv_budget`).
GH200 = MemorySystemSpec(
    name="gh200",
    hbm_bw=4.9 * TBps,
    hbm_capacity=24 * GB,
    link_bw=900 * GBps,
    dram_bw=500 * GBps,
    dram_capacity=480 * GB,
)

# --- TPU adaptations --------------------------------------------------------
# TPU v5e: 16 GB HBM @ 819 GB/s; host DDR reached over PCIe Gen4 x16 (~32
# GB/s per direction per chip, 4 chips share a host in v5e-4 trays — we model
# the per-chip share).  Host DDR channel bandwidth is generous relative to
# the link, so min(B_k, B_d) = link, which is the realistic TPU regime.
TPU_V5E = MemorySystemSpec(
    name="tpu_v5e",
    hbm_bw=819 * GBps,
    hbm_capacity=16 * GB,
    link_bw=32 * GBps,
    dram_bw=150 * GBps,
    dram_capacity=512 * GB,
)

# TPU v5p: 95 GB HBM @ 2765 GB/s; PCIe Gen5-class host link.
TPU_V5P = MemorySystemSpec(
    name="tpu_v5p",
    hbm_bw=2765 * GBps,
    hbm_capacity=95 * GB,
    link_bw=64 * GBps,
    dram_bw=300 * GBps,
    dram_capacity=1024 * GB,
)

# TPU v6e (Trillium): 32 GB HBM @ 1640 GB/s.
TPU_V6E = MemorySystemSpec(
    name="tpu_v6e",
    hbm_bw=1640 * GBps,
    hbm_capacity=32 * GB,
    link_bw=64 * GBps,
    dram_bw=300 * GBps,
    dram_capacity=1024 * GB,
)

SPECS = {s.name: s for s in (GH200, TPU_V5E, TPU_V5P, TPU_V6E)}

#: the modeled memory system of each device JAX can report
#: (`device.device_kind`). A CPU run models the paper's own GH200.
SPEC_BY_DEVICE_KIND = {
    "TPU v5 lite": "tpu_v5e",
    "cpu": "gh200",
}


def spec_for_device(device) -> MemorySystemSpec:
    """The spec modeling `device`; an unlisted kind is an error."""
    kind = device.device_kind
    if kind not in SPEC_BY_DEVICE_KIND:
        raise ValueError(
            f"no modeled memory system for device kind {kind!r}; known: "
            f"{sorted(SPEC_BY_DEVICE_KIND)} (pass a spec explicitly)")
    return SPECS[SPEC_BY_DEVICE_KIND[kind]]


# --- Compute-roofline constants for the dry-run target (v5e) ----------------
@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_flops_bf16: float   # FLOP/s
    hbm_bw: float            # bytes/s
    ici_bw: float            # bytes/s per link (uni-directional)
    hbm_capacity: float


TPU_V5E_CHIP = ChipSpec(
    name="tpu_v5e",
    peak_flops_bf16=197e12,
    hbm_bw=819e9,
    ici_bw=50e9,
    hbm_capacity=16 * GB,
)
