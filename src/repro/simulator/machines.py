"""Machine registry: one ``machines.get()`` lookup for every call site.

Anywhere the simulator accepts a machine — ``engine.run``,
``experiment.sweep``, ``tuning.tune``, ``search.run``, the benchmarks —
it accepts a registry NAME (``"pmem-large"``, ``"numa"``,
``"cxl-1hop"``, ``"dram-cxl-pmem"``), a legacy two-tier ``MachineSpec``,
or a ``TieredMachineSpec``; resolution happens here instead of each call
site importing the preset dict.

Presets (Table-3-style; the two-tier ones are exact conversions of the
paper's Table 3 rows in machine.py):

  * ``pmem-large`` — DRAM + Optane PMem (paper's main machine);
  * ``numa``       — emulated-CXL remote NUMA node (paper §7.3);
  * ``cxl-1hop``   — DRAM + one-hop CXL-attached expander: DRAM-class
    media behind a CXL.mem link, so latency sits between local DRAM and
    PMem while read/write bandwidth stay symmetric-ish (HybridTier's
    CXL setting);
  * ``dram-cxl-pmem`` — three-tier chain: DRAM (capacity k), CXL
    expander (capacity 2k), PMem bottom (unbounded) — the multi-tier
    thrashing topology of Jenga's analysis;
  * ``hbm-pcie``  — accelerator HBM over host memory via PCIe: the
    serving-layer topology (paged-KV / expert slabs / embedding blocks,
    tiering/tiered_pool.py), tier-0 bandwidth pinned to the roofline's
    HBM constant so the serving cost model and roofline agree.
"""
from __future__ import annotations

from repro import roofline
from repro.simulator import machine as machine_mod
from repro.simulator import machine_spec
from repro.simulator.machine_spec import TieredMachineSpec

# Serving topology: accelerator HBM (tier 0, the roofline's memory-bound
# bandwidth — src/repro/roofline.py) over host memory reached through PCIe
# (~25 GB/s, the expert-slab latency budget in tiering/expert_tiering.py).
# This is the machine the TieredPool serving cost model charges against.
HBM_PCIE = machine_spec.make(
    "hbm-pcie",
    lat_ns=[120.0, 900.0],
    bw_read=[roofline.HBM_BW, 25e9],
    bw_write=[roofline.HBM_BW, 25e9])

CXL_1HOP = machine_spec.make(
    "cxl-1hop",
    lat_ns=[80.0, 250.0],
    bw_read=[138e9, 30e9],
    bw_write=[138e9, 25e9])

DRAM_CXL_PMEM = machine_spec.make(
    "dram-cxl-pmem",
    lat_ns=[80.0, 250.0, 400.0],
    bw_read=[138e9, 30e9, 7.45e9],
    bw_write=[138e9, 25e9, 2.25e9],
    capacity_pages=[-1.0, -2.0, 0.0])   # k / 2k / unbounded

REGISTRY: dict[str, TieredMachineSpec] = {
    **{nm: machine_spec.from_machine(m)
       for nm, m in machine_mod.MACHINES.items()},
    "cxl-1hop": CXL_1HOP,
    "dram-cxl-pmem": DRAM_CXL_PMEM,
    "hbm-pcie": HBM_PCIE,
}


def names() -> list[str]:
    return sorted(REGISTRY)


def get(m) -> TieredMachineSpec:
    """Resolve anything machine-shaped to a ``TieredMachineSpec``."""
    if isinstance(m, TieredMachineSpec):
        return m
    if isinstance(m, machine_mod.MachineSpec):
        return machine_spec.from_machine(m)
    if isinstance(m, str):
        key = m.lower()
        if key not in REGISTRY:
            raise ValueError(f"unknown machine {m!r}; known: {names()}")
        return REGISTRY[key]
    raise TypeError(f"machine must be a name, MachineSpec or "
                    f"TieredMachineSpec, got {type(m).__name__}")
