"""repro.roofline — compute/memory/collective terms from compiled HLO."""

from .hlo import HloCounts, analyze, parse_hlo
from .terms import (
    DEFAULT_MACHINE,
    HBM_BW,
    ICI_BW,
    DEVICE_MACHINES,
    MACHINES,
    PEAK_FLOPS,
    MachineSpec,
    RooflineTerms,
    census_machine,
    get_machine,
    machine_for_device,
    register_machine,
    synthetic_machine,
    terms_from_counts,
)

__all__ = [
    "DEFAULT_MACHINE",
    "DEVICE_MACHINES",
    "HBM_BW",
    "HloCounts",
    "ICI_BW",
    "MACHINES",
    "MachineSpec",
    "PEAK_FLOPS",
    "RooflineTerms",
    "analyze",
    "census_machine",
    "get_machine",
    "machine_for_device",
    "parse_hlo",
    "register_machine",
    "synthetic_machine",
    "terms_from_counts",
]
