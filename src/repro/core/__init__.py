# The paper's primary contribution: tiered-memory characterization substrate
# (device models, DES, MVA) + MIKU dynamic memory request control
# (Little's-Law estimator + hierarchical throttle controller), plus the
# TPU-native tier/offload runtime they govern.

from repro.core.controller import (
    Decision,
    MergedSlowPolicy,
    MikuConfig,
    MikuController,
    Phase,
    SlowTierMiku,
    StragglerGovernor,
    TierDecisions,
)
from repro.core.des import SimResult, TieredMemorySim, WorkloadSpec
from repro.core.des import validate_workloads
from repro.core.device_model import (
    CXL_DEVICE,
    CXL_SWITCH_DEVICE,
    DDR5_DIMM,
    DDR_REMOTE_DIMM,
    DeviceModel,
    PlatformModel,
    PLATFORMS,
    UnknownTierError,
    platform_a,
    platform_a_numa,
    platform_a_switch,
    platform_b,
    tpu_host_platform,
)
from repro.core.littles_law import (
    EstimatorConfig,
    LittlesLawEstimator,
    OpClass,
    TierCounters,
    TierEstimate,
    TierWindow,
    merge_tier_counters,
)
from repro.core.offload import HostOffloader, TransferQueue
from repro.core.substrate import (
    ControlLoop,
    MemorySubstrate,
    ReplaySubstrate,
    StepTimingSubstrate,
    TierSetWindowedCounters,
    WindowedCounters,
    WindowRecord,
    window_record_jsonable,
)
from repro.core.tiers import (
    HBM_TIER,
    HOST_TIER,
    TieredLayout,
    TierSpec,
)

__all__ = [
    "Decision",
    "MergedSlowPolicy",
    "MikuConfig",
    "MikuController",
    "Phase",
    "SlowTierMiku",
    "StragglerGovernor",
    "TierDecisions",
    "SimResult",
    "TieredMemorySim",
    "WorkloadSpec",
    "validate_workloads",
    "CXL_DEVICE",
    "CXL_SWITCH_DEVICE",
    "DDR5_DIMM",
    "DDR_REMOTE_DIMM",
    "DeviceModel",
    "PlatformModel",
    "PLATFORMS",
    "UnknownTierError",
    "platform_a",
    "platform_a_numa",
    "platform_a_switch",
    "platform_b",
    "tpu_host_platform",
    "EstimatorConfig",
    "LittlesLawEstimator",
    "OpClass",
    "TierCounters",
    "TierEstimate",
    "TierWindow",
    "merge_tier_counters",
    "HostOffloader",
    "TransferQueue",
    "ControlLoop",
    "MemorySubstrate",
    "ReplaySubstrate",
    "StepTimingSubstrate",
    "TierSetWindowedCounters",
    "WindowedCounters",
    "WindowRecord",
    "window_record_jsonable",
    "HBM_TIER",
    "HOST_TIER",
    "TieredLayout",
    "TierSpec",
]
