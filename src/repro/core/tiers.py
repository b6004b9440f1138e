"""Memory-tier specifications and JAX memory-kind placement helpers.

The TPU deployment of the paper's tiered memory (ARCHITECTURE.md): HBM is
the fast tier (``memory_kind="device"``), pinned host DRAM over PCIe is the
slow tier (``memory_kind="pinned_host"``).  JAX exposes both through
shardings' ``with_memory_kind``; XLA compiles explicit device<->host
transfers for arrays annotated this way.

The TPU and the CPU backend both offer a ``pinned_host`` memory space.  A
backend without one is an error for host placement: nothing quietly keeps
"host" state in device memory.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import numpy as np

DEVICE = "device"
PINNED_HOST = "pinned_host"


@dataclasses.dataclass(frozen=True)
class TierSpec:
    """One memory tier of the serving/training runtime."""

    name: str
    memory_kind: str  # jax memory kind
    bandwidth_gbps: float  # per chip
    capacity_gib: float  # per chip
    #: Max concurrently in-flight fetch streams before device-side queueing
    #: explodes (the paper's hardware-parallelism disparity).
    parallelism: int


#: TPU v5e-flavoured tiers (roofline constants from the assignment).
HBM_TIER = TierSpec(
    name="hbm", memory_kind=DEVICE, bandwidth_gbps=819.0, capacity_gib=16.0,
    parallelism=64,
)
HOST_TIER = TierSpec(
    name="host", memory_kind=PINNED_HOST, bandwidth_gbps=16.0, capacity_gib=256.0,
    parallelism=8,
)


def require_host_offload(device: Optional[jax.Device] = None) -> None:
    """Raise unless ``device`` has a pinned_host memory space."""
    dev = device or jax.devices()[0]
    kinds = sorted(m.kind for m in dev.addressable_memories())
    if PINNED_HOST not in kinds:
        raise RuntimeError(
            f"{dev} has no {PINNED_HOST!r} memory (memories: {kinds}); "
            "host-tier placement needs one"
        )


def put_on_tier(x, tier: TierSpec, sharding: Optional[jax.sharding.Sharding] = None):
    """Place an array on a tier (optionally with an explicit sharding)."""
    if sharding is None:
        dev = jax.devices()[0]
        sharding = jax.sharding.SingleDeviceSharding(dev)
    return jax.device_put(x, sharding.with_memory_kind(tier.memory_kind))


@dataclasses.dataclass(frozen=True)
class TieredLayout:
    """How one logical KV cache (or parameter bank) splits across tiers.

    ``hot_tokens`` is the HBM-resident suffix window (most-recent tokens —
    the ones decode touches every step); everything older lives on the host
    tier in ``page_tokens``-sized pages fetched on demand.  For
    sliding-window-attention layers the hot window naturally equals the
    attention window, making SWA models the ideal tiering citizens.
    """

    total_tokens: int
    hot_tokens: int
    page_tokens: int = 2048

    def __post_init__(self):
        assert 0 < self.hot_tokens <= self.total_tokens
        assert self.page_tokens > 0

    @property
    def cold_tokens(self) -> int:
        return self.total_tokens - self.hot_tokens

    @property
    def n_cold_pages(self) -> int:
        return -(-self.cold_tokens // self.page_tokens)  # ceil

    def page_slice(self, page: int) -> slice:
        start = page * self.page_tokens
        return slice(start, min(start + self.page_tokens, self.cold_tokens))

    def bytes_per_token(self, n_kv_heads: int, head_dim: int, n_layers: int,
                        dtype_bytes: int = 2) -> int:
        return 2 * n_kv_heads * head_dim * n_layers * dtype_bytes  # K and V

    def cold_bytes(self, n_kv_heads: int, head_dim: int, n_layers: int,
                   dtype_bytes: int = 2) -> int:
        return self.cold_tokens * self.bytes_per_token(
            n_kv_heads, head_dim, n_layers, dtype_bytes
        )


def estimate_fetch_ns(nbytes: int, tier: TierSpec) -> float:
    """First-order fetch-time estimate for the simulated serving clock."""
    return nbytes / max(tier.bandwidth_gbps, 1e-9)  # B / (B/ns) = ns


def np_bytes(x) -> int:
    return int(np.prod(x.shape)) * x.dtype.itemsize
