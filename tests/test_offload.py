"""Host offloader (real transfers) + transfer-queue timing model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.offload import HostOffloader, TransferQueue
from repro.core.tiers import TieredLayout


def test_offload_roundtrip_real_arrays():
    off = HostOffloader()
    tree = {"a": jnp.arange(64, dtype=jnp.float32),
            "b": jnp.ones((8, 8), jnp.bfloat16)}
    h = off.to_host(tree)
    d = off.to_device(h)
    off.block(d)
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(d)):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))
    kinds = {x.sharding.memory_kind for x in jax.tree.leaves(h)}
    assert kinds == {"pinned_host"}
    assert {x.sharding.memory_kind for x in jax.tree.leaves(d)} == {"device"}


def test_transfer_queue_stream_duration_is_bandwidth_bound():
    q = TransferQueue()
    total = 16 << 20  # 16 MiB
    done = q.submit_slow_stream(total, 64)
    expected = total / q.slow.bandwidth_gbps
    assert done == pytest.approx(expected, rel=0.05)


def test_cap_bounds_backlog_without_slowing_stream():
    from repro.core.controller import Decision, Phase

    q1 = TransferQueue()
    d1 = q1.submit_slow_stream(16 << 20, 64)
    backlog_uncapped = q1.slow_backlog()

    q2 = TransferQueue()
    q2._decision = Decision(max_concurrency=4, rate_factor=1.0,
                            phase=Phase.RESTRICTED)
    d2 = q2.submit_slow_stream(16 << 20, 64)
    assert q2.slow_backlog() == 0
    assert backlog_uncapped > 32
    # work conservation: the capped stream is not slower
    assert d2 == pytest.approx(d1, rel=0.01)


def test_unknown_slow_tier_is_a_loud_error():
    """Regression: decision_for/slow_inflight/slow_backlog/submit used to
    fall back silently (or KeyError) on unknown link names; they now raise
    UnknownTierError naming the queue's links, like the DES does."""
    from repro.core.device_model import UnknownTierError

    q = TransferQueue()
    with pytest.raises(UnknownTierError, match="slow"):
        q.decision_for("warp_drive")
    with pytest.raises(UnknownTierError):
        q.slow_inflight("warp_drive")
    with pytest.raises(UnknownTierError):
        q.slow_backlog("warp_drive")
    with pytest.raises(UnknownTierError):
        q.submit_slow_stream(1 << 20, 4, tier="warp_drive")
    # the valid names still work, including the tier=None backlog sum
    q.submit_slow_stream(1 << 20, 4)
    assert q.slow_backlog() >= 0
    assert q.decision_for("slow") is q.decision


def test_fast_penalty_rises_with_backlog():
    q = TransferQueue()
    assert q.fast_penalty() == 1.0
    q.submit_slow_stream(16 << 20, 64)
    assert q.fast_penalty() > 1.2


def test_tiered_layout_pages():
    lay = TieredLayout(total_tokens=10_000, hot_tokens=2_000,
                       page_tokens=1024)
    assert lay.cold_tokens == 8_000
    assert lay.n_cold_pages == 8
    assert lay.page_slice(0) == slice(0, 1024)
    assert lay.page_slice(7).stop == 8000
