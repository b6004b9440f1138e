"""Serving engine + tiered cluster behaviour."""

import jax
import pytest

from repro.configs import get_arch
from repro.core.controller import MikuConfig, MikuController
from repro.core.littles_law import EstimatorConfig
from repro.models.transformer import TransformerLM
from repro.serving.engine import (
    EngineConfig,
    Request,
    ServingEngine,
    TieredServingCluster,
)

CFG = get_arch("llama31-8b").smoke
MODEL = TransformerLM(CFG)
PARAMS, _ = MODEL.init(jax.random.PRNGKey(0))


def mk(name, placement, n_req, max_new=8):
    e = ServingEngine(
        EngineConfig(name=name, model=CFG, max_slots=2, max_len=64,
                     placement=placement, stream_chunks=64),
        PARAMS,
    )
    for i in range(n_req):
        e.submit(Request(rid=i, prompt=[1, 2, 3, 4], max_new_tokens=max_new))
    return e


def test_engine_completes_all_requests():
    cl = TieredServingCluster([mk("a", "device", 5)])
    res = cl.run(2000)
    assert res["a"]["requests"] == 5
    assert res["a"]["tokens"] == 5 * 8


def test_continuous_batching_more_requests_than_slots():
    eng = mk("a", "device", 7)
    cl = TieredServingCluster([eng])
    cl.run(4000)
    assert len(eng.done) == 7
    assert all(len(r.output) == 8 for r in eng.done)


def test_host_instance_slower_than_device():
    a = TieredServingCluster([mk("d", "device", 4)]).run(4000)
    b = TieredServingCluster([mk("h", "host", 4)]).run(8000)
    assert a["d"]["tokens_per_s"] > 3 * b["h"]["tokens_per_s"]


@pytest.mark.slow
def test_racing_degrades_fast_instance():
    """Full-length Fig. 12 analogue (slow lane; the quick MIKU-restriction
    check below covers the control path in tier-1)."""
    solo = TieredServingCluster([mk("d", "device", 8)]).run(8000)
    both = TieredServingCluster(
        [mk("d", "device", 8), mk("h", "host", 4)]
    ).run(16000)
    assert both["d"]["tokens_per_s"] < 0.92 * solo["d"]["tokens_per_s"]


def test_miku_restricts_under_racing():
    probe = mk("p", "host", 0)
    chunk_service = probe.param_bytes / 64 / 16.0
    ctl = MikuController(
        MikuConfig(levels=(1, 2, 4, 8)),
        EstimatorConfig(t_fast=1.2e3, slow_read_threshold=8 * chunk_service,
                        min_window_inserts=4, min_slow_inserts=1),
    )
    cl = TieredServingCluster(
        [mk("d", "device", 12), mk("h", "host", 6)],
        controller=ctl, window_ns=3e4,
    )
    cl.run(20000)
    assert any(d.restricted for d in ctl.decisions)


def test_host_only_ticks_jump_to_the_next_completion():
    """A host step spans many 1 µs idle ticks (about 10^5 at full width);
    with no HBM work the clock jumps to the host engine's completion, so a
    small tick budget still finishes every request."""
    eng = mk("h", "host", 2)
    res = TieredServingCluster([eng]).run(60)
    assert res["h"]["requests"] == 2
    assert all(len(r.output) == 8 for r in eng.done)
