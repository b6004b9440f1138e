"""Serving engine + tiered cluster behaviour."""

import dataclasses

import jax
import jax.numpy as jnp
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


def test_one_host_read_per_decode_step_and_admission():
    """A 4-slot engine through admissions, completions and an overflow
    (``max_len`` 24) reads the device once per decode step and once per
    admission, whatever the slot count; every finished request's output is
    a plain greedy decode of its prompt by the model itself."""
    from repro.obs.metrics import default_registry

    cfg = dataclasses.replace(CFG, dtype=jnp.float32)
    model = TransformerLM(cfg)
    params, _ = model.init(jax.random.PRNGKey(1))
    max_len = 24
    eng = ServingEngine(
        EngineConfig(name="e", model=cfg, max_slots=4, max_len=max_len),
        params,
    )
    # (prompt length, max_new_tokens): 7 requests over 4 slots; the last
    # two run into max_len before max_new_tokens.
    shapes = [(3, 4), (5, 6), (8, 2), (4, 5), (6, 3), (12, 30), (9, 40)]
    for i, (plen, n) in enumerate(shapes):
        eng.submit(Request(rid=i, prompt=[(7 * i + j) % cfg.vocab + 1
                                          for j in range(plen)],
                           max_new_tokens=n))
    reads = default_registry().counter("serving.host_reads")
    steps = 0
    while not eng.finished:
        before = reads.value
        admitted = eng.admit(0.0)
        assert reads.value - before == len(admitted)
        before = reads.value
        produced = eng.decode_once(0.0)
        assert reads.value - before == (1 if produced else 0)
        steps += 1
    assert steps > 1 and len(eng.done) == len(shapes)

    fwd = jax.jit(lambda p, t: model.forward(p, t, last_only=True)[0])
    for r in eng.done:
        plen, n = shapes[r.rid]
        assert len(r.output) == min(n, max_len - plen)
        seq = list(r.prompt)
        for _ in r.output:
            logits = fwd(params, jnp.asarray([seq], jnp.int32))
            seq.append(int(jnp.argmax(logits[0, -1])))
        assert r.output == seq[plen:], (r.rid, r.output, seq[plen:])


def _smoke_engine(arch, slots=3):
    cfg = get_arch(arch).smoke
    model = TransformerLM(cfg)
    params = jax.jit(lambda k: model.init(k)[0])(jax.random.PRNGKey(0))
    return ServingEngine(EngineConfig(name="e", model=cfg, max_slots=slots,
                                      max_len=48), params)


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "granite-4.0-h-small",
                                  "deepseek-v2-lite"],
                         ids=["uniform", "interleaved", "mla"])
def test_admission_leaves_every_other_slot_unchanged(arch):
    """The jitted, donated insert writes the admitted slot alone: after two
    slots have decoded, a third request's admission leaves their state
    bit for bit as it was and puts the prefill's state in its own slot."""
    import numpy as np

    eng = _smoke_engine(arch)
    for i, plen in enumerate([5, 9]):
        eng.submit(Request(rid=i, prompt=list(range(1, plen + 1)),
                           max_new_tokens=20))
    eng.admit(0.0)
    for _ in range(3):
        eng.decode_once(0.0)
    before = jax.tree.map(np.asarray, eng.state)
    prompt = [3, 1, 4, 1, 5, 9, 2]
    _, state1 = eng._prefill_fn(len(prompt))(
        eng.params, jnp.asarray([prompt], jnp.int32))
    req = Request(rid=2, prompt=prompt, max_new_tokens=4)
    eng.submit(req)
    eng.admit(0.0)
    assert eng.slot_req[2] is req
    after = jax.tree.map(np.asarray, eng.state)
    for name in ("kv", "ssm"):
        for k in (getattr(before, name) or {}):
            old, new = getattr(before, name)[k], getattr(after, name)[k]
            np.testing.assert_array_equal(new[:, :2], old[:, :2])
            np.testing.assert_array_equal(
                new[:, 2:], np.asarray(getattr(state1, name)[k]))
    assert list(after.length) == [int(before.length[0]),
                                  int(before.length[1]), len(prompt)]
    assert list(eng._lengths) == list(after.length)


def test_tier_charge_follows_the_state_leaves():
    """K/V bytes per token are read off the state's per-position leaves:
    K and V of every attention layer (unchanged for the uniform and
    interleaved stacks), and MLA's latent and rope key, 576 bf16 values
    in each of 27 layers (31,104 B) at the published widths."""
    import dataclasses as dc

    from repro.serving.engine import kv_bytes_per_token

    for arch, n_attn in (("qwen2.5-3b", 36), ("granite-4.0-h-small", 4)):
        cfg = get_arch(arch).config
        st = jax.eval_shape(
            lambda c=cfg: TransformerLM(c).init_decode_state(2, 16))
        assert kv_bytes_per_token(st) == \
            2 * cfg.n_kv_heads * cfg.head_dim * n_attn * 2
    ds = dc.replace(get_arch("deepseek-v2-lite").config, experts_held=(0, 8))
    st = jax.eval_shape(lambda: TransformerLM(ds).init_decode_state(2, 16))
    assert kv_bytes_per_token(st) == 27 * (512 + 64) * 2 == 31_104
    eng = _smoke_engine("deepseek-v2-lite", slots=2)
    smoke = get_arch("deepseek-v2-lite").smoke
    per_token = smoke.n_layers * (smoke.kv_lora_rank
                                  + smoke.qk_rope_head_dim) * 2
    assert eng.kv_bytes_per_token == per_token and eng.ssm_bytes_per_slot == 0
    eng.submit(Request(rid=0, prompt=[1, 2, 3, 4, 5], max_new_tokens=3))
    eng.admit(0.0)
    eng.decode_once(0.0)
    assert eng.step_bytes() == (eng.param_bytes, 6 * per_token)
