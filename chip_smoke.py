"""On-chip smoke check: both main paths once, on one TPU chip, in one process.

  python chip_smoke.py

Phases, in order (each prints its readings; any failure exits non-zero):

1. Device: platform, ``device_kind`` and device count.  Anything but a TPU
   is refused; there is no CPU fallback.
2. Lane: ``run_scenario("corun_sweep_1k", lane="batched")``, the planner
   entry behind ``benchmarks/run.py --scenario corun_sweep_1k --lane
   batched``, once with the fused Pallas window solver
   (``REPRO_BATCH_BACKEND=pallas``) and once with the numpy solver, each
   cold and warm.  No cell may fall back to the scalar DES, the two solvers
   must pass the kilo-grid gate of ``benchmarks/bench_des.py``, and the
   solver's compiled program must hold the Pallas kernel as a TPU custom
   call (compiled, not interpreted).
3. Serving: ``repro.launch.serve.build_cluster("qwen2.5-3b", smoke=False,
   mode="miku")``, the path of ``python -m repro.launch.serve --arch
   qwen2.5-3b --full --mode miku``.  The host engine's weights must sit in
   ``pinned_host`` and the HBM engine's in ``device``; the chip's prefill
   logits must match the same jitted prefill on the CPU backend within
   ``LOGIT_TOL``; every request must finish with ``max_new_tokens`` ids
   inside the vocabulary.  Wall seconds are host-clock readings fenced with
   ``block_until_ready``; the cluster's tokens/s comes from its simulated
   transfer-queue clock and is printed as such.

The last line of stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: Serving model: the largest catalog model whose cluster (two weight copies
#: in HBM at once) fits one 16 GB v5e; llama31-8b's 16 GB of weights do not.
ARCH = "qwen2.5-3b"
#: Relative L2 distance allowed between the chip's and the CPU's bf16
#: prefill logits: 8 bf16 ulps (bf16 keeps 8 significant bits, eps 2**-7).
LOGIT_TOL = 8 * 2.0 ** -7


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def device_info() -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _wall(fn):
    """(result, host wall seconds) of ``fn()``, fenced on its device work."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _memory(label: str) -> dict:
    """Print and return the device's bytes in use and its high-water mark."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    print(f"[serve] memory {label}: bytes_in_use "
          f"{stats.get('bytes_in_use')} peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use')} (bytes_limit "
          f"{stats.get('bytes_limit')})")
    return stats


def lane_phase(overrides=None) -> dict:
    """The batched lane on ``corun_sweep_1k``: fused Pallas vs numpy."""
    import jax

    from benchmarks.bench_des import lane_gate
    from repro.memsim.batched import fluid, kernel
    from repro.scenarios import run_scenario

    tables, out = {}, {}
    prev = os.environ.get("REPRO_BATCH_BACKEND")
    try:
        for backend in ("pallas", "numpy"):
            os.environ["REPRO_BATCH_BACKEND"] = backend
            walls = []
            for _ in range(2):
                t0 = time.perf_counter()
                table = run_scenario("corun_sweep_1k", overrides,
                                     lane="batched")
                walls.append(time.perf_counter() - t0)
            meta = table.meta
            print(f"[lane] {backend}: {len(table.rows)} cells, host wall s "
                  f"cold {walls[0]} warm {walls[1]}")
            print(f"[lane] {backend}: batched_jobs {meta['batched_jobs']} "
                  f"scalar_fallback_jobs {meta['scalar_fallback_jobs']} "
                  f"fallback_reason_counts "
                  f"{json.dumps(meta['fallback_reason_counts'])}")
            check(meta["scalar_fallback_jobs"] == 0,
                  f"{backend}: cells fell back to the scalar DES: "
                  f"{meta['fallback_reason_counts']}")
            tables[backend] = table
            out[f"{backend}_wall_s"] = walls
    finally:
        if prev is None:
            os.environ.pop("REPRO_BATCH_BACKEND", None)
        else:
            os.environ["REPRO_BATCH_BACKEND"] = prev

    def cells(table):
        return [(r["restricted_windows"] > 0,
                 {"ddr": r["ddr_gbps"], "cxl": r["cxl_gbps"]})
                for r in table.rows]

    gate = lane_gate(cells(tables["numpy"]), cells(tables["pallas"]))
    print(f"[lane] pallas vs numpy: {json.dumps(gate)}")
    check(gate["within_gate"], f"pallas and numpy solvers disagree: {gate}")

    solver = kernel.fused_solver(fluid._N_OUTER, fluid._DAMP)
    hlo = solver.lower(*kernel.fused_solver_args(1024, 2, 3)).compile()
    compiled = "tpu_custom_call" in hlo.as_text()
    print(f"[lane] fused solver on {jax.default_backend()}: Pallas kernel "
          f"{'compiled (tpu_custom_call)' if compiled else 'interpreted'}")
    out.update(gate=gate, kernel_compiled=compiled)
    return out


def serve_phase(arch: str = ARCH, *, smoke: bool = False,
                n_requests: int = 6, max_new: int = 16) -> dict:
    """Full-width tiered serving through ``launch.serve.build_cluster``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import build_cluster

    def build():
        cluster = build_cluster(arch, smoke=smoke, n_requests=n_requests,
                                mode="miku", max_new=max_new)
        jax.block_until_ready([e.params for e in cluster.engines])
        return cluster

    cl, t_build = _wall(build)
    hbm, host = cl.engines
    print(f"[serve] {arch} smoke={smoke}: build_cluster host wall s "
          f"{t_build}, weights {hbm.param_bytes} B per copy")
    _memory("after build_cluster")

    def kinds(eng):
        return {x.sharding.memory_kind for x in jax.tree.leaves(eng.params)}

    print(f"[serve] weight memory kinds: hbm {sorted(kinds(hbm))}, "
          f"host {sorted(kinds(host))}")
    check(kinds(hbm) == {"device"}, "HBM engine weights not in device memory")
    check(kinds(host) == {"pinned_host"},
          "host engine weights not in pinned_host memory")

    # Prefill: the engine's own jitted program, on the chip and on the CPU.
    prompt = hbm.queue[0].prompt
    tokens = jnp.asarray(prompt, jnp.int32)[None, :]
    prefill = hbm._prefill_fn(len(prompt))
    (logits, _), t_cold = _wall(lambda: prefill(hbm.params, tokens))
    _, t_warm = _wall(lambda: prefill(hbm.params, tokens))
    print(f"[serve] prefill {len(prompt)} tokens: host wall s "
          f"compile+run {t_cold} run {t_warm}")
    cpu = jax.devices("cpu")[0]
    ref, t_ref = _wall(lambda: prefill(jax.device_put(hbm.params, cpu),
                                       jax.device_put(tokens, cpu))[0])
    got = np.asarray(logits, np.float32)
    want = np.asarray(ref, np.float32)
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    print(f"[serve] prefill logits vs CPU backend: relative L2 {rel} "
          f"(tolerance {LOGIT_TOL}), top-1 "
          f"{'agrees' if got.argmax() == want.argmax() else 'differs'}; "
          f"CPU reference host wall s {t_ref}")
    check(np.isfinite(got).all(), "non-finite prefill logits")
    check(rel <= LOGIT_TOL, f"prefill logits off the CPU reference: {rel}")
    del ref

    # Decode: the engine's jitted step on a fresh state of the same shape.
    cfg = hbm.cfg
    state = hbm.model.init_decode_state(cfg.max_slots, cfg.max_len)
    tok = jnp.ones((cfg.max_slots,), jnp.int32)
    (_, state), t_cold = _wall(lambda: hbm._decode(hbm.params, state, tok))
    steps = 4
    t0 = time.perf_counter()
    for _ in range(steps):
        _, state = hbm._decode(hbm.params, state, tok)
    jax.block_until_ready(state)
    t_step = (time.perf_counter() - t0) / steps
    del state
    print(f"[serve] decode step ({cfg.max_slots} slots x {cfg.max_len}): "
          f"host wall s compile+run {t_cold} run {t_step}")
    _memory("after prefill and decode checks")

    # The cluster itself: both engines, MIKU on the simulated link.
    submitted = {e.cfg.name: len(e.queue) for e in cl.engines}
    res, t_run = _wall(cl.run)
    print(f"[serve] cluster run host wall s {t_run}")
    vocab = hbm.cfg.model.vocab
    for eng in cl.engines:
        name = eng.cfg.name
        outs = [r.output for r in eng.done]
        print(f"[serve] {name}: {len(outs)}/{submitted[name]} requests, "
              f"{res[name]['tokens']} tokens, simulated-clock "
              f"{res[name]['tokens_per_s']} tok/s")
        check(len(outs) == submitted[name], f"{name}: requests unfinished")
        check(all(len(o) == max_new for o in outs),
              f"{name}: a request stopped short of {max_new} tokens")
        check(all(0 <= t < vocab for o in outs for t in o),
              f"{name}: token id outside the vocabulary")
    peak = _memory("after cluster run").get("peak_bytes_in_use")
    return {"logit_rel_l2": rel, "peak_bytes_in_use": peak, "results": res}


def main() -> int:
    # The CPU backend hosts the serving phase's reference prefill.
    plats = os.environ.get("JAX_PLATFORMS")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    from repro.launch.compile_cache import use_compile_cache

    print(f"[cache] compile cache {use_compile_cache()}")
    dev = device_info()
    print(f"[device] {json.dumps(dev)}")
    if dev["platform"] != "tpu":
        print(f"error: no TPU (JAX platform {dev['platform']!r}); this check "
              "runs on the chip only", file=sys.stderr)
        return 1
    lane = lane_phase()
    check(lane["kernel_compiled"], "the Pallas kernel ran interpreted")
    serve_phase()
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
