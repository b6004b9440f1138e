"""Serving driver: tiered co-located instances with MIKU request control.

  PYTHONPATH=src python -m repro.launch.serve --arch llama31-8b --smoke \\
      --requests 24 --mode miku
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --full \\
      --requests 6 --mode miku

Modes: ``opt`` (each instance alone), ``racing`` (no control), ``miku``
(dynamic control).  Mirrors the paper's §6 LLM case study on the TPU tier
model (ARCHITECTURE.md).  ``--smoke`` (the default) runs the reduced
same-family config; ``--full`` runs the published widths.  At full width the
cluster holds two weight copies in HBM at once (the HBM engine's and the
host engine's per-step fetch), so ``llama31-8b`` (16 GB of bf16 weights)
does not fit one 16 GB TPU v5e; ``qwen2.5-3b`` does.

The reported tokens/s come from the simulated transfer-queue clock, not
from the device.
"""

from __future__ import annotations

import argparse

import jax

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


#: Slots and cache length of each engine that :func:`build_cluster` makes.
SLOTS, MAX_LEN = 4, 96


def build_cluster(arch_id: str, *, smoke: bool, n_requests: int, mode: str,
                  max_new: int = 24, stream_chunks: int = 64):
    spec = get_arch(arch_id)
    cfg = spec.smoke if smoke else spec.config
    model = TransformerLM(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))

    def mk(name, placement, n):
        e = ServingEngine(
            EngineConfig(name=name, model=cfg, max_slots=SLOTS,
                         max_len=MAX_LEN,
                         placement=placement, stream_chunks=stream_chunks),
            params,
        )
        for i in range(n):
            e.submit(Request(rid=i, prompt=list(range(1, 9)),
                             max_new_tokens=max_new))
        return e

    engines = [mk("hbm", "device", n_requests),
               mk("host", "host", max(n_requests // 3, 1))]
    controller = None
    if mode == "miku":
        chunk_service = engines[1].param_bytes / stream_chunks / 16.0
        controller = MikuController(
            MikuConfig(levels=(1, 2, 4, 8)),
            EstimatorConfig(t_fast=1.2e3,
                            slow_read_threshold=8 * chunk_service,
                            min_window_inserts=4, min_slow_inserts=1),
        )
    return TieredServingCluster(engines, controller=controller,
                                window_ns=3e4)


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama31-8b")
    width = ap.add_mutually_exclusive_group()
    width.add_argument("--smoke", dest="smoke", action="store_true",
                       help="reduced same-family config (default)")
    width.add_argument("--full", dest="smoke", action="store_false",
                       help="published widths")
    ap.set_defaults(smoke=True)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--mode", choices=("opt", "racing", "miku"),
                    default="miku")
    args = ap.parse_args()
    use_compile_cache()
    if args.mode == "opt":
        for placement in ("device", "host"):
            cl = build_cluster(args.arch, smoke=args.smoke,
                               n_requests=args.requests, mode="racing")
            cl.engines = [e for e in cl.engines
                          if e.cfg.placement == placement]
            res = cl.run()
            for k, v in res.items():
                print(f"[serve/opt] {k}: {v['tokens_per_s']:.0f} simulated "
                      f"tok/s ({v['requests']:.0f} requests)")
        return
    cl = build_cluster(args.arch, smoke=args.smoke,
                       n_requests=args.requests, mode=args.mode)
    res = cl.run()
    for k, v in res.items():
        print(f"[serve/{args.mode}] {k}: {v['tokens_per_s']:.0f} simulated "
              f"tok/s ({v['requests']:.0f} requests)")


if __name__ == "__main__":
    main()
