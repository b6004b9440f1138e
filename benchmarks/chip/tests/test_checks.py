"""The correctness check, driven through the whole harness on the CPU at
small sizes: it passes the program as it is, and fails the control and a
timed path broken underneath in each way a cell can be broken.

Faults (none of these cells runs on more than one chip, so no exchange
between chips can be left out):

* ``unchanged``: a step returns its state as it got it;
* ``half``: half of the batch is left out and takes the mean of the rest;
* ``altered``: an answer or a token is altered where it is produced.

The lane adds a fault in each of the layers its check covers beyond the
window solve: the MIKU ladder, the stacking of cells into arrays, and the
scenario's reduction to rows.
"""

import dataclasses
import io
import json
import os
from contextlib import redirect_stdout

import numpy as np
import pytest

import cell as cell_lib
import run as run_lib
from conftest import BENCH

SEED = 2_147_483_659  # wider than 32 bits: the seed is any whole number


def small_lane(cell):
    # A grid of 32 cells in which MIKU restricts the 16-thread ones.
    cell.config["axes"] = {"threads": (4, 16), "mlp": (64, 160)}
    cell.traffic["draw"] = {}
    cell.traffic["sample"] = 32
    return cell


def harness(workload, shrink, seconds="2"):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run_lib.run(["--workload", workload, "--seed", str(SEED),
                          "--seconds", seconds, "--trace", "0"],
                         require_chip=False, shrink=shrink)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_no_chip_no_result(capsys):
    rc = run_lib.run(["--workload", "lane.corun1k", "--seed", "1",
                      "--seconds", "1", "--trace", "0"])
    assert rc == 2
    assert capsys.readouterr().out == ""


# -- the batched lane --------------------------------------------------------


def test_lane_as_it_is():
    line = harness("lane.corun1k", small_lane)
    assert line["correct"], line["checks"]
    assert line["checks"]["rows_compared"]["value"] == 32
    assert list(line)[-1] == "checks"


def _solver_fault(kind):
    from repro.memsim.batched import kernel

    orig = kernel.fused_window_solve

    def broken(*args):
        y, wq, lam = orig(*args)
        if kind == "unchanged":
            wq = np.array(args[9], copy=True)
        elif kind == "half":
            h = y.shape[0] // 2
            y = y.copy()
            wq = wq.copy()
            y[h:] = y[:h].mean(axis=0)
            wq[h:] = wq[:h].mean(axis=0)
        return y, wq, lam

    return broken


def _plant(kind, monkeypatch):
    """Break the lane underneath the harness."""
    from repro.core.controller import VectorMikuLadder
    from repro.memsim.batched import kernel, stacking
    from repro.scenarios import registry

    if kind in ("unchanged", "half"):
        monkeypatch.setattr(kernel, "fused_window_solve", _solver_fault(kind))
    elif kind == "altered":  # a row's answer, where the scenario makes it
        sc = registry.get("corun_sweep_1k")

        def reduce(*args, _f=sc.reduce):
            rows = _f(*args)
            for r in rows:
                if r["threads"] == 16 and r["mlp"] == 160:
                    r["cxl_gbps"] *= 1.01
            return rows

        monkeypatch.setitem(registry._REGISTRY, "corun_sweep_1k",
                            dataclasses.replace(sc, reduce=reduce))
    elif kind == "ladder":  # the ladder's state never leaves its start
        window = VectorMikuLadder.window

        def frozen(self, *deltas):
            out = window(self, *deltas)
            self.reset()
            return {**out, "restricted": np.zeros_like(out["restricted"]),
                    "cap": np.full_like(out["cap"], np.inf),
                    "rate": np.ones_like(out["rate"])}

        monkeypatch.setattr(VectorMikuLadder, "window", frozen)
    elif kind == "stacking":  # the tiers' service times swapped
        init = stacking.BatchGroup.__init__

        def swapped(self, cells):
            init(self, cells)
            self.svc[:, :, [0, 1]] = self.svc[:, :, [1, 0]]

        monkeypatch.setattr(stacking.BatchGroup, "__init__", swapped)


@pytest.mark.parametrize(
    "kind", ["unchanged", "half", "altered", "ladder", "stacking"])
def test_lane_fault_fails(kind, monkeypatch):
    _plant(kind, monkeypatch)
    line = harness("lane.corun1k", small_lane)
    assert not line["correct"]
    assert any(c["value"] > c["limit"] for k, c in line["checks"].items()
               if k.startswith("row_"))


def test_lane_control_fails(monkeypatch):
    """The reference with bfloat16 window solves, in the solver's place,
    reads above the limits."""
    import ml_dtypes

    from reference import fluid_window
    from repro.memsim.batched import kernel

    def control(*args):
        return fluid_window.solve(*args, dtype=ml_dtypes.bfloat16)

    monkeypatch.setattr(kernel, "fused_window_solve", control)
    line = harness("lane.corun1k", small_lane)
    assert not line["correct"]


# -- serving -----------------------------------------------------------------
#
# The serving driver is checked here on a cell of the tests' own: the
# serving cells' closed loop at small sizes.


def serve_cell(engines):
    cfg = cell_lib.load_json(os.path.join(BENCH, "configs", "qwen2.5-3b.json"))
    cfg.update(hidden_size=128, intermediate_size=256, num_hidden_layers=8,
               num_attention_heads=4, num_key_value_heads=2, vocab_size=512)
    # Limits for this size, set as a cell's are: between the program's
    # readings here (logit error <= 0.0065 on seeds 1-3) and the fp8
    # control's (>= 0.031).
    cfg["limits"] = {"max_logit_gap": 0.5, "max_logit_err": 0.015}
    host = any(e["placement"] == "host" for e in engines)
    traffic = {
        "engines": engines, "miku": host, "max_len": 160,
        "stream_chunks": 64, "prompt_lengths": [16, 32, 64, 96],
        "prompt_counts": [4, 3, 2, 1], "output_min": 4, "output_max": 32,
        "post_window_s": 60, "trace_seconds": 1,
        "check": {"requests": 6, "host_requests": 2 if host else 0,
                  "min_tokens": 40},
    }
    metrics = [{"name": "serve_tokens_per_s", "unit": "tokens/s"},
               {"name": "setup_s", "unit": "s"}]
    return cell_lib.Cell(name="serve.test", config_name=cfg["name"],
                         config=cfg, traffic_name="test", traffic=traffic,
                         chips=1, end_to_end=metrics, per_layer=[])


HBM = [{"name": "hbm", "placement": "device", "clients": 4}]
TIERED = HBM + [{"name": "host", "placement": "host", "clients": 4}]


def serve_harness(engines, monkeypatch):
    monkeypatch.setattr(cell_lib, "resolve",
                        lambda workload, root=None: serve_cell(engines))
    return harness("serve.test", None)


@pytest.mark.parametrize("engines", [HBM, TIERED], ids=["hbm", "tiered"])
def test_serve_as_it_is(engines, monkeypatch):
    line = serve_harness(engines, monkeypatch)
    assert line["correct"], line["checks"]
    assert line["checks"]["window_compiles"]["value"] == 0
    if len(engines) > 1:
        assert line["checks"]["host_requests_compared"]["value"] >= 2


def _decode_fault(kind, orig):
    import jax.numpy as jnp

    def broken(self, params, state, token):
        logits, new = orig(self, params, state, token)
        if kind == "unchanged":
            return logits, state
        h = logits.shape[0] // 2
        rest = jnp.broadcast_to(logits[:h].mean(axis=0), logits[h:].shape)
        return jnp.concatenate([logits[:h], rest]), new

    return broken


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_serve_fault_fails(kind, monkeypatch):
    from repro.models.transformer import TransformerLM
    from repro.serving import sampler

    if kind == "altered":
        greedy = sampler.greedy

        def shifted(logits, key=None):
            return (greedy(logits) + 1) % logits.shape[-1]

        monkeypatch.setattr(sampler, "greedy", shifted)
    else:
        monkeypatch.setattr(TransformerLM, "decode_step",
                            _decode_fault(kind, TransformerLM.decode_step))
    line = serve_harness(HBM, monkeypatch)
    assert not line["correct"]
    assert any(c["value"] > c["limit"] for k, c in line["checks"].items()
               if k.startswith("max_logit"))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serve_control_fails(seed):
    """The fp8 reference, read at the served positions in the program's
    place, fails this size's limits where the program passes them."""
    c = serve_cell(HBM)
    drv = cell_lib.driver("serve")
    run_lib.use_cache()
    state = drv.setup(c, seed)
    data = drv.window(state, 1.5, None)
    res = cell_lib.Run(cell=c, seed=seed, device={}, setup_s=0.0,
                       window_s=data["window_s"], data=data)
    r = drv.readings(state, res, control=True)
    lim = c.config["limits"]
    assert r["max_logit_err"] <= lim["max_logit_err"]
    assert r["max_logit_gap"] <= lim["max_logit_gap"]
    assert (r["control_max_logit_err"] > lim["max_logit_err"]
            or r["control_max_logit_gap"] > lim["max_logit_gap"])
