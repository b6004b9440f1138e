"""``chip_smoke.py`` rehearsed on the CPU backend at small sizes.

The script itself refuses any platform but the TPU; its phases are plain
functions, so the same code runs here on a small grid and at smoke widths,
with the Pallas kernel interpreted.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_tpu(chip_smoke, capsys):
    assert chip_smoke.main() == 1
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1].split(" ", 1)[1])["platform"] == "cpu"
    assert not any(line.startswith('{"ok"') for line in out)


def test_lane_phase_on_a_small_grid(chip_smoke):
    out = chip_smoke.lane_phase({"threads": (4, 16), "mlp": (64, 160)})
    assert out["gate"]["within_gate"]
    assert not out["kernel_compiled"]  # interpreted on the CPU backend
    assert len(out["pallas_wall_s"]) == len(out["numpy_wall_s"]) == 2


def test_serve_phase_at_smoke_widths(chip_smoke):
    out = chip_smoke.serve_phase(smoke=True, n_requests=6, max_new=4)
    assert out["logit_rel_l2"] <= chip_smoke.LOGIT_TOL
    assert out["results"]["hbm"]["requests"] == 6
    assert out["results"]["host"]["requests"] == 2


def test_a_failed_check_raises(chip_smoke):
    with pytest.raises(chip_smoke.SmokeFailure, match="phase"):
        chip_smoke.check(False, "phase")
