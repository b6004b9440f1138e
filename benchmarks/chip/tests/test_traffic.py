"""The committed serving mixes hold the source's two medians, the same
lengths in every cell, and prompt and output lengths that are independent."""

import glob
import os
import statistics

import pytest

import cell as cell_lib
from conftest import BENCH

SERVE = sorted(
    p for p in glob.glob(os.path.join(BENCH, "traffic", "*.json"))
    if "prompt_lengths" in cell_lib.load_json(p))


def sizes(path):
    return cell_lib.driver("serve").request_sizes(cell_lib.load_json(path))


def test_serving_mixes_exist():
    assert len(SERVE) >= 2


@pytest.mark.parametrize("path", SERVE, ids=os.path.basename)
def test_block_medians(path):
    block = sizes(path)
    assert statistics.median(p for p, _ in block) == 1024
    assert 120 <= statistics.median(o for _, o in block) <= 136


@pytest.mark.parametrize("path", SERVE, ids=os.path.basename)
def test_lengths_independent(path):
    """Every prompt bucket is served outputs from both halves of the
    output range."""
    block = sizes(path)
    mid = statistics.median(o for _, o in block)
    for p in {p for p, _ in block}:
        outs = [o for q, o in block if q == p]
        assert min(outs) < mid < max(outs), (p, outs)


def test_cells_share_lengths():
    assert len({tuple(sizes(p)) for p in SERVE}) == 1
