"""Property test: the batched lane is *total* and order-preserving.

For an arbitrary mixed job list — exact fast-path cells, fluid co-run
cells, and jobs that dynamically fall back to the scalar DES (a tiering
policy outside the vectorizable registry) — ``run_sweep_batched`` must:

* return one result per job, in job order;
* reproduce the scalar DES bit-for-bit on exact-regime cells;
* reproduce the scalar DES bit-for-bit on fallback cells (they *are*
  scalar reruns), and record the fallback with its reason;
* stay within the pinned fluid tolerance on co-run cells.

Runs as a hypothesis property when hypothesis is installed, and otherwise
over a fixed spread of kind-sequences and rng seeds.  The policy fixture is
module-scoped: hypothesis refuses function-scoped fixtures under
``@given``, since they would not be reset between examples.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro.core.device_model import platform_a
from repro.core.littles_law import OpClass
from repro.memsim.batched import partition_jobs
from repro.memsim.batched.exact import exact_regime
from repro.memsim.batched.lane import run_sweep_batched
from repro.memsim.sweep import SimJob, run_sweep
from repro.memsim.workloads import bw_test
from repro.tiering import HotSetPattern, RegionSpec, TieringSpec
from repro.tiering.policies import POLICIES

_OPS = tuple(OpClass)
_SIM_NS = 100_000.0
_KINDS = ("exact", "fluid", "fallback")


class _FrozenPolicy:  # deliberately outside the vectorizable hierarchy
    name = "frozen_property_policy"

    def decide(self, pagemap, ctx):
        del pagemap, ctx
        return []


@pytest.fixture(scope="module")
def frozen_policy():
    POLICIES[_FrozenPolicy.name] = _FrozenPolicy
    try:
        yield _FrozenPolicy.name
    finally:
        POLICIES.pop(_FrozenPolicy.name, None)


def _mk_job(kind: str, i: int, rng, platform, frozen: str) -> SimJob:
    name = f"x{i}"
    if kind == "exact":
        op = _OPS[int(rng.integers(0, 3))]
        tier = ("ddr", "cxl")[int(rng.integers(0, 2))]
        return SimJob(platform=platform,
                      workloads=[bw_test(tier, op, 16, name=name)],
                      sim_ns=_SIM_NS)
    if kind == "fluid":
        op = _OPS[int(rng.integers(0, 3))]
        wls = [bw_test("ddr", op, int(rng.integers(8, 17)), name=name,
                       miku_managed=False),
               bw_test("cxl", op, int(rng.integers(8, 17)), name=name + "s")]
        return SimJob(platform=platform, workloads=wls, sim_ns=_SIM_NS,
                      miku=bool(rng.integers(0, 2)))
    spec = TieringSpec(
        regions=(RegionSpec(workload=name, n_pages=128,
                            placement={"cxl": 1.0},
                            pattern=HotSetPattern()),),
        policy=frozen,
    )
    return SimJob(platform=platform,
                  workloads=[bw_test("cxl", OpClass.LOAD, 4, name=name)],
                  sim_ns=_SIM_NS, tiering=spec)


def _check_mixed_list(kinds, seed: int, frozen: str) -> None:
    platform = platform_a()
    rng = np.random.default_rng(seed)
    jobs = [_mk_job(k, i, rng, platform, frozen)
            for i, k in enumerate(kinds)]
    plans, fallbacks = partition_jobs(jobs)
    assert not fallbacks  # every job passes the static screen
    batched = run_sweep_batched(jobs, partition=(plans, fallbacks))
    scalar = run_sweep(jobs)
    assert len(batched) == len(jobs)

    fell_back = dict(fallbacks)  # filled dynamically during the run
    assert sorted(fell_back) == [i for i, k in enumerate(kinds)
                                 if k == "fallback"]
    for i, (job, kind, s, b) in enumerate(zip(jobs, kinds, scalar, batched)):
        name = job.workloads[0].name
        assert name in b.stats, (i, kind)  # results stay in job order
        if kind == "exact":
            assert exact_regime(plans[i]) in ("noqueue", "saturated")
            assert b.stats[name].bytes == s.stats[name].bytes
            assert b.bandwidth(name) == s.bandwidth(name)
        elif kind == "fallback":
            assert _FrozenPolicy.name in fell_back[i]
            assert b.bandwidth(name) == s.bandwidth(name)  # scalar rerun
            assert b.tiering == s.tiering
        else:
            assert b.bandwidth(name) == pytest.approx(
                s.bandwidth(name), rel=0.12), (i, kind)


try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    _CASES = [
        (("exact",), 11),
        (("fluid",), 12),
        (("fallback",), 13),
        (("exact", "fluid", "fallback"), 14),
        (("fallback", "exact", "exact", "fluid"), 15),
        (("fluid", "fallback", "fluid", "exact", "fallback"), 16),
        (("exact", "exact", "fluid", "fluid", "fallback", "exact"), 17),
    ]

    @pytest.mark.parametrize("kinds,seed", _CASES)
    def test_mixed_job_lists_property(kinds, seed, frozen_policy):
        _check_mixed_list(list(kinds), seed, frozen_policy)
else:
    @given(kinds=st.lists(st.sampled_from(_KINDS), min_size=1, max_size=6),
           seed=st.integers(0, 2 ** 16))
    @settings(max_examples=12, deadline=None)
    def test_mixed_job_lists_property(kinds, seed, frozen_policy):
        _check_mixed_list(kinds, seed, frozen_policy)
