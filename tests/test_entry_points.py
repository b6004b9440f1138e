"""Entry-point plumbing: spawned host pools, where the compile cache goes,
and the figure harness's exit status and placement of device modules."""

from __future__ import annotations

import pathlib
import sys

import jax
import pytest

from repro.core.device_model import platform_a
from repro.core.littles_law import OpClass
from repro.launch.compile_cache import ENV_VAR, use_compile_cache
from repro.memsim.sweep import SimJob, host_pool, run_sweep
from repro.memsim.workloads import bw_test

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_scalar_pool_spawns_and_matches_serial():
    p = platform_a()
    jobs = [SimJob(platform=p, sim_ns=20_000.0,
                   workloads=[bw_test(t, OpClass.LOAD, 4, name="w")])
            for t in ("ddr", "cxl")]
    with host_pool(1) as pool:
        assert pool._mp_context.get_start_method() == "spawn"
    serial = run_sweep(jobs)
    pooled = run_sweep(jobs, processes=2)
    assert [r.bandwidth("w") for r in pooled] == \
        [r.bandwidth("w") for r in serial]


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = use_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_figure_harness_exits_nonzero_on_an_error_row(monkeypatch, capsys):
    import benchmarks.run as harness

    monkeypatch.setattr(harness, "_module_names", lambda: ["no_such_fig"])
    monkeypatch.setattr(sys, "argv", ["run.py"])
    with pytest.raises(SystemExit) as ex:
        harness.main()
    assert ex.value.code == 1
    assert "no_such_fig,0,ERROR:ModuleNotFoundError" in \
        capsys.readouterr().out


def test_device_modules_stay_in_the_parent(monkeypatch):
    import benchmarks.run as harness

    from repro.memsim.batched import kernel

    monkeypatch.delenv("REPRO_SWEEP_LANE", raising=False)
    monkeypatch.setattr(kernel, "uses_fused_solver", lambda: True)
    assert harness._uses_device("fig11_llm")
    assert not harness._uses_device("fig5_corun")
    monkeypatch.setenv("REPRO_SWEEP_LANE", "batched")
    assert harness._uses_device("fig5_corun")
    monkeypatch.setattr(kernel, "uses_fused_solver", lambda: False)
    assert not harness._uses_device("fig5_corun")
