"""Observability-layer tests (repro.obs: tracing, histograms, metrics).

Six contracts:

1. **Histogram algebra** — the log16 bucket layout partitions the positive
   reals; merge over any window/cell split is *exact* (bucket-for-bucket
   equal to bucketing the concatenated samples); percentiles land within
   the documented 1/16 bucket relative error of the order statistics.
2. **Linear-interpolated percentiles** — ``linear_percentile`` (and
   ``WorkloadStats.percentile_ns`` on top of it) matches hand-computed
   order-statistic interpolation on pinned inputs.
3. **Tracing-off bit-identity** — enabling tracing + histograms +
   profiling changes *nothing* about the simulation outcome: bandwidth,
   latency sums, completion counts and ToR inserts are equal bit for bit
   (the sampler draws no random numbers).
4. **Span-chain physics** — every traced request's spans contiguously
   partition ``[t_tor, t_retire]`` (monotone, non-overlapping,
   non-negative), so queue + service + stall + flight exactly equals the
   ToR residency; fabric requests show the hop-port stations.
5. **Golden Perfetto export** — the canonical spine co-run's sampled trace
   reproduces the pinned Chrome trace-event JSON
   (``tests/data/spine_perfetto_golden.json``; set ``REPRO_REGEN=1`` to
   re-record after an intentional change).
6. **Lane parity** — the batched exact lane's histogram equals the scalar
   DES's exactly; the fluid lane's analytic synthesis lands within the
   documented tolerance; traced jobs fall back to the scalar DES.
7. **Program spans** — ``span`` charges only the current
   ``PhaseProfiler``; a profiled batched grid reports the lane's phases
   (one ``lane.window`` per group and window) with rows unchanged, and
   only jobs that run on the scalar DES carry per-job profiles.
"""

import dataclasses
import json
import math
import os

import pytest

from repro.core.des import WorkloadStats, run_corun
from repro.core.device_model import platform_a
from repro.core.littles_law import OpClass, linear_percentile
from repro.memsim.sweep import SimJob, run_job, run_sweep
from repro.memsim.workloads import bw_test
from repro.obs import (
    LatencyHistogram,
    MetricsRegistry,
    PhaseProfiler,
    RequestTracer,
    TraceConfig,
    TransferTracer,
    default_registry,
    span,
    to_chrome,
)
from repro.obs.histogram import bucket_bounds, bucket_index, merge_all

DATA = os.path.join(os.path.dirname(__file__), "data")
GOLDEN = os.path.join(DATA, "spine_perfetto_golden.json")

#: Max relative error of a log16 bucket (docs/observability.md): 1/16
#: between bucket edges, plus interpolation slack inside the bucket.
BUCKET_TOL = 1.0 / 16.0 + 0.01


# -- 1. histogram algebra -----------------------------------------------------


def _samples(n: int = 400) -> list:
    # Deterministic, spread over ~4 decades (LCG — no random module).
    xs, state = [], 12345
    for _ in range(n):
        state = (state * 1103515245 + 12345) % (1 << 31)
        xs.append(50.0 + (state % 1_000_000) / 37.0)
    return xs


def test_bucket_layout_partitions():
    for v in (1e-3, 0.5, 1.0, 17.3, 291.0, 1e6, 3.7e9):
        idx = bucket_index(v)
        lo, hi = bucket_bounds(idx)
        assert lo <= v < hi, (v, lo, hi)
        # Adjacent buckets tile: this bucket's hi is the next one's lo.
        assert bucket_bounds(idx + 1)[0] == hi
        # Relative bucket width is 1/(16+s) <= 1/16 (6.25% max error).
        assert (hi - lo) / lo <= 1.0 / 16.0 + 1e-12


def test_histogram_percentiles_within_bucket_error():
    xs = _samples()
    h = LatencyHistogram.from_samples(xs)
    assert h.n == len(xs)
    s = sorted(xs)
    for q in (0.0, 0.25, 0.5, 0.95, 0.99, 1.0):
        exact = linear_percentile(s, q)
        assert h.percentile(q) == pytest.approx(exact, rel=BUCKET_TOL)
    assert h.mean() == pytest.approx(sum(xs) / len(xs), rel=BUCKET_TOL)
    # min/max are tracked exactly, and percentiles clamp to them.
    assert h.percentile(0.0) == min(xs)
    assert h.percentile(1.0) == max(xs)


def test_histogram_merge_is_exact():
    xs = _samples(600)
    whole = LatencyHistogram.from_samples(xs)
    parts = [
        LatencyHistogram.from_samples(xs[i::4]) for i in range(4)
    ]
    merged = merge_all(parts)
    # Exact merge: same bucket counts, n, zero count, min and max — not
    # "approximately equal", *equal* (the acceptance contract).
    assert merged == whole
    # Pairwise merge agrees too, in any order.
    alt = parts[3].merge(parts[1]).merge(parts[0]).merge(parts[2])
    assert alt == whole
    assert merge_all([]) == LatencyHistogram()


def test_histogram_from_samples_numpy_parity():
    # The >=512-sample numpy fast path must bucket identically to the
    # scalar loop.
    xs = _samples(700)
    fast = LatencyHistogram.from_samples(xs)
    slow = LatencyHistogram()
    for v in xs:
        slow.record(v)
    assert fast == slow


def test_histogram_weighted_and_zero():
    h = LatencyHistogram()
    h.record_weighted(100.0, 3.0)
    h.record_weighted(100.0, 0.0)  # ignored
    h.record_weighted(-5.0, 2.0)  # zero bucket
    g = LatencyHistogram()
    for _ in range(3):
        g.record(100.0)
    g.record(-5.0)
    g.record(-5.0)
    assert h.n == 5 and h.zero == 2
    assert h.counts == g.counts
    # Rank 0 lands in the zero bucket: reports min(0, vmin).
    assert h.percentile(0.0) == -5.0


def test_histogram_jsonable_roundtrip():
    h = LatencyHistogram.from_samples(_samples(300))
    h.record_weighted(0.0, 2.0)
    blob = json.loads(json.dumps(h.to_jsonable()))
    assert blob["scheme"] == "log16"
    back = LatencyHistogram.from_jsonable(blob)
    assert back == h
    for q in (0.5, 0.99):
        assert back.percentile(q) == h.percentile(q)


# -- 2. linear-interpolated percentiles ---------------------------------------


def test_linear_percentile_pins():
    xs = [10.0, 20.0, 30.0, 40.0]
    assert linear_percentile(xs, 0.5) == 25.0
    assert linear_percentile(xs, 0.25) == 17.5
    assert linear_percentile(xs, 0.0) == 10.0
    assert linear_percentile(xs, 1.0) == 40.0
    assert linear_percentile([7.0], 0.9) == 7.0
    assert linear_percentile([], 0.5) == 0.0
    # Out-of-range q clamps.
    assert linear_percentile(xs, -1.0) == 10.0
    assert linear_percentile(xs, 2.0) == 40.0


def test_workload_stats_percentile_interpolates():
    st = WorkloadStats()
    st.latency_samples = [40.0, 10.0, 30.0, 20.0]  # unsorted on purpose
    assert st.percentile_ns(0.5) == 25.0
    assert st.percentile_ns(0.75) == 32.5
    # Zero completions → NaN, not a fake 0 ns latency: a NaN p99 can never
    # satisfy an SLO budget comparison (see WorkloadStats.percentile_ns).
    assert math.isnan(WorkloadStats().percentile_ns(0.5))


# -- 3. tracing-off bit-identity ----------------------------------------------


def _corun_job(**over) -> SimJob:
    p = platform_a()
    wls = [
        bw_test("ddr", OpClass.LOAD, 16, name="ddr", miku_managed=False),
        bw_test("cxl", OpClass.LOAD, 16, name="cxl"),
    ]
    return SimJob(platform=p, workloads=wls, sim_ns=150_000.0, miku=True,
                  **over)


@pytest.fixture(scope="module")
def corun_pair():
    plain = run_job(_corun_job())
    instr = run_job(
        dataclasses.replace(
            _corun_job(), trace=16, latency_hist=True, profile=True,
            record_windows=True,
        )
    )
    return plain, instr


def test_observability_is_bit_identical(corun_pair):
    plain, instr = corun_pair
    for w in ("ddr", "cxl"):
        assert instr.stats[w].bytes == plain.stats[w].bytes
        assert instr.stats[w].completed == plain.stats[w].completed
        assert instr.stats[w].latency_sum == plain.stats[w].latency_sum
        assert instr.stats[w].latency_samples == plain.stats[w].latency_samples
    assert instr.tor_inserts == plain.tor_inserts
    assert instr.tor_peak == plain.tor_peak
    assert [repr(d) for d in instr.decisions] == \
        [repr(d) for d in plain.decisions]
    # The plain run carries no observability payloads at all.
    assert plain.trace is None and plain.profile is None
    assert plain.stats["ddr"].latency_hist is None
    assert instr.trace is not None and instr.profile is not None


def test_histogram_tracks_reservoir(corun_pair):
    _, instr = corun_pair
    for w in ("ddr", "cxl"):
        st = instr.stats[w]
        h = st.latency_hist
        assert h is not None and h.n == st.latency_count
        for q in (0.5, 0.99):
            assert h.percentile(q) == pytest.approx(
                st.percentile_ns(q), rel=BUCKET_TOL
            )
    # Per-tier histograms cover every completion.
    tier_n = sum(h.n for h in instr.tier_latency_hist.values())
    assert tier_n == sum(s.latency_count for s in instr.stats.values())


def test_window_histograms_merge_to_full(corun_pair):
    _, instr = corun_pair
    per_window = {}
    for rec in instr.window_records:
        for w, blob in rec.get("latency_hist", {}).items():
            per_window.setdefault(w, []).append(
                LatencyHistogram.from_jsonable(blob)
            )
    for w in ("ddr", "cxl"):
        merged = merge_all(per_window[w])
        # Exact cross-window merge: equal to the full-run histogram
        # bucket for bucket (windows slice the same sample stream).
        assert merged == instr.stats[w].latency_hist


def test_phase_profile_shape(corun_pair):
    _, instr = corun_pair
    phases = instr.profile["phases"]
    assert {"setup", "event_loop", "window_pass"} <= set(phases)
    assert phases["event_loop"]["seconds"] > 0
    assert phases["window_pass"]["calls"] == len(
        [r for r in instr.window_records]
    )


# -- 4. span-chain physics ----------------------------------------------------


def _check_span_conservation(rec, tol=1e-6):
    assert rec["t_issue"] <= rec["t_tor"] <= rec["t_retire"]
    spans = rec["spans"]
    assert spans, rec
    t = rec["t_issue"] if spans[0]["kind"] == "irq" else rec["t_tor"]
    for sp in spans:
        # Contiguous partition: each span starts where the last ended.
        assert sp["t0"] == pytest.approx(t, abs=tol), (sp, t)
        assert sp["t1"] >= sp["t0"]
        t = sp["t1"]
    assert t == pytest.approx(rec["t_retire"], abs=tol)
    # Conservation: queue + service + stall + flight == ToR residency.
    tor = sum(sp["t1"] - sp["t0"] for sp in spans if sp["kind"] != "irq")
    assert tor == pytest.approx(rec["t_retire"] - rec["t_tor"], abs=tol)


def test_trace_spans_conserve(corun_pair):
    _, instr = corun_pair
    payload = instr.trace
    assert 0 < payload["n_traced"] <= payload["limit"]
    assert payload["sample_every"] == 16
    kinds = set()
    for rec in payload["requests"]:
        _check_span_conservation(rec)
        kinds.update(sp["kind"] for sp in rec["spans"])
    assert {"service", "flight"} <= kinds


@pytest.fixture(scope="module")
def spine_trace():
    from repro.scenarios import get

    sc = get("fabric_spine_congestion")
    cell = {
        "op": OpClass.LOAD, "law": "peredge", "n_threads": 16,
        "spine_slots": 8, "spine_service_ns": 36.0, "sim_ns": 120_000.0,
    }
    corun = sc.build(None, cell)[2]
    job = dataclasses.replace(
        corun, trace=TraceConfig(sample_every=997, limit=64)
    )
    return run_job(job).trace


def test_fabric_spans_show_hop_ports(spine_trace):
    stations = set()
    for rec in spine_trace["requests"]:
        _check_span_conservation(rec)
        stations.update(
            sp["station"] for sp in rec["spans"]
            if sp["kind"] in ("queue", "service", "stall")
        )
    # Hop-port stations (uplinks + the shared spine downlink) appear in
    # the span chains, not just the terminal device.
    assert any("uplink" in s or "spine" in s for s in stations), stations


def test_trace_is_deterministic(spine_trace):
    from repro.scenarios import get

    sc = get("fabric_spine_congestion")
    cell = {
        "op": OpClass.LOAD, "law": "peredge", "n_threads": 16,
        "spine_slots": 8, "spine_service_ns": 36.0, "sim_ns": 120_000.0,
    }
    corun = sc.build(None, cell)[2]
    again = run_job(dataclasses.replace(
        corun, trace=TraceConfig(sample_every=997, limit=64)
    )).trace
    assert again == spine_trace


# -- 5. golden Perfetto export ------------------------------------------------


def test_perfetto_golden(spine_trace):
    doc = to_chrome(spine_trace["requests"])
    if os.environ.get("REPRO_REGEN"):
        with open(GOLDEN, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
    with open(GOLDEN) as f:
        golden = json.load(f)
    assert doc == golden, (
        "spine Perfetto trace drifted from tests/data/"
        "spine_perfetto_golden.json; if intentional, re-record with "
        "REPRO_REGEN=1 pytest tests/test_obs.py::test_perfetto_golden"
    )


def test_chrome_export_schema(spine_trace):
    doc = to_chrome(spine_trace["requests"])
    assert doc["displayTimeUnit"] == "ns"
    evs = doc["traceEvents"]
    for e in evs:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0
    procs = {e["args"]["name"] for e in evs if e["name"] == "process_name"}
    assert procs == {r["workload"] for r in spine_trace["requests"]}


# -- 6. lane parity -----------------------------------------------------------


def test_exact_lane_histogram_equals_scalar():
    p = platform_a()
    job = SimJob(
        platform=p,
        workloads=[bw_test("cxl", OpClass.LOAD, 16, name="bw")],
        sim_ns=100_000.0, latency_hist=True,
    )
    (batched,) = run_sweep([job], lane="batched")
    (scalar,) = run_sweep([job], lane="scalar")
    # The exact lane buckets the full (bit-identical) latency vector, so
    # its histogram equals the scalar DES's exactly.
    assert batched.stats["bw"].latency_hist == scalar.stats["bw"].latency_hist
    assert batched.tier_latency_hist["cxl"] == scalar.tier_latency_hist["cxl"]
    assert batched.tier_latency_hist["ddr"].n == 0


def test_fluid_lane_histogram_tolerance():
    job = dataclasses.replace(_corun_job(), latency_hist=True)
    (batched,) = run_sweep([job], lane="batched")
    (scalar,) = run_sweep([job], lane="scalar")
    for w in ("ddr", "cxl"):
        hb, hs = batched.stats[w].latency_hist, scalar.stats[w].latency_hist
        assert hb is not None
        # Analytic synthesis from station waits: means track closely,
        # counts within the fluid lane's flow approximation.
        assert hb.mean() == pytest.approx(hs.mean(), rel=0.10)
        assert hb.n == pytest.approx(hs.n, rel=0.05)


def test_traced_jobs_fall_back_to_scalar():
    from repro.memsim.batched.lane import can_batch

    assert can_batch(dataclasses.replace(_corun_job(), trace=16)) == "trace"
    assert can_batch(dataclasses.replace(_corun_job(), latency_hist=True)) \
        is None


# -- transfer-queue tracing & metrics -----------------------------------------


def test_transfer_queue_trace_records():
    from repro.core.offload import TransferQueue

    q = TransferQueue(trace=1)
    q.submit_slow_stream(8 << 20, 8, OpClass.LOAD)
    q.advance(5e6)
    recs = q.trace_records
    assert len(recs) == 8
    for rec in recs:
        _check_span_conservation(rec)
        assert rec["workload"] == "offload:slow"
    # Sampling: every 4th chunk only.
    q4 = TransferQueue(trace=4)
    q4.submit_slow_stream(8 << 20, 8, OpClass.LOAD)
    assert len(q4.trace_records) == 2
    # to_chrome renders transfer records alongside DES ones.
    doc = to_chrome(recs)
    assert any(e["ph"] == "X" for e in doc["traceEvents"])


def test_transfer_tracer_respects_limit():
    tr = TransferTracer(sample_every=1, limit=3)
    for i in range(10):
        tr.on_chunk("slow", float(i), float(i + 2), 1.0)
    assert len(tr.records) == 3 and tr.count == 10


def test_metrics_registry():
    reg = MetricsRegistry()
    reg.counter("a").inc()
    reg.counter("a").inc(2.0)
    reg.gauge("g").set(7.5)
    reg.histogram("h").record(100.0)
    snap = reg.snapshot()
    assert snap["counters"]["a"] == 3.0
    assert snap["gauges"]["g"] == 7.5
    assert snap["histograms"]["h"]["n"] == 1
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}
    assert default_registry() is default_registry()


def test_des_registers_metrics():
    reg = default_registry()
    before = reg.snapshot()["counters"].get("des.runs", 0.0)
    run_corun(platform_a(), op=OpClass.LOAD, n_threads=4, sim_ns=20_000)
    after = reg.snapshot()["counters"]
    assert after["des.runs"] == before + 1.0
    assert after["des.requests"] > 0


def test_phase_profiler():
    prof = PhaseProfiler()
    with prof.phase("work"):
        math.sqrt(2.0)
    with prof.phase("work"):
        pass
    snap = prof.snapshot()
    assert snap["phases"]["work"]["calls"] == 2
    assert snap["phases"]["work"]["seconds"] >= 0.0


def test_span_is_a_noop_without_a_current_profiler():
    prof = PhaseProfiler()
    with span("lane.window"):
        pass
    assert prof.snapshot() == {"phases": {}}
    with prof.activate():
        pass
    with span("lane.window"):  # the activation has ended
        pass
    assert prof.snapshot() == {"phases": {}}


def test_nested_spans_charge_the_current_profiler():
    prof = PhaseProfiler()
    with prof.activate():
        for _ in range(3):
            with span("outer"):
                with span("inner"):
                    math.sqrt(2.0)
    phases = prof.snapshot()["phases"]
    assert phases["outer"]["calls"] == 3
    assert phases["inner"]["calls"] == 3
    assert prof.seconds["outer"] >= prof.seconds["inner"] > 0.0


#: A small batched co-run grid: 2 ops x 2 MIKU settings, so two fluid
#: groups (MIKU on, MIKU off) of 10 windows each.
_LANE_GRID = {"platform": ("A",), "threads": (4,), "mlp": (64,)}


@pytest.fixture(scope="module")
def lane_tables():
    from repro.scenarios import run_scenario

    def run(profile):
        return run_scenario("corun_sweep_1k", _LANE_GRID, lane="batched",
                            profile=profile)

    return run(True), run(False)


def test_batched_lane_reports_its_phases(lane_tables):
    profiled, _ = lane_tables
    phases = profiled.meta["profile"]["phases"]
    for name in ("plan", "sweep", "reduce", "lane.partition", "lane.stack",
                 "lane.ladder", "lane.apply", "lane.window", "lane.solve"):
        assert phases[name]["calls"] > 0, name
    groups = phases["lane.group"]["calls"]
    assert groups == 2
    assert phases["lane.window"]["calls"] == groups * 10
    # Only the MIKU group runs the ladder; every window applies.
    assert phases["lane.ladder"]["calls"] == 10
    assert phases["lane.apply"]["calls"] == groups * 10
    assert phases["lane.window"]["seconds"] <= phases["sweep"]["seconds"]
    # The fluid and exact paths read no per-job profile.
    assert profiled.meta["profile"]["jobs"] == []
    counters = profiled.meta["metrics"]["counters"]
    assert counters["lane.windows"] >= groups * 10
    assert counters["lane.cells_fluid"] >= len(profiled.rows)


def test_batched_lane_rows_are_identical_when_profiled(lane_tables):
    profiled, plain = lane_tables
    assert len(profiled.rows) == 4
    assert profiled.rows == plain.rows
    assert "profile" not in plain.meta


def test_fallback_jobs_keep_their_profiles():
    from repro.scenarios import run_scenario

    table = run_scenario(
        "fig4_latency",
        {"platform": "A", "tier": ("cxl",), "threads": (4,)},
        lane="batched", perfetto=True, profile=True,
    )
    n = table.meta["scalar_fallback_jobs"]
    assert n > 0 and table.meta["fallback_reasons"] == ["trace"]
    jobs = table.meta["profile"]["jobs"]
    assert len(jobs) == n
    assert all("event_loop" in j["phases"] for j in jobs)
    assert table.meta["profile"]["phases"]["lane.scalar"]["calls"] == 1


def test_tracer_config_validation():
    with pytest.raises(ValueError):
        TraceConfig(sample_every=0)
    with pytest.raises(ValueError):
        TraceConfig(limit=0)
    with pytest.raises(ValueError):
        TransferTracer(sample_every=0)
    tracer = RequestTracer(TraceConfig(limit=1), ["w"], ["st"], ["t"])
    tracer.admit(1, 0, 0, 0.0, 1.0)
    tracer.admit(2, 0, 0, 0.0, 1.0)  # over the limit: dropped
    tracer.retire(1, 5.0)
    assert len(tracer.done) == 1 and tracer.dropped == 1


# -- planner + CLI integration ------------------------------------------------


def test_planner_perfetto_collects_traces():
    from repro.scenarios import run_scenario

    table = run_scenario(
        "fig4_latency",
        {"platform": "A", "tier": ("cxl",), "threads": (4,)},
        perfetto=True,
    )
    assert table.request_traces is not None
    payload = table.request_traces[0]["jobs"][0]["trace"]
    assert payload["n_traced"] > 0
    # request_traces never leak into the JSON document.
    assert "request_traces" not in table.to_json()


def test_planner_perfetto_rejects_run_cell():
    from repro.scenarios import run_scenario

    with pytest.raises(ValueError, match="run_cell"):
        run_scenario("fig2_tiering", perfetto=True)


def test_fig4_reports_p95():
    from repro.scenarios import run_scenario

    table = run_scenario(
        "fig4_latency", {"platform": "A", "tier": ("ddr",), "threads": (2,)}
    )
    (row,) = table.rows
    assert row["p50_ns"] <= row["p95_ns"] * (1 + BUCKET_TOL)
    assert row["p95_ns"] <= row["p99_ns"] * (1 + BUCKET_TOL)
    assert row["p95_ns"] > 0
