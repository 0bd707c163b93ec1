"""Telemetry layer (src/repro/telemetry/): registry semantics, cold-start
spans on the profiler's timeline, the stats snapshotter on a fake clock (no sleeps — REP004
thread shutdown is the only wall-clock moment), the canonical stat-key
schema, forecast-profile persistence round trips, and the bench trend
gate (scripts/bench_compare.py --history).
"""
import importlib.util
import json
import os
import threading

import pytest
from fakeclock import FakeClock

from repro.telemetry import (LEGACY_ALIASES, SAMPLE_KEYS, MetricsRegistry,
                             StatsSnapshotter, canonicalize)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- registry ------------------------------------------------------------

def test_counter_gauge_histogram_roundtrip():
    reg = MetricsRegistry()
    reg.inc("a.hits")
    reg.inc("a.hits", 4)
    reg.set_gauge("a.depth", 3.5)
    for v in (0.001, 0.002, 0.004, 0.1):
        reg.observe("a.lat_s", v)
    snap = reg.collect()
    assert snap["enabled"] is True
    assert snap["counters"]["a.hits"] == 5
    assert snap["gauges"]["a.depth"] == 3.5
    h = snap["histograms"]["a.lat_s"]
    assert h["count"] == 4
    assert h["sum"] == pytest.approx(0.107)
    assert h["min"] == 0.001 and h["max"] == 0.1
    assert sum(h["buckets"]) == 4


def test_histogram_percentile_bucket_resolution():
    reg = MetricsRegistry()
    h = reg.histogram("lat_s")
    for _ in range(99):
        h.observe(0.001)
    h.observe(1.0)
    # p50 lands in the 0.001 bucket, p99.5+ in the 1.0 bucket
    assert reg.histogram("lat_s").percentile(50) < 0.01
    assert reg.histogram("lat_s").percentile(99.9) >= 0.5


def test_same_name_returns_same_instrument():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    assert reg.histogram("y") is reg.histogram("y")


def test_disabled_registry_is_noop():
    reg = MetricsRegistry()
    reg.inc("hits", 2)
    reg.disable()
    reg.inc("hits", 100)
    reg.observe("lat_s", 1.0)
    with reg.span("restore.install", member=0) as span:
        span.set_metadata(pages=3)
    assert reg.span("a") is reg.span("b", x=1)    # one shared null span
    reg.enable()
    snap = reg.collect()
    assert snap["counters"]["hits"] == 2
    assert "lat_s" not in snap["histograms"]


def test_reset_clears_everything():
    reg = MetricsRegistry()
    reg.inc("a")
    reg.observe("b", 1.0)
    with reg.span("s"):
        pass
    reg.reset()
    snap = reg.collect()
    assert snap == {"enabled": True, "counters": {}, "gauges": {},
                    "histograms": {}}


# -- spans on the profiler's timeline -----------------------------------

def _profiled(log_dir, body):
    """Run ``body`` inside a profiler session; the host events of the
    trace as {name: [(start_ns, end_ns, stats)]}."""
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(log_dir))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)[0]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns,
                     dict(ev.stats)))
    return out


def test_span_lands_in_the_profiler_trace_only_when_enabled(tmp_path):
    on, off = MetricsRegistry(), MetricsRegistry(enabled=False)

    def body():
        with on.span("telemetry.outer", inv=7, queue_s=0.25) as span:
            with on.span("telemetry.inner", bytes=4096):
                pass
            span.set_metadata(pages=3)
        with off.span("telemetry.off", inv=8):
            pass

    events = _profiled(tmp_path, body)
    assert "telemetry.off" not in events
    [(s0, t0, outer)] = events["telemetry.outer"]
    [(s1, t1, inner)] = events["telemetry.inner"]
    assert outer == {"inv": 7, "queue_s": 0.25, "pages": 3}
    assert inner == {"bytes": 4096}
    assert s0 <= s1 <= t1 <= t0               # nested under its caller


@pytest.mark.parametrize("enabled", [True, False])
def test_a_span_whose_body_raises_closes_and_reraises(tmp_path, enabled):
    reg = MetricsRegistry(enabled=enabled)

    def body():
        with pytest.raises(ValueError, match="boom"):
            with reg.span("telemetry.raises", member=1):
                raise ValueError("boom")
        with reg.span("telemetry.after"):
            pass

    events = _profiled(tmp_path, body)
    if not enabled:
        assert "telemetry.raises" not in events
        return
    [(s0, t0, attrs)] = events["telemetry.raises"]
    [(s1, _, _)] = events["telemetry.after"]
    assert attrs == {"member": 1}
    assert t0 <= s1                            # closed before the next


def test_restore_install_span_reports_copy_workers(tmp_path, monkeypatch):
    """Each ``restore.install`` span carries the copy's ``workers``;
    ``arena.install.parallel_pages`` counts the pages of a block
    at or above the parallel threshold, and none of one below it."""
    import numpy as np

    from repro.core import arena as arena_mod
    from repro.core.arena import PAGE, ArenaLayout, GuestMemoryFile
    from repro.core.restore import RestorePipeline
    from repro.telemetry import TELEMETRY

    monkeypatch.setattr(arena_mod, "PARALLEL_MIN_PAGES", 64)
    monkeypatch.setattr(arena_mod, "COPY_CHUNK_PAGES", 16)
    layout = ArenaLayout.build([("w", (512 * PAGE,), "uint8", "serve")])
    gm = GuestMemoryFile(str(tmp_path / "fn"), layout)
    with open(gm.mem_path, "wb") as f:
        f.truncate(layout.total_bytes)
    blocks = [np.r_[0:100, 200:300], np.arange(400, 440)]   # 200, 40 pages
    counter = TELEMETRY.counter("arena.install.parallel_pages")
    n0 = counter.value
    pipes = []

    def body():
        for member, pages in enumerate(blocks):
            p = RestorePipeline(gm.base, mode="vanilla")
            p.member = member
            p.load_vmm(layout=layout)
            pipes.append(p)
            p.install_block(pages, np.ones((len(pages), PAGE), np.uint8),
                            hit=True)

    events = _profiled(tmp_path / "prof", body)
    for p in pipes:
        p.close()
    spans = {s[2]["member"]: s[2] for s in events["restore.install"]}
    big, small = spans[0], spans[1]
    assert big["pages"] == 200
    assert big["workers"] == min(arena_mod._copy_workers(), 200 // 16 + 1)
    assert small == {"member": 1, "pages": 40, "workers": 1}
    assert counter.value - n0 == (200 if big["workers"] > 1 else 0)


# -- snapshotter ---------------------------------------------------------

def test_snapshotter_fakeclock_cadence():
    clock = FakeClock()
    reg = MetricsRegistry()
    snap = StatsSnapshotter(interval_s=1.0, clock=clock, registry=reg)
    snap.add_source("const", lambda: {"v": 1})
    assert snap.maybe_sample() is not None    # first sample always taken
    assert snap.maybe_sample() is None        # same instant: gated
    clock.advance(0.5)
    assert snap.maybe_sample() is None        # inside the interval
    clock.advance(0.5)
    assert snap.maybe_sample() is not None    # exactly one interval later
    assert snap.n_samples == 2


def test_snapshotter_schema_stability():
    clock = FakeClock()
    snap = StatsSnapshotter(clock=clock, registry=MetricsRegistry())
    snap.add_source("a", lambda: {"x": 1})
    snap.add_source("b", lambda: {"y": 2})
    for _ in range(5):
        rec = snap.sample()
        assert tuple(sorted(rec)) == tuple(sorted(SAMPLE_KEYS))
        assert set(rec["sources"]) == {"a", "b"}
        clock.advance(1.0)
    seqs = [r["seq"] for r in snap.samples()]
    assert seqs == sorted(seqs)


def test_snapshotter_ring_bound():
    clock = FakeClock()
    snap = StatsSnapshotter(ring=8, clock=clock, registry=MetricsRegistry())
    snap.add_source("a", lambda: {})
    for _ in range(30):
        snap.sample()
        clock.advance(1.0)
    assert len(snap.samples()) == 8
    assert snap.n_samples == 30


def test_snapshotter_failing_source_isolated():
    clock = FakeClock()
    snap = StatsSnapshotter(clock=clock, registry=MetricsRegistry())
    snap.add_source("good", lambda: {"v": 7})
    snap.add_source("bad", lambda: 1 / 0)
    rec = snap.sample()
    assert rec["sources"]["good"] == {"v": 7}
    assert "ZeroDivisionError" in rec["sources"]["bad"]["error"]
    assert rec["errors"] == 1


def test_snapshotter_jsonl_output(tmp_path):
    path = str(tmp_path / "telemetry" / "stream.jsonl")
    clock = FakeClock()
    reg = MetricsRegistry()
    reg.inc("hits", 3)
    snap = StatsSnapshotter(path=path, clock=clock, registry=reg)
    snap.add_source("registry", reg.collect)
    for _ in range(3):
        snap.sample()
        clock.advance(1.0)
    snap.close()                              # +1 final sample
    with open(path) as f:
        lines = [json.loads(line) for line in f if line.strip()]
    assert len(lines) == 4
    for rec in lines:
        assert tuple(sorted(rec)) == tuple(sorted(SAMPLE_KEYS))
        assert rec["sources"]["registry"]["counters"]["hits"] == 3


def test_snapshotter_thread_shutdown():
    """REP004: daemon thread, stop event, join — and close() is idempotent."""
    snap = StatsSnapshotter(interval_s=0.01, registry=MetricsRegistry())
    snap.add_source("a", lambda: {})
    snap.start()
    assert snap._thread is not None and snap._thread.daemon
    t = snap._thread
    snap.close()
    assert not t.is_alive()
    assert snap._thread is None
    snap.close()                              # second close: no-op


def test_snapshotter_concurrent_samples_consistent(tmp_path):
    path = str(tmp_path / "s.jsonl")
    snap = StatsSnapshotter(path=path, registry=MetricsRegistry())
    snap.add_source("a", lambda: {"v": 1})
    threads = [threading.Thread(target=snap.sample) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap.close()
    with open(path) as f:
        lines = [json.loads(line) for line in f if line.strip()]
    assert len(lines) == 9                    # 8 + close()'s final sample
    assert sorted(r["seq"] for r in lines) == list(range(9))


# -- schema / legacy aliases ---------------------------------------------

def test_canonicalize_renames_legacy_keys():
    raw = {"ws_hits": 3, "nested": [{"ws_cache_hit": 1}],
           "warm_counts": {"f": 2}, "untouched": 0}
    out = canonicalize(raw)
    assert out["ws_cache_hits"] == 3
    assert out["nested"][0]["ws_cache_hits"] == 1
    assert out["warm_instances"] == {"f": 2}
    assert out["untouched"] == 0
    assert "ws_hits" not in out


def test_canonicalize_canonical_key_wins_on_collision():
    out = canonicalize({"ws_hits": 1, "ws_cache_hits": 9})
    assert out["ws_cache_hits"] == 9


def test_legacy_aliases_map_into_schema():
    for legacy, canonical in LEGACY_ALIASES.items():
        assert legacy != canonical
        assert canonical not in LEGACY_ALIASES


# -- forecast persistence ------------------------------------------------

def _periodic_demand(clock, *, period=8.0, cycles=3):
    from repro.serving import ForecastConfig, ForecastDemand, PolicyConfig
    fcfg = ForecastConfig(bin_s=0.5, history_s=60.0, min_period_s=2.0,
                          max_period_s=30.0, lookahead_s=2.0,
                          period_hint_s=period)
    d = ForecastDemand(PolicyConfig(), fcfg, clock=clock)
    t0 = clock()
    for c in range(cycles):
        base = t0 + c * period
        d.observe([base + 0.1 * i for i in range(10)])  # one busy phase/cycle
        clock.advance(period)
    return d, fcfg


def test_forecast_demand_state_roundtrip():
    from repro.serving import ForecastDemand, PolicyConfig
    clock = FakeClock()
    d, fcfg = _periodic_demand(clock)
    state = d.export_state()
    assert state is not None
    assert state["period_s"] == pytest.approx(8.0)
    assert state["bin_s"] == pytest.approx(0.5)
    assert any(r > 0 for r in state["rates"])

    # fresh process, zero history: the seeded detector forecasts day one
    clock2 = FakeClock()
    d2 = ForecastDemand(PolicyConfig(), fcfg, clock=clock2)
    assert d2.seed_state(json.loads(json.dumps(state)))   # file round trip
    assert d2.detector.seeded
    period, conf = d2.detector.detect(clock2())
    assert period == pytest.approx(8.0)
    assert conf > 0
    assert not d2.forgettable(clock2())       # seeded entries survive sweeps


def test_forecast_seed_rejects_bin_mismatch():
    from repro.serving import (ForecastConfig, ForecastDemand, PolicyConfig)
    clock = FakeClock()
    d, _ = _periodic_demand(clock)
    state = d.export_state()
    other = ForecastDemand(PolicyConfig(),
                           ForecastConfig(bin_s=1.0), clock=clock)
    assert not other.seed_state(state)
    assert not other.detector.seeded


def test_aggregator_profile_roundtrip():
    from repro.cluster import DemandAggregator, DemandConfig
    from repro.serving import ForecastConfig

    class _StubCluster:
        store = None

        def alive_nodes(self):
            return []

    clock = FakeClock()
    fcfg = ForecastConfig(bin_s=0.5, history_s=60.0, min_period_s=2.0,
                          max_period_s=30.0, period_hint_s=8.0)
    agg = DemandAggregator(_StubCluster(),
                           DemandConfig(forecast=fcfg), clock=clock)
    t0 = clock()
    for c in range(3):
        agg.ingest({"fn_a": [t0 + c * 8.0 + 0.1 * i for i in range(10)]})
        clock.advance(8.0)
    profiles = agg.export_profiles()
    assert "fn_a" in profiles

    clock2 = FakeClock()
    agg2 = DemandAggregator(_StubCluster(),
                            DemandConfig(forecast=fcfg), clock=clock2)
    payload = json.loads(json.dumps({"version": 1, "profiles": profiles}))
    assert agg2.seed_profiles(payload["profiles"]) == 1
    assert agg2.demand["fn_a"].detector.seeded
    period, _ = agg2.demand["fn_a"].detector.detect(clock2())
    assert period == pytest.approx(8.0)


# -- bench trend gate ----------------------------------------------------

@pytest.fixture(scope="module")
def bench_compare():
    spec = importlib.util.spec_from_file_location(
        "bench_compare", os.path.join(ROOT, "scripts", "bench_compare.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write_trajectory(path, series, direction="up"):
    with open(path, "w") as f:
        for v in series:
            f.write(json.dumps({"metrics": {"m": v},
                                "directions": {"m": direction}}) + "\n")


def test_history_fails_on_monotone_degradation(tmp_path, bench_compare):
    traj = str(tmp_path / "t.jsonl")
    _write_trajectory(traj, [1.0, 1.03, 1.06, 1.11])
    assert bench_compare.history_check(traj, window=4, trend_threshold=0.05)


def test_history_passes_on_flat_and_noisy(tmp_path, bench_compare):
    traj = str(tmp_path / "t.jsonl")
    _write_trajectory(traj, [1.0, 1.2, 0.9, 1.1])     # noisy, not monotone
    assert not bench_compare.history_check(traj, window=4)
    _write_trajectory(traj, [1.0, 1.01, 1.02, 1.03])  # monotone, tiny drift
    assert not bench_compare.history_check(traj, window=4,
                                           trend_threshold=0.05)


def test_history_direction_down_metric(tmp_path, bench_compare):
    traj = str(tmp_path / "t.jsonl")
    _write_trajectory(traj, [0.9, 0.8, 0.7, 0.6], direction="down")
    assert bench_compare.history_check(traj, window=4, trend_threshold=0.05)
    _write_trajectory(traj, [0.6, 0.7, 0.8, 0.9], direction="down")
    assert not bench_compare.history_check(traj, window=4)


def test_history_needs_full_window(tmp_path, bench_compare):
    traj = str(tmp_path / "t.jsonl")
    _write_trajectory(traj, [1.0, 2.0])
    assert not bench_compare.history_check(traj, window=4)


def test_committed_trajectory_passes(bench_compare):
    traj = os.path.join(ROOT, "benchmarks", "baselines", "trajectory.jsonl")
    assert os.path.exists(traj)
    assert not bench_compare.history_check(traj)


def test_history_append_collects_guarded_metrics(tmp_path, bench_compare):
    art_dir = str(tmp_path)
    with open(os.path.join(art_dir, "BENCH_scalability.json"), "w") as f:
        json.dump({"burst_ab": {"k8": {"batched": {"cold_e2e_p95_s": 0.08}}},
                   "overlap_ab": {}, "policy_ab": {}}, f)
    traj = str(tmp_path / "traj.jsonl")
    rec = bench_compare.history_append(traj, art_dir)
    assert rec is not None
    key = "BENCH_scalability.json:burst_ab.k8.batched.cold_e2e_p95_s"
    assert rec["metrics"][key] == pytest.approx(0.08)
    assert rec["directions"][key] == "up"
    assert len(bench_compare.load_trajectory(traj)) == 1
