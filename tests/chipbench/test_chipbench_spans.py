"""The program's cold-start spans on the profiler's timeline: a traced CPU
rehearsal of the cold cell holds each of them where it belongs, and the
readers of the span metrics give exact values on known spans."""
import pytest
from jax.profiler import ProfileData

from chipbench import generator, harness, trace_reduce
from chipbench.trace_reduce import Event, Trace
from chipbench_cpu import last_line, run_main, steer

CELL = "olmo-1b.cold-burst2"
#: spans a group restore of two and its two invocations put on the timeline
SPANS = ("router.dispatch", "restore.group", "restore.load_vmm",
         "restore.connect", "restore.ws_fetch", "restore.fuse",
         "restore.install", "executor.touch", "executor.placement",
         "executor.forward", "instance.make_warm", "instance.finish_cold")
#: the spans that must sit inside a dispatch
STAGES = SPANS[1:]
FUSE_STEPS = ("restore.fuse.h2d", "restore.fuse.gather", "restore.fuse.d2h")


def _host_spans(log_dir) -> dict:
    """{name: [(thread, start_ns, end_ns, stats)]} of the program's spans
    (and the harness's) in the newest trace under ``log_dir``."""
    prof = ProfileData.from_file(trace_reduce.find_xplane(log_dir))
    out = {}
    for plane in prof.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in SPANS + FUSE_STEPS or ev.name.startswith(
                        "chipbench."):
                    out.setdefault(ev.name, []).append(
                        ((plane.name, line.name), ev.start_ns,
                         ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return out


def _inside(inner, outer) -> bool:
    return (inner[0] == outer[0] and outer[1] <= inner[1]
            and inner[2] <= outer[2])


@pytest.mark.parametrize("engine", ["numpy", "pallas"])
def test_traced_rehearsal_puts_each_stage_span_in_place(engine, monkeypatch,
                                                        tmp_path):
    steer(monkeypatch, tmp_path)
    from repro.core import restore
    # the pallas engine runs the page gather in interpret mode on the CPU
    monkeypatch.setattr(restore, "default_fuse_engine", lambda: engine)
    rc, lines = run_main(CELL, trace=1, seconds=1.0)
    assert rc == 0, lines[-5:]
    res = last_line(lines)
    assert res["correct"] is True, res["checks"]
    n_cold = res["attempted"]
    spans = _host_spans(str(tmp_path / "trace"))

    assert set(SPANS) <= set(spans)
    groups = spans["restore.group"]
    assert n_cold == 2 * len(groups) > 0
    assert [g[3]["n"] for g in groups] == [2] * len(groups)
    assert len(spans["restore.ws_fetch"]) == len(groups)
    assert len(spans["restore.fuse"]) == len(groups)
    assert len(spans["executor.placement"]) == 2 * n_cold
    assert len(spans["executor.forward"]) == n_cold
    assert all(s[3]["engine"] == engine for s in spans["restore.fuse"])
    assert all(s[3]["bytes"] > 0 for s in spans["executor.placement"])
    for g in groups:
        fetch = [s for s in spans["restore.ws_fetch"] if _inside(s, g)]
        installs = [s for s in spans["restore.install"] if _inside(s, g)]
        assert len(fetch) == 1 and len(installs) == 2
        assert fetch[0][2] <= min(s[1] for s in installs)
        assert sorted(s[3]["member"] for s in installs) == [0, 1]

    dispatches = spans["router.dispatch"]
    assert len(dispatches) == n_cold
    assert len({d[3]["inv"] for d in dispatches}) == n_cold
    for name in STAGES:
        for s in spans[name]:
            assert any(_inside(s, d) for d in dispatches), (name, s)

    if engine == "numpy":
        assert not set(FUSE_STEPS) & set(spans)
        return
    for fuse in spans["restore.fuse"]:
        steps = [next(s for s in spans[name] if _inside(s, fuse))
                 for name in FUSE_STEPS]
        assert [s[1] for s in steps] == sorted(s[1] for s in steps)
        assert all(s[3]["bytes"] == fuse[3]["bytes"] for s in steps)


# -- the readers, on spans whose sums are known ---------------------------

def _ev(name, start, end):
    return Event(name, start, end - start)


#: two bursts; the first holds nested and overlapping spans of two
#: threads, the second a stage span that runs past its end
HOST = [
    _ev("chipbench.burst", 0, 1000),
    _ev("router.dispatch", 10, 900),
    _ev("restore.group", 20, 500),
    _ev("restore.load_vmm", 20, 60),
    _ev("restore.ws_fetch", 100, 300),
    _ev("restore.fuse", 300, 400),
    _ev("restore.fuse.h2d", 300, 350),
    _ev("restore.install", 400, 450),
    _ev("restore.install", 450, 500),
    _ev("executor.touch", 500, 550),
    _ev("executor.placement", 550, 700),
    _ev("executor.forward", 700, 750),
    _ev("executor.forward", 720, 780),
    _ev("instance.make_warm", 760, 900),
    _ev("executor.touch", 760, 800),
    _ev("executor.placement", 800, 900),
    _ev("chipbench.burst", 2000, 2600),
    _ev("restore.fuse", 2100, 2300),
    _ev("executor.placement", 2250, 2400),
    _ev("instance.finish_cold", 2500, 2700),
]
#: what each reader gives on HOST with two cold invocations served:
#: placement 150 + 100 + 150 ns over 2; fuse (100 + 200) / 2 ns; not
#: covered: 1000 - (40 + 800) ns and 600 - (300 + 100) ns, over 2 bursts
WANT = {"placement_s.cold": 200e-9, "fuse_s.cold": 150e-9,
        "untraced_s.cold": 180e-9}


def _run(host, cold=(True, True, False)):
    rows = [generator.Row(i, i, 0.0, t_done=1.0, cold=c)
            for i, c in enumerate(cold)]
    trace = Trace(3000.0, 1, [Event("fusion.1", 0, 1, "m", "d0")], [],
                  sorted(host, key=lambda e: e.start_ns))
    return harness.Run(rows=rows, setup_s=1.0, seconds=1.0, window_s=1.0,
                       config={}, mix={}, ws_pages=1, forward_flops=1.0,
                       trace=trace)


@pytest.mark.parametrize("metric", sorted(WANT))
def test_span_reader_on_known_spans(metric):
    assert harness.reader(metric)(_run(HOST)) == pytest.approx(
        WANT[metric], rel=1e-12)


@pytest.mark.parametrize("metric", sorted(WANT))
def test_span_reader_needs_a_cold_invocation(metric):
    read = harness.reader(metric)
    assert read(_run(HOST, cold=(False, False))) is None
    untraced = _run(HOST)
    untraced.trace = None
    assert read(untraced) is None


@pytest.mark.parametrize("metric", sorted(WANT))
def test_span_reader_reads_nothing_from_a_program_without_spans(metric):
    """A program that puts no stage span on the timeline, as before the
    spans existed: only the harness's own spans are there."""
    host = [e for e in HOST if e.name.startswith("chipbench.")]
    assert harness.reader(metric)(_run(host)) is None
