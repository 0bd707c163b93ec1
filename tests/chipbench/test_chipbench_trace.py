"""The trace reduction on a small trace recorded by a CPU run, the peaks
table, and the FLOP and byte counts."""
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import flops, harness, peaks, trace_reduce
from chipbench.trace_reduce import Event, Trace


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    log_dir = str(tmp_path_factory.mktemp("trace"))

    @jax.jit
    def step(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((256, 256))
    step(x).block_until_ready()
    trace_reduce.start(log_dir)
    # long beside any stall of a loaded test host, so that this gap is
    # the longest of the trace
    with jax.profiler.TraceAnnotation("chipbench.test_idle"):
        time.sleep(0.3)
    for _ in range(3):
        step(x).block_until_ready()
    jax.profiler.stop_trace()
    return trace_reduce.load(trace_reduce.find_xplane(log_dir))


def test_cpu_trace_has_ops_modules_and_a_window(cpu_trace):
    assert cpu_trace.n_devices == 1
    assert cpu_trace.ops and cpu_trace.window_ns > 0
    runs = trace_reduce.module_runs(cpu_trace, "step")
    assert len(runs) == 3
    assert trace_reduce.ops_in(cpu_trace, "step")


def test_busy_and_idle_add_up(cpu_trace):
    busy = trace_reduce.busy_ns(cpu_trace)
    idle = trace_reduce.idle_share(cpu_trace)
    assert 0 < busy < cpu_trace.window_ns
    assert idle == pytest.approx(1 - busy / cpu_trace.window_ns)
    gaps = trace_reduce.idle_gaps(cpu_trace)
    assert sum(t - s for s, t in gaps) == pytest.approx(
        cpu_trace.window_ns - busy, rel=1e-6)


def test_breakdown_names_ops_and_what_the_host_did(cpu_trace):
    b = trace_reduce.breakdown(cpu_trace)
    assert 1 <= len(b["device_ops"]) <= 10
    assert 1 <= len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["idle_gaps"]]
    assert secs == sorted(secs, reverse=True)
    assert b["idle_gaps"][0][0] == "chipbench.test_idle"
    assert b["idle_gaps"][0][1] >= 0.29


def _synthetic():
    ops = [Event("custom-call.1", 0, 10, "jit_gather_pages", "d0"),
           Event("broadcast.2", 10, 5, "jit_gather_pages", "d0"),
           Event("fusion.3", 30, 20, "jit__jit_forward", "d0"),
           Event("fusion.4", 40, 20, "jit__jit_forward", "d0")]
    return Trace(100.0, 1, ops, [], [Event("host", 0, 100)])


def test_union_kernel_ops_and_gaps_on_known_events():
    t = _synthetic()
    assert trace_reduce.busy_ns(t) == 15 + 30
    assert trace_reduce.idle_gaps(t) == [(15, 30), (60, 100.0)]
    assert [e.name for e in trace_reduce.kernel_ops(t, "gather_pages")] == [
        "custom-call.1"]
    assert trace_reduce.top_ops(t, 1) == [["fusion", 40e-9]]


def test_short_names_and_ops_named_by_their_program_run():
    assert trace_reduce.short_name(
        "%gather_pages.14 = u32[8]{0} custom-call(s32[4] %a), x=1") == (
        "gather_pages")
    assert trace_reduce.short_name("dot_general.1") == "dot_general"
    ops = [Event("%a.1 = f32[] add()", 5, 1, "", "d0"),
           Event("%b.2 = f32[] mul()", 25, 1, "", "d0"),
           Event("%c.3 = f32[] sub()", 40, 1, "", "d0")]
    runs = [Event("jit_f(1)", 0, 10, "jit_f(1)", "d0"),
            Event("jit_g(2)", 20, 10, "jit_g(2)", "d0")]
    trace_reduce._attribute(ops, runs, "d0")
    assert [e.module for e in ops] == ["jit_f(1)", "jit_g(2)", ""]


def test_peaks_are_keyed_by_device_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_s"] == 197e12 and p["hbm_bytes_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")


def _config(name):
    conf = {c["name"]: c for c in harness.manifest()["configs"]}[name]
    return harness.load_json(f"{harness.ROOT}/{conf['file']}")


def test_forward_flops_follow_the_weights():
    """At 64 tokens the matmuls dominate: FLOPs are twice the matmul
    weights per token (the tied head's table counted once more), plus
    attention scores (a few percent)."""
    for name in [c["name"] for c in harness.manifest()["configs"]]:
        c = _config(name)
        ref = harness.reference_module(c)
        n = sum(int(jnp.prod(jnp.array(s))) for p, s, d, _ in
                ref.param_table(c) if d == "bfloat16" and p != "embed/table")
        if c.get("tied_embeddings"):
            n += c["vocab"] * c["d_model"]
        per_token = 2 * n
        got = ref.forward_flops(c, 2, 32)
        assert per_token * 64 <= got <= 1.1 * per_token * 64, name


def test_gather_bytes_and_roofline():
    assert flops.page_gather_bytes(10) == 2 * 10 * 4096
    peak = peaks.peaks("TPU v5 lite")
    assert flops.least_seconds(197e12, 0, peak) == pytest.approx(1.0)
    assert flops.least_seconds(0, 819e9, peak) == pytest.approx(1.0)
