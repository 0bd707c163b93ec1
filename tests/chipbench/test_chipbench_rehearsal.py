"""Each traffic mix end to end on the CPU at SMOKES size for a window of
about two seconds: the result line has the contract's keys, and what the
window served is correct."""
import pytest

from chipbench import harness
from chipbench_cpu import last_line, run_main, steer

MAN = harness.manifest()
CELLS = {w["traffic"]: w["name"] for w in MAN["workloads"]}


@pytest.mark.parametrize("traffic", sorted(CELLS))
def test_each_mix_runs_end_to_end(traffic, monkeypatch, tmp_path):
    steer(monkeypatch, tmp_path)
    cell = CELLS[traffic]
    rc, lines = run_main(cell)
    assert rc == 0, lines[-5:]
    res = last_line(lines)
    assert list(res)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(res)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    want = {m["name"] for m in harness.metrics_of(MAN, cell, False)}
    assert set(res["metrics"]) == want
    for name, m in res["metrics"].items():
        assert m["value"] > 0, name
    window = [line for line in lines if line.startswith("window ")]
    assert '"compiles": 0' in window[0]
    assert not (tmp_path / "store").exists()


def test_traced_run_reports_layer_metrics_and_breakdown(monkeypatch,
                                                        tmp_path):
    steer(monkeypatch, tmp_path)
    cell = CELLS["cold-burst2"]
    rc, lines = run_main(cell, trace=1)
    assert rc == 0, lines[-5:]
    res = last_line(lines)
    assert res["correct"] is True
    assert res["device"]["busy_s"] > 0
    assert res["device"]["window_s"] >= res["device"]["busy_s"]
    want = {m["name"] for m in harness.metrics_of(MAN, cell, True)}
    # on the CPU the page gather runs in Pallas interpret mode, as plain
    # XLA ops with no kernel call to time, so its roofline has nothing to
    # read and is left out of the line
    assert set(res["metrics"]) == want - {"page_gather_roofline.cold"}
    for name, m in res["metrics"].items():
        assert m["value"] >= 0, name
        if m["unit"] == "%":
            assert 0 < m["value"] <= 100, name
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(res["breakdown"]["device_ops"]) <= 10


def test_unknown_device_fails_a_traced_run(monkeypatch, tmp_path):
    steer(monkeypatch, tmp_path)
    import jax

    from chipbench import peaks
    monkeypatch.delitem(peaks.PEAKS, jax.devices()[0].device_kind)
    rc, lines = run_main(CELLS["cold-burst2"], trace=1, seconds=0.5)
    assert rc != 0
    assert not lines or not lines[-1].startswith("{")
