"""BENCHMARK.json against the benchmark's contract, the files each cell
needs, and run.py's refusal to report without a chip."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from chipbench import cuts, generator, harness

ROOT = harness.ROOT
MAN = harness.manifest()
NAME = cuts.NAME
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in MAN["workloads"]]


def _all_metrics():
    return MAN["end_to_end"] + MAN["per_layer"]


def test_top_level_keys_and_command():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["command"] == ["python3", "chipbench/run.py"]
    assert MAN["paths"] == ["chipbench", "tests/chipbench"]
    assert 1 <= MAN["run_seconds"] <= 51
    assert len(json.dumps(MAN)) <= 64 << 10


def test_names_and_units_use_allowed_characters():
    names = ([c["name"] for c in MAN["configs"]] + CELLS
             + [m["name"] for m in _all_metrics()]
             + [w["traffic"] for w in MAN["workloads"]]
             + [k for c in MAN["configs"] for k in c["reduced"]])
    for n in names:
        assert NAME.match(n), n
    for m in _all_metrics():
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in MAN["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MAN["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    for group in (MAN["configs"], CELLS, _all_metrics()):
        names = [g if isinstance(g, str) else g["name"] for g in group]
        assert len(names) == len(set(names))


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_has_its_files(cell):
    w, config, mix = harness.cell_files(MAN, cell)
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert config["name"] == w["config"]
    assert os.path.isfile(os.path.join(ROOT, config["reference"]))
    assert mix["kind"] in generator.KINDS
    ref = harness.reference_module(config)
    assert all(callable(getattr(ref, f)) for f in
               ("param_table", "forward", "forward_flops"))
    assert "logit_err" in config["limits"]
    for trace in (False, True):
        for m in harness.metrics_of(MAN, cell, trace):
            assert callable(harness.reader(m["name"])), m["name"]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_an_end_to_end_and_a_layer_metric(cell):
    e2e = {m["name"] for m in harness.metrics_of(MAN, cell, False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = harness.metrics_of(MAN, cell, True)
    assert layer
    for m in layer:
        assert m["moves"] in e2e, (cell, m["name"])


def test_configurations_are_whole_and_named_once():
    """Each configuration differs from its program's published config in
    the keys it declares cut, and only there, each cut within its floor
    (``cuts.py``)."""
    files = [c["file"] for c in MAN["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in MAN["workloads"]}
    for c in MAN["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("chipbench/")
        config = harness.load_json(os.path.join(ROOT, c["file"]))
        published = harness.published_config(config["program"])
        assert cuts.problems(published, config, c["reduced"]) == [], c["name"]


def _run_py(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", str(2**31 + 11), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    return not lines or not lines[-1].startswith("{")


def test_run_exits_nonzero_without_a_tpu():
    proc = _run_py(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "no TPU" in proc.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in MAN["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__", ".store",
                                                      ".trace"))
    proc = _run_py(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert _no_result(proc)
