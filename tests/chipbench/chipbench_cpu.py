"""Helpers that drive a benchmark run on the CPU at SMOKES size.

The run's own look for a chip is steered here, in the test, never through
an option of ``run.py``: ``require_accelerator`` is replaced by one that
returns the CPU devices, the configuration files are read with SMOKES
sizes, and the store and trace directories are the test's own.
"""
import argparse
import io
import json
import time
from contextlib import redirect_stdout

import jax

from chipbench import harness, peaks
from repro.configs import SMOKES

SEED = 2**31 + 3
#: a CPU row for traced runs only: the CPU has no published peak
CPU_PEAKS = {"bf16_flops_s": 1e12, "hbm_bytes_s": 1e11, "hbm_bytes": 0,
             "source": "test stand-in"}


def smoke(config: dict) -> dict:
    """``config`` with the SMOKES sizes of its program (its dtype kept)."""
    sm = SMOKES[config["program"]]
    config = dict(config)
    for k in harness.model_keys() - {"dtype"}:
        if k in config:
            config[k] = getattr(sm, k)
    config["head_dim"] = sm.head_dim
    return config


def steer(monkeypatch, tmp_path):
    """Point a run at the CPU, at SMOKES size, inside ``tmp_path``."""
    load = harness.load_json

    def load_smoke(path):
        d = load(path)
        return smoke(d) if "program" in d else d

    monkeypatch.setattr(harness, "load_json", load_smoke)
    monkeypatch.setattr(harness, "require_accelerator",
                        lambda chips: jax.devices())
    monkeypatch.setattr(harness, "STORE", str(tmp_path / "store"))
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind, CPU_PEAKS)
    import repro.launch.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "(off)")


def run_main(cell: str, *, trace: int = 0, seconds: float = 2.0,
             seed: int = SEED) -> tuple[int, list[str]]:
    """``harness.main`` as ``run.py`` calls it; (exit code, stdout lines)."""
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=trace)
    out = io.StringIO()
    with redirect_stdout(out):
        rc = harness.main(args, t_start=time.perf_counter())
    return rc, out.getvalue().strip().splitlines()


def last_line(lines: list[str]) -> dict:
    return json.loads(lines[-1])
