"""A run with the timed path broken underneath has to come out not
correct: once for each fault a serving cell can have.  (A training step
that returns its state unchanged and a lost exchange between chips do not
arise in these one-chip serving cells.)"""
import jax
import numpy as np
import pytest

import repro.core.executor as executor
import repro.core.restore as restore
from chipbench import compare, harness
from chipbench_cpu import last_line, run_main, steer

CELLS = {w["traffic"]: w["name"] for w in harness.manifest()["workloads"]}


def _broken_forward(monkeypatch, alter):
    real = executor._jit_forward

    def forward(cfg, params, batch):
        return alter(real(cfg, params, batch))

    monkeypatch.setattr(executor, "_jit_forward", forward)


def _altered_answer(logits):
    """One position's logits altered where they are produced."""
    return logits.at[0, 3].add(0.5)


def _half_batch(logits):
    """The second sequence left out: the first one's answer stands in."""
    return logits.at[1].set(logits[0])


def _check(cell, name):
    rc, lines = run_main(cell)
    assert rc == 0, lines[-5:]
    res = last_line(lines)
    assert res["correct"] is False
    c = res["checks"][name]
    assert c["value"] > c["limit"], res["checks"]
    return res


@pytest.mark.parametrize("traffic", ["cold-burst2"])
@pytest.mark.parametrize("alter", [_altered_answer, _half_batch])
def test_wrong_logits_fail(traffic, alter, monkeypatch, tmp_path):
    steer(monkeypatch, tmp_path)
    _broken_forward(monkeypatch, alter)
    _check(CELLS[traffic], "logit_err")


def test_fp8_control_in_the_programs_place_fails(monkeypatch, tmp_path):
    """The control, the plain reference with every matmul operand rounded
    to float8 (the precision below the configuration's bf16), serves the
    window in place of the program's forward: the run's own comparison,
    at the configuration's limit, finds it not correct."""
    steer(monkeypatch, tmp_path)
    cell = CELLS["cold-burst2"]
    w, config, _ = harness.cell_files(harness.manifest(), cell)
    ref = harness.reference_module(config)

    def forward(cfg, params, batch):
        flat = {"/".join(str(k.key) for k in path): leaf for path, leaf
                in jax.tree_util.tree_flatten_with_path(params)[0]}
        with jax.default_matmul_precision("highest"):
            return ref.forward(config, flat, batch["tokens"], compare.fp8)

    monkeypatch.setattr(executor, "_jit_forward", forward)
    _check(cell, "logit_err")


def test_corrupt_restored_pages_fail(monkeypatch, tmp_path):
    """The group restore installs wrong bytes for part of the WS."""
    steer(monkeypatch, tmp_path)
    real = restore.fuse_ws_block

    def fuse(pages, data, *, engine="auto"):
        idx, block = real(pages, data, engine=engine)
        block = block.copy()
        block[-len(block) // 4:] = 0
        return idx, block

    monkeypatch.setattr(restore, "fuse_ws_block", fuse)
    _check(CELLS["cold-burst2"], "logit_err")


def test_short_prefetch_fails(monkeypatch, tmp_path):
    """The group restore leaves the WS's last pages out of the prefetch:
    the invocation still faults them in and answers right, but REAP did
    not prefetch the recorded WS."""
    steer(monkeypatch, tmp_path)
    real = restore.RestorePipeline.install_block

    def install_block(self, sorted_pages, block, hit, **kw):
        tail = kw.get("tail")
        if tail is not None and len(tail[0]) > 1:
            kw["tail"] = (tail[0][:-1],
                          None if tail[1] is None else tail[1][:-1])
        else:
            sorted_pages, block = sorted_pages[:-1], block[:-1]
        return real(self, np.asarray(sorted_pages), block, hit, **kw)

    monkeypatch.setattr(restore.RestorePipeline, "install_block",
                        install_block)
    res = _check(CELLS["cold-burst2"], "restore_mismatch")
    assert res["checks"]["logit_err"]["value"] <= res["checks"][
        "logit_err"]["limit"]


# the forward call that fails is the first of the window: set-up makes one
# at register, one record-mode, then two (a warm-up burst)
@pytest.mark.parametrize("traffic,nth", [("cold-burst2", 5)])
def test_an_answer_that_never_comes_fails(traffic, nth, monkeypatch,
                                          tmp_path):
    steer(monkeypatch, tmp_path)
    real = executor._jit_forward
    calls = {"n": 0}

    def forward(cfg, params, batch):
        calls["n"] += 1
        if calls["n"] == nth:
            raise RuntimeError("planted failure")
        return real(cfg, params, batch)

    monkeypatch.setattr(executor, "_jit_forward", forward)
    res = _check(CELLS[traffic], "unanswered")
    assert res["failed"] >= 1
