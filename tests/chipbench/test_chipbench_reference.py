"""The plain references against the program's forward, at SMOKES size on
the CPU, and the lower-precision control that the comparison rejects."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, cuts, generator, harness, weights
import chipbench_cpu
from repro.configs import ARCHS
from repro.core.executor import _jit_forward
from repro.models import get_family
from repro.nn import spec as nnspec

CONFIGS = [c["name"] for c in harness.manifest()["configs"]]
SEED = 2**31 + 5


def _config(name, smoke=True):
    conf = {c["name"]: c for c in harness.manifest()["configs"]}[name]
    c = harness.load_json(os.path.join(harness.ROOT, conf["file"]))
    return chipbench_cpu.smoke(c) if smoke else c


def _served(c, flat, tokens, dtype=None):
    """The program's compiled forward over the benchmark's weights."""
    cfg = harness.program_config(c)
    specs = get_family(cfg).param_specs(cfg)
    tree = nnspec.map_leaves(
        lambda p, s: flat[p] if dtype is None else flat[p].astype(dtype),
        specs)
    return [np.asarray(_jit_forward(cfg, tree, {"tokens": t}))
            for t in tokens]


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("smoke", [True, False])
def test_reference_names_every_program_leaf(name, smoke):
    c = _config(name, smoke)
    harness.check_table(harness.program_config(c),
                        harness.reference_module(c).param_table(c))


def test_published_sizes_are_the_programs():
    """With its declared cuts set back to their published values, each
    configuration is its program's registered config."""
    for name in CONFIGS:
        c = _config(name, smoke=False)
        assert (harness.program_config(cuts.uncut(c))
                == ARCHS[c["program"]]), name


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_matches_program_in_float32(name):
    """With every weight in float32, the program computes in float32 too,
    and must agree with the reference up to summation order: 2e-4 is some
    hundred float32 roundings of the logits' norm."""
    c = _config(name)
    ref = harness.reference_module(c)
    flat = weights.generate(ref.param_table(c), SEED)
    tokens = list(generator.requests(weights.seed_words(SEED), c["vocab"],
                                     3, 2, 32))
    with jax.default_matmul_precision("highest"):
        served = _served(c, flat, tokens, jnp.float32)
    err = compare.logit_error(ref, c, flat, served, tokens, block=2)
    assert err < 2e-4


@pytest.mark.parametrize("name", CONFIGS)
def test_limit_passes_bf16_serving_and_the_fp8_control_reads_far_higher(
        name):
    """The served bf16 logits pass the run's own check at the
    configuration's limit, and the same reference computed with float8
    operands fails it, reading at least five times higher.  (The control
    grows with depth and width: ``chipbench/calibrate.py`` and ``PERF.md``
    give its readings at the cell's size on the chip.)"""
    c = _config(name)
    ref = harness.reference_module(c)
    flat = weights.generate(ref.param_table(c), SEED)
    tokens = list(generator.requests(weights.seed_words(SEED), c["vocab"],
                                     4, 2, 32))
    served = _served(c, flat, tokens)
    prog = compare.logit_error(ref, c, flat, served, tokens, block=2)
    control = compare.reference_logits(ref, c, flat, tokens, block=2,
                                       quant=compare.fp8)
    ctrl = compare.logit_error(ref, c, flat, control, tokens, block=2)
    assert harness.correct({"logit_err": harness.logit_check(
        prog, c, len(tokens))})
    assert not harness.correct({"logit_err": harness.logit_check(
        ctrl, c, len(tokens))})
    assert ctrl > 5 * prog


def test_weights_depend_on_the_whole_seed():
    table = [("w", (4, 4), "bfloat16", "normal"), ("z", (2,), "float32",
                                                  "zeros")]
    a = weights.generate(table, 2**32 + 1)
    b = weights.generate(table, 1)
    c = weights.generate(table, 2**32 + 1)
    assert not np.array_equal(np.asarray(a["w"]), np.asarray(b["w"]))
    assert np.array_equal(np.asarray(a["w"]), np.asarray(c["w"]))
    assert a["w"].dtype == jnp.bfloat16 and not np.asarray(a["z"]).any()


def test_row_error_sees_one_altered_position():
    rng = np.random.default_rng(0)
    ref = rng.standard_normal((2, 4, 64)).astype(np.float32)
    served = ref.copy()
    assert compare.row_error(served, ref) == 0.0
    served[1, 3] += 0.5
    assert compare.row_error(served, ref) > 0.05
