"""The init laws the benchmark draws its weights by."""
import hashlib
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, weights
import chipbench_cpu

SEED = 2**31 + 5
#: sha256 of olmo-1b's SMOKES-size draw from SEED (each leaf's path,
#: dtype, shape and bytes, in table order), as the draw stood before the
#: Mamba2 laws were added: the laws that were there have not moved a bit
OLMO_SMOKE_SHA256 = (
    "87acf86694f167ca84e973740ee6de02fac76a6f546d63e0522bc24aa3ed1274")


def _digest(table, flat) -> str:
    h = hashlib.sha256()
    for path, *_ in table:
        a = np.asarray(flat[path])
        for part in (path, str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_olmo_smoke_draw_is_pinned():
    c = chipbench_cpu.smoke(harness.load_json(os.path.join(
        harness.ROOT, "chipbench/configs/olmo-1b.json")))
    table = harness.reference_module(c).param_table(c)
    assert _digest(table, weights.generate(table, SEED)) == OLMO_SMOKE_SHA256


def test_zeros_and_ones():
    flat = weights.generate([("z", (3, 2), "float32", "zeros"),
                             ("o", (5,), "bfloat16", "ones")], SEED)
    assert not np.asarray(flat["z"]).any()
    assert (np.asarray(flat["o"], np.float32) == 1).all()
    assert flat["o"].dtype == jnp.bfloat16


TABLE = [("a_log", (4096,), "float32", "mamba2_a_log"),
         ("dt_bias", (4096,), "float32", "mamba2_dt_bias")]


def _softplus(x):
    return np.logaddexp(0.0, np.asarray(x, np.float64))


@pytest.mark.parametrize("path, lo, hi, of", [
    ("a_log", 0.0, math.log(16.0), np.asarray),
    ("dt_bias", weights.DT_FLOOR, weights.DT_MAX, _softplus),
])
def test_mamba2_laws_draw_in_their_ranges(path, lo, hi, of):
    """log A in [0, log 16]; the step size softplus(dt_bias) in
    [1e-4, 0.1], up to float32's rounding of the stored value."""
    flat = weights.generate(TABLE, SEED)
    assert flat[path].dtype == jnp.float32
    x = of(flat[path]).astype(np.float64)
    eps = 1e-6
    assert x.min() >= lo * (1 - eps) and x.max() <= hi * (1 + eps)
    # spread over the range, not a constant
    assert x.max() - x.min() > 0.5 * (hi - lo)


def test_mamba2_step_sizes_are_log_uniform():
    """log dt is uniform on [log 1e-3, log 0.1]: its mean is the middle."""
    dt = _softplus(weights.generate(TABLE, SEED)["dt_bias"])
    mid = 0.5 * (math.log(weights.DT_MIN) + math.log(weights.DT_MAX))
    assert abs(np.log(dt).mean() - mid) < 0.1


@pytest.mark.parametrize("path", ["a_log", "dt_bias"])
def test_mamba2_laws_follow_the_seed(path):
    a = np.asarray(weights.generate(TABLE, SEED)[path])
    b = np.asarray(weights.generate(TABLE, SEED + 1)[path])
    c = np.asarray(weights.generate(TABLE, SEED)[path])
    assert not np.array_equal(a, b)
    assert np.array_equal(a, c)
