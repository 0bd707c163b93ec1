"""Seeded weights and the function image built from them.

The benchmark makes the weights, not the program: one jitted call on the
device draws every leaf of a configuration's parameter table from
``--seed``, in the dtype it is served in.  The same call, made again after
the window, gives the reference its weights, so the reference takes nothing
that the program made.

The image is the platform's deploy step: the weights are written into a
guest-memory file in the program's own arena layout (``params/...`` plus
the ``infra/...`` runtime pages), and ``Orchestrator.register`` then finds
it in the store.  The image carries no boot-only region (fp32 master
weights and optimizer moments): see ``PERF.md`` for why.
"""
from __future__ import annotations

import math

import numpy as np

#: standard deviation of every leaf drawn at random
SCALE = 0.02
#: Mamba2's initialization of its SSM (Dao & Gu, arXiv:2405.21060;
#: ``A_init_range``, ``dt_min``, ``dt_max``, ``dt_init_floor``, the same
#: as Zamba2's ``time_step_min``, ``time_step_max``, ``time_step_floor``):
#: A is drawn from U[1, 16], the step size log-uniformly from [1e-3, 0.1]
#: and raised to at least 1e-4
A_INIT_RANGE = (1.0, 16.0)
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 0.1, 1e-4


def seed_words(seed: int) -> tuple[np.uint32, np.uint32]:
    """A seed of any size up to 64 bits as two 32-bit words."""
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed {seed} is not a 64-bit unsigned number")
    return np.uint32(seed & 0xFFFFFFFF), np.uint32(seed >> 32)


def _leaf(key, shape, dtype, init):
    """One leaf of init law ``init``: ``normal`` (N(0, SCALE)),
    ``zeros``, ``ones``, ``mamba2_a_log`` (log A, A ~ U[1, 16]) or
    ``mamba2_dt_bias`` (softplus^-1 of the step size: Mamba2's ``dt_bias``).
    Each law draws in float32 from ``key``."""
    import jax
    import jax.numpy as jnp

    if init == "zeros":
        return jnp.zeros(shape, dtype)
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "normal":
        x = jax.random.normal(key, shape, jnp.float32)
        return (x * SCALE).astype(dtype)
    if init == "mamba2_a_log":
        a = jax.random.uniform(key, shape, jnp.float32, *A_INIT_RANGE)
        return jnp.log(a).astype(dtype)
    if init == "mamba2_dt_bias":
        u = jax.random.uniform(key, shape, jnp.float32, math.log(DT_MIN),
                               math.log(DT_MAX))
        dt = jnp.maximum(jnp.exp(u), DT_FLOOR)
        # softplus^-1(dt) = log(exp(dt) - 1), accurate for small dt
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    raise ValueError(f"unknown init law {init!r}")


def generator(table: list[tuple]):
    """A jitted ``(lo, hi) -> {path: array}`` over the leaves of ``table``
    (rows of ``(path, shape, dtype, init)``; ``init`` as in :func:`_leaf`)."""
    import jax
    import jax.numpy as jnp

    def make(lo, hi):
        key = jax.random.fold_in(jax.random.fold_in(jax.random.key(0), lo), hi)
        return {path: _leaf(jax.random.fold_in(key, i), shape,
                            jnp.dtype(dtype), init)
                for i, (path, shape, dtype, init) in enumerate(table)}

    return jax.jit(make)


def generate(table: list[tuple], seed: int) -> dict:
    """Device arrays of every leaf, from ``seed``."""
    lo, hi = seed_words(seed)
    return generator(table)(lo, hi)


def _np_dtype(name: str):
    import jax.numpy as jnp
    return jnp.dtype(name)


def write_image(cfg, base: str, host_params: dict, seed: int) -> dict:
    """Write ``cfg``'s function image at ``base`` from ``host_params``
    (``{path: numpy array}``); runtime pages are filler drawn from
    ``seed``.  Returns its size."""
    from repro.core.arena import ArenaLayout, GuestMemoryFile
    from repro.core.snapshot import instance_tensor_list

    tensors = instance_tensor_list(cfg, include_boot=False)
    rng = np.random.default_rng(list(seed_words(seed)) + [1])
    arrays = {}
    for path, shape, dtype, _region in tensors:
        if path.startswith("params/"):
            arrays[path] = host_params[path[len("params/"):]]
        elif dtype == "uint8":
            arrays[path] = rng.integers(0, 255, shape, dtype=np.uint8)
        else:
            arrays[path] = (rng.standard_normal(shape, dtype=np.float32)
                            * SCALE).astype(_np_dtype(dtype))
    layout = ArenaLayout.build(tensors)
    GuestMemoryFile.create(base, layout, arrays)
    return {"bytes": layout.total_bytes, "pages": layout.n_pages}
