"""The comparison that decides ``correct``.

Served logits are compared, position by position, with the plain float32
reference run over the same seeded weights and the same tokens.  The
number compared is the widest relative error of any served position:

    max over (request, sequence, position) of ||served - ref|| / ||ref||

taken over the whole vocabulary's logits.  A position whose logits came
from other weights, other tokens or another request reads near 1 or more;
bf16 serving reads about a hundredth (``PERF.md`` gives the readings each
limit was set from, and the control's).

``fp8`` is the control: the reference itself with every matmul operand
rounded to float8 e4m3, the precision below the bf16 the configurations
state.  The limit has to reject it.
"""
from __future__ import annotations

import numpy as np


def fp8(a):
    import jax.numpy as jnp
    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def row_error(served: np.ndarray, ref: np.ndarray) -> float:
    """Widest relative error of any position (last axis: the vocabulary)."""
    s = np.asarray(served, np.float32).reshape(-1, served.shape[-1])
    r = np.asarray(ref, np.float32).reshape(-1, ref.shape[-1])
    num = np.linalg.norm(s - r, axis=-1)
    den = np.maximum(np.linalg.norm(r, axis=-1), 1e-30)
    return float(np.max(num / den))


def reference_logits(ref_mod, c: dict, params: dict, tokens: list[np.ndarray],
                     *, block: int, quant=None) -> list[np.ndarray]:
    """The reference's logits for each request in ``tokens`` (each (B, S)),
    run ``block`` requests at a time (the last block padded by repeating
    its first request, so one compiled program serves every block)."""
    import jax
    import jax.numpy as jnp

    fwd = jax.jit(lambda p, t: ref_mod.forward(c, p, t, quant))
    out: list[np.ndarray] = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(tokens), block):
            chunk = list(tokens[i:i + block])
            n = len(chunk)
            chunk += [chunk[0]] * (block - n)
            t = jnp.asarray(np.concatenate(chunk, axis=0))
            logits = np.asarray(fwd(params, t))
            per = logits.shape[0] // block
            out += [logits[j * per:(j + 1) * per] for j in range(n)]
    return out


def logit_error(ref_mod, c: dict, params: dict, served: list[np.ndarray],
                tokens: list[np.ndarray], *, block: int,
                quant=None) -> float | None:
    """Widest relative error of ``served`` against the reference (or of
    the control, with ``quant``); None when nothing was served."""
    if not served:
        return None
    refs = reference_logits(ref_mod, c, params, tokens, block=block,
                            quant=quant)
    return max(row_error(s, r) for s, r in zip(served, refs))
