"""Plain float32 forward of OLMo-1B, for deciding whether served logits are
right.

Written from the published description (Groeneveld et al., "OLMo:
Accelerating the Science of Language Models", arXiv:2402.00838, and the
allenai/OLMo-1B ``config.json``): a decoder-only transformer with
non-parametric LayerNorm (no scale, no bias, eps 1e-5) before attention and
before the MLP, full multi-head causal self-attention with rotary position
embeddings (rotate-half form, theta 10,000), a SwiGLU MLP, no biases, and
an output head tied to the token embedding.

It uses ``jax.numpy`` only and computes in float32; the caller traces it
under ``jax.default_matmul_precision("highest")``.  It imports nothing of
the system under test.  ``quant``, when given, rounds every matmul operand
first: that is the lower-precision control the comparison has to reject.

Departures from the published model, each one shared with the served path:
- weights are random, drawn by the benchmark from the seed, not trained;
- the parameter table names each leaf as the serving image lays it out
  (layers stacked along a leading axis, q/k/v/o as (d, heads, head_dim)).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def param_table(c: dict) -> list[tuple]:
    """(path, shape, dtype, init) of every weight, sorted by path."""
    d, h, layers = c["d_model"], c["n_heads"], c["n_layers"]
    hd = c.get("head_dim") or d // h
    kv = c["n_kv_heads"]
    f, v = c["d_ff"], c["vocab"]
    rows = [
        ("embed/table", (v, d), "bfloat16", "normal"),
        ("layers/attn/wk", (layers, d, kv, hd), "bfloat16", "normal"),
        ("layers/attn/wo", (layers, h, hd, d), "bfloat16", "normal"),
        ("layers/attn/wq", (layers, d, h, hd), "bfloat16", "normal"),
        ("layers/attn/wv", (layers, d, kv, hd), "bfloat16", "normal"),
        ("layers/mlp/wi_gate", (layers, d, f), "bfloat16", "normal"),
        ("layers/mlp/wi_up", (layers, d, f), "bfloat16", "normal"),
        ("layers/mlp/wo", (layers, f, d), "bfloat16", "normal"),
    ]
    return sorted(rows)


def forward_flops(c: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one forward over ``batch`` x ``seq`` tokens: the
    q/k/v/o projections, the attention scores and their mix over values (the
    causal mask computed in full, as the served forward does), the SwiGLU
    MLP, and the logits over the whole vocabulary."""
    d, h, kv = c["d_model"], c["n_heads"], c["n_kv_heads"]
    hd = c.get("head_dim") or d // h
    t = batch * seq
    proj = 2 * t * d * hd * (h + 2 * kv) + 2 * t * h * hd * d
    scores = 2 * 2 * batch * h * seq * seq * hd
    mlp = 2 * t * 3 * d * c["d_ff"]
    head = 2 * t * d * c["vocab"]
    return c["n_layers"] * (proj + scores + mlp) + head


def _ln(x, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def _rope(x, theta):
    """x: (B, S, H, D) -> rotated, rotate-half form."""
    s, dim = x.shape[1], x.shape[-1]
    half = dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, quant):
    """Causal softmax attention; q: (B, S, H, D), k/v: (B, S, KV, D)."""
    b, s, h, dim = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", quant(q), quant(k)) / jnp.sqrt(
        jnp.float32(dim))
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", quant(p), quant(v))


def forward(c: dict, params: dict, tokens, quant=None):
    """Logits (B, S, vocab) in float32 for ``tokens`` (B, S)."""
    qz = quant or (lambda a: a)
    theta = c.get("rope_theta", 10000.0)
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    x = p["embed/table"][tokens]
    stacked = {k[len("layers/"):]: v for k, v in p.items()
               if k.startswith("layers/")}

    def layer(x, lp):
        h = qz(_ln(x))
        q = _rope(jnp.einsum("bsd,dhk->bshk", h, qz(lp["attn/wq"])), theta)
        k = _rope(jnp.einsum("bsd,dhk->bshk", h, qz(lp["attn/wk"])), theta)
        v = jnp.einsum("bsd,dhk->bshk", h, qz(lp["attn/wv"]))
        a = attention(q, k, v, qz)
        x = x + jnp.einsum("bshk,hkd->bsd", qz(a), qz(lp["attn/wo"]))
        h = qz(_ln(x))
        g = jnp.einsum("bsd,df->bsf", h, qz(lp["mlp/wi_gate"]))
        u = jnp.einsum("bsd,df->bsf", h, qz(lp["mlp/wi_up"]))
        m = jax.nn.silu(g) * u
        return x + jnp.einsum("bsf,fd->bsd", qz(m), qz(lp["mlp/wo"])), None

    x, _ = jax.lax.scan(layer, x, stacked)
    return jnp.einsum("bsd,vd->bsv", qz(_ln(x)), qz(p["embed/table"]))
