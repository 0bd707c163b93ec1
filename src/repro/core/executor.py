"""Demand-paged invocation executor.

Runs one function invocation against an :class:`InstanceArena`, faulting
guest pages in execution order -- the framework-level userfaultfd analogue
(DESIGN.md §3).  The fault schedule is *model-aware*:

  * infra pages first (runtime/tokenizer/channel state -- every invocation),
  * embedding rows for exactly the request's tokens (the whole table when
    the LM head is tied to it),
  * trunk weights layer by layer (row-sliced from the scanned stacks),
  * for MoE layers: attention + router + shared experts first, then -- after
    computing the true routing on the actual activations -- only the pages
    of the *routed* experts (the input-dependent "unique pages" of Fig. 5),
  * modality frontend banks only when the invocation carries that modality.

Compute runs eagerly (jnp on host) using the same family apply functions as
the jitted path, so the result is numerically identical to a warm
invocation; unrouted expert slots stay zero-filled and are provably unused.
"""
from __future__ import annotations

import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..models import get_family, moe as moe_mod
from ..nn import layers as nn
from ..nn import spec as nnspec
from ..telemetry import TELEMETRY
from .arena import InstanceArena


def _np(arena: InstanceArena, path: str, fault: bool = True, parallel: int = 0):
    return arena.tensor(path, fault=fault, parallel=parallel)


# Jitted compute pieces.  ``cfg`` is a frozen dataclass => hashable => static;
# executables are compiled once per (cfg, shapes) at function deploy time and
# *restored* (cache lookup) at cold start, like Firecracker's device-state
# restore.  Invocation-time compute therefore reflects steady-state serving.
import functools


@functools.partial(jax.jit, static_argnums=0)
def _jit_forward(cfg, params, batch):
    return get_family(cfg).forward(cfg, params, batch)


@functools.partial(jax.jit, static_argnums=0)
def _jit_dense_layer(cfg, lp, x):
    return moe_mod._dense_fwd(cfg, lp, x)[0]


@functools.partial(jax.jit, static_argnums=0)
def _jit_moe_attn(cfg, mp, x):
    """Attention sub-block + router logits of an MoE layer."""
    h = nn.apply_rmsnorm(mp["ln1"], x)
    h_attn, _ = nn.apply_attention(mp["attn"], h, rope_theta=cfg.rope_theta,
                                   chunk=cfg.attn_chunk)
    x = x + h_attn
    h2 = nn.apply_rmsnorm(mp["ln2"], x)
    experts = moe_mod.routed_experts(mp["moe"], h2, cfg)
    return x, h2, experts


@functools.partial(jax.jit, static_argnums=0)
def _jit_moe_apply(cfg, moe_p, x, h2):
    return x + moe_mod.apply_moe_mlp(moe_p, h2, cfg)


@functools.partial(jax.jit, static_argnums=0)
def _jit_embed(cfg, table, tokens):
    return nn.apply_embedding({"table": table}, tokens)


@functools.partial(jax.jit, static_argnums=0)
def _jit_head(cfg, ln_f, lm_head, x):
    x = nn.apply_rmsnorm(ln_f, x)
    return nn.apply_lm_head(lm_head, x)


def warm_executables(cfg: ModelConfig, example_batch: dict,
                     device=None) -> None:
    """Compile (once, at function deploy) every executable an invocation
    needs, by running them on zero-filled params of the right shapes placed
    on ``device`` (None => the default device)."""
    specs = get_family(cfg).param_specs(cfg)
    zeros = nnspec.map_leaves(
        lambda _, s: jnp.zeros(s.shape, s.dtype, device=device), specs)
    if cfg.family != "moe":
        _jit_forward(cfg, zeros, example_batch)[0].block_until_ready()
        return
    tokens = jnp.asarray(example_batch["tokens"])
    x = _jit_embed(cfg, zeros["embed"]["table"], tokens)
    if cfg.first_dense:
        lp = jax.tree.map(lambda a: a[0], zeros["first_dense"])
        x = _jit_dense_layer(cfg, lp, x)
    gp = jax.tree.map(lambda a: a[0], zeros["groups"])
    if "dense_layers" in gp:
        lp = jax.tree.map(lambda a: a[0], gp["dense_layers"])
        x = _jit_dense_layer(cfg, lp, x)
    x2, h2, _ = _jit_moe_attn(cfg, gp["moe_layer"], x)
    x3 = _jit_moe_apply(cfg, gp["moe_layer"]["moe"], x2, h2)
    _jit_head(cfg, zeros["ln_f"], zeros["lm_head"], x3).block_until_ready()


class LazyParams:
    """Materializes the (stacked) param tree from the arena, page-faulting
    tensors on first access.  ``touch_order`` controls fault scheduling;
    ``device`` is where :meth:`tree` places them (None => default device)."""

    def __init__(self, cfg: ModelConfig, arena: InstanceArena, *,
                 parallel: int = 0, device=None):
        self.cfg = cfg
        self.arena = arena
        self.parallel = parallel
        self.device = device
        self.specs = get_family(cfg).param_specs(cfg)
        self.paths = [p for p, _ in nnspec.tree_paths(self.specs)]

    def fault_all(self, skip_prefixes: tuple[str, ...] = (),
                  embed_rows: np.ndarray | None = None) -> None:
        with TELEMETRY.span("executor.touch") as span:
            n = 0
            for p in self.paths:
                if any(p.startswith(s) for s in skip_prefixes):
                    continue
                e = self.arena.layout.entries[f"params/{p}"]
                if embed_rows is not None and p == "embed/table":
                    pages = sorted(e.row_pages(embed_rows.tolist()))
                else:
                    pages = e.pages()
                self.arena.touch_pages(pages, parallel=self.parallel)
                n += len(pages)
            span.set_metadata(pages=n)

    def tree(self) -> Any:
        """Full param tree as jnp arrays (zero-filled where never faulted),
        ready on the device: every caller runs the forward on it next."""
        with TELEMETRY.span("executor.placement") as span:
            tree = place(self.arena, self.specs, self.device)
            jax.block_until_ready(tree)
            span.set_metadata(bytes=placed_bytes(tree))
        return tree


def place(arena: InstanceArena, specs, device) -> Any:
    """Put every parameter of ``specs`` on ``device`` from the arena's
    pages as they stand (no fault)."""
    return nnspec.map_leaves(
        lambda p, s: jax.device_put(_np(arena, f"params/{p}", fault=False),
                                    device),
        specs)


def placed_bytes(tree) -> int:
    """The sum of the placed leaves' ``nbytes``."""
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


def _touch_infra(arena: InstanceArena) -> None:
    arena.touch_pages(sorted(arena.layout.region_pages("infra")))


def _expert_paths(prefix: str) -> tuple[str, ...]:
    return tuple(f"{prefix}/{n}" for n in ("wi_gate", "wi_up", "wo"))


def run_invocation(cfg: ModelConfig, arena: InstanceArena, batch: dict, *,
                   parallel: int = 0, device=None) -> tuple[jax.Array, float]:
    """Execute one inference invocation against the demand-paged arena.

    Returns (logits, seconds).  Every page the computation needs is faulted
    through the arena (so ``arena.stats`` is the paper's fault trace);
    parameters are placed on ``device`` (None => the default device).
    """
    t0 = time.perf_counter()
    _touch_infra(arena)
    lp = LazyParams(cfg, arena, parallel=parallel, device=device)
    tokens = np.asarray(batch["tokens"])
    # a tied LM head reads every row of the table, not only the request's
    embed_rows = None if cfg.tied_embeddings else np.unique(tokens)

    if "patch_embeds" in batch and "vision/vit_stub" in arena.layout.entries:
        arena.touch_pages(arena.layout.pages_of("vision/vit_stub"),
                          parallel=parallel)
    if "frames" in batch and "audio/frontend_stub" in arena.layout.entries:
        arena.touch_pages(arena.layout.pages_of("audio/frontend_stub"),
                          parallel=parallel)

    if cfg.family != "moe":
        lp.fault_all(embed_rows=embed_rows)
        params = lp.tree()
        with TELEMETRY.span("executor.forward"):
            logits = _jit_forward(cfg, params, batch)
            logits.block_until_ready()
        return logits, time.perf_counter() - t0

    # ---- MoE: interleave routing with expert faulting ---------------------
    lp.fault_all(skip_prefixes=("groups/moe_layer/moe/wi",
                                "groups/moe_layer/moe/wo"),
                 embed_rows=embed_rows)
    params = lp.tree()
    x = _jit_embed(cfg, params["embed"]["table"], jnp.asarray(tokens))

    if cfg.first_dense:
        for i in range(cfg.first_dense):
            lpar = jax.tree.map(lambda a, i=i: a[i], params["first_dense"])
            x = _jit_dense_layer(cfg, lpar, x)

    for g in range(moe_mod.n_groups(cfg)):
        gp = jax.tree.map(lambda a, g=g: a[g], params["groups"])
        if "dense_layers" in gp:
            for j in range(cfg.moe_every - 1):
                lpar = jax.tree.map(lambda a, j=j: a[j], gp["dense_layers"])
                x = _jit_dense_layer(cfg, lpar, x)
        # route on the true activations, then fault only the routed experts
        mp = gp["moe_layer"]
        x, h2, routed = _jit_moe_attn(cfg, mp, x)
        experts = np.unique(np.asarray(routed))
        for path in _expert_paths("params/groups/moe_layer/moe"):
            e = arena.layout.entries[path]
            # stacked layout (n_groups, E, ...): rows within group g
            per_group = e.nbytes // e.shape[0]
            per_expert = per_group // e.shape[1]
            pages: set[int] = set()
            for ex in experts:
                lo = e.offset + g * per_group + int(ex) * per_expert
                hi = lo + per_expert
                pages.update(range(lo // 4096, (hi - 1) // 4096 + 1))
            arena.touch_pages(sorted(pages), parallel=parallel)
        # re-read the (now faulted) expert bank for this group
        moe_p = dict(mp["moe"])
        for name in ("wi_gate", "wi_up", "wo"):
            full = _np(arena, f"params/groups/moe_layer/moe/{name}", fault=False)
            moe_p[name] = jax.device_put(full[g], device)
        x = _jit_moe_apply(cfg, moe_p, x, h2)

    logits = _jit_head(cfg, params["ln_f"], params["lm_head"], x)
    return logits, time.perf_counter() - t0
