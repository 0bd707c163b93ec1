"""The one general traffic generator.

A traffic mix is a JSON file under ``chipbench/traffic/``: parameters only.
Its ``kind`` picks the loop that drives ``Router.submit`` on the cell's
``Orchestrator``:

``cold_burst`` (closed loop)
    Scale the function to zero (``Orchestrator.scale_to_zero``, the
    platform's idle reclaim), submit ``burst`` invocations together so the
    router restores them as one group, wait for all of them, and repeat
    while the window is open.  The burst in flight when the window closes
    is waited for and counted.  Latency: from the burst's submit to the
    logits on the host.

Every invocation carries one request of ``batch`` sequences of ``seq``
tokens, drawn from the seed (a pool of ``pool`` requests, used in turn).
Set-up: one invocation in record mode, which writes the function's
working-set (WS) record (the platform's deploy step), then the kind's own
warm-up.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class Row:
    """One invocation of the window."""
    idx: int
    request: int              # index into the request pool
    t_due: float              # perf_counter seconds: the burst's submit
    t_done: float = float("nan")
    error: str = ""
    cold: bool = False
    report: dict = dataclasses.field(default_factory=dict)
    logits: np.ndarray | None = None   # the answer, for the comparison

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_due

    def as_dict(self) -> dict:
        out = {"idx": self.idx, "request": self.request,
               "latency_s": self.latency_s, "cold": self.cold}
        if self.error:
            out["error"] = self.error
        out.update(self.report)
        return out


def report_fields(rep) -> dict:
    st = rep.stages
    return {"queue_s": rep.queue_s, "load_vmm_s": st.load_vmm_s,
            "ws_fetch_s": st.ws_fetch_s, "install_s": st.install_s,
            "tail_wait_s": st.tail_wait_s,
            "processing_s": rep.processing_s, "n_faults": rep.n_faults,
            "n_prefetched_pages": rep.n_prefetched_pages,
            "ws_cache_hit": rep.ws_cache_hit, "batch_size": rep.batch_size}


@dataclasses.dataclass
class Context:
    """What a traffic loop drives, and how it reports."""
    orch: object
    name: str
    requests: np.ndarray                    # (pool, batch, seq) int32
    log: object = lambda label, data: None
    span: object = lambda name: contextlib.nullcontext()

    def batch(self, i: int) -> dict:
        return {"tokens": self.requests[i % len(self.requests)]}


def requests(seed_words, vocab: int, pool: int, batch: int,
             seq: int) -> np.ndarray:
    rng = np.random.default_rng(list(seed_words) + [2])
    return rng.integers(0, vocab, (pool, batch, seq), dtype=np.int32)


def _finish(row: Row, inv, timeout: float) -> None:
    try:
        logits, rep = inv.result(timeout)
        row.logits = np.asarray(logits)
        row.t_done = time.perf_counter()
        row.report = report_fields(rep)
        row.cold = rep.stages.load_vmm_s > 0
    except Exception as e:   # the row records the failure; the run goes on
        row.error = f"{type(e).__name__}: {e}"


def record_invocation(ctx: Context) -> dict:
    """The deploy step: one record-mode invocation, which writes the WS
    record, then one read of that record into the platform's WS cache.
    Every cold start the window times finds the WS there; reading it here
    stands in for a first cold start that misses the cache (which at a
    GB-scale WS takes minutes: ``PERF.md``).  Returns what it measured."""
    from repro.core.reap import WS_CACHE
    from repro.serving import Router, RouterConfig

    with Router(ctx.orch, RouterConfig(max_concurrency=1)) as router:
        t0 = time.perf_counter()
        _, rep = router.invoke(ctx.name, ctx.batch(0))
        out = dict(report_fields(rep), wall_s=time.perf_counter() - t0)
    t0 = time.perf_counter()
    cache = ctx.orch.ws_cache or WS_CACHE
    cache.fetch(ctx.orch.functions[ctx.name].base, ctx.orch.reap)
    out["ws_cache_fill_s"] = time.perf_counter() - t0
    ctx.log("record_invocation", out)
    return out


class ColdBurst:
    def __init__(self, spec: dict):
        self.spec = spec
        self.burst = int(spec["burst"])

    def _one(self, ctx: Context, first_idx: int, timeout: float) -> list[Row]:
        from repro.serving import Router, RouterConfig

        with ctx.span("chipbench.scale_to_zero"):
            ctx.orch.scale_to_zero(ctx.name)
        # a router that starts only once the whole burst is queued: its
        # first dispatch sees the others waiting and restores one group
        router = Router(ctx.orch, RouterConfig(**self.spec["router"]),
                        start=False)
        try:
            with ctx.span("chipbench.burst"):
                t0 = time.perf_counter()
                rows, invs = [], []
                for j in range(self.burst):
                    i = first_idx + j
                    invs.append(router.submit(ctx.name, ctx.batch(i),
                                              force_cold=True))
                    rows.append(Row(i, i % len(ctx.requests), t0))
                router.start()
                for row, inv in zip(rows, invs):
                    _finish(row, inv, timeout)
        finally:
            router.close(drain=False)
        return rows

    def setup(self, ctx: Context) -> None:
        for k in range(int(self.spec.get("warmup_bursts", 1))):
            rows = self._one(ctx, 1 + k * self.burst, timeout=600.0)
            ctx.log("warmup_burst", [r.as_dict() for r in rows])

    def window(self, ctx: Context, seconds: float) -> tuple[list[Row], dict]:
        t_end = time.perf_counter() + seconds
        rows: list[Row] = []
        k = 0
        while time.perf_counter() < t_end:
            burst = self._one(ctx, k * self.burst, timeout=600.0)
            ctx.log("burst", {"k": k, "rows": [r.as_dict() for r in burst]})
            rows += burst
            k += 1
        return rows, {"bursts": k}


KINDS = {"cold_burst": ColdBurst}


def load(spec: dict):
    try:
        return KINDS[spec["kind"]](spec)
    except KeyError:
        raise ValueError(f"unknown traffic kind {spec.get('kind')!r}; "
                         f"known: {sorted(KINDS)}") from None
