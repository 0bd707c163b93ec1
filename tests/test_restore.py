"""Staged restore pipeline + batched group restores (core/restore.py).

Covers: per-stage timing attribution (fakeclock-driven), fused
gather/scatter install parity vs the per-page ``install_span`` path (arena
bytes and logits), one-WS-read/k-install group semantics through the
orchestrator and the router, the drop_record-vs-cold-start race fallback,
and the shard-tier push invalidation broadcast.
"""
import threading

import jax
import numpy as np
import pytest
from fakeclock import FakeClock

from repro.configs import SMOKES
from repro.core import ReapConfig
from repro.core import arena as arena_mod
from repro.core import reap as reap_mod
from repro.core.arena import PAGE, ArenaLayout, GuestMemoryFile, InstanceArena
from repro.core.reap import WS_CACHE, WSCache
from repro.core.restore import (RestoreBatch, RestorePipeline, TailInstall,
                                fuse_ws_block)
from repro.telemetry import TELEMETRY
from repro.launch import steps
from repro.serving import Orchestrator, Router, RouterConfig


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One registered+recorded function on a module-scoped orchestrator."""
    store = str(tmp_path_factory.mktemp("batchstore"))
    cfg = SMOKES["olmo-1b"]
    batch = steps.make_batch(cfg, 32, 2, "train", jax.random.key(0))
    orch = Orchestrator(store, mode="reap", reap=ReapConfig())
    orch.register("fn", cfg, warmup_batch=batch)
    orch.invoke("fn", batch)          # record phase
    orch.scale_to_zero("fn")
    return orch, batch


@pytest.fixture()
def small_recorded(tmp_path):
    """A tiny recorded guest-memory file for arena-level parity tests."""
    tensors = [
        ("infra/tab", (3000,), "uint8", "infra"),
        ("params/w", (64, 33), "float32", "serve"),
        ("boot/opt", (64, 33), "float32", "boot"),
    ]
    layout = ArenaLayout.build(tensors)
    rng = np.random.default_rng(7)
    arrays = {
        "infra/tab": np.arange(3000, dtype=np.uint8),
        "params/w": rng.standard_normal((64, 33)).astype(np.float32),
        "boot/opt": np.ones((64, 33), np.float32),
    }
    gm = GuestMemoryFile.create(str(tmp_path / "fn"), layout, arrays)
    arena = InstanceArena(gm)
    arena.tensor("infra/tab")
    arena.tensor("params/w")
    reap_mod.write_record(gm.base, arena.stats.trace)
    arena.close()
    return gm


# -- fused install parity ----------------------------------------------


def _blank_gm(path, n_pages: int) -> GuestMemoryFile:
    """A guest memory file of ``n_pages`` zero pages (sparse on disk)."""
    layout = ArenaLayout.build([("w", (n_pages * PAGE,), "uint8", "serve")])
    gm = GuestMemoryFile(str(path), layout)
    with open(gm.mem_path, "wb") as f:
        f.truncate(layout.total_bytes)
    return gm


def _small_copies(monkeypatch) -> None:
    """Copy blocks of 64 pages and more on several threads, 16 pages a task,
    so that a small arena has many chunks."""
    monkeypatch.setattr(arena_mod, "PARALLEL_MIN_PAGES", 64)
    monkeypatch.setattr(arena_mod, "COPY_CHUNK_PAGES", 16)


#: runs of 1 to 280 pages, cut by chunk edges
GAPPED = np.r_[0:50, 51:70:2, 70:200, 203:205, 300:500:2, 520:800]
#: a few long runs, below the real threshold: the calling thread
RUNS = np.r_[10:100, 150:300, 301, 400:800]
#: block-install cases beside the two fuse engines: (arena pages, target
#: pages, pages resident beforehand, small parallel-copy sizes)
INSTALL_CASES = {
    # contiguous, above the real parallel threshold
    "contiguous": (arena_mod.PARALLEL_MIN_PAGES + 1000,
                   np.arange(arena_mod.PARALLEL_MIN_PAGES + 1000), [], False),
    "gapped": (800, GAPPED, [], True),
    # partly resident: the masked path, on several threads
    "resident": (800, GAPPED, [0, 1, 2, 61, 63, 71, 400, 530, 799], True),
    # below the real threshold: the calling thread
    "serial": (800, RUNS, [], False),
}


@pytest.mark.parametrize("case", ["numpy", "pallas", *INSTALL_CASES])
def test_fused_block_install_matches_install_span(request, tmp_path,
                                                  monkeypatch, case):
    """The block install must be byte-identical to the per-page
    install_span path, in ``resident`` and ``n_pages_installed`` too: for
    a fused block of each engine, and for contiguous, gapped, partly
    resident and small blocks on each copy path."""
    before = []
    parallel = False
    if case in ("numpy", "pallas"):
        gm = request.getfixturevalue("small_recorded")
        span_pages, data = reap_mod._read_ws(gm.base, ReapConfig())
        pages, block = fuse_ws_block(span_pages, data, engine=case)
    else:
        n_arena, pages, before, small = INSTALL_CASES[case]
        parallel = len(pages) >= (64 if small else
                                  arena_mod.PARALLEL_MIN_PAGES)
        if small:
            _small_copies(monkeypatch)
        gm = _blank_gm(tmp_path / "fn", n_arena)
        block = np.random.default_rng(3).integers(
            0, 256, (len(pages), PAGE), dtype=np.uint8)
        span_pages, data = pages, block.tobytes()

    a_span = InstanceArena(gm)
    a_block = InstanceArena(gm)
    for a in (a_span, a_block):
        # resident beforehand with other bytes: both paths must skip them
        a.install_span(before, b"\xaa" * PAGE * len(before))
    a_span.install_span(span_pages, data)
    installed, workers = a_block.install_block(pages, block)

    assert installed == len(set(pages.tolist()) - set(before))
    np.testing.assert_array_equal(np.asarray(a_span.resident),
                                  np.asarray(a_block.resident))
    assert (a_span.stats.n_pages_installed
            == a_block.stats.n_pages_installed)
    assert bytes(a_span.view) == bytes(a_block.view)   # full arena bytes
    assert (workers > 1) == (parallel and arena_mod._copy_workers() > 1)
    a_span.close()
    a_block.close()


def _gate_copies(monkeypatch):
    """Hold every chunk of a parallel copy until ``go`` is set; ``started``
    is set when the first one begins."""
    started, go = threading.Event(), threading.Event()
    real = arena_mod._copy_rows

    def gated(*args):
        started.set()
        assert go.wait(10)
        real(*args)

    monkeypatch.setattr(arena_mod, "_copy_rows", gated)
    return started, go


def _thread(fn, *args):
    """A started thread running ``fn``; what it raised lands in the
    returned list."""
    errors = []

    def run():
        try:
            fn(*args)
        except Exception as e:  # noqa: BLE001 - reported to the test
            errors.append(e)

    t = threading.Thread(target=run)
    t.start()
    return t, errors


def test_fault_on_a_page_of_an_inflight_block_install_waits(tmp_path,
                                                           monkeypatch):
    """A parallel copy runs outside the arena lock; a fault on one of its
    pages meanwhile waits for the install instead of reading disk."""
    _small_copies(monkeypatch)
    started, go = _gate_copies(monkeypatch)
    arena = InstanceArena(_blank_gm(tmp_path / "fn", 256))
    pages = np.arange(256)
    block = np.random.default_rng(4).integers(0, 256, (256, PAGE),
                                              dtype=np.uint8)
    installer, inst_err = _thread(arena.install_block, pages, block)
    assert started.wait(10)
    toucher, touch_err = _thread(arena.touch_pages, [100])
    toucher.join(0.3)
    assert toucher.is_alive()            # parked on the in-flight copy
    go.set()
    installer.join(10)
    toucher.join(10)
    assert not installer.is_alive() and not toucher.is_alive()
    assert inst_err == [] and touch_err == []
    assert arena.stats.n_faults == 0     # no disk read
    assert arena.stats.tail_waits == 1
    assert arena.resident.all()
    got = np.frombuffer(bytes(arena.view), np.uint8).reshape(-1, PAGE)
    np.testing.assert_array_equal(got, block)
    arena.close()


@pytest.mark.parametrize("when", ["during", "after"])
def test_close_around_a_block_install_parks_no_waiter(tmp_path, monkeypatch,
                                                      when):
    """``close()`` during a parallel copy wakes the fault parked on it, and
    the install still ends; after the install no marker is left."""
    _small_copies(monkeypatch)
    started, go = _gate_copies(monkeypatch)
    arena = InstanceArena(_blank_gm(tmp_path / "fn", 256))
    block = np.zeros((256, PAGE), np.uint8)
    if when == "after":
        go.set()
    installer, inst_err = _thread(arena.install_block, np.arange(256), block)
    assert started.wait(10)
    if when == "during":
        toucher, _ = _thread(arena.touch_pages, [7])
        toucher.join(0.3)
        assert toucher.is_alive()
        arena.close()
        toucher.join(10)
        assert not toucher.is_alive()    # woken by close, not parked
        go.set()
    installer.join(10)
    assert not installer.is_alive() and inst_err == []
    assert arena._n_copying == 0 and not arena._copying.any()
    assert arena.pending_count == 0
    arena.close()


def test_tail_install_pending_copies_on_its_own_thread(tmp_path,
                                                      monkeypatch):
    """``TailInstall`` still lands its chunks through ``install_pending``,
    serially under the arena lock: none of its pages is copied in parallel."""
    _small_copies(monkeypatch)
    arena = InstanceArena(_blank_gm(tmp_path / "fn", 800))
    block = np.random.default_rng(5).integers(0, 256, (len(GAPPED), PAGE),
                                              dtype=np.uint8)
    counter = TELEMETRY.counter("arena.install.parallel_pages")
    n0 = counter.value
    tail = TailInstall(arena, GAPPED, block, deadline_s=60.0)
    tail.wait(30)
    assert tail.done_at is not None and not tail.demoted
    assert arena.pending_count == 0
    assert arena.resident[GAPPED].all() and arena.resident.sum() == len(GAPPED)
    assert counter.value == n0
    got = np.frombuffer(bytes(arena.view), np.uint8).reshape(-1, PAGE)
    np.testing.assert_array_equal(got[GAPPED], block)
    arena.close()


def test_fuse_engines_agree_and_scatter_kernel_roundtrips(small_recorded):
    """numpy and pallas fuse engines produce identical blocks, and the
    scatter_pages kernel (the install's TPU-native realization) lands the
    block on the same pages as install_block."""
    gm = small_recorded
    pages, data = reap_mod._read_ws(gm.base, ReapConfig())
    idx_np, block_np = fuse_ws_block(pages, data, engine="numpy")
    idx_pl, block_pl = fuse_ws_block(pages, data, engine="pallas")
    np.testing.assert_array_equal(idx_np, idx_pl)
    np.testing.assert_array_equal(block_np, block_pl)

    import jax.numpy as jnp
    from repro.kernels import scatter_pages
    n_pages = gm.layout.n_pages
    dest = jnp.zeros((n_pages, PAGE), jnp.uint8)
    out = np.asarray(scatter_pages(jnp.asarray(block_np),
                                   jnp.asarray(idx_np.astype(np.int32)),
                                   dest))
    arena = InstanceArena(GuestMemoryFile.open(gm.base))
    arena.install_block(idx_np, block_np)
    arena_pages = np.frombuffer(bytes(arena.view), np.uint8,
                                count=n_pages * PAGE).reshape(-1, PAGE)
    np.testing.assert_array_equal(out[idx_np], arena_pages[idx_np])
    arena.close()


def test_batched_restore_identical_logits(served):
    """A batch-restored instance computes logits identical to an unbatched
    cold instance (same params, same request)."""
    orch, batch = served
    ref, _ = orch.invoke("fn", batch, force_cold=True)
    orch.scale_to_zero("fn")
    insts = orch.spawn_batch("fn", 2)
    try:
        for inst in insts:
            assert inst.try_acquire()
            logits, _ = inst.invoke(batch)
            np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref))
            inst.release()
    finally:
        for inst in insts:
            inst.try_reclaim()


# -- group restore semantics -------------------------------------------


def test_spawn_batch_one_fetch_k_installs(served):
    """k instances restored as one group: exactly one WS-cache transaction,
    one underlying read, k arena installs, per-report batch_size=k."""
    orch, _ = served
    WS_CACHE.clear()
    WS_CACHE.reset_stats()
    k = 4
    insts = orch.spawn_batch("fn", k)
    try:
        s = WS_CACHE.stats()
        assert s["reads"] == 1
        assert s["misses"] == 1 and s["hits"] == 0   # one transaction total
        assert s["group_fetches"] == 1 and s["group_instances"] == k
        ws_pages = insts[0].report.n_prefetched_pages
        assert ws_pages > 0
        for inst in insts:
            assert inst.report.batch_size == k
            assert inst.report.load_vmm_s > 0
            assert inst.report.install_s > 0
            assert inst.report.prefetch_s >= inst.report.install_s
            assert inst.report.n_prefetched_pages == ws_pages
            # each arena performed its own (single, fused) install
            assert inst.monitor.arena.stats.n_pages_installed == ws_pages
        # identical residency across the group
        r0 = np.asarray(insts[0].monitor.arena.resident)
        for inst in insts[1:]:
            np.testing.assert_array_equal(
                r0, np.asarray(inst.monitor.arena.resident))
    finally:
        for inst in insts:
            inst.try_reclaim()


def test_group_hint_invoke_parks_fresh_for_followers(served):
    """A cold invoke with group_hint=k restores k instances; the k-1 extras
    park in the fresh pool and later cold invocations consume them without
    spawning (or re-reading) anything."""
    orch, batch = served
    orch.scale_to_zero("fn")
    WS_CACHE.clear()
    WS_CACHE.reset_stats()
    rec = orch.functions["fn"]
    spawned0 = rec.n_spawned
    k = 3
    _, rep = orch.invoke("fn", batch, force_cold=True, group_hint=k)
    assert rep.batch_size == k and rep.load_vmm_s > 0
    with rec.lock:
        assert len(rec.fresh) == k - 1
    for _ in range(k - 1):
        _, rep = orch.invoke("fn", batch, force_cold=True)
        assert rep.batch_size == k          # restored by the group
        assert rep.load_vmm_s > 0           # still charged the full split
    assert rec.n_spawned - spawned0 == k    # no extra spawns
    assert WS_CACHE.stats()["reads"] == 1
    with rec.lock:
        assert not rec.fresh
    orch.scale_to_zero("fn")


def test_router_serial_worker_batches_whole_queue(served):
    """k-deep same-function cold queue, one worker: the first dispatch
    group-restores everything queued behind it — exactly one WS read and
    k installs, every report batch_size=k (deterministic: no racing
    workers)."""
    orch, batch = served
    orch.scale_to_zero("fn")
    WS_CACHE.clear()
    WS_CACHE.reset_stats()
    rec = orch.functions["fn"]
    spawned0 = rec.n_spawned
    k = 4
    router = Router(orch, RouterConfig(max_concurrency=1,
                                       max_instances_per_function=k,
                                       batch_restore_limit=k), start=False)
    invs = [router.submit("fn", batch, force_cold=True) for _ in range(k)]
    router.start()
    reports = [inv.result(timeout=300)[1] for inv in invs]
    router.close()
    assert rec.n_spawned - spawned0 == k
    assert WS_CACHE.stats()["reads"] == 1
    assert WS_CACHE.stats()["misses"] == 1       # one cache transaction
    ws_pages = reports[0].n_prefetched_pages
    for r in reports:
        assert r.batch_size == k
        assert r.load_vmm_s > 0 and r.connection_s > 0
        assert r.n_prefetched_pages == ws_pages
    orch.scale_to_zero("fn")


def test_batch_restore_limit_one_disables_grouping(served):
    orch, batch = served
    orch.scale_to_zero("fn")
    rec = orch.functions["fn"]
    router = Router(orch, RouterConfig(max_concurrency=1,
                                       max_instances_per_function=4,
                                       batch_restore_limit=1), start=False)
    invs = [router.submit("fn", batch, force_cold=True) for _ in range(3)]
    router.start()
    reports = [inv.result(timeout=300)[1] for inv in invs]
    router.close()
    assert all(r.batch_size == 1 for r in reports)
    with rec.lock:
        assert not rec.fresh
    orch.scale_to_zero("fn")


def test_failed_materialize_reclaims_whole_group(served, monkeypatch):
    """If make_warm fails mid-group (records dropped mid-spawn), every
    already-adopted arena is reclaimed — nothing leaks."""
    from repro.serving.instance import FunctionInstance, State, restore_group
    orch, _ = served
    rec = orch.functions["fn"]
    insts = [FunctionInstance("fn", rec.cfg, rec.base, orch.reap)
             for _ in range(2)]

    def boom(self):
        raise RuntimeError("materialize failed")

    monkeypatch.setattr(FunctionInstance, "make_warm", boom)
    with pytest.raises(RuntimeError):
        restore_group(insts, materialize=True)
    assert all(i.state is State.RECLAIMED for i in insts)


def test_prewarm_restores_as_one_group(served):
    """A prewarm burst is one group restore: one WS-cache transaction, and
    the instances park warm with their restore off-path."""
    orch, batch = served
    orch.scale_to_zero("fn")
    WS_CACHE.clear()
    WS_CACHE.reset_stats()
    rec = orch.functions["fn"]
    assert orch.prewarm("fn", 3, wait=True) == 3
    s = WS_CACHE.stats()
    assert s["reads"] == 1 and s["misses"] == 1
    assert s["group_fetches"] == 1 and s["group_instances"] == 3
    with rec.lock:
        assert len(rec.idle) == 3
        assert all(i.prewarmed and i.report.batch_size == 3
                   for i in rec.idle)
    _, rep = orch.invoke("fn", batch)
    assert rep.prewarmed and rep.load_vmm_s == 0.0   # restore stayed off-path
    orch.scale_to_zero("fn")


# -- stage timing attribution (fakeclock-driven) -----------------------


class _TickClock(FakeClock):
    """A fake perf counter that advances 1s per reading: each pipeline
    stage is bracketed by exactly two readings, so its timing must come
    out at exactly 1.0 — proving stages are timed separately and nothing
    else reads the clock inside a stage."""

    def __call__(self) -> float:
        t = super().__call__()
        self.advance(1.0)
        return t


def test_pipeline_stage_timings_are_attributed(small_recorded):
    gm = small_recorded
    pipe = RestorePipeline(gm.base, ReapConfig(), clock=_TickClock())
    pipe.load_vmm()
    pipe.connect()
    fetched = pipe.ws_fetch()
    pipe.install(fetched)
    t = pipe.timings
    assert t.load_vmm_s == 1.0
    assert t.connection_s == 1.0
    assert t.ws_fetch_s == 1.0
    assert t.install_s == 1.0
    assert t.prefetch_s == 2.0           # fetch + install, the §4.2 segment
    assert t.materialize_s == 0.0
    pipe.close()


def test_batch_charges_shared_fetch_to_every_member(small_recorded):
    """In a group, the single fetch + fuse pass land on every member's
    ws_fetch_s (they all waited on it), install_s stays per-member."""
    gm = small_recorded
    pipes = [RestorePipeline(gm.base, ReapConfig(), clock=_TickClock())
             for _ in range(3)]
    batch = RestoreBatch(pipes).run()
    assert batch.fuse_s > 0
    shared = pipes[0].timings.ws_fetch_s
    for p in pipes:
        assert p.timings.ws_fetch_s == shared
        assert p.timings.install_s == 1.0
        assert p.monitor.prefetched > 0
    stages = batch.stage_seconds()
    assert stages["load_vmm_s"] == 3.0 and stages["connection_s"] == 3.0
    for p in pipes:
        p.close()


# -- drop_record vs cold start race (§7.2) -----------------------------


def test_monitor_falls_back_to_record_when_record_dropped(small_recorded):
    """drop_record between mode selection and start() must not fail the
    cold start: the monitor falls back to record mode."""
    gm = small_recorded
    mon = reap_mod.Monitor(GuestMemoryFile.open(gm.base), gm.base,
                           ReapConfig())
    assert mon.mode == "prefetch"
    reap_mod.drop_record(gm.base)        # concurrent §7.2 re-record wins
    mon.start()                          # must not raise
    assert mon.mode == "record"
    assert mon.prefetched == 0
    mon.arena.close()


def test_cold_start_racing_drop_record_rerecords(served, monkeypatch):
    """End-to-end: a drop_record landing inside the WS fetch window falls
    back to record mode, the invocation succeeds, and a fresh record is
    written by finish()."""
    orch, batch = served
    orch.scale_to_zero("fn")
    base = orch.functions["fn"].base
    assert reap_mod.has_record(base)
    real_fetch = WSCache.fetch
    raced = threading.Event()

    def racing_fetch(self, b, cfg, group=1):
        if b == base and not raced.is_set():
            raced.set()
            reap_mod.drop_record(b)      # the re-record wins the race
        return real_fetch(self, b, cfg, group)

    monkeypatch.setattr(WSCache, "fetch", racing_fetch)
    _, rep = orch.invoke("fn", batch, force_cold=True)   # must not raise
    assert raced.is_set()
    assert rep.n_prefetched_pages == 0   # fell back to record mode
    assert reap_mod.has_record(base)     # finish() re-recorded
    orch.scale_to_zero("fn")
    monkeypatch.undo()
    _, rep = orch.invoke("fn", batch, force_cold=True)
    assert rep.n_prefetched_pages > 0    # prefetch engaged on the new record
    orch.scale_to_zero("fn")


def test_ws_cache_threads_group_to_source(tmp_path):
    """A group-aware miss source (the shard tier) receives the restore
    batch size; legacy two-arg sources keep working."""
    base = str(tmp_path / "f")
    with open(reap_mod.ws_path(base), "wb") as f:
        f.write(b"x")
    seen = []

    def tiered(b, cfg, group=1):
        seen.append(group)
        return [0], b"A" * PAGE

    cache = WSCache(source=tiered)
    cache.fetch(base, ReapConfig(), group=5)
    assert seen == [5]
    s = cache.stats()
    assert s["group_fetches"] == 1 and s["group_instances"] == 5

    legacy_calls = []
    legacy = WSCache(source=lambda b, cfg: (legacy_calls.append(b)
                                            or ([0], b"B" * PAGE)))
    legacy.fetch(base, ReapConfig(), group=3)
    assert legacy_calls == [base]        # called without the kwarg


# -- shard-tier push invalidation --------------------------------------


def test_rerecord_pushes_invalidation_to_peer_caches(small_recorded):
    """A re-record (write_record/drop_record) eagerly drops the stale WS
    from every attached L1 — counted in pushed_invalidations — instead of
    waiting for each node's next mtime-checked fetch."""
    from repro.cluster.shardmap import ConsistentHashRing
    from repro.cluster.snapstore import ShardedSnapshotStore, TransferModel
    gm = small_recorded
    ring = ConsistentHashRing()
    store = ShardedSnapshotStore(ring, transfer=TransferModel(latency_s=0.0),
                                 sleep=lambda s: None)
    try:
        a = store.attach("node-a")
        b = store.attach("node-b")
        a.fetch(gm.base, ReapConfig())
        b.fetch(gm.base, ReapConfig())
        assert a.contains(gm.base) and b.contains(gm.base)

        reap_mod.drop_record(gm.base)    # re-record path
        assert not a.contains(gm.base) and not b.contains(gm.base)
        assert store.stats()["pushed_invalidations"] == 2
    finally:
        store.close()

    # after close() the store must stop receiving broadcasts
    a._entries["zzz"] = (0.0, [0], b"")  # fake entry; invalidate would drop
    reap_mod._broadcast_invalidation("zzz")
    assert "zzz" in a._entries           # detached: untouched
