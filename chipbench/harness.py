"""One run of one cell: set-up, the measured window, the check of what the
window served, and the result line.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: the sizes as run (every key that names a
  field of the program's ``ModelConfig`` sets it), the program's
  architecture name, the reference module and the limits of the
  comparison.  The program's registered config, ``ARCHS[program]``, is
  the published one.  ``reduced`` lists the program config keys the file
  cuts from it (the same list as the ``BENCHMARK.json`` entry's); a file
  whose ``reduced`` is not empty also gives ``published``, each cut key's
  published value, and a non-empty ``deployment``, what the cut stands
  for: the stage of how many chips, or the chip's share of what.  Only
  depth, the experts held, the vocabulary and the lists that follow the
  layers may be cut, never a width (``cuts.py`` checks it);
- ``reference/<module>.py``: the plain float32 forward of that model, its
  parameter table and its FLOP count;
- ``traffic/<traffic>.json``: the parameters of the one generator
  (``generator.py``);
- ``metrics/<metric>.py``: a reader ``read(run) -> float | None``.

A cell's result line carries its ``end_to_end`` metrics (``--trace 0``) or
its ``per_layer`` metrics (``--trace 1``), each reported only where its
reader found something to read.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
import traceback

import numpy as np

from . import compare, generator, trace_reduce, weights

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: the function store of a run (git-ignored; emptied before and after)
STORE = os.path.join(HERE, ".store")
#: the newest traced run's profile (git-ignored; replaced by the next)
TRACE_DIR = os.path.join(HERE, ".trace")

class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log_line(label: str, data) -> None:
    print(f"{label} {json.dumps(data)}", flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell_files(man: dict, cell_name: str, root: str = ROOT) -> tuple:
    """(cell, configuration dict, traffic dict) of ``cell_name``."""
    cells = {w["name"]: w for w in man["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no cell {cell_name!r}; known: {sorted(cells)}")
    cell = cells[cell_name]
    conf = {c["name"]: c for c in man["configs"]}[cell["config"]]
    config = load_json(os.path.join(root, conf["file"]))
    mix = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    return cell, config, mix


def metrics_of(man: dict, cell_name: str, trace: bool) -> list[dict]:
    """The metrics a cell reports in a run of this kind."""
    group = man["per_layer"] if trace else man["end_to_end"]
    return [m for m in group
            if cell_name in m.get("workloads", [cell_name])]


def reader(metric_name: str):
    path = os.path.join(HERE, "metrics", metric_name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric_name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference_module(config: dict):
    rel = config["reference"]
    if not rel.endswith(".py"):
        raise ValueError(f"reference {rel!r} is not a .py file")
    return importlib.import_module(rel[:-3].replace("/", "."))


def require_accelerator(chips: int) -> list:
    """The devices, or :class:`NoAccelerator`: a run never falls back to
    the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform "
                            f"{devices[0].platform!r})")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX found "
                            f"{len(devices)}")
    return devices


def import_program(root: str = ROOT) -> None:
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise ImportError(f"the system under test (repro) is not in {src}")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro  # noqa: F401


def model_keys() -> set[str]:
    """The configuration keys that set the program's ModelConfig: each of
    its fields but ``name`` (a configuration file's own name)."""
    from repro.configs.base import ModelConfig
    return {f.name for f in dataclasses.fields(ModelConfig)} - {"name"}


def program_config(config: dict):
    """The program's ModelConfig, with the configuration file's sizes (a
    ``dtype`` given by its name, as ``"bfloat16"``; a list as a tuple, so
    that the config stays hashable)."""
    import jax.numpy as jnp
    from repro.configs import ARCHS
    over = {k: tuple(v) if isinstance(v, list) else v
            for k, v in config.items() if k in model_keys()}
    if "dtype" in over:
        over["dtype"] = getattr(jnp, over["dtype"])
    return dataclasses.replace(ARCHS[config["program"]], **over)


def published_config(program: str) -> dict:
    """The program's registered config of ``program`` as a configuration
    file states it: each model key, the dtype by its name."""
    from repro.configs import ARCHS
    cfg = ARCHS[program]
    out = {k: getattr(cfg, k) for k in model_keys()}
    out["dtype"] = np.dtype(out["dtype"]).name
    return out


def check_table(cfg, table: list[tuple]) -> None:
    """The reference's parameter table names every leaf of the program's,
    with the same shape and dtype."""
    from repro.models import get_family
    from repro.nn import spec as nnspec
    prog = sorted((p, tuple(s.shape), str(np.dtype(s.dtype)))
                  for p, s in nnspec.tree_paths(get_family(cfg)
                                                .param_specs(cfg)))
    ref = sorted((p, tuple(shape), str(np.dtype(dtype)))
                 for p, shape, dtype, _ in table)
    if prog != ref:
        diff = sorted(set(prog) ^ set(ref))[:8]
        raise ValueError(f"reference and program parameters differ: {diff}")


class CompileCounter:
    """Counts the traces and backend compiles JAX makes while on."""

    def __init__(self):
        import jax
        self.on = False
        self.traces = self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name: str, _secs: float, **_kw) -> None:
        if not self.on:
            return
        if name.endswith("/jaxpr_trace_duration"):
            self.traces += 1
        elif name.endswith("/backend_compile_duration"):
            self.compiles += 1


@dataclasses.dataclass
class Run:
    """What a metric's reader reads."""
    rows: list
    setup_s: float
    seconds: float
    window_s: float
    config: dict
    mix: dict
    ws_pages: int
    forward_flops: float
    peak: dict | None = None
    trace: object = None          # trace_reduce.Trace of a traced run

    def served(self, cold: bool | None = None) -> list:
        return [r for r in self.rows if not r.error
                and (cold is None or r.cold == cold)]


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def run_cell(cell: dict, config: dict, mix: dict, *, seed: int,
             seconds: float, trace: bool, t_start: float, device,
             store: str | None = None, trace_dir: str | None = None,
             log=log_line) -> dict:
    """Set up, measure, check.  Returns the result (without metrics)."""
    import jax

    from repro.core.reap import WS_CACHE, trace_path
    from repro.core.restore import shutdown_tail_pool
    from repro.serving import Orchestrator, ServeConfig

    store = store or STORE
    trace_dir = trace_dir or TRACE_DIR
    ref_mod = reference_module(config)
    table = ref_mod.param_table(config)
    cfg = program_config(config)
    check_table(cfg, table)
    name = config["name"]
    shutil.rmtree(store, ignore_errors=True)
    os.makedirs(store)
    base = os.path.join(store, name)
    batch, seq = int(mix["batch"]), int(mix["seq"])

    t0 = time.perf_counter()
    host = jax.device_get(weights.generate(table, seed))
    gen_s = time.perf_counter() - t0
    image = weights.write_image(cfg, base, host, seed)
    del host
    log("image", dict(image, generate_s=gen_s,
                      write_s=time.perf_counter() - t0 - gen_s))
    reqs = generator.requests(weights.seed_words(seed), int(config["vocab"]),
                            int(mix["pool"]), batch, seq)

    compiles = CompileCounter()
    loop = generator.load(mix)
    orch = Orchestrator(store, ServeConfig(**mix.get("serve", {})))
    try:
        t0 = time.perf_counter()
        orch.register(name, cfg, seed=seed, warmup_batch={"tokens": reqs[0]})
        log("register", {"seconds": time.perf_counter() - t0})
        ctx = generator.Context(orch, name, reqs, log=log)
        if trace:
            ctx.span = _span
        generator.record_invocation(ctx)
        ws_pages = len(np.load(trace_path(base)))
        log("working_set", {"pages": ws_pages, "bytes": ws_pages * 4096})
        t0 = time.perf_counter()
        loop.setup(ctx)
        # the image and the WS record (~4.7 GB) are still being written
        # back; flush them now, so that the window does not share the disk
        # and the host with the writeback
        os.sync()
        log("warmup", {"seconds": time.perf_counter() - t0})
        setup_s = time.perf_counter() - t_start
        log("setup", {"seconds": setup_s})
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            trace_reduce.start(trace_dir)
        compiles.on = True
        t0 = time.perf_counter()
        try:
            rows, info = loop.window(ctx, seconds)
        finally:
            window_s = time.perf_counter() - t0
            compiles.on = False
            if trace:
                jax.profiler.stop_trace()
        log("window", dict(info, seconds=window_s, invocations=len(rows),
                           traces=compiles.traces,
                           compiles=compiles.compiles))
        stats = device.memory_stats() or {}
        peak_bytes = stats.get("peak_bytes_in_use")
        log("device_memory", {"peak_bytes_in_use": peak_bytes})
    finally:
        orch.close()
        WS_CACHE.clear()
        shutdown_tail_pool()
        shutil.rmtree(store, ignore_errors=True)

    checks = _checks(rows, mix, config, ref_mod, table, seed, reqs, ws_pages)
    run = Run(rows=rows, setup_s=setup_s, seconds=seconds, window_s=window_s,
              config=config, mix=mix, ws_pages=ws_pages,
              forward_flops=ref_mod.forward_flops(config, batch, seq))
    return {"run": run, "checks": checks, "memory_peak_bytes": peak_bytes}


def _checks(rows, mix, config, ref_mod, table, seed, reqs, ws_pages) -> dict:
    """Each number compared, beside its limit."""
    unanswered = sum(1 for r in rows if r.error or not np.isfinite(r.t_done))
    checks = {"unanswered": {"value": unanswered, "limit": 0}}
    if mix["kind"] == "cold_burst":
        bad = sum(1 for r in rows if not r.error and not (
            r.cold and r.report["n_prefetched_pages"] == ws_pages
            and r.report["batch_size"] == int(mix["burst"])))
        checks["restore_mismatch"] = {"value": bad, "limit": 0}
    kept = [r for r in rows if r.logits is not None]
    params = weights.generate(table, seed)
    err = compare.logit_error(
        ref_mod, config, params, [r.logits for r in kept],
        [reqs[r.request] for r in kept], block=int(mix["compare_block"]))
    del params
    checks["logit_err"] = logit_check(err, config, len(kept))
    return checks


def logit_check(err: float | None, config: dict, compared: int) -> dict:
    """The ``logit_err`` check: a reading beside its configuration's limit.
    The run's own answers and the control (``calibrate.py``) are judged by
    the same check."""
    return {"value": err, "limit": config["limits"]["logit_err"],
            "compared": compared}


def correct(checks: dict) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


def read_metrics(man: dict, cell_name: str, run: Run, trace: bool) -> dict:
    out = {}
    for m in metrics_of(man, cell_name, trace):
        value = reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(args, *, t_start: float) -> int:
    """The command line's run; returns the exit code."""
    try:
        man = manifest()
        cell, config, mix = cell_files(man, args.workload)
        devices = require_accelerator(int(cell["chips"]))
        import_program()
        from repro.launch.compile_cache import enable_compile_cache
    except (NoAccelerator, ImportError, KeyError, OSError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log_line("compile_cache", {"dir": enable_compile_cache()})
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    log_line("device", device)
    try:
        peak = None
        if args.trace:
            from .peaks import peaks
            peak = peaks(d0.device_kind)
        res = run_cell(cell, config, mix, seed=args.seed,
                       seconds=float(args.seconds), trace=bool(args.trace),
                       t_start=t_start, device=d0)
        run = res["run"]
        run.peak = peak
        extra = {}
        if args.trace:
            t0 = time.perf_counter()
            run.trace = trace_reduce.load(trace_reduce.find_xplane(
                TRACE_DIR))
            busy = trace_reduce.busy_ns(run.trace) * 1e-9
            extra = {"busy_s": busy, "window_s": run.trace.window_s}
            log_line("trace", {"ops": len(run.trace.ops),
                               "modules": len(run.trace.modules),
                               "host_events": len(run.trace.host),
                               "read_s": time.perf_counter() - t0})
            log_line("trace_op_names", trace_reduce.op_names(run.trace))
        metrics = read_metrics(man, cell["name"], run, bool(args.trace))
        log_line("invocations", {
            "attempted": len(run.rows), "cold": len(run.served(cold=True)),
            "errors": [r.error for r in run.rows if r.error][:5]})
    except Exception:
        traceback.print_exc()
        return 1
    checks = res["checks"]
    ok = correct(checks)
    result = {"correct": ok, "attempted": len(run.rows),
              "failed": checks["unanswered"]["value"], "metrics": metrics,
              "device": dict(device, memory_peak_bytes=res["memory_peak_bytes"],
                             **extra)}
    if args.trace:
        result["breakdown"] = trace_reduce.breakdown(run.trace)
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
