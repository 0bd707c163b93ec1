"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

What counts as a device operation: on an accelerator, the events of the
``XLA Ops`` line of each ``/device:`` plane; in a CPU run, which has no
device plane, the host events that carry an ``hlo_op`` statistic (XLA's
CPU client runs the ops on host threads).  A program run ("module") is an
event of the ``XLA Modules`` line, or, where that line is missing, the span
of the ops of one ``(hlo_module, run_id)``.  Every other host event is what
the host was doing, and labels the device's idle gaps.

All times are nanoseconds from the start of the trace.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    module: str = ""
    device: str = ""

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    window_ns: float
    n_devices: int
    ops: list[Event]          # device operations
    modules: list[Event]      # program runs on the device
    host: list[Event]         # host events with a duration

    @property
    def window_s(self) -> float:
        return self.window_ns * 1e-9


def start(log_dir: str) -> None:
    """Start the profiler as a traced run does: host and device events,
    no Python function tracer (it would trace every call of the per-page
    host loops and slow the host it measures)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _stats(ev) -> dict:
    return dict(ev.stats)


def short_name(name: str) -> str:
    """``%gather_pages.14 = u32[...] custom-call(...)`` -> ``gather_pages``:
    a device op's HLO instruction name without its operands or number."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def _attribute(ops: list[Event], runs: list[Event], device: str) -> None:
    """Name the program of every op of ``device`` that carries none, by the
    program run whose interval holds the op's start."""
    runs = sorted(runs, key=lambda m: m.start_ns)
    starts = [m.start_ns for m in runs]
    for i, e in enumerate(ops):
        if e.device != device or e.module:
            continue
        j = bisect.bisect_right(starts, e.start_ns) - 1
        if j >= 0 and e.start_ns < runs[j].end_ns:
            ops[i] = dataclasses.replace(e, module=runs[j].name)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    window_ns = 0.0
    ops: list[Event] = []
    modules: list[Event] = []
    host: list[Event] = []
    cpu_ops: list[tuple[Event, object]] = []
    tagged: list[tuple[Event, object]] = []   # op, the run it belongs to
    n_devices = 0
    for plane in prof.planes:
        if plane.name == "Task Environment":
            st = dict(plane.stats)
            if "profile_start_time" in st and "profile_stop_time" in st:
                window_ns = float(st["profile_stop_time"]
                                  - st["profile_start_time"])
            continue
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            n_devices += 1
            for ev in lines["XLA Ops"].events:
                st = _stats(ev)
                e = Event(ev.name, ev.start_ns, ev.duration_ns,
                          str(st.get("hlo_module", "")), plane.name)
                ops.append(e)
                tagged.append((e, (plane.name, st.get("run_id"))))
            if "XLA Modules" in lines:
                runs = [Event(ev.name, ev.start_ns, ev.duration_ns, ev.name,
                              plane.name)
                        for ev in lines["XLA Modules"].events]
                modules += runs
                _attribute(ops, runs, plane.name)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.duration_ns <= 0:
                    continue
                st = _stats(ev)
                if "hlo_op" in st:
                    cpu_ops.append((Event(ev.name, ev.start_ns, ev.duration_ns,
                                          str(st.get("hlo_module", ""))),
                                    st.get("run_id")))
                else:
                    host.append(Event(ev.name, ev.start_ns, ev.duration_ns))
    if not n_devices and cpu_ops:
        n_devices = 1
        ops = [e for e, _ in cpu_ops]
        tagged = cpu_ops
    if not modules:
        runs: dict[tuple, list[float]] = {}
        for e, run in tagged:
            key = (e.module, run)
            span = runs.setdefault(key, [e.start_ns, e.end_ns])
            span[0] = min(span[0], e.start_ns)
            span[1] = max(span[1], e.end_ns)
        modules = [Event(k[0], s, t - s, k[0]) for k, (s, t) in runs.items()]
    if not window_ns:
        ends = [e.end_ns for e in ops + host]
        window_ns = max(ends) if ends else 0.0
    ops.sort(key=lambda e: e.start_ns)
    modules.sort(key=lambda e: e.start_ns)
    host.sort(key=lambda e: e.start_ns)
    return Trace(window_ns, n_devices, ops, modules, host)


def busy_intervals(ops: list[Event]) -> list[tuple[float, float]]:
    """Union of the ops' intervals, as sorted disjoint (start, end)."""
    out: list[list[float]] = []
    for e in sorted(ops, key=lambda e: e.start_ns):
        if out and e.start_ns <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end_ns)
        else:
            out.append([e.start_ns, e.end_ns])
    return [(s, t) for s, t in out]


def busy_ns(trace: Trace) -> float:
    """Nanoseconds in which some op ran on a device, averaged over the
    devices traced."""
    per_device: dict[str, list[Event]] = {}
    for e in trace.ops:
        per_device.setdefault(e.device, []).append(e)
    total = sum(t - s for ops in per_device.values()
                for s, t in busy_intervals(ops))
    return total / max(trace.n_devices, 1)


def idle_share(trace: Trace) -> float | None:
    """1 - busy / window, or None without device ops."""
    if not trace.ops or trace.window_ns <= 0:
        return None
    return 1.0 - busy_ns(trace) / trace.window_ns


def idle_gaps(trace: Trace) -> list[tuple[float, float]]:
    """Intervals of the traced window in which no op ran."""
    gaps = []
    t = 0.0
    for s, e in busy_intervals(trace.ops):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if trace.window_ns > t:
        gaps.append((t, trace.window_ns))
    return gaps


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` ops (by :func:`short_name`) that took most device time:
    [[name, seconds]]."""
    tot: dict[str, float] = {}
    for e in trace.ops:
        k = short_name(e.name)
        tot[k] = tot.get(k, 0.0) + e.dur_ns
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9] for name, ns in best]


def _label(gap: tuple[float, float], host: list[Event]) -> str:
    """What the host was doing in ``gap``: of the host events that overlap
    it, the shortest among those whose overlap is within 10% of the
    largest (the innermost span that covers the gap)."""
    s, t = gap
    best = 0.0
    cand: list[tuple[float, Event]] = []
    for e in host:
        if e.start_ns >= t:
            break
        ov = min(t, e.end_ns) - max(s, e.start_ns)
        if ov > 0:
            cand.append((ov, e))
            best = max(best, ov)
    if not cand:
        return "(no host event)"
    near = [e for ov, e in cand if ov >= 0.9 * best]
    return min(near, key=lambda e: e.dur_ns).name


def labelled_gaps(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` longest idle gaps: [[what the host did, seconds]]."""
    gaps = sorted(idle_gaps(trace), key=lambda g: g[0] - g[1])[:n]
    return [[_label(g, trace.host), (g[1] - g[0]) * 1e-9] for g in gaps]


def module_runs(trace: Trace, name_part: str) -> list[Event]:
    """Runs of every program whose name contains ``name_part``."""
    return [m for m in trace.modules if name_part in m.name]


def ops_in(trace: Trace, module_part: str) -> list[Event]:
    """Device ops of every program whose name contains ``module_part``."""
    return [e for e in trace.ops if module_part in e.module]


def breakdown(trace: Trace) -> dict:
    return {"device_ops": top_ops(trace), "idle_gaps": labelled_gaps(trace)}


#: what a Pallas/Mosaic kernel's op is called in a trace
KERNEL_MARKS = ("custom-call", "custom_call", "copy_kernel", "page_gather")


def kernel_ops(trace: Trace, module_part: str,
               marks: tuple[str, ...] = KERNEL_MARKS) -> list[Event]:
    """The kernel calls among the ops of the programs named by
    ``module_part`` (the program's other ops, such as the zero fill of its
    output, are not the kernel's)."""
    return [e for e in ops_in(trace, module_part)
            if any(m in e.name for m in marks)]


def op_names(trace: Trace, limit: int = 12) -> dict:
    """Per program: how many distinct op names, and the first few."""
    names: dict[str, set] = {}
    for e in trace.ops:
        names.setdefault(e.module, set()).add(short_name(e.name))
    return {m: [len(n)] + sorted(n)[:limit] for m, n in names.items()}
