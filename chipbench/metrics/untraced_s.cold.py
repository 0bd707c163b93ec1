"""untraced_s.cold: mean seconds, per ``chipbench.burst`` span of the
traced window, that no stage span of the program covers (on any thread).
The stage spans are the program's restore stages, the fuse pass, page
touches, placements, the forward and ``finish_cold``; the spans that
only hold others (``router.dispatch``, ``restore.group``,
``instance.make_warm``) do not count.  Nothing to read where the program
puts no stage span on the timeline."""
from chipbench import trace_reduce

STAGES = ("restore.load_vmm", "restore.connect", "restore.ws_fetch",
          "restore.fuse", "restore.install", "executor.touch",
          "executor.placement", "executor.forward", "instance.finish_cold")


def read(run):
    if run.trace is None or not run.served(cold=True):
        return None
    stages = [e for e in run.trace.host if e.name in STAGES]
    bursts = [e for e in run.trace.host if e.name == "chipbench.burst"]
    if not stages or not bursts:
        return None
    covered = trace_reduce.busy_intervals(stages)
    uncovered = []
    for b in bursts:
        inside = sum(max(0.0, min(t, b.end_ns) - max(s, b.start_ns))
                     for s, t in covered)
        uncovered.append(b.dur_ns - inside)
    return sum(uncovered) * 1e-9 / len(bursts)
