"""forward_mfu.cold: the model step's share of the chip's peak in a window
of cold starts: model FLOPs of one invocation's forward (counted by the
configuration's reference module) over the forward program's mean device
time in the trace, first op to last, times the peak bf16 FLOP/s, in
percent."""
from chipbench import trace_reduce


def read(run):
    if run.trace is None or run.peak is None or not run.served(cold=True):
        return None
    runs = trace_reduce.module_runs(run.trace, "_jit_forward")
    if not runs:
        return None
    mean_s = sum(m.dur_ns for m in runs) * 1e-9 / len(runs)
    return 100.0 * run.forward_flops / (mean_s * run.peak["bf16_flops_s"])
