"""page_gather_roofline.cold: the least time the chip could take for the
group restores' page gathers (every WS page read once and written once at
peak HBM bandwidth; the kernel does no arithmetic) over the device time of
the page_gather kernel's calls in the trace, in percent."""
from chipbench import flops, trace_reduce


def read(run):
    if run.trace is None or run.peak is None:
        return None
    runs = trace_reduce.module_runs(run.trace, "gather_pages")
    kernel = trace_reduce.kernel_ops(run.trace, "gather_pages")
    if not runs or not kernel:
        return None
    least = flops.least_seconds(
        0, len(runs) * flops.page_gather_bytes(run.ws_pages), run.peak)
    return 100.0 * least / (sum(e.dur_ns for e in kernel) * 1e-9)
