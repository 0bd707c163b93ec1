"""device_idle.cold: percent of the traced window in which no operation
ran on the device, in a window of cold starts."""
from chipbench import trace_reduce


def read(run):
    if run.trace is None or not run.served(cold=True):
        return None
    idle = trace_reduce.idle_share(run.trace)
    return None if idle is None else 100.0 * idle
