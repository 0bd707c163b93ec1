"""install_s.cold: mean seconds per cold invocation spent installing the
working set: the eager install plus the time faults waited on the
background tail (``StageTimings.install_s + tail_wait_s``)."""
import statistics


def read(run):
    rows = run.served(cold=True)
    return (statistics.fmean(r.report["install_s"] + r.report["tail_wait_s"]
                             for r in rows) if rows else None)
