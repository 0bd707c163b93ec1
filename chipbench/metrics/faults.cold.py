"""faults.cold: mean page faults served from disk per cold invocation
(``ColdStartReport.n_faults``): pages REAP did not install in time."""
import statistics


def read(run):
    rows = run.served(cold=True)
    return (statistics.fmean(r.report["n_faults"] for r in rows)
            if rows else None)
