"""cold_start_mean_s: mean, over every cold invocation the window sent, of
the seconds from its burst's submit to its logits on the host.  A mean and
not a median: a window holds a handful of cold starts, and a stall in one
of them has to show."""
import statistics


def read(run):
    rows = run.served(cold=True)
    return statistics.fmean(r.latency_s for r in rows) if rows else None
