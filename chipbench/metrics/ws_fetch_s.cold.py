"""ws_fetch_s.cold: mean seconds of the working-set fetch per cold
invocation (the program's ``StageTimings.ws_fetch_s``: the WS read or
cache hit plus the group's page-gather pass)."""
import statistics


def read(run):
    rows = run.served(cold=True)
    return (statistics.fmean(r.report["ws_fetch_s"] for r in rows)
            if rows else None)
