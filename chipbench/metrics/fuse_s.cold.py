"""fuse_s.cold: mean seconds of the group restore's fuse pass, one per
group: the ``restore.fuse`` spans of the traced window (on a TPU the
copy of the WS to the chip, the page gather and the copy of the block
back).  The pass is part of ``ws_fetch_s.cold``."""


def read(run):
    if run.trace is None or not run.served(cold=True):
        return None
    spans = [e for e in run.trace.host if e.name == "restore.fuse"]
    if not spans:
        return None
    return sum(e.dur_ns for e in spans) * 1e-9 / len(spans)
