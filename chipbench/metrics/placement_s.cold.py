"""placement_s.cold: seconds per cold invocation served that the program
spent putting parameters on the device: the summed durations of every
``executor.placement`` span in the traced window, over the cold
invocations the window served.  A cold invocation places its tree twice:
for its forward (the span ends when the tree is on the device) and in
``make_warm`` (the span ends at dispatch)."""


def read(run):
    cold = run.served(cold=True)
    if run.trace is None or not cold:
        return None
    spans = [e for e in run.trace.host if e.name == "executor.placement"]
    if not spans:
        return None
    return sum(e.dur_ns for e in spans) * 1e-9 / len(cold)
