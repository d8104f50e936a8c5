"""Share of the traced window in which no operation ran on the device:
1 - (union of device op intervals) / window, from the profiler trace; none
where the trace lost device events (``trace_reduce.Reduced.complete``)."""


def read(run):
    t = run.trace
    if t is None or not t.complete:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
