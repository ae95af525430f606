"""device_idle_share (device): 1 - (union of the intervals in which an
operation ran on the device) / traced window, averaged over the chips."""


def reduce(run):
    if run.trace is None or not run.trace.planes():
        return None
    planes = run.trace.planes()
    busy = sum(run.trace.busy_ns(p) for p in planes) / len(planes) * 1e-9
    return 100.0 * (1.0 - busy / run.trace_window_s)
