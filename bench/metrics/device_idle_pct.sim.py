"""device_idle_pct.sim: share of the traced window in which no operation
ran on the device, averaged over the chips used (profiler trace)."""


def read(run):
    t = run.trace
    if t is None or not t.window_s:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
