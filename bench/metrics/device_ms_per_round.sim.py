"""device_ms_per_round.sim: device busy time of the window per simulated
round, in ms (profiler trace; busy is the union of the intervals in
which an operation ran)."""


def read(run):
    t = run.trace
    if t is None or not run.work or not t.busy_s:
        return None
    return 1e3 * t.busy_s / run.work
