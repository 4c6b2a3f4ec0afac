"""waterfill_ms_per_round: device time of the Pallas waterfill grant
kernel (kernels/ponsim/kernel.py) per simulated round, in ms: the summed
durations of its events in the profiler trace."""

KERNEL = "waterfill"


def read(run):
    t = run.trace
    if t is None or not run.work:
        return None
    seconds = t.op_seconds(lambda name: KERNEL in name.lower())
    if not seconds:
        return None
    return 1e3 * seconds / run.work
