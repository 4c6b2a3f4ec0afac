"""host_fallbacks: phases the device engine handed back to the numpy
engine inside the window (``repro.kernels.ponsim.ops.host_fallbacks``,
summed over reasons); reads 0 when the device path did all the work."""


def read(run):
    return run.counters.get("host_fallbacks")
