"""ssd_ms_per_round: device time per FL round of the ops of the local
step program (``jit_fed_step``) in the named scope ``ssd_scan`` (the
SSD's chunked scan, forward, backward and rematerialised), in ms,
averaged over the pods' chips (profiler trace; ops read from the
compiled program, ``bench.kinds.fed.scope_seconds``)."""
from bench.kinds import fed


def read(run):
    if run.trace is None or not run.work:
        return None
    seconds = fed.scope_seconds(run.trace, ["jit_fed_step"], "ssd_scan")
    return None if seconds is None else 1e3 * seconds / run.work
