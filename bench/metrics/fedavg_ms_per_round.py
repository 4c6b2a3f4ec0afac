"""fedavg_ms_per_round: device time per FL round of the FedAvg round's
program (``jit_fed_round``, span ``fed.round``): each pod's int8 round
trip of its upload and the cross-pod weighted mean, collectives
included, in ms, averaged over the pods' chips (profiler trace;
``bench.kinds.fed.scope_seconds``)."""
from bench.kinds import fed


def read(run):
    if run.trace is None or not run.work:
        return None
    seconds = fed.scope_seconds(run.trace, ["jit_fed_round"])
    return None if seconds is None else 1e3 * seconds / run.work
