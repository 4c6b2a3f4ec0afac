"""fed_mfu_pct: model FLOP utilization of the traced FL round, as a
share of the bf16 peak of the pods' chips (``bench/peaks.json``): the
matrix-multiply operations the pods' local steps need, forward and
backward with no recomputation (``bench.kinds.fed.flops_per_round``),
over the traced window's length times the chips' peak."""
from bench import env
from bench.kinds import fed


def read(run):
    t = run.trace
    if t is None or not t.busy_s or not run.work or not fed.CURRENT:
        return None
    import jax

    cell = fed.CURRENT[0]
    peak = env.peaks(jax.devices()[0].device_kind)["bf16_flops"]
    flops = fed.flops_per_round(cell.config, cell.traffic) * run.work
    return 100.0 * flops / (t.window_s * cell.pods * peak)
