"""Co-simulator cells: traffic generator, timed call, reference check.

One general generator reads a deployment (``configs/<name>.json``) and a
traffic mix (``traffic/<name>.json``) and makes, from the run's seed and
a call index, the plain description of one ``simulate`` call: its cases
(policy, load, stream seed), its clients with their compute times and,
for a timeline mix, its rounds.  The timed path is
``repro.net.simulate(SweepSpec(..., backend="jit"))`` on that call; the
check reruns a sample of the window's rounds, drawn from the seed, on
:mod:`bench.reference` and compares every value the program returned
for them.

Compute times are a stratified draw: the client of rank ``k`` takes a
time drawn uniformly from the ``k``-th of ``n`` equal strata of the
configured range, and which client holds which rank is fixed by the
mix's ``rank_seed``.  Every seed so gives the same spread of compute
times over the ONUs, with fresh values, and fresh background streams:
which ONU holds the late clients sets most of a round's length, so a
free draw would make the work of a call depend on the seed.
"""
from __future__ import annotations

import math

import numpy as np

from bench import reference

RATE = "sim_rounds_per_s"       # the end-to-end rate the calls feed
MASK64 = (1 << 64) - 1


class Cell:
    """One co-simulator cell: ``config`` and ``traffic`` are the parsed
    JSON files of its deployment and its traffic mix."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config = config
        self.traffic = traffic
        self.seed = int(seed) & MASK64
        pon = config["pon"]
        self.n_pons = config["topology"]["n_pons"]
        self.n_clients = traffic.get(
            "n_clients",
            pon["n_onus"] * self.n_pons * traffic.get("clients_per_onu", 1))
        self.rank = np.random.default_rng(traffic["rank_seed"]).permutation(
            self.n_clients)
        self.n_rounds = traffic.get("n_rounds", 1)
        self.n_cases = (len(traffic["policies"]) * len(traffic["loads"])
                        * traffic["seeds_per_point"])
        self.rounds_per_call = self.n_cases * self.n_rounds
        self.calls = []           # (call, answers) of the window
        self.missing = 0          # rounds the window's calls lacked

    # -- generator -------------------------------------------------------

    def call(self, index: int) -> dict:
        """Plain inputs of call ``index`` (0 is the warm-up)."""
        rng = np.random.default_rng([self.seed, index])
        cl = self.config["clients"]
        lo, hi = cl["t_ud_s"]
        n = self.n_clients
        t_ud = np.empty(n)
        t_ud[self.rank] = lo + (hi - lo) * (np.arange(n)
                                            + rng.uniform(size=n)) / n
        clients = [(i, float(t_ud[i]), float(cl["m_ud_bits"]),
                    float(self.config["pon"]["distance_m"]))
                   for i in range(n)]
        seeds = rng.integers(0, 2**31, self.n_cases)
        grid = [(p, load) for p in self.traffic["policies"]
                for load in self.traffic["loads"]
                for _ in range(self.traffic["seeds_per_point"])]
        cases = [dict(policy=p, load=float(load), seed=int(s))
                 for (p, load), s in zip(grid, seeds)]
        return dict(index=index, clients=clients, cases=cases)

    def spec(self, call: dict):
        """The ``SweepSpec`` of a call, on the device backend."""
        from repro.core.slicing import ClientProfile
        from repro.net import (FLRoundWorkload, MultiPonTopology,
                               PONConfig, SweepCase, SweepSpec,
                               TimelineSchedule)

        cfg = self.config
        pon = PONConfig(**cfg["pon"])
        topo = None
        if self.n_pons > 1 or cfg["topology"].get("cps_rate_bps"):
            topo = MultiPonTopology(
                n_pons=self.n_pons,
                cps_rate_bps=cfg["topology"].get("cps_rate_bps"))
        wl = FLRoundWorkload(
            clients=[ClientProfile(client_id=c[0], t_ud=c[1], t_dl=0.0,
                                   m_ud_bits=c[2], distance_m=c[3])
                     for c in call["clients"]],
            model_bits=float(cfg["clients"]["model_bits"]),
            t_aggregate=float(cfg["clients"]["t_aggregate_s"]))
        cases = tuple(SweepCase(workload=wl, load=c["load"],
                                policy=c["policy"], seed=c["seed"],
                                topology=topo) for c in call["cases"])
        kw = dict(cases=cases, pon=pon, backend="jit",
                  t_round_hint=cfg["t_round_hint_s"], max_t=cfg["max_t_s"])
        if "n_rounds" in self.traffic:
            kw.update(schedule=TimelineSchedule(n_rounds=self.n_rounds),
                      mode=self.traffic["mode"])
        return SweepSpec(**kw)

    # -- the run ---------------------------------------------------------

    def inputs(self, index: int):
        """What the timed call of window call ``index`` takes."""
        call = self.call(index)
        return call, self.spec(call)

    def warmup(self) -> None:
        """One call of the cell's own shapes."""
        self.run(self.inputs(0))

    def run(self, inputs):
        """The timed call."""
        from repro.net import simulate

        return simulate(inputs[1])

    def record(self, inputs, out) -> int:
        """Keep a window call's answers for the check; its work, in
        simulated rounds."""
        answers = self.answers(out)
        self.missing += sum(a is None for case in answers for a in case)
        self.calls.append((inputs[0], answers))
        return self.rounds_per_call

    @staticmethod
    def counters() -> dict:
        from repro.kernels.ponsim import ops

        return {"host_fallbacks": sum(ops.host_fallbacks().values())}

    def answers(self, results):
        """The program's answer for each case and round of a call, as
        plain values; ``None`` where it returned no such round."""
        out = [[None] * self.n_rounds for _ in range(self.n_cases)]
        for j, res in enumerate(results[:self.n_cases]):
            if "n_rounds" not in self.traffic:
                out[j][0] = dict(dl_done=res.dl_done, ready=res.ready,
                                 ul_done=res.ul_done,
                                 sync_time=res.sync_time,
                                 ul_remaining=res.ul_remaining)
                continue
            for r, rnd in enumerate(res.rounds[:self.n_rounds]):
                if rnd.result is None:
                    continue
                out[j][r] = dict(
                    dl_done=rnd.result.dl_done, ready=rnd.result.ready,
                    ul_done=rnd.result.ul_done, sync_time=rnd.sync_time,
                    ul_remaining=rnd.result.ul_remaining,
                    span=rnd.t_end - rnd.t_start, arrived=rnd.arrived,
                    t_start=rnd.t_start,
                    t_prev_end=(res.rounds[r - 1].t_end if r else 0.0))
        return out

    # -- check -----------------------------------------------------------

    def sample(self, calls, rng):
        """(call, case, round) triples to check: for each entry of the
        mix's ``check`` list, one matching case of a call drawn from
        ``calls``, and a round drawn from its rounds."""
        out = []
        for want in self.traffic["check"]:
            call = calls[int(rng.integers(len(calls)))]
            match = [j for j, c in enumerate(call["cases"])
                     if all(c[k] == v for k, v in want.items())]
            j = match[int(rng.integers(len(match)))]
            out.append((call, j, int(rng.integers(self.n_rounds))))
        return out

    def reference(self, picks, dtype=np.float64):
        """Reference rounds of ``picks``, in order."""
        cfg = self.config
        rounds = [dict(clients=call["clients"],
                       model_bits=float(cfg["clients"]["model_bits"]),
                       t_aggregate=float(cfg["clients"]["t_aggregate_s"]),
                       load=call["cases"][j]["load"],
                       policy=call["cases"][j]["policy"],
                       seed=call["cases"][j]["seed"], stream_round=r)
                  for call, j, r in picks]
        return reference.simulate_rounds(
            cfg["pon"], cfg["topology"], rounds,
            t_round_hint=cfg["t_round_hint_s"], max_t=cfg["max_t_s"],
            dtype=dtype)

    def check(self, limits: dict):
        """``(checks, failed)`` once the window has closed: a sample of
        its rounds drawn from the seed against the reference, as
        ``{name: {"value", "limit"}}``, and the rounds that failed."""
        rng = np.random.default_rng([self.seed, 1 << 20])
        picks = self.sample([c for c, _ in self.calls], rng)
        by_index = {c["index"]: a for c, a in self.calls}
        refs = self.reference(picks)
        worst, bad, failed = 0.0, self.missing, self.missing
        for (call, j, r), ref in zip(picks, refs):
            w, b = compare(by_index[call["index"]][j][r], ref)
            worst = max(worst, w)
            bad += b
            failed += int(b > 0 or w > limits["worst_rel_gap"])
        checks = {
            "worst_rel_gap": {"value": worst,
                              "limit": limits["worst_rel_gap"]},
            "mismatches": {"value": bad, "limit": limits["mismatches"]},
        }
        return checks, failed


class Control(Cell):
    """The control of the check: the reference computed in float32, the
    precision below the configuration's float64, in the program's place
    for every case and round of each call."""

    def warmup(self) -> None:
        pass                      # nothing to compile

    def run(self, inputs):
        call = inputs[0]
        picks = [(call, j, r) for j in range(self.n_cases)
                 for r in range(self.n_rounds)]
        return self.reference(picks, dtype=np.float32)

    def answers(self, refs):
        out = []
        for j in range(self.n_cases):
            rounds = refs[j * self.n_rounds:(j + 1) * self.n_rounds]
            if "n_rounds" in self.traffic:
                rounds = [dict(r, span=r["sync_time"],
                               arrived=list(r["ul_done"]), t_start=0.0,
                               t_prev_end=0.0) for r in rounds]
            out.append(rounds)
        return out


def compare(got, ref) -> tuple:
    """``(worst relative gap, mismatches)`` of one round: the gap over
    every time both sides give, and a count of answers that one side
    lacks or that differ in kind (a client missing, a value NaN on one
    side only, a deferral or an arrival the reference does not have)."""
    if got is None:
        return 0.0, 1
    worst = 0.0
    bad = 0

    def gap(a, b):
        nonlocal worst, bad
        a, b = float(a), float(b)
        if math.isnan(a) or math.isnan(b):
            bad += int(math.isnan(a) != math.isnan(b))
            return
        if math.isinf(a) or math.isinf(b):
            bad += int(a != b)
            return
        worst = max(worst, abs(a - b) / max(abs(b), 1e-300))

    for name in ("dl_done", "ready", "ul_done"):
        g, r = got[name], ref[name]
        bad += len(set(g) ^ set(r))
        for cid in set(g) & set(r):
            gap(g[cid], r[cid])
    gap(got["sync_time"], ref["sync_time"])
    bad += int(bool(got.get("ul_remaining")))
    if "span" in got:
        gap(got["span"], ref["sync_time"])
        bad += int(sorted(got["arrived"]) != sorted(ref["ul_done"]))
        bad += int(got["t_start"] != got["t_prev_end"])
    return worst, bad
