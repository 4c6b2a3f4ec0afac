"""Fault injection: stream determinism, engine/oracle parity, recovery.

Four contracts are pinned here:

* the counter-based fault streams (``repro.faults.streams``) are
  chunk-invariant, O(1)-seekable and bit-stable (pinned fingerprints);
* faults off — ``faults=None`` and a trivial ``FaultSchedule`` — is
  bitwise identical across every timeline mode, including the Fig. 2b
  operating-point sync pin;
* with faults on, the batched engine matches the cycle-level reference
  oracle at rtol 1e-6 across dropout/outage/loss x {fcfs, bs} x
  {defer, drop, partial, async} x multi-PON, *including* the fault
  bookkeeping (failed/lost/retry_at/gave_up/quorum verdicts);
* the recovery machinery behaves: retry-with-backoff suppresses fresh
  membership entry while backing off (the satellite-2 invariant),
  quorum aggregation extends-then-degrades, and a killed
  ``launch/train`` co-sim resumes to bitwise-identical final params.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.slicing import ClientProfile
from repro.faults import FaultSchedule, RetryPolicy
from repro.faults.streams import (
    FAULT_DROPOUT,
    FAULT_LOSS,
    FAULT_OUTAGE,
    fault_fingerprint,
    fault_key,
    fault_uniforms,
)
from repro.net import (
    FLRoundWorkload,
    MultiPonTopology,
    PONConfig,
    SweepCase,
    SweepSpec,
    TimelineSchedule,
    simulate,
    simulate_timeline_reference,
)
from repro.net.timeline import _RetryEntry, _round_setup

CFG = PONConfig(n_onus=8, line_rate_bps=1e9)

# rates chosen so every fault class fires within a handful of rounds
FAULTS = FaultSchedule(seed=3, dropout_rate=0.25, loss_rate=0.15,
                       outage_rate=0.5, outage_duration_s=0.1,
                       outage_start_max_s=0.5)


def _clients(ids, seed=0, m_lo=1e5, m_hi=2e6):
    rng = np.random.default_rng(seed)
    return [
        ClientProfile(client_id=int(i),
                      t_ud=float(rng.uniform(0.05, 0.6)), t_dl=0.0,
                      m_ud_bits=float(rng.uniform(m_lo, m_hi)))
        for i in ids
    ]


def _wl(policy, seed=0):
    ids = range(6) if policy == "bs" else [0, 1, 5, 9, 17, 19]
    return FLRoundWorkload(clients=_clients(ids, seed), model_bits=1.5e6)


def _assert_equal(a, b, rtol=1e-6):
    for ra, rb in zip(a, b):
        assert np.allclose(ra.sync_times, rb.sync_times, rtol=rtol), (
            f"sync {ra.sync_times} vs {rb.sync_times}"
        )
        for x, y in zip(ra.rounds, rb.rounds):
            assert x.arrived == y.arrived
            assert x.staleness == y.staleness
            assert sorted(x.lost) == sorted(y.lost)
            assert sorted(x.gave_up) == sorted(y.gave_up)
            assert x.retry_at == y.retry_at
            assert x.quorum_met == y.quorum_met
            assert x.deadline_extensions == y.deadline_extensions
            assert set(x.failed) == set(y.failed)
            for cid, v in x.failed.items():
                assert v == pytest.approx(y.failed[cid], rel=rtol,
                                          abs=2.0)
            for name in ("ul_bits", "deferred", "dropped", "partial"):
                xd, yd = getattr(x, name), getattr(y, name)
                assert set(xd) == set(yd), (x.round_index, name)
                for cid, v in xd.items():
                    assert v == pytest.approx(yd[cid], rel=rtol, abs=2.0)


# ---------------------------------------------------------------------------
# counter-based streams
# ---------------------------------------------------------------------------


class TestFaultStreams:
    CLASSES = (FAULT_DROPOUT, FAULT_OUTAGE, FAULT_LOSS)

    def test_chunk_invariance(self):
        """One batched draw == per-entity draws, bit for bit."""
        ids = np.arange(24)
        for cls in self.CLASSES:
            b0, b1 = fault_uniforms(3, cls, 2, ids, case_seed=5)
            for i in ids:
                s0, s1 = fault_uniforms(3, cls, 2, int(i), case_seed=5)
                assert s0 == b0[i] and s1 == b1[i]

    def test_seekable_any_order(self):
        """Round r's draws don't depend on which rounds were drawn
        before (no sequential RNG state)."""
        fwd = [fault_uniforms(1, FAULT_DROPOUT, r, 7)[0]
               for r in range(6)]
        rev = [fault_uniforms(1, FAULT_DROPOUT, r, 7)[0]
               for r in reversed(range(6))]
        assert fwd == rev[::-1]

    def test_pinned_fingerprints(self):
        """Exact stream bits — any change to keying or the threefry
        core is a determinism break, not a refactor."""
        pins = {
            (FAULT_DROPOUT, 0, 0): 0x4B14B5901A556C85,
            (FAULT_DROPOUT, 5, 7): 0x5379E8E3DA420974,
            (FAULT_OUTAGE, 0, 0): 0x770188B2C65163C8,
            (FAULT_OUTAGE, 5, 7): 0x4C4DA1B9F892DE6E,
            (FAULT_LOSS, 0, 0): 0x94778675CC2AA9A1,
            (FAULT_LOSS, 5, 7): 0xC0FAF1B1D2B640CD,
        }
        for (cls, r, case), want in pins.items():
            assert fault_fingerprint(3, cls, r, 16, case_seed=case) == want

    def test_streams_distinct_per_class_and_case(self):
        keys = {fault_key(3, cls, case)
                for cls in self.CLASSES for case in (0, 1, 7)}
        assert len(keys) == len(self.CLASSES) * 3

    def test_uniforms_open_interval(self):
        u0, u1 = fault_uniforms(0, FAULT_LOSS, 0, np.arange(4096))
        for u in (u0, u1):
            assert np.all(u > 0.0) and np.all(u < 1.0)


class TestFaultScheduleModel:
    def test_rate_zero_never_fires_rate_one_always(self):
        ids = list(range(32))
        never = FaultSchedule(seed=0)
        assert never.dropouts(0, ids) == {}
        assert never.losses(0, ids) == frozenset()
        assert np.all(np.isinf(never.outage_windows(0, 4)))
        always = FaultSchedule(seed=0, dropout_rate=1.0, loss_rate=1.0,
                               outage_rate=1.0)
        assert set(always.dropouts(0, ids)) == set(ids)
        assert always.losses(0, ids) == frozenset(ids)
        assert np.all(np.isfinite(always.outage_windows(0, 4)))

    def test_trivial_and_couples_rounds(self):
        assert FaultSchedule().trivial
        assert not FaultSchedule().couples_rounds
        assert not FaultSchedule(outage_rate=0.5).trivial
        assert not FaultSchedule(outage_rate=0.5).couples_rounds
        assert FaultSchedule(dropout_rate=0.1).couples_rounds
        assert FaultSchedule(loss_rate=0.1).couples_rounds

    def test_outage_window_shape(self):
        w = FaultSchedule(seed=1, outage_rate=1.0, outage_duration_s=0.2,
                          outage_start_max_s=0.5).outage_windows(3, 5)
        assert w.shape == (5, 2)
        assert np.all(w[:, 0] >= 0.0) and np.all(w[:, 0] <= 0.5)
        assert np.allclose(w[:, 1] - w[:, 0], 0.2)

    def test_validation(self):
        with pytest.raises(ValueError):
            FaultSchedule(dropout_rate=1.5)
        with pytest.raises(ValueError):
            FaultSchedule(outage_duration_s=0.0)
        with pytest.raises(ValueError):
            FaultSchedule(outage_start_max_s=-1.0)

    def test_retry_policy(self):
        p = RetryPolicy()
        assert [p.delay_rounds(a) for a in (1, 2, 3)] == [1, 2, 4]
        assert RetryPolicy(base_delay_rounds=2, backoff=1.0
                           ).delay_rounds(3) == 2
        with pytest.raises(ValueError):
            RetryPolicy(base_delay_rounds=0)
        with pytest.raises(ValueError):
            RetryPolicy(backoff=0.5)
        with pytest.raises(ValueError):
            RetryPolicy(max_retries=-1)


# ---------------------------------------------------------------------------
# faults off == no faults, bitwise
# ---------------------------------------------------------------------------


class TestFaultsOffBitwise:
    SCHEDS = (
        dict(n_rounds=3),
        dict(n_rounds=3, deadline_s=0.35, deadline_policy="defer"),
        dict(n_rounds=3, deadline_s=0.35, deadline_policy="drop"),
        dict(n_rounds=3, deadline_s=0.35, deadline_policy="partial"),
        dict(n_rounds=3, buffer_k=3),
    )

    def test_trivial_schedule_bitwise_identical(self):
        cases = [SweepCase(workload=_wl("fcfs"), load=0.6,
                           policy="fcfs", seed=5)]
        for kw in self.SCHEDS:
            off = simulate(SweepSpec(cases=cases, pon=CFG,
                                     schedule=TimelineSchedule(**kw)))
            triv = simulate(SweepSpec(
                cases=cases, pon=CFG,
                schedule=TimelineSchedule(faults=FaultSchedule(), **kw),
            ))
            for a, b in zip(off, triv):
                assert np.array_equal(a.sync_times, b.sync_times)
                for x, y in zip(a.rounds, b.rounds):
                    assert x.ul_bits == y.ul_bits
                    assert x.arrived == y.arrived
                    assert x.failed == {} and y.failed == {}

    def test_operating_point_pin_with_trivial_faults(self):
        """The Fig. 2b 0.8-load pin survives a wired-but-all-zero
        FaultSchedule bit for bit."""
        rng = np.random.default_rng(42)
        t_uds = rng.uniform(1.0, 5.0, 128)
        clients = [
            ClientProfile(client_id=i, t_ud=float(t_uds[i]), t_dl=0.0,
                          m_ud_bits=26.416e6)
            for i in range(12)
        ]
        wl = FLRoundWorkload(clients=clients, model_bits=26.416e6)
        case = SweepCase(workload=wl, load=0.8, policy="fcfs", seed=1)
        for sched in (
            TimelineSchedule(n_rounds=1, faults=FaultSchedule()),
            TimelineSchedule(n_rounds=1, deadline_s=30.0,
                             deadline_policy="drop",
                             faults=FaultSchedule(seed=9)),
        ):
            res = simulate(SweepSpec(cases=[case], pon=PONConfig(n_onus=128),
                                     schedule=sched))[0]
            assert res.rounds[0].sync_time == pytest.approx(
                5.058100000000024, abs=1e-9
            )


# ---------------------------------------------------------------------------
# fault-enabled engine vs cycle-level reference oracle
# ---------------------------------------------------------------------------


class TestFaultParityVsOracle:
    @pytest.mark.parametrize("policy", ["fcfs", "bs"])
    @pytest.mark.parametrize("sched_kw", [
        dict(n_rounds=5, deadline_s=0.4, deadline_policy="defer"),
        dict(n_rounds=5, deadline_s=0.35, deadline_policy="drop"),
        dict(n_rounds=5, deadline_s=0.35, deadline_policy="partial"),
        dict(n_rounds=5, buffer_k=3),
    ], ids=["defer", "drop", "partial", "async"])
    def test_modes(self, policy, sched_kw):
        sched = TimelineSchedule(faults=FAULTS, **sched_kw)
        cases = [SweepCase(workload=_wl(policy), load=0.6,
                           policy=policy, seed=5)]
        eng = simulate(SweepSpec(cases=cases, pon=CFG, schedule=sched))
        ref = simulate_timeline_reference(CFG, cases, sched)
        assert sum(len(r.failed) + len(r.lost)
                   for r in eng[0].rounds) > 0, (
            "rates chosen to actually fire"
        )
        _assert_equal(eng, ref)

    @pytest.mark.parametrize("policy", ["fcfs", "bs"])
    def test_multi_pon(self, policy):
        topo = MultiPonTopology(n_pons=2, cps_rate_bps=1.8e9)
        ids = range(12) if policy == "bs" else [0, 1, 5, 9, 12, 14]
        wl = FLRoundWorkload(clients=_clients(ids), model_bits=1.5e6)
        faults = FaultSchedule(seed=7, dropout_rate=0.25, loss_rate=0.15,
                               outage_rate=0.5, outage_duration_s=0.1,
                               outage_start_max_s=0.5)
        cases = [SweepCase(workload=wl, load=0.4, policy=policy,
                           seed=5, topology=topo)]
        for sched in (
            TimelineSchedule(n_rounds=4, deadline_s=0.4, faults=faults),
            TimelineSchedule(n_rounds=4, buffer_k=3, faults=faults),
        ):
            _assert_equal(
                simulate(SweepSpec(cases=cases, pon=CFG, schedule=sched)),
                simulate_timeline_reference(CFG, cases, sched),
            )

    def test_outage_only_folded_matches_sequential(self):
        """Outage masks capacity but cancels nothing, so outage-only
        schedules stay fold-legal — all three drivers agree."""
        faults = FaultSchedule(seed=3, outage_rate=1.0,
                               outage_duration_s=0.2,
                               outage_start_max_s=0.0)
        sched = TimelineSchedule(n_rounds=4, faults=faults)
        cases = [SweepCase(workload=_wl("fcfs"), load=0.6,
                           policy="fcfs", seed=5)]
        base = simulate(SweepSpec(cases=cases, pon=CFG,
                                  schedule=TimelineSchedule(n_rounds=4)))
        fold = simulate(SweepSpec(cases=cases, pon=CFG, schedule=sched,
                                  mode="folded"))
        seq = simulate(SweepSpec(cases=cases, pon=CFG, schedule=sched,
                                 mode="sequential"))
        ref = simulate_timeline_reference(CFG, cases, sched)
        assert not np.array_equal(base[0].sync_times,
                                  fold[0].sync_times), (
            "outage rate chosen to actually slow a round"
        )
        _assert_equal(fold, seq, rtol=1e-12)
        _assert_equal(fold, ref)

    def test_coupling_faults_reject_folded(self):
        sched = TimelineSchedule(n_rounds=2, faults=FAULTS)
        cases = [SweepCase(workload=_wl("fcfs"), load=0.6,
                           policy="fcfs", seed=5)]
        with pytest.raises(ValueError, match="folded"):
            simulate(SweepSpec(cases=cases, pon=CFG, schedule=sched,
                               mode="folded"))


# ---------------------------------------------------------------------------
# retry-with-backoff rescheduling (satellite 2 regression included)
# ---------------------------------------------------------------------------


class TestRetrySemantics:
    def _run(self, retry=None, n_rounds=6, faults=None):
        sched = TimelineSchedule(
            n_rounds=n_rounds, deadline_s=0.4, deadline_policy="drop",
            faults=faults or FaultSchedule(seed=3, dropout_rate=0.35),
            retry=retry,
        )
        cases = [SweepCase(workload=_wl("fcfs"), load=0.6,
                           policy="fcfs", seed=5)]
        return simulate(SweepSpec(cases=cases, pon=CFG, schedule=sched))[0]

    def test_retry_due_rounds_follow_backoff(self):
        res = self._run()
        delays = RetryPolicy()
        booked = 0
        for r in res.rounds:
            for cid, due in r.retry_at.items():
                booked += 1
                gaps = [r.round_index + delays.delay_rounds(a)
                        for a in (1, 2, 3)]
                assert due in gaps, (cid, due, gaps)
        assert booked > 0, "dropout rate chosen to book retries"

    def test_backoff_suppresses_membership_reentry(self):
        """Satellite-2 invariant: while a client is backing off, the
        (implicit all-ones) membership mask must NOT re-admit it as a
        fresh member — it is absent from every round before its due
        round, then re-enters exactly once."""
        res = self._run(retry=RetryPolicy(base_delay_rounds=2))
        checked = 0
        for r in res.rounds:
            for cid, due in r.retry_at.items():
                for mid in res.rounds[r.round_index + 1:due]:
                    checked += 1
                    assert cid not in mid.ul_bits, (
                        f"client {cid} revived at round "
                        f"{mid.round_index} while backing off until "
                        f"{due}"
                    )
                if due < len(res.rounds):
                    assert cid in res.rounds[due].ul_bits
        assert checked > 0, "need a backoff window inside the horizon"

    def test_retry_resends_full_payload(self):
        """The retry re-sends the failure round's pre-truncation
        pending bits — under the drop policy every entry is full, so
        a completed retry serves the whole payload even though the
        failure round only wasted a fragment (``rnd.failed``)."""
        wl = _wl("fcfs")
        m_ud = {c.client_id: c.m_ud_bits for c in wl.clients}
        res = self._run()
        completed = 0
        for r in res.rounds:
            for cid, due in r.retry_at.items():
                assert r.failed[cid] <= m_ud[cid] + 2.0
                if due < len(res.rounds):
                    rr = res.rounds[due]
                    if cid in rr.arrived:
                        completed += 1
                        assert rr.ul_bits[cid] == pytest.approx(
                            m_ud[cid], rel=1e-9, abs=2.0)
        assert completed > 0, "need at least one completed retry"

    def test_max_retries_zero_gives_up_immediately(self):
        res = self._run(retry=RetryPolicy(max_retries=0))
        gave = sum(len(r.gave_up) for r in res.rounds)
        assert gave > 0
        assert all(r.retry_at == {} for r in res.rounds)

    def test_carry_and_retry_overlap_is_a_hard_error(self):
        """A cid in both the deferred carry and the retry table means
        the bookkeeping desynced; _round_setup must refuse."""
        case = SweepCase(workload=_wl("fcfs"), load=0.6,
                         policy="fcfs", seed=5)
        sched = TimelineSchedule(n_rounds=2)
        with pytest.raises(RuntimeError, match="both a deferred"):
            _round_setup(case, sched, 1, {1: 1000.0},
                         {1: _RetryEntry(1, 500.0, 1)})


# ---------------------------------------------------------------------------
# quorum aggregation: timeline extension + fl/dist commit gates
# ---------------------------------------------------------------------------


class TestQuorumTimeline:
    def _sched(self, **kw):
        base = dict(n_rounds=5, deadline_s=0.12,
                    deadline_policy="drop",
                    faults=FaultSchedule(seed=3, dropout_rate=0.25),
                    quorum_frac=0.75)
        base.update(kw)
        return TimelineSchedule(**base)

    def test_engine_matches_reference(self):
        cases = [SweepCase(workload=_wl("fcfs"), load=0.6,
                           policy="fcfs", seed=5)]
        sched = self._sched()
        eng = simulate(SweepSpec(cases=cases, pon=CFG, schedule=sched))
        ref = simulate_timeline_reference(CFG, cases, sched)
        assert sum(r.deadline_extensions for r in eng[0].rounds) > 0, (
            "deadline chosen tight enough to force extensions"
        )
        _assert_equal(eng, ref)

    def test_extension_doubles_until_met_or_degrades(self):
        res = simulate(SweepSpec(
            cases=[SweepCase(workload=_wl("fcfs"), load=0.6,
                             policy="fcfs", seed=5)],
            pon=CFG, schedule=self._sched(),
        ))[0]
        for r in res.rounds:
            assert r.quorum_met is not None
            assert 0 <= r.deadline_extensions <= 2
            if r.quorum_met:
                # enough un-faulted arrivals relative to what entered
                assert len(r.arrived) >= 1
            else:
                assert r.deadline_extensions == 2, (
                    "an unmet round must have used every extension"
                )

    def test_validation(self):
        with pytest.raises(ValueError, match="quorum_frac"):
            TimelineSchedule(n_rounds=1, deadline_s=1.0,
                             quorum_frac=1.5)
        with pytest.raises(ValueError, match="deadline"):
            TimelineSchedule(n_rounds=1, quorum_frac=0.5)
        with pytest.raises(ValueError, match="quorum"):
            TimelineSchedule(n_rounds=1, buffer_k=2, quorum_frac=0.5)
        with pytest.raises(ValueError):
            TimelineSchedule(n_rounds=1, deadline_s=1.0,
                             quorum_frac=0.5, quorum_max_extends=-1)


class TestQuorumAggregation:
    def test_threshold(self):
        from repro.fl.aggregation import quorum_threshold

        assert quorum_threshold(8, 0.5) == 4
        assert quorum_threshold(8, 0.51) == 5
        assert quorum_threshold(8, 1.0) == 8
        assert quorum_threshold(0, 0.5) == 1   # never commit on zero
        with pytest.raises(ValueError):
            quorum_threshold(8, 0.0)
        with pytest.raises(ValueError):
            quorum_threshold(-1, 0.5)

    def test_commit_degrades_below_quorum(self):
        from repro.fl.aggregation import quorum_commit

        g = {"w": np.ones(3, np.float32)}
        deltas = [{"w": np.full(3, 0.5, np.float32)}]
        out, ok = quorum_commit(g, deltas, [1.0], n_expected=4,
                                quorum_frac=0.5)
        assert not ok and out is g     # untouched, same object
        out, ok = quorum_commit(g, deltas * 2, [1.0, 1.0],
                                n_expected=4, quorum_frac=0.5)
        assert ok
        assert np.allclose(out["w"], 1.5)

    def test_server_apply_updates_quorum(self):
        from repro.fl.server import CPSServer, PendingUpdate

        g = {"w": np.zeros(2, np.float32)}
        srv = CPSServer(global_params=g, clients=[])
        upd = PendingUpdate(client_id=0,
                            delta={"w": np.ones(2, np.float32)},
                            weight=1.0, loss=0.1, bits=8.0)
        log = srv.apply_updates([(upd, 0, 1.0)], n_expected=3,
                                quorum_frac=0.5)   # need ceil(1.5) = 2
        assert log.quorum_met is False
        assert np.allclose(srv.global_params["w"], 0.0)
        log = srv.apply_updates([(upd, 0, 1.0), (upd, 0, 1.0)],
                                n_expected=3, quorum_frac=0.5)
        assert log.quorum_met is True
        assert not np.allclose(srv.global_params["w"], 0.0)
        with pytest.raises(ValueError, match="n_expected"):
            srv.apply_updates([(upd, 0, 1.0)], quorum_frac=0.5)

    def test_fedbuff_pods_quorum_gate(self):
        import jax.numpy as jnp

        from repro.dist.fedops import fedbuff_pods

        n = 2
        pending = {"w": jnp.ones((n, 3), jnp.float32)}
        g = {"w": jnp.zeros((n, 3), jnp.float32)}
        weights = jnp.ones(n)
        stale = jnp.zeros(n)
        one = jnp.array([True, False])
        met = fedbuff_pods(pending, g, weights, one, stale,
                           quorum_frac=0.5)
        assert float(jnp.abs(met["w"]).sum()) > 0.0
        degraded = fedbuff_pods(pending, g, weights, one, stale,
                                quorum_frac=1.0)
        assert float(jnp.abs(degraded["w"]).sum()) == 0.0
        # n_expected overrides the pod count
        degraded2 = fedbuff_pods(pending, g, weights, one, stale,
                                 quorum_frac=0.5, n_expected=4)
        assert float(jnp.abs(degraded2["w"]).sum()) == 0.0


# ---------------------------------------------------------------------------
# satellite 1: per-baseline-file gate coverage (benchmarks/compare.py)
# ---------------------------------------------------------------------------


class TestCompareCoverage:
    def _mod(self):
        sys.path.insert(0, os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        from benchmarks import compare
        return compare

    def test_uncovered_file_flagged_with_its_keys(self):
        compare = self._mod()
        errs = compare.check_baseline_coverage(
            {"BENCH_a.json": {"a.rounds_per_sec": 1.0},
             "BENCH_b.json": {"b.rounds_per_sec": 2.0}},
            {"a.rounds_per_sec": 1.0},
        )
        assert len(errs) == 1
        assert "BENCH_b.json" in errs[0]
        assert "b.rounds_per_sec" in errs[0]

    def test_covered_and_empty_files_pass(self):
        compare = self._mod()
        assert compare.check_baseline_coverage(
            {"BENCH_a.json": {"a.rounds_per_sec": 1.0},
             "BENCH_empty.json": {}},
            {"a.rounds_per_sec": 1.0},
        ) == []

    def test_main_exits_2_on_uncovered_baseline(self, tmp_path):
        compare = self._mod()
        cur = tmp_path / "cur.json"
        base_ok = tmp_path / "base_ok.json"
        base_orphan = tmp_path / "base_orphan.json"
        payload = {"benchmark": "fault_injection_grid", "cells": [
            {"mode": "sync", "dropout_rate": 0.2, "outage_rate": 0.5,
             "rounds_per_sec": 2.0},
        ]}
        cur.write_text(json.dumps(payload))
        base_ok.write_text(json.dumps(payload))
        orphan = {"rows": [{"name": "phantom",
                            "derived": "rounds_per_sec=9.9"}]}
        base_orphan.write_text(json.dumps(orphan))
        assert compare.main(["--current", str(cur),
                             "--baseline", str(base_ok)]) == 0
        assert compare.main(["--current", str(cur),
                             "--baseline", str(base_ok),
                             str(base_orphan)]) == 2

    def test_fault_grid_payload_metrics(self):
        compare = self._mod()
        payload = {"benchmark": "fault_injection_grid", "cells": [
            {"mode": "quorum", "dropout_rate": 0.2, "outage_rate": 0.5,
             "rounds_per_sec": 1.25},
        ]}
        assert compare.extract_metrics(payload) == {
            "fault_grid_quorum_d20_o50.rounds_per_sec": 1.25
        }


# ---------------------------------------------------------------------------
# crash/resume of a long co-sim (launch/train --resume)
# ---------------------------------------------------------------------------


_RESUME_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import shutil
    import numpy as np, jax
    from repro.launch.train import train

    base = os.environ["RESUME_TMP"]
    d1, d2 = os.path.join(base, "full"), os.path.join(base, "resumed")
    kw = dict(arch="olmo-1b", smoke=True, steps_per_round=2, rounds=3,
              n_pods=2, global_batch=4, seq_len=16, deadline_s=2.0,
              deadline_policy="defer", dropout_rate=0.4, loss_rate=0.2,
              outage_rate=0.5, fault_seed=3, quorum=0.5)
    sa, _ = train(ckpt_dir=d1, resume=False, **kw)
    # emulate a mid-timeline crash after round 2: only that round's
    # checkpoint survives into a fresh directory
    os.makedirs(d2)
    shutil.copy(os.path.join(d1, "step_2.ckpt"), d2)
    sb, _ = train(ckpt_dir=d2, resume=True, **kw)
    la, lb = jax.tree.leaves(sa), jax.tree.leaves(sb)
    assert len(la) == len(lb)
    assert all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb)), "resume diverged from the "
    print("RESUME_BITWISE_OK")
""")


@pytest.mark.slow
class TestCrashResume:
    def test_resume_reproduces_uninterrupted_run(self, tmp_path):
        """Kill-after-round-2 + ``--resume`` must land on bitwise the
        same final params as the uninterrupted run (faults + quorum
        active, coupled async state checkpointed alongside train
        state)."""
        env = dict(os.environ)
        env["RESUME_TMP"] = str(tmp_path)
        env["PYTHONPATH"] = os.pathsep.join(filter(None, [
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "src"),
            env.get("PYTHONPATH", ""),
        ]))
        out = subprocess.run(
            [sys.executable, "-c", _RESUME_SCRIPT],
            capture_output=True, text=True, env=env, timeout=900,
        )
        assert out.returncode == 0, out.stderr[-4000:]
        assert "RESUME_BITWISE_OK" in out.stdout
