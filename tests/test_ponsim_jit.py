"""``backend="jit"`` device cycle engine vs the numpy engine.

The jit backend (``repro.kernels.ponsim``) must reproduce the numpy
engine at rtol 1e-6 across {fcfs, bs} x {defer, drop, partial, async}
x multi-PON x faults on/off — the numpy engine itself is pinned to the
cycle-level reference oracles by the existing suites, so engine parity
chains the device program all the way down.  On top of parity:

* the fused in-scan sampler must be *bit-identical* to the host
  ``kernels.traffic`` streams (pinned fingerprint);
* one device program compiles per (mode, shape, flag) spec — re-running
  the same schedule shape must not retrace;
* importing ``repro.net`` / running a jit round must never flip the
  global ``jax_enable_x64`` flag (the backend scopes x64 locally);
* the Pallas waterfill kernel (interpret mode on CPU) must agree with
  the engine's sequential-grant semantics.
"""
import hashlib

import jax
import numpy as np
import pytest

from repro.core.slicing import ClientProfile
from repro.faults import FaultSchedule
from repro.kernels import ponsim
from repro.kernels.ponsim import ops as ponsim_ops
from repro.kernels.ponsim.kernel import waterfill_grants_pallas
from repro.kernels.traffic.ops import (
    _poisson_thresholds,
    _tail_bound,
    make_stream_key,
    sample_arrival_bits,
)
from repro.kernels.traffic.ref import WINDOW, geometric_tables
from repro.net import (
    FLRoundWorkload,
    PONConfig,
    SweepCase,
    SweepSpec,
    simulate,
)
from repro.net.engine import PACKET_BITS, _waterfill
from repro.net.multi_pon import MultiPonTopology
from repro.net.timeline import TimelineSchedule
from repro.net.traffic import burst_lambda

CFG = PONConfig(n_onus=4, line_rate_bps=1e9)
FAULTS = FaultSchedule(seed=3, dropout_rate=0.25, loss_rate=0.15,
                       outage_rate=0.5, outage_duration_s=0.1,
                       outage_start_max_s=0.5)


def _workload(ids, seed=1):
    rng = np.random.default_rng(seed)
    clients = [
        ClientProfile(client_id=int(i), t_ud=float(rng.uniform(0.05, 0.5)),
                      t_dl=0.0, m_ud_bits=float(rng.uniform(1e5, 2e6)))
        for i in ids
    ]
    return FLRoundWorkload(clients=clients, model_bits=1.5e6)


WL = _workload([0, 1, 2, 3])
WL_MULTI = _workload([0, 1, 2, 3, 5, 9])   # multi-client-per-ONU (fcfs)


def _dicts_close(a, b):
    assert set(a) == set(b)
    for k in a:
        assert np.isclose(b[k], a[k], rtol=1e-6, equal_nan=True), (
            f"[{k}]: numpy={a[k]} jit={b[k]}"
        )


def _assert_round_parity(a, b):
    assert np.isclose(b.sync_time, a.sync_time, rtol=1e-6, equal_nan=True)
    for name in ("dl_done", "ready", "ul_done"):
        _dicts_close(getattr(a, name), getattr(b, name))
    _dicts_close(a.ul_remaining or {}, b.ul_remaining or {})


def _assert_timeline_parity(a, b):
    for ra, rb in zip(a.rounds, b.rounds):
        for attr in ("sync_time", "t_start", "t_end"):
            assert np.isclose(getattr(rb, attr), getattr(ra, attr),
                              rtol=1e-6, equal_nan=True), attr
        assert set(ra.arrived) == set(rb.arrived)
        assert set(ra.lost) == set(rb.lost)
        assert set(ra.gave_up) == set(rb.gave_up)
        assert ra.quorum_met == rb.quorum_met
        assert ra.deadline_extensions == rb.deadline_extensions
        for attr in ("ul_bits", "deferred", "staleness", "dropped",
                     "partial", "failed", "retry_at"):
            _dicts_close(getattr(ra, attr), getattr(rb, attr))


class TestEngineParity:
    """``SweepSpec(backend="jit")`` vs the default numpy engine."""

    @pytest.mark.parametrize("policy,load", [
        ("fcfs", 0.2), ("fcfs", 0.6), ("fcfs", 0.9),
        ("bs", 0.2), ("bs", 0.9),
    ])
    def test_single_round(self, policy, load):
        wl = WL_MULTI if policy == "fcfs" else WL
        cases = [SweepCase(workload=wl, load=load, policy=policy, seed=7)]
        a = simulate(SweepSpec(cases=cases, pon=CFG))
        b = simulate(SweepSpec(cases=cases, pon=CFG, backend="jit"))
        _assert_round_parity(a[0], b[0])

    @pytest.mark.parametrize("policy", ["fcfs", "bs"])
    def test_deadline_and_outage(self, policy):
        wl = WL_MULTI if policy == "fcfs" else WL
        cases = [SweepCase(workload=wl, load=0.8, policy=policy, seed=3)]
        kw = dict(ul_deadline_s=[1.5], ul_outage_s=[(0.2, 0.6)])
        a = simulate(SweepSpec(cases=cases, pon=CFG, **kw))
        b = simulate(SweepSpec(cases=cases, pon=CFG, backend="jit", **kw))
        _assert_round_parity(a[0], b[0])

    @pytest.mark.parametrize("policy", ["fcfs", "bs"])
    def test_multi_pon_cps(self, policy):
        topo = MultiPonTopology(n_pons=3, cps_rate_bps=1.5e9)
        ids = [0, 3, 5, 8, 11] if policy == "fcfs" else [0, 2, 5, 7, 10]
        wl = _workload(ids, seed=2)
        outage = np.array([[0.1, 0.4], [0.0, 0.0], [0.2, 0.5]])
        cases = [SweepCase(workload=wl, load=0.3, policy=policy, seed=5,
                           topology=topo)]
        for kw in ({}, {"ul_deadline_s": [1.2], "ul_outage_s": [outage]}):
            a = simulate(SweepSpec(cases=cases, pon=CFG, **kw))
            b = simulate(SweepSpec(cases=cases, pon=CFG, backend="jit", **kw))
            _assert_round_parity(a[0], b[0])

    def test_mixed_batch(self):
        cases = [SweepCase(workload=WL_MULTI, load=l, policy="fcfs", seed=s)
                 for l in (0.3, 0.7) for s in (1, 2)]
        cases.append(SweepCase(workload=WL, load=0.5, policy="bs", seed=4))
        a = simulate(SweepSpec(cases=cases, pon=CFG))
        b = simulate(SweepSpec(cases=cases, pon=CFG, backend="jit"))
        for ra, rb in zip(a, b):
            _assert_round_parity(ra, rb)

    def test_simulate_round_backend(self):
        case = SweepCase(workload=WL, load=0.5, policy="fcfs", seed=9)
        a = simulate(SweepSpec(cases=(case,), pon=CFG))[0]
        b = simulate(SweepSpec(cases=(case,), pon=CFG, backend="jit"))[0]
        _assert_round_parity(a, b)

    def test_jit_rejects_injected_arrivals(self):
        dl = np.zeros((64, CFG.n_onus))
        cases = [SweepCase(workload=WL, load=0.3, policy="fcfs", seed=0,
                           dl_arrivals=dl, ul_arrivals=dl)]
        with pytest.raises(ValueError, match="jit"):
            simulate(SweepSpec(cases=cases, pon=CFG, backend="jit"))


class TestTimelineParity:
    """Timeline specs with ``backend="jit"`` across every mode."""

    @pytest.mark.parametrize("policy", ["fcfs", "bs"])
    @pytest.mark.parametrize("schedule", [
        TimelineSchedule(n_rounds=4),                                # folded
        TimelineSchedule(n_rounds=4, deadline_s=0.35),               # defer
        TimelineSchedule(n_rounds=4, deadline_s=0.35,
                         deadline_policy="drop"),
        TimelineSchedule(n_rounds=4, deadline_s=0.35,
                         deadline_policy="partial"),
        TimelineSchedule(n_rounds=3, buffer_k=2),                    # async
        TimelineSchedule(n_rounds=3, deadline_s=0.25,
                         quorum_frac=0.9),                           # quorum
        TimelineSchedule(n_rounds=4, deadline_s=0.5, faults=FAULTS),
    ], ids=["folded", "defer", "drop", "partial", "async", "quorum",
            "faults"])
    def test_modes(self, policy, schedule):
        cases = [SweepCase(workload=WL, load=0.4, policy=policy, seed=11)]
        a = simulate(SweepSpec(cases=cases, pon=CFG, schedule=schedule))
        b = simulate(SweepSpec(cases=cases, pon=CFG, schedule=schedule,
                               backend="jit"))
        _assert_timeline_parity(a[0], b[0])

    def test_multi_pon_timeline(self):
        topo = MultiPonTopology(n_pons=2, cps_rate_bps=1.2e9)
        wl = _workload([0, 2, 5, 7], seed=4)
        cases = [SweepCase(workload=wl, load=0.3, policy="fcfs", seed=6,
                           topology=topo)]
        schedule = TimelineSchedule(n_rounds=3, deadline_s=0.6,
                                    deadline_policy="drop")
        a = simulate(SweepSpec(cases=cases, pon=CFG, schedule=schedule))
        b = simulate(SweepSpec(cases=cases, pon=CFG, schedule=schedule,
                               backend="jit"))
        _assert_timeline_parity(a[0], b[0])


class TestFusedSampler:
    """The in-scan sampler is bit-identical to the host traffic streams."""

    def _stream_params(self):
        keys = np.stack([make_stream_key(7, 1, r, p)
                         for r in (0, 1) for p in (0, 2)])
        lam = burst_lambda(0.3 * 1e9 / 16, 1e-3, PACKET_BITS, 16.0)
        return keys, np.full((keys.shape[0],), lam, np.float32)

    def test_bit_identical_to_host(self):
        keys, lams = self._stream_params()
        n_onus = 16
        host = sample_arrival_bits(keys, 0, 4 * WINDOW, n_onus, lams,
                                   1.0 / 16.0, PACKET_BITS,
                                   backend="numpy")
        n_draws = _tail_bound(float(lams.max()) * WINDOW)
        thr = _poisson_thresholds(
            np.asarray(lams, np.float64) * WINDOW, n_draws)
        dev = np.concatenate([
            np.asarray(
                ponsim.sample_window_ref(
                    keys, thr, w, n_onus=n_onus, n_draws=n_draws,
                    burst_thr=geometric_tables(1.0 / 16.0)[1],
                    packet_bits=np.float32(PACKET_BITS)),
                np.float64)
            for w in range(4)
        ], axis=1)
        assert np.array_equal(dev, host)

    def test_pinned_fingerprint(self):
        # Bitwise regression of the exact stream the fused sampler (and
        # every host backend) must produce.  If this moves, every
        # multi-round result in the repo moves with it.
        keys, lams = self._stream_params()
        host = sample_arrival_bits(keys, 0, 4 * WINDOW, 16, lams,
                                   1.0 / 16.0, PACKET_BITS,
                                   backend="numpy")
        digest = hashlib.sha256(
            np.ascontiguousarray(host).tobytes()).hexdigest()
        assert digest == ("7df0b5fe7c7a5a214089bec8540252e0"
                          "8add05f7bce9f2c0ba49c770a693f3fe")
        assert host.sum() == 327768000.0


class TestCompileCaching:
    """One trace per (mode, shape, flags) spec; replays hit the cache."""

    def test_no_retrace_on_same_shape(self):
        ponsim_ops.clear_cache()
        cases = [SweepCase(workload=WL, load=0.5, policy="fcfs", seed=21)]
        simulate(SweepSpec(cases=cases, pon=CFG, backend="jit"))
        first = ponsim_ops.compile_count()
        assert first > 0
        # same spec (same shapes, same load hence same n_draws), new
        # seed: the stream keys are dynamic inputs — zero new traces
        cases2 = [SweepCase(workload=WL, load=0.5, policy="fcfs", seed=22)]
        simulate(SweepSpec(cases=cases2, pon=CFG, backend="jit"))
        assert ponsim_ops.compile_count() == first
        # new batch shape: retraces
        simulate(SweepSpec(cases=cases + cases2, pon=CFG, backend="jit"))
        assert ponsim_ops.compile_count() > first


class TestPrecisionPolicy:
    """The jit backend scopes x64 locally; the global flag never flips."""

    def test_global_x64_untouched(self):
        import repro.net  # noqa: F401

        assert jax.config.jax_enable_x64 is False
        cases = [SweepCase(workload=WL, load=0.5, policy="bs", seed=13)]
        res = simulate(SweepSpec(cases=cases, pon=CFG, backend="jit"))
        assert np.isfinite(res[0].sync_time)
        assert jax.config.jax_enable_x64 is False


class TestHostFallbacks:
    """Every device request the host engine serves is counted."""

    def test_ring_overflow_counted_and_exact(self, monkeypatch):
        # a 2-cycle prefix ring ages marginal bg heads out at load 0.9
        monkeypatch.setattr(ponsim_ops, "HISTORY_CYCLES", 2)
        ponsim_ops.clear_cache()
        try:
            before = ponsim_ops.host_fallbacks()["ring_overflow"]
            cases = [SweepCase(workload=WL, load=0.9, policy="fcfs",
                               seed=7)]
            a = simulate(SweepSpec(cases=cases, pon=CFG))
            b = simulate(SweepSpec(cases=cases, pon=CFG, backend="jit"))
            assert ponsim_ops.host_fallbacks()["ring_overflow"] > before
            _assert_round_parity(a[0], b[0])
        finally:
            ponsim_ops.clear_cache()

    def test_multi_job_sweep_counted(self):
        from repro.net import JobSpec

        jobs = (JobSpec(job_id=0, clients=(0, 1), model_bits=1.5e6),
                JobSpec(job_id=1, clients=(2, 3), model_bits=7.5e5))
        cases = [SweepCase(workload=WL, load=0.3, policy="fcfs", seed=2,
                           jobs=jobs)]
        before = ponsim_ops.host_fallbacks()["multi_job"]
        a = simulate(SweepSpec(cases=cases, pon=CFG))
        b = simulate(SweepSpec(cases=cases, pon=CFG, backend="jit"))
        assert ponsim_ops.host_fallbacks()["multi_job"] == before + 1
        assert a[0].sync_time == b[0].sync_time


class TestPallasWaterfillKernel:
    """Interpret-mode Pallas grant kernel vs the engine's numpy grants."""

    @pytest.mark.parametrize("policy,wl,load", [
        ("fcfs", WL_MULTI, 0.9), ("fcfs", WL, 0.9), ("bs", WL, 0.9),
    ], ids=["fcfs_many_per_onu", "fcfs_fast", "bs"])
    def test_engine_parity_with_kernel(self, monkeypatch, policy, wl, load):
        # the TPU path: phase programs built with the Pallas waterfill
        # (interpret mode here) still match the numpy engine
        import functools

        monkeypatch.setattr(ponsim_ops, "_PALLAS_INTERPRET", True)
        monkeypatch.setattr(
            ponsim_ops, "run_phase_device",
            functools.partial(ponsim_ops.run_phase_device, use_pallas=True))
        for seed in (0, 3):
            cases = [SweepCase(workload=wl, load=load, policy=policy,
                               seed=seed)]
            a = simulate(SweepSpec(cases=cases, pon=CFG))
            b = simulate(SweepSpec(cases=cases, pon=CFG, backend="jit"))
            _assert_round_parity(a[0], b[0])

    def test_matches_engine_waterfill(self):
        rng = np.random.default_rng(5)
        R, N = 4, 128
        backlog = np.where(rng.random((R, N)) < 0.6,
                           rng.uniform(0.0, 3e4, (R, N)), 0.0)
        key = np.where(backlog > 0,
                       rng.integers(0, 500, (R, N)).astype(np.float64),
                       np.inf)
        cap = np.array([1e4, 2e5, backlog[2].sum() + 10.0, 5.0])
        want = _waterfill(backlog, lambda: key, cap)
        g32 = np.asarray(waterfill_grants_pallas(
            backlog.astype(np.float32), key.astype(np.float32),
            cap.astype(np.float32), interpret=True), np.float64)
        # f32 kernel: full-drain lanes are exact, partial lanes are
        # f32-rounded — the engine restores f64 on full lanes, so check
        # the same contract here.
        full = want == backlog
        assert np.array_equal(g32 == backlog.astype(np.float32), full)
        assert np.allclose(g32, want, rtol=1e-4, atol=1.0)

    def test_full_rows_bitwise(self):
        rng = np.random.default_rng(6)
        R, N = 2, 128
        backlog = rng.uniform(0.0, 1e3, (R, N))
        key = rng.integers(0, 99, (R, N)).astype(np.float64)
        cap = backlog.sum(axis=1) + 100.0
        g32 = np.asarray(waterfill_grants_pallas(
            backlog.astype(np.float32), key.astype(np.float32),
            cap.astype(np.float32), interpret=True))
        assert np.array_equal(g32, backlog.astype(np.float32))


def _phase_digest(monkeypatch, run):
    """sha256 of the ``done_t`` and ``rem`` bytes of every phase the
    device engine returns while ``run()`` runs."""
    h = hashlib.sha256()
    real = ponsim_ops.run_phase_device

    def record(*args, **kw):
        out = real(*args, **kw)
        for a in out:
            h.update(np.ascontiguousarray(a).tobytes())
        return out

    monkeypatch.setattr(ponsim_ops, "run_phase_device", record)
    run()
    return h.hexdigest()


class TestBsPinned:
    """Bitwise pins of bs phase outputs, taken while the slot grants
    still reached the ONUs through a scatter-add.  In both runs the CPS
    uplink cuts the slot grants, so the regrant is pinned too."""

    def test_multi_pon_cps_sweep(self, monkeypatch):
        topo = MultiPonTopology(n_pons=3, cps_rate_bps=0.6e9)
        cases = [SweepCase(workload=_workload([0, 2, 5, 7, 10], seed=2),
                           load=0.3, policy="bs", seed=5, topology=topo)]
        spec = SweepSpec(cases=tuple(cases), pon=CFG, backend="jit")
        digest = _phase_digest(monkeypatch, lambda: simulate(spec))
        assert digest == ("e5b3fce6dd9606cf10b32857549ac7fb"
                          "dda59298ab1dde827b32fc93e3e472c1")

    def test_folded_timeline(self, monkeypatch):
        topo = MultiPonTopology(n_pons=2, cps_rate_bps=0.5e9)
        cases = [SweepCase(workload=_workload(range(8), seed=4), load=0.4,
                           policy="bs", seed=11, topology=topo)]
        spec = SweepSpec(cases=tuple(cases), pon=CFG, backend="jit",
                         schedule=TimelineSchedule(n_rounds=4),
                         mode="folded")
        digest = _phase_digest(monkeypatch, lambda: simulate(spec))
        assert digest == ("90fba85cfb495dfea39fa6b8d73883da"
                          "f56bfc9e452ca0a49798a7b0a705f786")


# slot layouts (ONU of each slot, valid) of slot fan-in K = 1, 2, 3, on
# 6 ONUs: padding slots are invalid and name ONU 0, and some rows hold
# no valid slot at all
SLOT_LAYOUTS = {
    1: ([[3, 0, 5, 1, 0, 0], [0, 0, 0, 0, 0, 0], [2, 4, 0, 1, 0, 0]],
        [[1, 1, 1, 1, 0, 0], [0, 0, 0, 0, 0, 0], [1, 1, 1, 0, 0, 0]]),
    2: ([[1, 3, 1, 0, 0, 0], [0, 5, 0, 2, 4, 0], [0, 0, 0, 0, 0, 0]],
        [[1, 1, 1, 0, 0, 0], [1, 1, 1, 1, 1, 0], [0, 0, 0, 0, 0, 0]]),
    3: ([[4, 4, 2, 4, 0, 0], [0, 1, 0, 1, 0, 3], [0, 0, 0, 0, 0, 0]],
        [[1, 1, 1, 1, 0, 0], [1, 1, 1, 1, 0, 1], [0, 0, 0, 0, 0, 0]]),
}
N_SLOT_ONUS = 6


class TestSlotGrantMap:
    """The bs slot-to-ONU grant map is ``np.add.at`` to the bit."""

    @pytest.mark.parametrize("fanin", sorted(SLOT_LAYOUTS))
    def test_table(self, fanin):
        sonu, valid = (np.asarray(a) for a in SLOT_LAYOUTS[fanin])
        table = ponsim_ops.slot_of_onu(sonu, valid.astype(bool),
                                       N_SLOT_ONUS)
        R, S = sonu.shape
        assert table.dtype == np.int32
        assert table.shape == (R, N_SLOT_ONUS, fanin)
        for r in range(R):
            for o in range(N_SLOT_ONUS):
                mine = [s for s in range(S) if valid[r, s] and sonu[r, s] == o]
                assert list(table[r, o]) == mine + [S] * (fanin - len(mine))

    @pytest.mark.parametrize("fanin", sorted(SLOT_LAYOUTS))
    def test_map_bitwise_equals_add_at(self, fanin):
        sonu, valid = (np.asarray(a) for a in SLOT_LAYOUTS[fanin])
        valid = valid.astype(bool)
        table = ponsim_ops.slot_of_onu(sonu, valid, N_SLOT_ONUS)
        rows = np.broadcast_to(np.arange(sonu.shape[0])[:, None], sonu.shape)
        mapped = jax.jit(ponsim_ops.grants_per_onu)
        rng = np.random.default_rng(fanin)
        for _ in range(50):
            # magnitudes 1e-3..1e17, so the order of the adds shows; some
            # valid slots grant exactly 0.0, as inactive slots do, some
            # -0.0, which an add from +0.0 turns into +0.0
            g = 10.0 ** rng.uniform(-3.0, 17.0, sonu.shape)
            zero = np.where(valid & (rng.random(sonu.shape) < 0.5), -0.0, 0.0)
            g = np.where(valid & (rng.random(sonu.shape) < 0.85), g, zero)
            want = np.zeros((sonu.shape[0], N_SLOT_ONUS))
            np.add.at(want, (rows, sonu), g)
            with jax.enable_x64(True):
                got = np.asarray(mapped(g, table))
            assert got.dtype == np.float64
            assert np.array_equal(got.view(np.int64), want.view(np.int64))

    @pytest.mark.parametrize("fanin", sorted(SLOT_LAYOUTS))
    def test_lookup_bitwise_equals_take_along_axis(self, fanin):
        """The ONU-to-slot lookup of the slot wants: each slot reads its
        ONU's backlog, valid and invalid (padding) slots alike."""
        sonu = np.asarray(SLOT_LAYOUTS[fanin][0], np.int32)
        lookup = jax.jit(ponsim_ops.select_columns)
        rng = np.random.default_rng(fanin)
        for _ in range(50):
            b = 10.0 ** rng.uniform(-3.0, 17.0, (sonu.shape[0], N_SLOT_ONUS))
            b = np.where(rng.random(b.shape) < 0.85, b, 0.0)
            want = np.take_along_axis(b, sonu, axis=1)
            with jax.enable_x64(True):
                got = np.asarray(lookup(b, sonu))
            assert got.dtype == np.float64
            assert np.array_equal(got.view(np.int64), want.view(np.int64))


class TestSharedOnuBs:
    """bs with several clients per ONU (slot fan-in K >= 2) on the device
    engine against the numpy engine.  The public entry points refuse bs
    with a client id of n_onus * n_pons or more, so the phase is built
    from the engine's own layout and slot tables."""

    @pytest.mark.parametrize("n_pons,cps_rate,fanin", [
        (1, None, 3),       # ONU 1 holds clients 1, 5 and 9
        (2, 0.6e9, 2),      # PON 0's ONU 1 holds clients 1 and 9
    ], ids=["single_pon", "multi_pon_cps"])
    def test_phase_parity(self, n_pons, cps_rate, fanin):
        from repro.net.engine import (_bs_slice, _Layout, _run_phase,
                                      _stack_slots)

        n, cyc = CFG.n_onus, CFG.cycle_time_s
        lay = _Layout([SweepCase(workload=WL_MULTI, load=0.0, policy="bs",
                                 seed=0)], n, n_pons)
        rate = CFG.line_rate_bps * CFG.efficiency
        per_row = [_bs_slice([c for c in WL_MULTI.clients
                              if c.client_id % (n * n_pons) // n == p],
                             rate) for p in range(n_pons)]
        kw = dict(slot_arrays=_stack_slots(per_row, n), max_t=600.0,
                  cap_row=np.full(n_pons, rate * cyc), n_pons=n_pons,
                  cps_cap=None if cps_rate is None else cps_rate * cyc)
        rem0 = np.where(lay.part, lay.m_ud, 0.0)
        ready = np.where(lay.part, lay.t_ud, np.inf)
        before = ponsim_ops.slot_fanin()
        dev = ponsim_ops.run_phase_device(CFG, lay, rem0, ready, "bs", **kw)
        after = ponsim_ops.slot_fanin()
        host = _run_phase(CFG, lay, rem0, ready, None, "bs", **kw)
        assert {k: v - before.get(k, 0) for k, v in after.items()
                if v != before.get(k, 0)} == {fanin: 1}
        for d, h in zip(dev, host):
            assert np.allclose(d, h, rtol=1e-6, equal_nan=True)
        if cps_rate is not None:
            # the CPS cuts: without it the phase ends otherwise
            free = _run_phase(CFG, lay, rem0, ready, None, "bs",
                              **{**kw, "cps_cap": None})
            assert not np.allclose(free[0], host[0], rtol=1e-6,
                                   equal_nan=True)

    def test_one_client_per_onu_is_fanin_1(self):
        before = ponsim_ops.slot_fanin()
        simulate(SweepSpec(cases=(SweepCase(workload=WL, load=0.4,
                                            policy="bs", seed=1),),
                           pon=CFG, backend="jit"))
        after = ponsim_ops.slot_fanin()
        assert {k: v - before.get(k, 0) for k, v in after.items()
                if v != before.get(k, 0)} == {1: 1}
