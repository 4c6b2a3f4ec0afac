"""Observability (``repro.obs``): the no-perturbation contract + tools.

Four guarantees are pinned here:

* ``collector=None`` (the default everywhere) is *bitwise identical*
  to an uninstrumented build — including the Fig. 2b operating-point
  sync pin, every timeline deadline mode, and the multi-PON oracle;
* the streaming histogram (both the scattered ``add`` and the chunked
  ``add_block_per_row`` fast path) matches ``np.histogram`` counts
  exactly and ``np.percentile`` estimates within the bin width;
* Chrome traces round-trip through save/load/validate;
* ``launch.train --log-jsonl`` writes parseable structured events
  whose console lines are a formatted view of the same records.
"""
import json

import numpy as np
import pytest

from repro.core.slicing import ClientProfile
from repro.net import (
    FLRoundWorkload,
    MultiPonTopology,
    PONConfig,
    SweepCase,
    SweepSpec,
    TimelineSchedule,
    simulate,
)
from repro.net.multi_pon import simulate_multi_pon_round
from repro.obs import (
    Collector,
    EventLog,
    GaugeArray,
    SpanTracer,
    StreamingHistogram,
)
from repro.obs.trace import load_trace, validate_trace


def _op_point_case(policy="fcfs"):
    """The pinned Fig. 2b operating point (BENCH_net_engine.json)."""
    rng = np.random.default_rng(42)
    t_uds = rng.uniform(1.0, 5.0, 128)
    clients = [
        ClientProfile(client_id=i, t_ud=float(t_uds[i]), t_dl=0.0,
                      m_ud_bits=26.416e6)
        for i in range(12)
    ]
    wl = FLRoundWorkload(clients=clients, model_bits=26.416e6)
    return SweepCase(workload=wl, load=0.8, policy=policy, seed=1)


def _nan_safe(items):
    """NaN compares unequal to itself; map it to None for tuple
    equality (identity here means bit-identical or both-NaN)."""
    return tuple(
        (k, None if isinstance(v, float) and np.isnan(v) else v)
        for k, v in items
    )


def _fingerprint(res):
    """Everything a timeline result exposes, as comparable tuples."""
    out = []
    for rnd in res.rounds:
        out.append((
            rnd.result.sync_time,
            _nan_safe(sorted(rnd.result.ul_done.items())),
            tuple(sorted(rnd.ul_bits.items())),
            tuple(sorted(rnd.arrived)),
            tuple(sorted(rnd.deferred.items())),
            tuple(sorted(rnd.dropped)),
            tuple(sorted(rnd.partial.items())),
            tuple(sorted(rnd.staleness.items())),
        ))
    return out


class TestDisabledCollectorIdentity:
    def test_round_sweep_bitwise_and_pinned(self):
        cfg = PONConfig(n_onus=128)
        cases = [_op_point_case("fcfs"), _op_point_case("bs")]
        base = simulate(SweepSpec(cases=cases, pon=cfg))
        col = Collector(tracer=SpanTracer())
        inst = simulate(SweepSpec(cases=cases, pon=cfg), collector=col)
        for a, b in zip(base, inst):
            assert a.sync_time == b.sync_time
            assert a.dl_done == b.dl_done
            assert a.ul_done == b.ul_done
        # the PR 3/4 operating-point pin still holds on both paths
        assert base[0].sync_time == pytest.approx(5.058100000000024,
                                                  abs=1e-9)
        # and the enabled run actually collected
        assert len(col.phases) == 3
        assert ("fcfs", 0.8) in col.delay_hist

    @pytest.mark.parametrize("schedule", [
        TimelineSchedule(n_rounds=3),
        TimelineSchedule(n_rounds=3, deadline_s=4.0,
                         deadline_policy="drop"),
        TimelineSchedule(n_rounds=3, deadline_s=4.0,
                         deadline_policy="partial"),
        TimelineSchedule(n_rounds=3, deadline_s=4.0,
                         deadline_policy="defer"),
        TimelineSchedule(n_rounds=3, buffer_k=6),
    ], ids=["nodl", "drop", "partial", "defer", "async"])
    def test_timeline_bitwise(self, schedule):
        cfg = PONConfig(n_onus=128)
        cases = [_op_point_case("fcfs"), _op_point_case("bs")]
        base = simulate(SweepSpec(cases=cases, pon=cfg, schedule=schedule))
        on = simulate(SweepSpec(cases=cases, pon=cfg,
                                schedule=schedule), collector=Collector())
        for a, b in zip(base, on):
            assert np.array_equal(a.sync_times, b.sync_times)
            assert _fingerprint(a) == _fingerprint(b)

    def test_multi_pon_oracle_bitwise(self):
        # feasible CPS share (an overloaded shared uplink starves FL
        # behind prioritized background and runs the oracle to max_t)
        cfg = PONConfig(n_onus=4, line_rate_bps=1e9)
        topo = MultiPonTopology(n_pons=2, cps_rate_bps=15e9)
        rng = np.random.default_rng(5)
        clients = [
            ClientProfile(client_id=i, t_ud=float(rng.uniform(0.05, 0.3)),
                          t_dl=0.0, m_ud_bits=2e6)
            for i in range(6)
        ]
        wl = FLRoundWorkload(clients=clients, model_bits=2e6)
        base = simulate_multi_pon_round(cfg, topo, wl, 0.5, "fcfs",
                                        seed=3, max_t=5.0)
        col = Collector()
        inst = simulate_multi_pon_round(cfg, topo, wl, 0.5, "fcfs",
                                        seed=3, max_t=5.0, collector=col)
        assert base.sync_time == inst.sync_time
        assert base.ul_done == inst.ul_done
        assert col.counters["multi_pon.cps_want_bits"].total > 0.0


class TestStreamingHistogram:
    def test_counts_match_numpy(self):
        rng = np.random.default_rng(0)
        vals = rng.uniform(-1.0, 31.0, 5000)  # spills both edge bins
        edges = np.linspace(0.0, 30.0, 61)
        h = StreamingHistogram(edges)
        h.add(vals)
        ref, _ = np.histogram(vals, bins=edges)
        np.testing.assert_array_equal(h.counts[1:-1], ref)
        assert h.counts[0] == np.sum(vals < edges[0])
        assert h.counts[-1] == np.sum(vals > edges[-1])
        assert float(h.n) == vals.size
        assert float(h.sum) == pytest.approx(vals.sum(), rel=1e-12)

    def test_exact_edges_follow_numpy_convention(self):
        edges = np.array([0.0, 1.0, 2.0, 3.0])
        h = StreamingHistogram(edges)
        vals = np.array([0.0, 1.0, 2.0, 3.0])  # top edge -> last bin
        h.add(vals)
        ref, _ = np.histogram(vals, bins=edges)
        np.testing.assert_array_equal(h.counts[1:-1], ref)

    def test_percentiles_close_to_numpy(self):
        rng = np.random.default_rng(1)
        vals = rng.gamma(2.0, 2.0, 20000)
        edges = np.linspace(0.0, 30.0, 301)
        h = StreamingHistogram(edges)
        h.add(vals)
        width = float(edges[1] - edges[0])
        for q in (50.0, 95.0, 99.0):
            est = h.percentile(q)
            ref = np.percentile(vals, q)
            assert est == pytest.approx(ref, abs=width)
        s = h.summary()
        assert s["mean"] == pytest.approx(vals.mean(), rel=1e-6)
        assert s["min"] == pytest.approx(vals.min(), rel=1e-6)
        assert s["max"] == pytest.approx(vals.max(), rel=1e-6)

    def test_block_per_row_equals_scattered_add(self):
        rng = np.random.default_rng(2)
        C, B = 500, 7
        block = rng.uniform(0.0, 1.2, (C, B))  # overflow bin exercised
        edges = np.linspace(0.0, 1.0, 26)
        fast = StreamingHistogram(edges, (B,))
        fast.add_block_per_row(block)
        slow = StreamingHistogram(edges, (B,))
        rows = np.arange(B)
        for c in range(C):
            slow.add(block[c], rows=rows)
        np.testing.assert_array_equal(fast.counts, slow.counts)
        np.testing.assert_array_equal(fast.n, slow.n)
        np.testing.assert_allclose(fast.sum, slow.sum, rtol=1e-12)
        np.testing.assert_array_equal(fast.vmin, slow.vmin)
        np.testing.assert_array_equal(fast.vmax, slow.vmax)

    def test_merge_and_flat(self):
        edges = np.linspace(0.0, 1.0, 11)
        a = StreamingHistogram(edges)
        b = StreamingHistogram(edges)
        a.add([0.1, 0.2])
        b.add([0.8, 0.9])
        a.merge(b)
        assert float(a.n) == 4
        assert a.summary()["max"] == pytest.approx(0.9)

    def test_gauge_block_equals_sequential(self):
        rng = np.random.default_rng(3)
        block = rng.normal(size=(40, 5))
        g1, g2 = GaugeArray(5), GaugeArray(5)
        g1.observe_block(block)
        for row in block:
            g2.observe(row)
        for attr in ("last", "min", "max", "count"):
            np.testing.assert_array_equal(getattr(g1, attr),
                                          getattr(g2, attr))
        np.testing.assert_allclose(g1.sum, g2.sum, rtol=1e-12)


class TestTraceRoundTrip:
    def test_save_load_validate(self, tmp_path):
        tr = SpanTracer()
        with tr.span("outer", rows=4):
            with tr.span("inner"):
                pass
        path = str(tmp_path / "trace.json")
        tr.save(path)
        payload = load_trace(path)
        events = validate_trace(payload)
        names = {e["name"] for e in events}
        assert names == {"outer", "inner"}
        outer = next(e for e in events if e["name"] == "outer")
        assert outer["args"] == {"rows": 4}
        assert payload["displayTimeUnit"] == "ms"

    def test_validate_rejects_malformed(self):
        with pytest.raises(ValueError, match="traceEvents"):
            validate_trace({})
        with pytest.raises(ValueError, match="missing"):
            validate_trace({"traceEvents": [{"name": "x", "ph": "X"}]})

    def test_disabled_tracer_collects_nothing(self):
        tr = SpanTracer(enabled=False)
        with tr.span("ignored"):
            with tr.span("also ignored"):
                pass
        assert tr.events == []


class TestExport:
    def test_report_round_trip(self, tmp_path):
        col = Collector()
        col.record_upload_times("fcfs", 0.8, [1.0, 2.0, 3.0])
        col.record_staleness([0, 0, 2])
        col.counter("bits").add(42.0)
        col.record_round(round=0, sync_time=1.5)
        report = col.report()
        path = str(tmp_path / "summary.json")
        report.save_json(path)
        with open(path) as f:
            loaded = json.load(f)
        assert loaded["counters"]["bits"] == 42.0
        assert loaded["staleness"] == {"0": 2.0, "2": 1.0}
        assert loaded["delay_percentiles"]["fcfs@load0.8"]["n"] == 3.0
        assert loaded["rounds"] == [{"round": 0, "sync_time": 1.5}]
        # CSV artifact: header + one row per phase (none here)
        report.save_csv(str(tmp_path / "summary.csv"))

    def test_event_log_jsonl_and_echo(self, tmp_path, capsys):
        path = str(tmp_path / "events.jsonl")
        log = EventLog(jsonl_path=path)
        log.emit("step", echo="round {round} step {step}: loss={loss:.4f}",
                 round=1, step=0, loss=0.25)
        log.emit("round", round=1, loss=0.25)       # silent
        log.close()
        assert capsys.readouterr().out == "round 1 step 0: loss=0.2500\n"
        events = [json.loads(line) for line in open(path)]
        assert [e["event"] for e in events] == ["step", "round"]
        assert events[0]["loss"] == 0.25
        assert all("ts" in e for e in events)


@pytest.mark.slow
class TestTrainJsonlSmoke:
    def test_train_writes_structured_events(self, tmp_path):
        from repro.launch.train import train

        jsonl = str(tmp_path / "train.jsonl")
        trace = str(tmp_path / "train_trace.json")
        train(
            arch="olmo-1b", smoke=True, steps_per_round=1, rounds=1,
            n_pods=1, global_batch=2, seq_len=16,
            log_jsonl=jsonl, trace_path=trace,
        )
        events = [json.loads(line) for line in open(jsonl)]
        kinds = [e["event"] for e in events]
        for expected in ("mesh", "payload", "step", "round", "done",
                         "metrics"):
            assert expected in kinds, (expected, kinds)
        summary = events[kinds.index("metrics")]["summary"]
        assert "phases" in summary and "delay_percentiles" in summary
        validate_trace(load_trace(trace))
