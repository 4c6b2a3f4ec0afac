"""Array-native metrics: counters, gauges, streaming histograms.

The engine computes per-cycle queue depths, grant totals and waterfill
residuals and (until now) threw them away.  This module provides
accumulators that live as batched numpy array state — shaped ``(B, …)``
so they fold under the engine's batch/round/PON row axes exactly like
``_BgQueues`` does — and are updated with a handful of vectorized
reductions per cycle (no per-row Python loops, no host round-trips
beyond the arrays the engine already holds).

Building blocks:

* ``CounterArray`` — monotone additive totals, ``(B, …)`` float64;
* ``GaugeArray`` — last/min/max/sum/count of an observed series
  (mean = sum/count), same shapes;
* ``StreamingHistogram`` — fixed-edge counts with underflow/overflow
  bins, exact ``n``/``sum``/``min``/``max`` sidecars, mergeable, with
  percentile estimation by linear interpolation inside bins (clamped
  to the exact observed min/max so tail percentiles of a single spike
  do not leak outside the data range).

``Collector`` is the config-and-state object the simulation stack
threads through (``repro.net.simulate``/``CoSimConfig``): it owns the
histograms (FL upload delay, deadline slack, per-cycle utilization),
named counters/gauges, per-phase engine accumulators (``PhaseStats``)
and a span tracer.  The strict contract
everywhere it is accepted: ``collector=None`` (the default) leaves
every output bitwise identical — metrics observe, never perturb.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

__all__ = [
    "CounterArray",
    "GaugeArray",
    "StreamingHistogram",
    "PhaseStats",
    "Collector",
    "DEFAULT_DELAY_EDGES",
    "DEFAULT_UTIL_EDGES",
]

# upload delays: 0.1 s bins to 30 s (the engine's default max_t region
# of interest); utilization: 0..1 in 4% steps. Fixed edges keep the
# accumulators mergeable across phases/rounds/processes.
DEFAULT_DELAY_EDGES = np.round(np.linspace(0.0, 30.0, 301), 6)
DEFAULT_UTIL_EDGES = np.round(np.linspace(0.0, 1.0, 26), 6)


class CounterArray:
    """Monotone additive totals, optionally batched ``(B, …)``."""

    def __init__(self, shape=()):
        self.value = np.zeros(shape, np.float64)

    def add(self, x) -> None:
        np.add(self.value, x, out=self.value)

    @property
    def total(self) -> float:
        return float(np.sum(self.value))


class GaugeArray:
    """Summary of an observed series: last/min/max/sum/count."""

    def __init__(self, shape=()):
        self.last = np.zeros(shape, np.float64)
        self.min = np.full(shape, np.inf)
        self.max = np.full(shape, -np.inf)
        self.sum = np.zeros(shape, np.float64)
        self.count = np.zeros(shape, np.int64)

    def observe(self, x) -> None:
        x = np.asarray(x, np.float64)
        self.last = np.broadcast_to(x, self.last.shape).copy() \
            if x.shape != self.last.shape else x.copy()
        np.minimum(self.min, x, out=self.min)
        np.maximum(self.max, x, out=self.max)
        np.add(self.sum, x, out=self.sum)
        self.count += 1

    def observe_block(self, block: np.ndarray) -> None:
        """Fold ``(C, …)`` stacked observations (C per-cycle rows) in
        one shot — the chunked path ``PhaseStats`` flushes through."""
        block = np.asarray(block, np.float64)
        self.last = block[-1].copy()
        np.minimum(self.min, block.min(axis=0), out=self.min)
        np.maximum(self.max, block.max(axis=0), out=self.max)
        np.add(self.sum, block.sum(axis=0), out=self.sum)
        self.count += block.shape[0]

    @property
    def mean(self) -> np.ndarray:
        return self.sum / np.maximum(self.count, 1)

    def summary(self) -> dict:
        n = int(np.max(self.count)) if self.count.size else 0
        if n == 0:
            return {"count": 0}
        return {
            "count": n,
            "mean": float(np.mean(self.mean)),
            "min": float(np.min(self.min)),
            "max": float(np.max(self.max)),
            "last": float(np.mean(self.last)),
        }


class StreamingHistogram:
    """Fixed-edge streaming histogram with under/overflow bins.

    ``edges`` (strictly increasing, length ``E``) define ``E - 1``
    interior bins; ``counts`` has length ``E + 1`` where slot 0 holds
    values ``< edges[0]`` and slot ``E`` values ``> edges[-1]``
    (value ``v`` lands in ``searchsorted(edges, v, side="left")`` with
    exact-edge values going to the bin they close, matching
    ``np.histogram``'s half-open convention on the interior).  With a
    ``batch_shape`` the counts are ``(B, …, E + 1)`` and ``add`` takes
    matching leading row indices — the engine updates every sweep row
    in one call.
    """

    def __init__(self, edges: Sequence[float], batch_shape=()):
        edges = np.asarray(edges, np.float64)
        if edges.ndim != 1 or edges.size < 2:
            raise ValueError("edges must be a 1-D array of >= 2 values")
        if np.any(np.diff(edges) <= 0):
            raise ValueError("edges must be strictly increasing")
        self.edges = edges
        shape = tuple(batch_shape) + (edges.size + 1,)
        self.counts = np.zeros(shape, np.float64)
        lead = tuple(batch_shape)
        self.n = np.zeros(lead, np.float64)
        self.sum = np.zeros(lead, np.float64)
        self.vmin = np.full(lead, np.inf)
        self.vmax = np.full(lead, -np.inf)

    def _bin(self, values: np.ndarray) -> np.ndarray:
        # np.histogram's convention: [e_i, e_{i+1}) half-open, last bin
        # closed. side="right" maps e_i -> bin i+1; shift interior by 1
        # so slot 0 is the underflow and exact top-edge values stay in
        # the last interior bin.
        idx = np.searchsorted(self.edges, values, side="right")
        idx = np.where(values == self.edges[-1], self.edges.size - 1, idx)
        return idx

    def add(self, values, weights=None, rows=None) -> None:
        """Accumulate ``values`` (any shape).

        ``rows``: optional integer row indices (same shape as values)
        selecting the leading batch row each value belongs to; without
        it all values land in the (un-batched) histogram.
        """
        values = np.asarray(values, np.float64).ravel()
        if values.size == 0:
            return
        w = (np.ones_like(values) if weights is None
             else np.asarray(weights, np.float64).ravel())
        idx = self._bin(values)
        if rows is None:
            np.add.at(self.counts, idx, w)
            self.n += w.sum()
            self.sum += float((values * w).sum())
            self.vmin = np.minimum(self.vmin, values.min())
            self.vmax = np.maximum(self.vmax, values.max())
        else:
            rows = np.asarray(rows, np.int64).ravel()
            np.add.at(self.counts, (rows, idx), w)
            np.add.at(self.n, rows, w)
            np.add.at(self.sum, rows, values * w)
            np.minimum.at(self.vmin, rows, values)
            np.maximum.at(self.vmax, rows, values)

    def add_block_per_row(self, block: np.ndarray) -> None:
        """Accumulate a ``(C, B)`` block: one value per batch row per
        cycle, for all ``C`` cycles at once.

        Equivalent to ``C`` calls to ``add(block[c], rows=arange(B))``
        but with a single ``bincount`` instead of per-cycle scattered
        ``ufunc.at`` updates — the fast path ``PhaseStats`` flushes
        its per-cycle utilization samples through.
        """
        block = np.asarray(block, np.float64)
        if block.size == 0:
            return
        C, B = block.shape
        nbins = self.edges.size + 1
        idx = self._bin(block)
        flat = idx + np.arange(B) * nbins        # offset per batch row
        self.counts += np.bincount(
            flat.ravel(), minlength=B * nbins
        ).reshape(B, nbins)
        self.n += C
        self.sum += block.sum(axis=0)
        np.minimum(self.vmin, block.min(axis=0), out=self.vmin)
        np.maximum(self.vmax, block.max(axis=0), out=self.vmax)

    def merge(self, other: "StreamingHistogram") -> None:
        if not np.array_equal(self.edges, other.edges):
            raise ValueError("cannot merge histograms with differing edges")
        self.counts += other.counts
        self.n += other.n
        self.sum += other.sum
        self.vmin = np.minimum(self.vmin, other.vmin)
        self.vmax = np.maximum(self.vmax, other.vmax)

    def flat(self) -> "StreamingHistogram":
        """Batch axes collapsed into one histogram."""
        out = StreamingHistogram(self.edges)
        out.counts = self.counts.reshape(-1, self.counts.shape[-1]) \
            .sum(axis=0)
        out.n = np.asarray(float(np.sum(self.n)))
        out.sum = np.asarray(float(np.sum(self.sum)))
        out.vmin = np.asarray(float(np.min(self.vmin)))
        out.vmax = np.asarray(float(np.max(self.vmax)))
        return out

    def percentile(self, q) -> np.ndarray:
        """Percentile estimate(s) by linear interpolation inside bins.

        Under/overflow mass is pinned to the exact observed min/max
        (the only honest value available outside the edge range).
        Batched histograms return ``(…,) + q.shape`` arrays.
        """
        qs = np.atleast_1d(np.asarray(q, np.float64))
        counts = self.counts.reshape(-1, self.counts.shape[-1])
        n = np.asarray(self.n, np.float64).reshape(-1)
        vmin = np.asarray(self.vmin, np.float64).reshape(-1)
        vmax = np.asarray(self.vmax, np.float64).reshape(-1)
        E = self.edges.size
        # bin supports: underflow/overflow collapse onto observed extremes
        lo = np.concatenate(([0.0], self.edges))
        hi = np.concatenate((self.edges, [0.0]))
        out = np.full((counts.shape[0], qs.size), np.nan)
        for b in range(counts.shape[0]):
            if n[b] <= 0:
                continue
            c = counts[b]
            cum = np.cumsum(c)
            targets = qs / 100.0 * n[b]
            idx = np.searchsorted(cum, targets, side="left")
            idx = np.minimum(idx, E)
            prev = np.where(idx > 0, cum[idx - 1], 0.0)
            width = np.where(c[idx] > 0, (targets - prev) / c[idx], 0.0)
            b_lo = lo[idx].copy()
            b_hi = hi[idx].copy()
            # edge bins: the observed extremes bound the support
            b_lo[idx == 0] = vmin[b]
            b_hi[idx == 0] = min(self.edges[0], vmax[b])
            b_hi[idx == E] = vmax[b]
            b_lo[idx == E] = max(self.edges[-1], vmin[b])
            est = b_lo + width * (b_hi - b_lo)
            out[b] = np.clip(est, vmin[b], vmax[b])
        shape = np.shape(self.n) + qs.shape
        out = out.reshape(shape)
        return out if np.ndim(q) or np.shape(self.n) else float(out[0])

    def summary(self, percentiles=(50.0, 95.0, 99.0)) -> dict:
        h = self.flat() if np.shape(self.n) else self
        n = float(h.n)
        out = {"n": n, "edges": [float(h.edges[0]), float(h.edges[-1])],
               "bins": int(h.edges.size - 1)}
        if n > 0:
            out.update({
                "mean": float(h.sum) / n,
                "min": float(h.vmin),
                "max": float(h.vmax),
            })
            for q, v in zip(percentiles, np.atleast_1d(
                    h.percentile(list(percentiles)))):
                out[f"p{q:g}"] = float(v)
        return out


class PhaseStats:
    """Per-phase engine accumulators over the ``(B,)`` row axis.

    One instance per ``_run_phase`` call; every field folds under the
    engine's row layout (rows are sweep cells, or ``(case, pon)`` pairs
    under a topology, or ``(case, round)`` pairs in the folded
    timeline).  ``cycle(...)`` is called once per polling cycle with
    the arrays the engine already computed; to keep the enabled-
    collector overhead inside the CI budget it only *buffers* the
    references (the engine never mutates them in place — every capture
    is a fresh reduction or a never-written array) and the actual
    sums/min/max/histogram folds run once per ``_CHUNK`` cycles over a
    stacked ``(C, B)`` block.  ``summary()`` flushes the tail.
    """

    _CHUNK = 1024        # cycles buffered between vectorized folds

    def __init__(self, label: str, n_rows: int,
                 util_edges: np.ndarray = DEFAULT_UTIL_EDGES):
        self.label = label
        self.n_rows = n_rows
        self.cycles = np.zeros(n_rows, np.int64)
        self.cap_bits = CounterArray(n_rows)          # offered capacity
        self.bg_backlog = GaugeArray(n_rows)          # per-cycle bg depth
        self.fl_backlog = GaugeArray(n_rows)          # per-cycle FL depth
        self.bg_grant_bits = CounterArray(n_rows)
        self.fl_grant_bits = CounterArray(n_rows)
        self.residual_bits = CounterArray(n_rows)     # unused capacity
        self.util = StreamingHistogram(util_edges, (n_rows,))
        self.cps_want_bits = CounterArray(n_rows)     # CPS demand (row)
        self.cps_eff_bits = CounterArray(n_rows)      # CPS share granted
        self._buf: list = []
        self._zero = np.zeros(n_rows)

    def cycle(self, cap, bg_backlog=None, fl_backlog=None,
              bg_grants=None, fl_grants=None,
              cps_want=None, cps_eff=None) -> None:
        self._buf.append((cap, bg_backlog, fl_backlog, bg_grants,
                          fl_grants, cps_want, cps_eff))
        if len(self._buf) >= self._CHUNK:
            self._flush()

    def _flush(self) -> None:
        if not self._buf:
            return
        B = self.n_rows
        buf = self._buf
        # np.array over a list of same-shape 1-D arrays is a single
        # C-level pass — much cheaper than np.stack's per-item
        # expand_dims (the engine always passes (B,) rows; scalar caps
        # only show up through direct API use)
        caps = np.array([t[0] for t in buf], np.float64)
        if caps.ndim == 1:
            caps = np.repeat(caps[:, None], B, axis=1)
        C = caps.shape[0]
        self.cycles += C
        self.cap_bits.add(caps.sum(axis=0))

        def gather(i):
            vals = [t[i] for t in buf if t[i] is not None]
            return np.array(vals, np.float64) if vals else None

        bgd, fld = gather(1), gather(2)
        if bgd is not None:
            self.bg_backlog.observe_block(bgd)
        if fld is not None:
            self.fl_backlog.observe_block(fld)
        bg_g = np.array([t[3] if t[3] is not None else self._zero
                         for t in buf])
        fl_g = np.array([t[4] if t[4] is not None else self._zero
                         for t in buf])
        self.bg_grant_bits.add(bg_g.sum(axis=0))
        self.fl_grant_bits.add(fl_g.sum(axis=0))
        granted = bg_g + fl_g
        self.residual_bits.add(np.maximum(caps - granted, 0.0).sum(axis=0))
        util = np.divide(granted, caps, out=np.zeros_like(granted),
                         where=caps > 0)
        self.util.add_block_per_row(util)
        cw, ce = gather(5), gather(6)
        if cw is not None:
            self.cps_want_bits.add(cw.sum(axis=0))
        if ce is not None:
            self.cps_eff_bits.add(ce.sum(axis=0))
        self._buf.clear()

    def summary(self) -> dict:
        self._flush()
        cap = self.cap_bits.total
        grant = self.bg_grant_bits.total + self.fl_grant_bits.total
        cps_w = self.cps_want_bits.total
        out = {
            "label": self.label,
            "rows": self.n_rows,
            "cycles": int(self.cycles.max()) if self.n_rows else 0,
            "cap_bits": cap,
            "bg_grant_bits": self.bg_grant_bits.total,
            "fl_grant_bits": self.fl_grant_bits.total,
            "residual_bits": self.residual_bits.total,
            "grant_utilization": grant / cap if cap > 0 else 0.0,
            "bg_backlog": self.bg_backlog.summary(),
            "fl_backlog": self.fl_backlog.summary(),
            "util_hist": self.util.summary(),
        }
        if cps_w > 0:
            out["cps_want_bits"] = cps_w
            out["cps_eff_bits"] = self.cps_eff_bits.total
            out["cps_utilization"] = self.cps_eff_bits.total / cps_w
        return out


class DevicePhase:
    """A phase the device engine ran (``backend="jit"``): its rows and
    the cycles its loop ran.  The per-cycle fields of
    :class:`PhaseStats` exist on the numpy engine only, so a device
    phase's summary leaves them out rather than reading zero."""

    def __init__(self, label: str, n_rows: int, cycles: int):
        self.label = label
        self.n_rows = n_rows
        self.cycles = int(cycles)

    def summary(self) -> dict:
        return {"label": self.label, "rows": self.n_rows,
                "cycles": self.cycles}


class Collector:
    """The observability hub threaded through the co-sim stack.

    Passing a ``Collector`` to ``repro.net.simulate`` /
    ``FLNetworkCoSim.run`` / ``launch.train`` turns collection on;
    ``None`` (the default everywhere) is the strict no-op whose outputs
    are bitwise identical to a build without this module.

    Collected state:

    * ``phases`` — per-``_run_phase`` ``PhaseStats`` (cycle counts,
      backlog depths, grant utilization, waterfill residuals, CPS
      want/eff per row); a phase on the device engine records a
      ``DevicePhase`` (rows and loop cycles only);
    * ``delay_hist[(policy, load)]`` — FL upload completion-time
      histograms (round-relative seconds);
    * ``slack_hist[(policy, load)]`` — deadline slack (deadline −
      completion) of arrived clients under deadline schedules;
    * ``staleness`` — counts per staleness value τ across rounds;
    * ``counters``/``gauges`` — named scalars (CPS bits, payload bits);
    * ``rounds``/``events`` — per-round and free-form event dicts
      (round wall time, arrived/dropped counts, payload bits);
    * ``tracer`` — a span tracer (``repro.obs.trace.SpanTracer``); the
      default is disabled (spans are no-ops) unless one is passed in.
    """

    def __init__(self,
                 delay_edges: Sequence[float] = DEFAULT_DELAY_EDGES,
                 util_edges: Sequence[float] = DEFAULT_UTIL_EDGES,
                 slack_edges: Optional[Sequence[float]] = None,
                 tracer=None,
                 keep_phases: bool = True):
        from repro.obs.trace import SpanTracer

        self.delay_edges = np.asarray(delay_edges, np.float64)
        self.util_edges = np.asarray(util_edges, np.float64)
        self.slack_edges = (self.delay_edges - self.delay_edges[-1] / 2
                            if slack_edges is None
                            else np.asarray(slack_edges, np.float64))
        self.tracer = tracer if tracer is not None else SpanTracer(
            enabled=False
        )
        self.keep_phases = keep_phases
        self.phases: List[PhaseStats] = []
        self.delay_hist: Dict[tuple, StreamingHistogram] = {}
        self.slack_hist: Dict[tuple, StreamingHistogram] = {}
        self.staleness: Dict[int, float] = {}
        self.counters: Dict[str, CounterArray] = {}
        self.gauges: Dict[str, GaugeArray] = {}
        self.rounds: List[dict] = []
        self.events: List[dict] = []

    # -- engine hooks -----------------------------------------------------

    def phase(self, label: str, n_rows: int) -> PhaseStats:
        st = PhaseStats(label, n_rows, self.util_edges)
        if self.keep_phases:
            self.phases.append(st)
        return st

    def device_phase(self, label: str, n_rows: int, cycles: int) -> None:
        if self.keep_phases:
            self.phases.append(DevicePhase(label, n_rows, cycles))

    def record_upload_times(self, policy: str, load: float,
                            times) -> None:
        times = np.asarray(times, np.float64)
        times = times[np.isfinite(times)]
        if times.size == 0:
            return
        key = (policy, round(float(load), 6))
        hist = self.delay_hist.get(key)
        if hist is None:
            hist = self.delay_hist[key] = StreamingHistogram(
                self.delay_edges
            )
        hist.add(times)

    def record_slack(self, policy: str, load: float, slack) -> None:
        slack = np.asarray(slack, np.float64)
        slack = slack[np.isfinite(slack)]
        if slack.size == 0:
            return
        key = (policy, round(float(load), 6))
        hist = self.slack_hist.get(key)
        if hist is None:
            hist = self.slack_hist[key] = StreamingHistogram(
                self.slack_edges
            )
        hist.add(slack)

    def record_staleness(self, taus) -> None:
        for t in np.atleast_1d(np.asarray(taus, np.int64)).ravel():
            t = int(t)
            self.staleness[t] = self.staleness.get(t, 0.0) + 1.0

    # -- generic named metrics -------------------------------------------

    def counter(self, name: str, shape=()) -> CounterArray:
        c = self.counters.get(name)
        if c is None:
            c = self.counters[name] = CounterArray(shape)
        return c

    def gauge(self, name: str, shape=()) -> GaugeArray:
        g = self.gauges.get(name)
        if g is None:
            g = self.gauges[name] = GaugeArray(shape)
        return g

    # -- event streams ----------------------------------------------------

    def record_round(self, **fields) -> None:
        self.rounds.append(dict(fields))

    def event(self, kind: str, **fields) -> None:
        self.events.append({"kind": kind, **fields})

    # -- reporting ---------------------------------------------------------

    def report(self):
        """Fold everything into a serialisable ``MetricsReport``."""
        from repro.obs.export import MetricsReport

        return MetricsReport.from_collector(self)
