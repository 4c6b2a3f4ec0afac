"""Export sinks: JSONL event logs, CSV/JSON summaries, MetricsReport.

Three consumers share these writers:

* ``launch.train`` / ``launch.serve`` — structured JSONL round/step
  events (``--log-jsonl``), with the legacy console lines kept as a
  *formatted view* of the same events (``EventLog``);
* benchmarks — ``MetricsReport`` summaries written as JSON + CSV
  artifacts next to the BENCH payloads;
* tests — round-trip the formats.

Every event is one JSON object per line with at least ``event`` and
``ts`` (unix seconds) keys; numeric values stay numbers so downstream
``jq``/pandas need no coercion.
"""
from __future__ import annotations

import csv
import json
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

__all__ = [
    "JsonlSink",
    "EventLog",
    "MetricsReport",
    "write_summary_json",
    "write_summary_csv",
]


def _jsonable(v):
    import numpy as np

    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    if isinstance(v, np.ndarray):
        return v.tolist()
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    return v


class JsonlSink:
    """Append-only JSON-lines event sink (one object per line)."""

    def __init__(self, path: str):
        self.path = path
        self._f = open(path, "a")
        self.n_events = 0

    def emit(self, event: dict) -> None:
        self._f.write(json.dumps(_jsonable(event), sort_keys=True))
        self._f.write("\n")
        self._f.flush()
        self.n_events += 1

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class EventLog:
    """Structured events with the console as a formatted view.

    ``emit("round", echo="round {round}: loss={loss:.4f}", round=3,
    loss=0.1)`` writes the full event to the JSONL sink (when one is
    attached) and prints the ``echo`` format string — so the CLI output
    stays exactly what it always was while every line gains a
    machine-readable twin.  ``echo=None`` logs silently.
    """

    def __init__(self, jsonl_path: Optional[str] = None,
                 console: bool = True, clock=time.time):
        self.sink = JsonlSink(jsonl_path) if jsonl_path else None
        self.console = console
        self._clock = clock

    def emit(self, event: str, echo: Optional[str] = None,
             **fields) -> None:
        if self.sink is not None:
            self.sink.emit({"event": event, "ts": self._clock(),
                            **fields})
        if self.console and echo is not None:
            print(echo.format(**fields), flush=True)

    def close(self) -> None:
        if self.sink is not None:
            self.sink.close()


def write_summary_json(path: str, payload: dict) -> None:
    with open(path, "w") as f:
        json.dump(_jsonable(payload), f, indent=2, sort_keys=True)
        f.write("\n")


def write_summary_csv(path: str, rows: List[dict]) -> None:
    """Rows of flat dicts -> CSV with the union of keys as header."""
    keys: List[str] = []
    for row in rows:
        for k in row:
            if k not in keys:
                keys.append(k)
    with open(path, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=keys)
        w.writeheader()
        for row in rows:
            w.writerow({k: _jsonable(row.get(k, "")) for k in keys})


@dataclass
class MetricsReport:
    """Serializable fold of a ``Collector``: what benchmarks emit and
    ``launch.train`` appends as its final JSONL event."""

    counters: Dict[str, float] = field(default_factory=dict)
    gauges: Dict[str, dict] = field(default_factory=dict)
    delay_percentiles: Dict[str, dict] = field(default_factory=dict)
    slack_percentiles: Dict[str, dict] = field(default_factory=dict)
    staleness: Dict[str, float] = field(default_factory=dict)
    phases: List[dict] = field(default_factory=list)
    rounds: List[dict] = field(default_factory=list)
    n_events: int = 0

    @classmethod
    def from_collector(cls, collector) -> "MetricsReport":
        delay = {
            f"{policy}@load{load:g}": hist.summary()
            for (policy, load), hist in sorted(collector.delay_hist.items())
        }
        slack = {
            f"{policy}@load{load:g}": hist.summary()
            for (policy, load), hist in sorted(collector.slack_hist.items())
        }
        return cls(
            counters={k: c.total for k, c in sorted(
                collector.counters.items())},
            gauges={k: g.summary() for k, g in sorted(
                collector.gauges.items())},
            delay_percentiles=delay,
            slack_percentiles=slack,
            staleness={str(k): v for k, v in sorted(
                collector.staleness.items())},
            phases=[p.summary() for p in collector.phases],
            rounds=list(collector.rounds),
            n_events=len(collector.events),
        )

    def to_dict(self) -> dict:
        return _jsonable({
            "counters": self.counters,
            "gauges": self.gauges,
            "delay_percentiles": self.delay_percentiles,
            "slack_percentiles": self.slack_percentiles,
            "staleness": self.staleness,
            "phases": self.phases,
            "rounds": self.rounds,
            "n_events": self.n_events,
        })

    def save_json(self, path: str) -> None:
        write_summary_json(path, self.to_dict())

    def phase_rows(self) -> List[dict]:
        """Flat per-phase rows for the CSV artifact."""
        rows = []
        for p in self.phases:
            rows.append({
                "phase": p.get("label", ""),
                "rows": p.get("rows", 0),
                "cycles": p.get("cycles", 0),
                "cap_bits": p.get("cap_bits", ""),
                "bg_grant_bits": p.get("bg_grant_bits", ""),
                "fl_grant_bits": p.get("fl_grant_bits", ""),
                "residual_bits": p.get("residual_bits", ""),
                "grant_utilization": p.get("grant_utilization", ""),
                "cps_utilization": p.get("cps_utilization", ""),
            })
        return rows

    def save_csv(self, path: str) -> None:
        write_summary_csv(path, self.phase_rows())
