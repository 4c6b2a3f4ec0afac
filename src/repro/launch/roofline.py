"""Roofline analysis from dry-run records.

Terms per (arch x shape x mesh) cell, all in seconds, with the peaks of
the record's ``device_kind`` (:data:`PEAKS`; an unknown kind is an
error, not a default):

  compute    = HLO_dot_FLOPs_per_device / peak_FLOPs
  memory     = HLO_HBM_bytes_per_device / HBM_bw
  collective = collective_bytes_per_device / link_bw

plus MODEL_FLOPS = 6·N·D (active-N for MoE) and the usefulness ratio
MODEL_FLOPS / (HLO_FLOPs x chips). The dominant term is the bottleneck the
§Perf loop iterates on. Per-device figures come straight from the post-SPMD
HLO (see hlo_analysis.py), so "roofline fraction" = compute / max(all terms).
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from typing import Dict, List, Optional


@dataclass(frozen=True)
class ChipPeaks:
    flops: float             # bf16 FLOP/s per chip
    hbm_bw: float            # HBM bytes/s per chip
    ici_bw: float            # interconnect bytes/s per link


V5E = "TPU v5 lite"          # jax's device_kind for a TPU v5e chip

# Published per-chip peaks keyed by ``jax.devices()[i].device_kind``.
# TPU v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 819 GB/s HBM, 1,600 Gbit/s chip-to-chip interconnect over 4 links.
PEAKS: Dict[str, ChipPeaks] = {
    V5E: ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=1600e9 / 8 / 4),
}


def peaks(device_kind: Optional[str]) -> ChipPeaks:
    """Peaks of ``device_kind``; raises on a kind without published
    peaks rather than assuming another chip's."""
    if device_kind not in PEAKS:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"have {sorted(PEAKS)}"
        )
    return PEAKS[device_kind]

TOKENS = {
    "train_4k": 4096 * 256,
    "prefill_32k": 32768 * 32,
    "decode_32k": 128,        # one token per sequence
    "long_500k": 1,
}


@dataclass
class RooflineRow:
    arch: str
    shape: str
    mesh: str
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    hlo_flops_global: float
    useful_ratio: float
    roofline_fraction: float
    mem_bytes_per_dev: Optional[int]
    record: Dict

    @property
    def bound(self) -> str:
        return self.dominant


def analyze_record(rec: Dict) -> Optional[RooflineRow]:
    if not rec.get("ok"):
        return None
    chips = rec.get("chips", 256)
    flops_dev = rec.get("hlo_flops", 0.0)
    hbm_dev = rec.get("hlo_hbm_bytes", 0.0)
    coll_dev = rec.get("collectives", {}).get("total_bytes", 0.0)

    chip = peaks(rec.get("device_kind"))
    compute_s = flops_dev / chip.flops
    memory_s = hbm_dev / chip.hbm_bw
    collective_s = coll_dev / chip.ici_bw

    terms = {
        "compute": compute_s, "memory": memory_s, "collective": collective_s
    }
    dominant = max(terms, key=terms.get)

    tokens = TOKENS.get(rec["shape"], 1)
    n_active = rec.get("params_active", rec.get("params_total", 0))
    mult = 6 if rec.get("kind") == "train" else 2
    model_flops = mult * n_active * tokens
    hlo_global = flops_dev * chips
    useful = model_flops / hlo_global if hlo_global else 0.0
    frac = compute_s / max(max(terms.values()), 1e-30)

    ma = rec.get("memory_analysis") or {}
    mem_dev = None
    if ma:
        out_extra = max(
            0,
            ma.get("output_size_in_bytes", 0)
            - ma.get("alias_size_in_bytes", 0),   # donated buffers alias
        )
        mem_dev = (
            ma.get("argument_size_in_bytes", 0)
            + out_extra
            + ma.get("temp_size_in_bytes", 0)
        )
    return RooflineRow(
        arch=rec["arch"], shape=rec["shape"], mesh=rec["mesh"],
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        dominant=dominant, model_flops=model_flops,
        hlo_flops_global=hlo_global, useful_ratio=useful,
        roofline_fraction=frac, mem_bytes_per_dev=mem_dev, record=rec,
    )


def load_rows(paths: List[str]) -> List[RooflineRow]:
    rows = []
    for path in paths:
        with open(path) as f:
            for line in f:
                if not line.strip():
                    continue
                row = analyze_record(json.loads(line))
                if row is not None:
                    rows.append(row)
    return rows


def format_table(rows: List[RooflineRow]) -> str:
    hdr = (
        f"{'arch':18s} {'shape':12s} {'mesh':8s} "
        f"{'compute_s':>10s} {'memory_s':>10s} {'coll_s':>10s} "
        f"{'dominant':>10s} {'useful':>7s} {'roofline':>9s} {'mem/dev':>9s}"
    )
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        mem = f"{r.mem_bytes_per_dev/2**30:.1f}G" if r.mem_bytes_per_dev else "-"
        lines.append(
            f"{r.arch:18s} {r.shape:12s} {r.mesh:8s} "
            f"{r.compute_s:10.4f} {r.memory_s:10.4f} {r.collective_s:10.4f} "
            f"{r.dominant:>10s} {r.useful_ratio:7.2f} "
            f"{r.roofline_fraction:9.3f} {mem:>9s}"
        )
    return "\n".join(lines)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("inputs", nargs="+", help="dryrun JSONL files")
    ap.add_argument("--csv", default=None)
    args = ap.parse_args(argv)
    rows = load_rows(args.inputs)
    rows.sort(key=lambda r: (r.mesh, r.arch, r.shape))
    print(format_table(rows))
    if args.csv:
        with open(args.csv, "w") as f:
            f.write(
                "arch,shape,mesh,compute_s,memory_s,collective_s,dominant,"
                "useful_ratio,roofline_fraction,mem_bytes_per_dev\n"
            )
            for r in rows:
                f.write(
                    f"{r.arch},{r.shape},{r.mesh},{r.compute_s:.6g},"
                    f"{r.memory_s:.6g},{r.collective_s:.6g},{r.dominant},"
                    f"{r.useful_ratio:.4g},{r.roofline_fraction:.4g},"
                    f"{r.mem_bytes_per_dev or ''}\n"
                )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
