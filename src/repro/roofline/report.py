"""Render the §Dry-run and §Roofline tables from the dry-run JSON records.

    PYTHONPATH=src python -m repro.roofline.report --dir results/dryrun
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

from .analysis import summarize_cell


def fmt_bytes(b):
    if b >= 2**30:
        return f"{b / 2**30:.2f}G"
    if b >= 2**20:
        return f"{b / 2**20:.1f}M"
    return f"{b / 2**10:.0f}K"


def fmt_s(t):
    if t == 0:
        return "0"
    if t < 1e-3:
        return f"{t * 1e6:.0f}us"
    if t < 1:
        return f"{t * 1e3:.2f}ms"
    return f"{t:.2f}s"


def load_records(d: Path, mesh: str | None = None):
    recs = []
    for f in sorted(d.glob("*.json")):
        r = json.loads(f.read_text())
        if mesh and not r["cell"].endswith("__" + mesh):
            continue
        recs.append(r)
    return recs


def dryrun_table(recs) -> str:
    rows = ["| cell | status | XLA flops/dev | XLA bytes/dev | "
            "collective B/dev | args+temp GiB/dev | fits 16G | notes |",
            "|---|---|---|---|---|---|---|---|"]
    for r in recs:
        if r["status"] == "skipped":
            rows.append(f"| {r['cell']} | skipped | | | | | | "
                        f"{r.get('skipped', '')} |")
            continue
        mem = (r["memory"]["argument_size_in_bytes"]
               + r["memory"]["temp_size_in_bytes"]) / 2**30
        fits = "yes" if mem <= 16 else "NO"
        notes = []
        if r.get("use_ep"):
            notes.append("EP")
        if r.get("fsdp"):
            notes.append("FSDP")
        if r.get("sequence_parallel"):
            notes.append("SP")
        if r.get("optimizer") == "adafactor":
            notes.append("adafactor")
        rows.append(
            f"| {r['cell']} | {r['status']} "
            f"| {r['cost'].get('flops', 0):.2e} "
            f"| {r['cost'].get('bytes accessed', 0):.2e} "
            f"| {fmt_bytes(r.get('collectives', {}).get('total', 0))} "
            f"| {mem:.1f} | {fits} | {'+'.join(notes)} |")
    return "\n".join(rows)


def roofline_table(recs, hw=None) -> str:
    rows = ["| cell | t_compute | t_memory | t_collective | dominant | "
            "useful (6ND/HLO) | fits | next lever |",
            "|---|---|---|---|---|---|---|---|"]
    data = []
    for r in recs:
        s = summarize_cell(r, hw)
        if s is None:
            continue
        data.append(s)
        lever = {
            "compute": "reduce remat recompute / bf16 accum paths",
            "memory": "fuse passes; larger per-chip batch to amortize "
                      "weight reads",
            "collective": "reshard to cut all-gathers; overlap with "
                          "compute",
        }[s["dominant"]]
        ur = s["useful_ratio"]
        ur_s = f"{ur:.2f}" if ur == ur else "n/a"
        rows.append(
            f"| {s['cell']} | {fmt_s(s['t_compute'])} "
            f"| {fmt_s(s['t_memory'])} | {fmt_s(s['t_collective'])} "
            f"| **{s['dominant']}** | {ur_s} "
            f"| {'y' if s['fits_hbm'] else 'N'} | {lever} |")
    return "\n".join(rows)


def pick_hillclimb(recs, hw=None):
    """The three §Perf cells: worst compute fraction (train), most
    collective-bound, most representative."""
    summaries = [s for s in (summarize_cell(r, hw) for r in recs) if s]
    trains = [s for s in summaries if "train" in s["cell"]]
    worst = min(trains,
                key=lambda s: s["t_compute"] / max(s["bound_s"], 1e-12))
    coll = max(summaries, key=lambda s: s["t_collective"]
               / max(s["bound_s"], 1e-12))
    return worst, coll


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="results/dryrun")
    ap.add_argument("--mesh", default="single")
    args = ap.parse_args()
    recs = load_records(Path(args.dir), args.mesh)
    print("## Dry-run (mesh:", args.mesh + ")\n")
    print(dryrun_table(recs))
    print("\n## Roofline\n")
    print(roofline_table(recs))
    worst, coll = pick_hillclimb(recs)
    print(f"\nworst-compute-fraction train cell: {worst['cell']}")
    print(f"most collective-bound cell: {coll['cell']}")


if __name__ == "__main__":
    main()
