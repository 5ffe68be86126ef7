"""Render KERNELS_TPU.jsonl (kernel_sweep.py output) into KERNELS_TPU.md.

Usage: python scripts/summarize_kernels.py [in.jsonl] [out.md]
"""

from __future__ import annotations

import json
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]


def fmt(v) -> str:
    return "-" if v is None else f"{v:.1f}"


def main() -> int:
    src = pathlib.Path(sys.argv[1] if len(sys.argv) > 1 else REPO / "KERNELS_TPU.jsonl")
    dst = pathlib.Path(sys.argv[2] if len(sys.argv) > 2 else REPO / "KERNELS_TPU.md")
    recs = []
    for l in src.read_text().splitlines():
        if not l.strip():
            continue
        try:
            recs.append(json.loads(l))
        except json.JSONDecodeError:
            # A truncated tail line is normal: producers append under
            # hard-kill timeouts.
            print(f"skipping malformed line: {l[:60]!r}", file=sys.stderr)
    if not recs:
        print("no records", file=sys.stderr)
        return 1
    n_skipped = sum(1 for r in recs if r.get("skipped"))
    recs = [r for r in recs if not r.get("skipped")]
    if n_skipped:
        print(f"dropping {n_skipped} timing-free tombstone record(s) "
              "(clamped block preference)", file=sys.stderr)
    if not recs:
        print("no measured records", file=sys.stderr)
        return 1

    # Group-probe rows (same config, varying blocks/group) vs sweep rows.
    probe = [r for r in recs if r.get("fused_only") or (
        r["kernel"].startswith("pallas") and r.get("sddmm_gflops") is None)]
    sweep = [r for r in recs if r not in probe]

    grid_points = sorted({(r["logM"], r["npr"], r["R"]) for r in recs})
    lines = [
        "# KERNELS_TPU — XLA vs Pallas local-kernel sweep (single v5e chip)",
        "",
        "Produced by `scripts/kernel_sweep.py` (resumable orchestrator over",
        "`scripts/tune_blocks.py` workers) on one v5e chip, from the records",
        "in KERNELS_TPU.jsonl (taken in round 5, on code older than PR 1);",
        "the reference analog is `local_kernel_benchmark.cpp:276-280`. The",
        "PLAN (`scripts/plans/star_sweep.json`) is a star design around the",
        "center (logM=14, nnz/row=32, R=128) covering every axis value of",
        "the prescribed grid, plus the heavy corner (16, 128, 512), instead",
        "of the full 36-config cross product. Measured so far:",
        f"**{len(grid_points)} grid point(s) measured** — "
        + ", ".join(f"({a},{b},{c})" for a, b, c in grid_points) + ".",
        "",
        "GFLOP/s = 2*nnz*R/elapsed per op; fused pair counts both ops",
        "(`benchmark_dist.cpp:147-149`).",
        "",
    ]

    if sweep:
        lines += [
            "## Star sweep",
            "",
            "| logM | nnz/row | R | kernel | blocks | group | scatter | batch | SDDMM | SpMM | fused pair |",
            "|---|---|---|---|---|---|---|---|---|---|---|",
        ]
        for r in sorted(sweep, key=lambda r: (r["logM"], r["npr"], r["R"], r["kernel"])):
            blocks = f"{r['bm']}x{r['bn']}" if "bm" in r else "-"
            form = r.get("scatter_form", "bt") if r["kernel"].startswith("pallas") else "-"
            lines.append(
                f"| {r['logM']} | {r['npr']} | {r['R']} | {r['kernel']} "
                f"| {blocks} | {r.get('group', '-')} | {form} "
                f"| {'y' if r.get('batch_step') else '-'} "
                f"| {fmt(r.get('sddmm_gflops'))} | {fmt(r.get('spmm_gflops'))} "
                f"| {fmt(r.get('fused_pair_gflops'))} |"
            )
        lines.append("")

    if probe:
        lines += [
            "## Block/group tuning probe (logM=16, nnz/row=32, R=128, fused pair)",
            "",
            "| blocks | group | scatter | batch | chunk | chunks | occupancy | ns/chunk | GFLOP/s |",
            "|---|---|---|---|---|---|---|---|---|",
        ]
        for r in sorted(probe, key=lambda r: (r.get("bm", 0), r.get("bn", 0),
                                              r.get("group", 1),
                                              r.get("scatter_form", "bt"),
                                              bool(r.get("batch_step")),
                                              r.get("chunk", 128))):
            lines.append(
                f"| {r.get('bm')}x{r.get('bn')} | {r.get('group', 1)} "
                f"| {r.get('scatter_form', 'bt')} "
                f"| {'y' if r.get('batch_step') else '-'} "
                f"| {r.get('chunk', 128)} "
                f"| {r.get('n_chunks')} | {r.get('occupancy')} "
                f"| {fmt(r.get('fused_ns_per_chunk'))} "
                f"| {fmt(r.get('fused_pair_gflops'))} |"
            )
        lines.append("")

    dst.write_text("\n".join(lines))
    print(f"wrote {dst} ({len(sweep)} sweep + {len(probe)} probe records)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
