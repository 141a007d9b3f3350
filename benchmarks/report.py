"""Render the EXPERIMENTS.md table from the dry-run JSONL records.

  PYTHONPATH=src python -m benchmarks.report --dryrun results_dryrun_baseline.jsonl
"""
from __future__ import annotations

import argparse
import json


def load(path: str) -> list[dict]:
    rows = []
    with open(path) as f:
        for line in f:
            if line.strip():
                rows.append(json.loads(line))
    # keep only the LAST record per key (reruns append); drop error records
    # superseded by a later ok/skip for the same combo
    out = {}
    for r in rows:
        key = (r.get("arch"), r.get("shape"), r.get("mesh"), r.get("tag", ""))
        out[key] = r
    combos_ok = {(r.get("arch"), r.get("shape"))
                 for r in out.values() if r.get("status") in ("ok", "skipped")}
    return [r for r in out.values()
            if not (r.get("status") == "error"
                    and (r.get("arch"), r.get("shape")) in combos_ok)]


def dryrun_table(rows: list[dict]) -> str:
    lines = ["| arch | shape | mesh | status | bytes/dev (args) | "
             "temp bytes/dev | collective bytes/dev | compile (s) |",
             "|---|---|---|---|---|---|---|---|"]
    order = {"train_4k": 0, "prefill_32k": 1, "decode_32k": 2, "long_500k": 3}
    for r in sorted(rows, key=lambda r: (r["arch"], order.get(r["shape"], 9),
                                         r.get("mesh", ""))):
        if r.get("status") == "ok":
            ma = r.get("memory_analysis", {})
            lines.append(
                f"| {r['arch']} | {r['shape']} | {r['mesh']} | ok | "
                f"{ma.get('argument_size_in_bytes', 0)/1e9:.2f} GB | "
                f"{ma.get('temp_size_in_bytes', 0)/1e9:.2f} GB | "
                f"{r.get('collective_bytes_per_device', 0)/1e9:.3f} GB | "
                f"{r.get('compile_s', 0)} |")
        elif r.get("status") == "skipped":
            lines.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                         f"skipped | — | — | — | — |")
        else:
            lines.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                         f"ERROR | — | — | — | — |")
    return "\n".join(lines)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", required=True)
    args = ap.parse_args()
    print("## Dry-run (raw compiled artifacts)\n")
    print(dryrun_table(load(args.dryrun)))


if __name__ == "__main__":
    main()
