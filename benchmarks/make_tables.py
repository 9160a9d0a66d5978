"""Render the §Scenarios markdown table from saved scenario/rate-sweep
runs:  PYTHONPATH=src python -m benchmarks.make_tables

Scenario inputs are the JSON files written by
``python -m benchmarks.run
--only figS_scenarios,figS_rates,figS_predict,figS_budget
--out benchmarks/results/scenarios/<name>.json`` (CI uploads one per
run as a workflow artifact — including the weekly extended sweep; drop
downloaded artifacts into that directory to render them alongside the
other runs).
"""
from __future__ import annotations

import json
from pathlib import Path

SCENARIOS = Path(__file__).resolve().parent / "results" / "scenarios"


def _derived_map(derived: str) -> dict:
    """``k1=v1;k2=v2`` -> dict (the emit() convention for figS rows)."""
    out = {}
    for part in derived.split(";"):
        k, sep, v = part.partition("=")
        if sep:
            out[k] = v
    return out


def main() -> None:
    files = sorted(SCENARIOS.glob("*.json"))
    if not files:
        return
    print("\n### §Scenarios (figS_* suites: mode switches, replanning, "
          "sensor-rate churn)\n")
    print("| run | suite row | viol | miss | realloc | switches | per-mode viol |")
    print("|---|---|---|---|---|---|---|")
    for p in files:
        d = json.loads(p.read_text())
        for row in d.get("rows", []):
            name = row.get("name", "")
            if not name.startswith("figS"):
                continue
            kv = _derived_map(row.get("derived", ""))
            per_mode = " ".join(
                f"{k[:-5]}={v}" for k, v in sorted(kv.items())
                if k.endswith("_viol")
            )
            print(
                f"| {p.stem} | {name} "
                f"| {kv.get('viol', '-')} | {kv.get('miss', '-')} "
                f"| {kv.get('realloc', '-')} | {kv.get('switches', '-')} "
                f"| {per_mode or '-'} |"
            )


if __name__ == "__main__":
    main()
