"""Cells by name: ``BENCHMARK.json``'s entry, then its data files.

A cell (one entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix.  Each lives in a file of its own,
found by name:

* ``configs/<config>.json`` -- the deployment: the system's
  ``ExperimentSpec`` fields under ``spec``, with its source;
* ``traffic/<traffic>.json`` -- the scenario script as data (mode
  segments, bursts, dropouts) and the scheduling policy;
* ``workloads/<cell>.json`` -- the cell's pins: drives per call, the
  job count its problem must have, the drives the reference checks,
  and the limit of each number compared.

Nothing here imports the system under test.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
REPO_ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    workload: dict

    @property
    def spec_fields(self) -> Dict[str, object]:
        return dict(self.config["spec"])

    @property
    def scenario(self) -> dict:
        return self.traffic["scenario"]

    @property
    def policy(self) -> str:
        return self.traffic["policy"]

    @property
    def drives_per_call(self) -> int:
        return int(self.workload["drives_per_call"])

    @property
    def jobs(self) -> int:
        return int(self.workload["jobs"])

    @property
    def sample_drives(self) -> int:
        return int(self.workload["sample_drives"])

    @property
    def limits(self) -> Dict[str, float]:
        return dict(self.workload["limits"])

    @property
    def duration_s(self) -> float:
        return float(sum(s for _m, s in self.scenario["segments"]))


def _read(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def benchmark(root: Optional[Path] = None) -> dict:
    return _read((root or REPO_ROOT) / "BENCHMARK.json")


def load(name: str, root: Optional[Path] = None) -> Cell:
    """The cell ``name`` with its configuration, traffic and pins."""
    root = root or REPO_ROOT
    entries = {w["name"]: w for w in benchmark(root)["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({sorted(entries)})")
    entry = entries[name]
    bench = root / BENCH_DIR.name
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=_read(bench / "configs" / f"{entry['config']}.json"),
        traffic=_read(bench / "traffic" / f"{entry['traffic']}.json"),
        workload=_read(bench / "workloads" / f"{name}.json"),
    )


def drive_seed(run_seed: int, call: int, lane: int, drives_per_call: int) -> int:
    """The seed of one drive: disjoint across calls of a run and across
    runs (a run's drives stay below 2**24)."""
    return (int(run_seed) << 24) + call * drives_per_call + lane
