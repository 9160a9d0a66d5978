"""The benchmark's data files: every name resolves, every reader loads."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from harness import bench, cells

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
BENCH = cells.benchmark(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_resolves(w):
    cell = cells.load(w["name"], ROOT)
    assert {c["name"] for c in BENCH["configs"]} >= {w["config"]}
    assert cell.config["name"] == w["config"]
    assert cell.policy in ("cyc", "ads_tile")
    assert cell.drives_per_call > 0 and cell.sample_drives > 0
    assert set(cell.limits) <= set(bench.gate.NUMBERS)
    from repro.core.experiment import ExperimentSpec

    ExperimentSpec(policy=cell.policy, **cell.spec_fields)
    # the script is data the frozen reference can build too
    from refsim.lockstep import scenario_from_data

    assert abs(scenario_from_data(cell.scenario).duration_s - cell.duration_s) < 1e-12


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(c):
    path = ROOT / c["file"]
    assert path.is_file() and path.resolve().is_relative_to(BENCH_DIR)
    assert json.loads(path.read_text())["reduced"] == c["reduced"]


def test_names_and_units():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics] + [w["name"] for w in BENCH["workloads"]]
    assert len(set(names)) == len(names)
    assert all(NAME.match(n) for n in names)
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_metric_reader_loads(m):
    mod = bench.load_metrics([m["name"]])[m["name"]]
    assert mod.UNIT == m["unit"]
    assert callable(mod.read)
    for target in getattr(mod, "HOOKS", {}).values():
        module, _, attr = target.partition(":")
        assert module.startswith("repro.") and attr
    empty = {
        "window": {"seconds": 1.0, "drive_s": 0.0, "spans": {}},
        "setup": {"compile_s": 0.0, "executables": 0, "cache_hits": 0, "spans": {}},
        "trace": None,
        "traced_rounds": None,
    }
    assert mod.read(empty) is None  # nothing to read: absent, never 0


def test_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH_DIR / "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_drive_seeds_are_disjoint():
    a = {cells.drive_seed(2**31 + 7, c, k, 64) for c in range(40) for k in range(64)}
    b = {cells.drive_seed(2**31 + 8, c, k, 64) for c in range(40) for k in range(64)}
    assert len(a) == 40 * 64 and not a & b
