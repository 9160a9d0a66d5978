"""The trace reduction, on a recorded TPU trace and on a made-up one."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from harness import bench, tracing

SAMPLE = Path(__file__).resolve().parent / "data" / "trace_sample.json"
DEV, HOST = "/device:TPU:0", "/host:CPU"


def made_up():
    ms = 1_000_000
    return [
        (HOST, "t1", "chipbench.call", 0, 100 * ms),
        (HOST, "t1", "chipbench.sample", 0, 10 * ms),
        (HOST, "t1", "chipbench.loop", 20 * ms, 60 * ms),
        (HOST, "t1", "chipbench.assemble", 80 * ms, 20 * ms),
        (DEV, tracing.MODULES_LINE, "jit_run(1)", 22 * ms, 55 * ms),
        (DEV, tracing.OPS_LINE, "fusion.1", 5 * ms, 2 * ms),
        (DEV, tracing.OPS_LINE, "fusion.2", 22 * ms, 30 * ms),
        (DEV, tracing.OPS_LINE, "fusion.3", 50 * ms, 27 * ms),  # overlaps .2
        (DEV, tracing.OPS_LINE, "outside", 150 * ms, 5 * ms),
    ]


def test_reduce_made_up_trace():
    red = tracing.reduce(made_up())
    assert red["window_s"] == pytest.approx(0.100)
    assert red["busy_s"] == pytest.approx(0.002 + 0.055)
    assert red["modules"] == {"jit_run(1)": [1, pytest.approx(0.055)]}
    assert red["device_ops"][0] == ["fusion.2", pytest.approx(0.030)]
    gaps = {name: s for name, s in red["idle_gaps"]}
    assert gaps["assemble"] == pytest.approx(0.023)
    assert gaps["sample"] == pytest.approx(0.005)


def test_reduce_finds_nothing_without_a_call_or_device():
    assert tracing.reduce([e for e in made_up() if e[2] != "chipbench.call"]) is None
    assert tracing.reduce([e for e in made_up() if e[0] == HOST]) is None


def test_reduce_recorded_tpu_sample():
    events = [tuple(e) for e in json.loads(SAMPLE.read_text())]
    red = tracing.reduce(events)
    assert red is not None and red["chips"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    loop = bench.load_metrics(["loop.device_us_per_round"])["loop.device_us_per_round"]
    idle = bench.load_metrics(["device.idle_share"])["device.idle_share"]
    ctx = {"trace": red, "traced_rounds": 2000}
    assert loop.read(ctx) > 0
    assert 0 < idle.read(ctx) < 100
    assert len(red["device_ops"]) == 10
    assert red["idle_gaps"] and all(s >= 1e-6 for _n, s in red["idle_gaps"])
    spans = {s.split(".", 1)[1] for _p, _l, s, _a, _d in events if s.startswith("chipbench.")}
    assert {name for name, _s in red["idle_gaps"]} <= spans | {"host"}
