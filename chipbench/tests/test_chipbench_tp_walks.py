"""``loop.tp_walks_per_lane_round`` reads the window's counters.

Walks over lane-rounds, both counted by the program's registry since
the window opened; absent where the program counts no walks (a parent
without the counters, a cyc or ads_tile cell) or the window never
opened.
"""
from __future__ import annotations

import pytest

from harness import bench, program

NAME = "loop.tp_walks_per_lane_round"
CTX = {"window": {"seconds": 1.0, "drive_s": 4.0, "spans": {}}}


@pytest.fixture
def registry():
    from repro.obs import metrics

    metrics.reset()
    metrics.enable()
    yield metrics
    metrics.enable(False)
    metrics.reset()


def _read(monkeypatch, reader):
    monkeypatch.setattr(program, "READER", reader)
    return bench.load_metrics([NAME])[NAME].read(CTX)


def _window(registry, counts):
    """A reader whose window opens now and then sees ``counts``."""
    registry.count("soa_lane_rounds", 7)  # before the window: not read
    reader = program.Reader()
    reader.at_window = registry.snapshot()
    for name, value in counts.items():
        registry.count(name, value)
    return reader


def test_reads_walks_over_lane_rounds(monkeypatch, registry):
    reader = _window(registry, {"soa_tp_walks": 1_600, "soa_lane_rounds": 2_560})
    assert _read(monkeypatch, reader) == pytest.approx(1_600 / 2_560)


@pytest.mark.parametrize("counts", [
    {},
    {"soa_lane_rounds": 2_560},
    {"soa_tp_walks": 0, "soa_lane_rounds": 2_560},
    {"soa_tp_walks": 12},
], ids=["none", "no-walks", "zero-walks", "no-lane-rounds"])
def test_absent_without_the_counters(monkeypatch, registry, counts):
    assert _read(monkeypatch, _window(registry, counts)) is None


def test_absent_before_the_window(monkeypatch):
    assert _read(monkeypatch, program.Reader()) is None
