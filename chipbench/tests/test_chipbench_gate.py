"""The yardstick against the system's own arithmetic, at a small size.

The frozen reference must give the system's lockstep reports bit for
bit, and the copied gate arithmetic the system's verdicts, on the same
reports, as long as the system's code is what was copied.
"""
from __future__ import annotations

import pytest

from harness import bench, cells, gate

SEEDS = [cells.drive_seed(2**31 + 3, 1, k, 4) for k in range(4)]


@pytest.fixture(scope="module", params=["ck1.rate_churn.cyc", "ck1.rate_churn.ads_tile"])
def reports(request):
    from repro.scenarios import ScenarioSpec, run

    cell = cells.load(request.param)
    spec = ScenarioSpec(
        scenario=bench.program_scenario(cell.scenario), policy=cell.policy,
        **cell.spec_fields,
    )
    lock = run(spec, seeds=SEEDS, backend="lockstep")
    soa = run(spec, seeds=SEEDS, backend="soa", fallback=False)
    ref = bench.reference_reports(cell, SEEDS)
    return lock, soa, ref


def test_reference_is_the_lockstep_engine(reports):
    from repro.core.sim.batch import report_digest

    lock, _soa, ref = reports
    assert [report_digest(r) for r in ref] == [report_digest(r) for r in lock]


def test_copied_gate_gives_the_systems_verdicts(reports):
    from benchmarks.check_equivalence import compare_distributional

    lock, soa, ref = reports
    mine = gate.compare_distributional(ref, soa, 0.08)
    theirs = compare_distributional(lock, soa, 0.08)
    assert mine == theirs


def test_numbers_of_a_sound_sample(reports):
    _lock, soa, ref = reports
    v = gate.numbers(ref, soa)
    assert set(v) == set(gate.NUMBERS)
    assert v["struct_bad"] == 0
    assert v["match_ratio"] < 0.5
    shuffled = gate.numbers(ref, soa[1:] + soa[:1])
    assert shuffled["match_ratio"] > 3 * v["match_ratio"]


def test_verdict_needs_every_number():
    assert gate.verdict({"ks": 0.01}, {"ks": 0.05})
    assert not gate.verdict({"ks": 0.06}, {"ks": 0.05})
    assert not gate.verdict({}, {"ks": 0.05})


def test_sample_keeps_k_drives_drawn_from_the_seed():
    def draw(seed):
        s = bench.Sample(8, seed)
        for c in range(20):
            batch = list(range(c * 16, c * 16 + 16))
            s.offer(batch, [f"r{x}" for x in batch])
        return s

    a, b, other = draw(5), draw(5), draw(6)
    assert a.seen == 320 and len(a.seeds) == 8
    assert a.seeds == b.seeds and a.seeds != other.seeds
    assert a.reports == [f"r{x}" for x in a.seeds]
    assert max(a.seeds) >= 16  # later calls get their share
