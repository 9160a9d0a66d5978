"""The SoA backend's report assembly against a per-lane loop.

``soa._assemble_reports`` builds every lane's ``SimReport`` from
reductions over ``(R, sinks)`` arrays.  :func:`reference_reports` below
is the per-lane, per-sink loop it replaced, kept as the reference: on
the same round-loop output both must give equal reports, field by field
and type by type (``==`` on floats, nan matching nan).  The cells are
the chip benchmark's platforms (``chipbench/configs``) at a few lanes.
"""
import dataclasses
import math
from typing import Dict, List, Tuple

import numpy as np
import pytest

from repro.core.sim import soa
from repro.core.sim import soa_kernels as K
from repro.core.sim.engine import ModeStats, SimReport
from repro.obs import metrics
from repro.scenarios.runner import ScenarioSpec, run
from repro.scenarios.script import get_scenario


def reference_reports(problem, out) -> Tuple[List[SimReport], int]:
    """Each lane's report by a Python loop over its sinks, chains and
    modes, and the number of lanes whose starvation deficits it
    reconciled."""
    R = problem.cfg.R
    dur = problem.duration
    total = problem.num_tiles * dur
    cons = problem.considered
    n_jobs = int(np.sum(cons))
    state = out["state"]
    fin = out["fin"].astype(np.float64)
    deg = out["deg"] > 0.5

    dropped = (state == K.DROP) & cons[None, :]
    late = (state == K.DONE) & cons[None, :] & (fin > problem.e2e_host[None, :] + 1e-6)
    unfinished = (state < K.DONE) & cons[None, :]
    n_dropped = dropped.sum(axis=1)
    n_miss = n_dropped + late.sum(axis=1) + unfinished.sum(axis=1)

    sink_pos = np.array([p for _c, p, _t, _d, _m in problem.sinks], dtype=np.int64)
    sink_t0 = np.array([t for _c, _p, t, _d, _m in problem.sinks])
    sink_ddl = np.array([d for _c, _p, _t, d, _m in problem.sinks])
    st_s = state[:, sink_pos] if len(sink_pos) else np.zeros((R, 0))
    fin_s = fin[:, sink_pos] if len(sink_pos) else np.zeros((R, 0))
    deg_s = deg[:, sink_pos] if len(sink_pos) else np.zeros((R, 0), bool)
    lat_s = fin_s - sink_t0[None, :]
    done_s = st_s == K.DONE
    drop_s = st_s == K.DROP
    viol_s = done_s & ((lat_s > sink_ddl[None, :] + 1e-9) | deg_s)

    busy_seg = out["busy"]
    rel_seg = out["realloc"]
    busy_tot = busy_seg.sum(axis=1)
    rel_tot = rel_seg.sum(axis=1)
    mode_busy: Dict[str, np.ndarray] = {}
    mode_rel: Dict[str, np.ndarray] = {}
    for s, m in enumerate(problem.seg_mode):
        mode_busy[m] = mode_busy.get(m, 0.0) + busy_seg[:, s]
        mode_rel[m] = mode_rel.get(m, 0.0) + rel_seg[:, s]

    reports, reconciled = [], 0
    for k in range(R):
        chain_count = {c: 0 for c in problem.chain_names}
        chain_viol = {c: 0 for c in problem.chain_names}
        chain_lats: Dict[str, List[float]] = {c: [] for c in problem.chain_names}
        sink_by_mode: Dict[Tuple[str, str], List[int]] = {}
        mode_lats: Dict[str, List[float]] = {}
        for i, (cname, _p, t0, _ddl, m) in enumerate(problem.sinks):
            if done_s[k, i]:
                chain_count[cname] += 1
                chain_viol[cname] += int(viol_s[k, i])
                chain_lats[cname].append(float(lat_s[k, i]))
                rec = sink_by_mode.setdefault((cname, m), [0, 0])
                rec[0] += 1
                rec[1] += int(viol_s[k, i])
                mode_lats.setdefault(m, []).append(float(lat_s[k, i]))
            elif drop_s[k, i]:
                chain_count[cname] += 1
                chain_viol[cname] += 1
                rec = sink_by_mode.setdefault((cname, m), [0, 0])
                rec[0] += 1
                rec[1] += 1

        lane_reconciled = False
        for cname in problem.chain_names:
            deficit = max(0, problem.expected[cname] - chain_count[cname])
            if not deficit:
                continue
            lane_reconciled = True
            chain_viol[cname] += deficit
            chain_count[cname] = problem.expected[cname]
            em = problem.expected_mode[cname]
            for m in problem.mode_order:
                if m not in em:
                    continue
                rec = sink_by_mode.setdefault((cname, m), [0, 0])
                take = min(max(0, em[m] - rec[0]), deficit)
                if take:
                    rec[0] += take
                    rec[1] += take
                    deficit -= take
                if not deficit:
                    break
        reconciled += lane_reconciled

        p99 = {
            c: (float(np.percentile(ls, 99)) if ls else float("nan"))
            for c, ls in chain_lats.items()
        }
        mode_stats: Dict[str, ModeStats] = {}
        for m, span in problem.spans.items():
            done_m = sum(
                rec[0] for (_c, mm), rec in sink_by_mode.items() if mm == m
            )
            viol_m = sum(
                rec[1] for (_c, mm), rec in sink_by_mode.items() if mm == m
            )
            lats = mode_lats.get(m, [])
            denom = problem.num_tiles * span
            mb = float(np.asarray(mode_busy.get(m, 0.0))[k]) if m in mode_busy else 0.0
            mr = float(np.asarray(mode_rel.get(m, 0.0))[k]) if m in mode_rel else 0.0
            mode_stats[m] = ModeStats(
                mode=m,
                span_s=span,
                n_completed=done_m,
                n_violations=viol_m,
                p99_s=(
                    float(np.percentile(np.asarray(lats), 99))
                    if lats else float("nan")
                ),
                effective_frac=mb / denom if denom > 0 else 0.0,
                realloc_frac=mr / denom if denom > 0 else 0.0,
            )

        busy = float(busy_tot[k])
        rel_ts = float(rel_tot[k])
        reports.append(SimReport(
            duration_s=dur,
            total_tiles=problem.num_tiles,
            effective_frac=busy / total,
            realloc_frac=rel_ts / total,
            idle_frac=max(0.0, 1.0 - (busy + rel_ts) / total),
            dropped_work_frac=float(out["dropped_work"][k]) / total,
            n_realloc=int(round(float(out["n_realloc"][k]))),
            realloc_bytes=float(out["realloc_bytes"][k]),
            n_jobs=n_jobs,
            n_dropped=int(n_dropped[k]),
            task_miss_rate=float(n_miss[k]) / max(n_jobs, 1),
            chain_count=chain_count,
            chain_violations=chain_viol,
            chain_p99_s=p99,
            chain_latencies=chain_lats,
            decision_ratios=[],
            mode_stats=mode_stats,
            n_mode_switches=problem.n_mode_switches,
            forecast=None,
            tiles_used=problem.tiles_used,
            tiles_reserved_mean=problem.tiles_reserved_mean,
            frontier_meta=dict(problem.frontier_meta),
        ))
    return reports, reconciled


def assert_same(a, b, where="report"):
    """Equal values of the same Python types, recursively; a float nan
    equals a nan."""
    assert type(a) is type(b), f"{where}: {type(a).__name__} != {type(b).__name__}"
    if dataclasses.is_dataclass(a):
        for f in dataclasses.fields(a):
            assert_same(getattr(a, f.name), getattr(b, f.name), f"{where}.{f.name}")
    elif isinstance(a, dict):
        assert list(a) == list(b), f"{where}: keys"
        for key in a:
            assert_same(a[key], b[key], f"{where}[{key!r}]")
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b), f"{where}: length {len(a)} != {len(b)}"
        for i, (x, y) in enumerate(zip(a, b)):
            assert_same(x, y, f"{where}[{i}]")
    elif isinstance(a, float) and math.isnan(a):
        assert math.isnan(b), f"{where}: nan != {b!r}"
    else:
        assert a == b, f"{where}: {a!r} != {b!r}"


def assemble(problem, out):
    """The program's reports and its ``soa_assemble_deficit_lanes``."""
    metrics.reset()
    metrics.enable()
    try:
        got = soa._assemble_reports(problem, out)
        counters = metrics.snapshot(reset_after=True)["counters"]
    finally:
        metrics.enable(False)
    return got, counters.get("soa_assemble_deficit_lanes", 0)


#: the chip benchmark's platforms (``chipbench/configs``)
PLATFORMS = {
    "paper-ck1": dict(tiles=400, cockpit_replicas=1, num_partitions=4),
    "paper-ck6": dict(tiles=512, cockpit_replicas=6, num_partitions=4),
}
SCENARIO = {"paper-ck1": "rate_churn", "paper-ck6": "commute"}


def soa_call(platform, policy, seeds, **spec):
    """Run ``seeds`` on the SoA path; each assembly's (problem, out) and
    the reports the runner returned."""
    fields = {**PLATFORMS[platform], "deadline_s": 0.1, "q": 0.95,
              "drop_policy": "soft", "p99_ratio": 3.3,
              "dram_utilization": 0.5, **spec}
    full = ScenarioSpec(
        scenario=get_scenario(SCENARIO[platform]),
        policy=policy, **fields,
    )
    calls = []
    real = soa._assemble_reports

    def recording(problem, out):
        calls.append((problem, out))
        return real(problem, out)

    soa._assemble_reports = recording
    try:
        reports = run(full, seeds=seeds, backend="soa", fallback=False)
    finally:
        soa._assemble_reports = real
    return calls, reports


@pytest.mark.parametrize("platform", sorted(PLATFORMS))
@pytest.mark.parametrize("policy", ["cyc", "ads_tile", "tp_driven"])
def test_reports_equal_the_per_lane_loop(platform, policy):
    seeds = [2_147_483_901 + i for i in range(4 if platform == "paper-ck6" else 8)]
    calls, reports = soa_call(platform, policy, seeds)
    problem, out = calls[-1]
    want, reconciled = reference_reports(problem, out)
    got, counted = assemble(problem, out)
    assert_same(got, want)
    assert_same(reports, want)
    assert counted == reconciled


def overloaded():
    """ads_tile on x1 rate_churn at twice the load under hard drops: its
    e2e timers drop jobs, and sinks queued near the horizon neither
    finish nor drop, which leaves starvation deficits."""
    calls, _ = soa_call("paper-ck1", "ads_tile", [7, 8, 9, 10],
                        load_factor=2.0, drop_policy="hard")
    return calls[-1]


def test_overloaded_drive_drops_starves_and_reconciles():
    problem, out = overloaded()
    st = out["state"][:, [p for _c, p, _t, _d, _m in problem.sinks]]
    assert np.any(st == K.DROP) and np.any(st < K.DONE)
    want, reconciled = reference_reports(problem, out)
    assert reconciled > 0
    got, counted = assemble(problem, out)
    assert_same(got, want)
    assert counted == reconciled


def test_a_chain_that_completes_nothing():
    """Lane 0's first chain never finishes a sink and its second drops
    every one: nan p99s, empty latency lists, the deficit filled."""
    problem, out = overloaded()
    out = dict(out, state=out["state"].copy())
    first, second = problem.chain_names[:2]
    for cname, p, _t, _d, _m in problem.sinks:
        if cname == first:
            out["state"][0, p] = K.PEND
        elif cname == second:
            out["state"][0, p] = K.DROP
    want, reconciled = reference_reports(problem, out)
    assert want[0].chain_latencies[first] == []
    assert want[0].chain_latencies[second] == []
    assert math.isnan(want[0].chain_p99_s[first])
    assert want[0].chain_count[first] == problem.expected[first]
    got, counted = assemble(problem, out)
    assert_same(got, want)
    assert counted == reconciled


P99_CASES = {
    "n0": ([], []),
    "n1": ([0.25], [True]),
    "n2": ([0.3, 0.1], [True, True]),
    "n3": ([0.2, 0.05, 0.9], [True, True, True]),
    "n100": (np.linspace(0.01, 1.0, 100) ** 2, [True] * 100),
    "n101": (np.cos(np.arange(101)) ** 2, [True] * 101),
    "ties": ([0.1, 0.2, 0.2, 0.2, 0.3, 0.3], [True] * 6),
    "all_equal": ([0.07] * 9, [True] * 9),
    "mask_hides_the_largest": ([0.5, 9.0, 0.1, 8.0, 0.3], [True, False, True, False, True]),
    # (n - 1) * 0.99 = 49.5 and 149.5, a fraction of exactly 0.5, between
    # neighbours where the two halves of _lerp round differently
    "half_51": (np.r_[0.7, np.linspace(0.0, 0.05, 49), 0.1], [True] * 51),
    "half_151": (np.r_[1.0, np.linspace(0.0, 0.2, 149), 0.3], [True] * 151),
}


@pytest.mark.parametrize("case", sorted(P99_CASES))
def test_row_p99_is_numpys_percentile(case):
    x, mask = P99_CASES[case]
    x = np.asarray(x, dtype=np.float64)
    mask = np.asarray(mask, dtype=bool)
    if case.startswith("half"):
        assert (len(x) - 1) * 0.99 % 1 == 0.5
    # the row alone, and beside a row of other length under one call
    other = np.linspace(0.5, 0.1, len(x) + 3)
    rows = np.zeros((2, len(x) + 3))
    ok = np.zeros((2, len(x) + 3), dtype=bool)
    rows[0, : len(x)], ok[0, : len(x)] = x, mask
    rows[1], ok[1] = other, True
    want = (float(np.percentile(x[mask], 99)) if mask.any() else float("nan"),
            float(np.percentile(other, 99)))
    for got in (soa._row_p99(x[None], mask[None]).tolist() + [want[1]],
                soa._row_p99(rows, ok).tolist()):
        assert_same(got, list(want))
