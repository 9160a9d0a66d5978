"""Hypothesis property tests on the system's invariants."""
import math

import jax
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import HealthCheck, given, settings, strategies as st  # noqa: E402

from repro.core.gha.guillotine import guillotine_cut
from repro.core.latency_model import LogNormal, ShiftedExponential
from repro.core.runtime import fit_quota
from repro.core.sim.engine import Job
from repro.core.workload import Chain, DnnTask, SensorTask, Workflow, unroll_hyperperiod

SET = settings(
    deadline=None, max_examples=40,
    suppress_health_check=[HealthCheck.too_slow],
)


# ---------------------------------------------------------------------------
@given(
    mean=st.floats(1e3, 1e15),
    ratio=st.floats(1.0, 3.3),
    q=st.floats(0.01, 0.99),
)
@SET
def test_lognormal_quantile_monotone_and_positive(mean, ratio, q):
    d = LogNormal(mean, ratio)
    v = d.quantile(q)
    assert v > 0
    assert d.quantile(min(q + 0.005, 0.995)) >= v - 1e-9


@given(
    base=st.floats(0, 1e-3),
    rate=st.floats(1.0, 1e7),
    q1=st.floats(0.01, 0.5),
    q2=st.floats(0.5, 0.99),
)
@SET
def test_shifted_exp_quantile_monotone(base, rate, q1, q2):
    d = ShiftedExponential(base, rate)
    assert d.quantile(q2) >= d.quantile(q1) >= base


# ---------------------------------------------------------------------------
@given(
    rows=st.integers(2, 12),
    cols=st.integers(2, 16),
    n=st.integers(1, 6),
    data=st.data(),
)
@SET
def test_guillotine_always_partitions(rows, cols, n, data):
    # random areas filling at most ~85% of the mesh (integer guillotine
    # cuts cannot always realise near-100% packings; the GHA compiler
    # keeps slack and falls back to logical binding when cutting fails)
    total = int(rows * cols * 0.85)
    n = min(n, total)
    areas = []
    left = total
    for i in range(n):
        hi = max(1, left - (n - i - 1))
        a = data.draw(st.integers(1, hi))
        areas.append(a)
        left -= a
    rects = guillotine_cut((rows, cols), areas)
    grid = np.zeros((rows, cols), int)
    for (r0, c0, h, w), need in zip(rects, areas):
        assert h * w >= need
        assert 0 <= r0 and 0 <= c0 and r0 + h <= rows and c0 + w <= cols
        grid[r0:r0 + h, c0:c0 + w] += 1
    assert grid.max() == 1  # disjoint; leftover tiles may stay idle


# ---------------------------------------------------------------------------
@given(
    work=st.floats(1e9, 1e14),
    io=st.floats(0, 1e-3),
    target=st.floats(1e-4, 1.0),
    cap=st.integers(0, 64),
)
@SET
def test_fit_quota_is_minimal_feasible(work, io, target, cap):
    job = Job(
        jid=0, task="t", cycle=0, idx=0, release=0.0, is_sensor=False,
        work_flops=work, io_s=io, sync_s=0.0, partition=0,
        ert=0.0, sub_ddl=1.0, e2e_ddl=2.0, plan_dop=4,
    )
    cands = (1, 2, 4, 8, 16, 32, 64)
    tf = 1.024e12
    c = fit_quota(job, cands, target, 0.0, tf, cap)
    feasible = [x for x in cands if x <= cap]
    if not feasible:
        assert c == 0
        return
    meeting = [x for x in feasible if job.remaining(x, tf) <= target]
    if meeting:
        assert c == min(meeting)        # minimum quota (reservation!)
    else:
        assert c == max(feasible)       # best effort


# ---------------------------------------------------------------------------
@given(
    r1=st.sampled_from([10, 20, 30, 60]),
    r2=st.sampled_from([10, 20, 30, 60]),
)
@SET
def test_unroll_instance_counts(r1, r2):
    wf = Workflow(
        tasks={
            "s1": SensorTask(name="s1", period_s=1.0 / r1),
            "s2": SensorTask(name="s2", period_s=1.0 / r2),
            "a": DnnTask(name="a", mean_flops=1e9, compiled_dops=(1, 2)),
            "b": DnnTask(name="b", mean_flops=1e9, compiled_dops=(1, 2)),
        },
        edges=[("s1", "a"), ("s2", "b"), ("a", "b")],
        chains=[Chain("c", ("s1", "a", "b"), 0.2)],
    )
    thp = wf.hyper_period_s
    assert np.isclose(thp * math.gcd(r1, r2), 1.0)
    insts = unroll_hyperperiod(wf)
    count = {}
    for i in insts:
        count[i.task] = count.get(i.task, 0) + 1
    assert count["s1"] == round(thp * r1)
    assert count["a"] == count["s1"]          # gated by s1
    assert count["b"] == round(thp * min(r1, r2))
    # dependency sanity
    by_key = {(i.task, i.index): i for i in insts}
    for i in insts:
        for dep in i.preds:
            assert by_key[dep].release_s <= i.release_s + 1e-12
