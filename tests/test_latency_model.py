"""Unit tests for the probabilistic latency model (paper Eq. 1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.benchmark import make_ads_benchmark
from repro.core.hardware import simba_chip
from repro.core.latency_model import (
    LatencyModel,
    LogNormal,
    ShiftedExponential,
    TaskLatencyProfile,
    chain_tail_composition,
    prune_dop_candidates,
)


def test_lognormal_moments():
    d = LogNormal(mean=100.0, p99_ratio=3.3)
    samples = d.sample(jax.random.PRNGKey(0), (200_000,))
    assert np.isclose(float(jnp.mean(samples)), 100.0, rtol=0.05)
    p99 = float(jnp.percentile(samples, 99))
    assert np.isclose(p99 / 100.0, 3.3, rtol=0.1)


def test_lognormal_quantile_matches_samples():
    d = LogNormal(mean=10.0, p99_ratio=2.0)
    samples = d.sample(jax.random.PRNGKey(1), (200_000,))
    for q in (0.5, 0.9, 0.99):
        emp = float(jnp.percentile(samples, q * 100))
        assert np.isclose(d.quantile(q), emp, rtol=0.05)


def test_shifted_exponential_quantile():
    d = ShiftedExponential(base=1.0, rate=2.0)
    # P[X <= base - ln(1-q)/rate] = q
    assert np.isclose(d.quantile(0.5), 1.0 + np.log(2) / 2)
    samples = d.sample(jax.random.PRNGKey(2), (100_000,))
    assert np.isclose(float(jnp.percentile(samples, 90)), d.quantile(0.9), rtol=0.05)


def test_latency_bound_probability():
    """Pr[L <= L(q, c)] >= q — the defining guarantee of Eq. 1."""
    prof = TaskLatencyProfile(
        name="t",
        work=LogNormal(1e12, 3.3),
        io=ShiftedExponential(5e-6, 1e4),
        sync_per_tile_s=1e-7,
    )
    P = 1.024e12
    for q in (0.5, 0.9, 0.95):
        for c in (2, 8, 32):
            bound = prof.latency_bound(q, c, P)
            lat = prof.sample_latency(jax.random.PRNGKey(3), c, P, (50_000,))
            frac = float(jnp.mean(lat <= bound))
            assert frac >= q - 0.02, (q, c, frac)


def test_bound_monotone_then_sync_dominated():
    prof = TaskLatencyProfile(
        name="t", work=LogNormal(1e12, 2.0),
        io=ShiftedExponential(1e-6, 1e5), sync_per_tile_s=2e-5,
    )
    P = 1.024e12
    bounds = [prof.latency_bound(0.95, c, P) for c in (1, 2, 4, 8)]
    assert bounds[1] < bounds[0]
    # with a strong sync term, very large DoP stops helping
    # (optimum c* = sqrt(W_q / (P * sync)) ~ 285 here)
    big = [prof.latency_bound(0.95, c, P) for c in (512, 4096)]
    assert big[1] > big[0]


def test_prune_dop_candidates():
    prof = TaskLatencyProfile(
        name="t", work=LogNormal(1e12, 2.0),
        io=ShiftedExponential(1e-6, 1e5), sync_per_tile_s=0.0,
    )
    kept = prune_dop_candidates(prof, 1.024e12, [1, 2, 3, 4, 8, 16], q=0.95,
                                improvement_threshold=0.3)
    assert kept[0] == 1
    assert all(a < b for a, b in zip(kept, kept[1:]))
    assert set(kept) <= {1, 2, 3, 4, 8, 16}


def test_tail_composition_headroom_positive():
    """The paper's §II-C3 scope note: summing per-task tail budgets
    overestimates the observed E2E tail."""
    wf = make_ads_benchmark()
    model = LatencyModel.from_workflow(wf, simba_chip(400))
    chain = next(c for c in wf.chains if c.name == "drv_vision")
    dops = {n: 8 for n in chain.nodes}
    out = chain_tail_composition(model, chain.nodes, dops, q=0.95)
    assert out["headroom"] > 0.05
    assert out["mc_quantile_s"] < out["sum_of_quantiles_s"]


def test_fitquota_helper():
    wf = make_ads_benchmark()
    model = LatencyModel.from_workflow(wf, simba_chip(400))
    task = wf.tasks["img_backbone"]
    c = model.min_dop_for_budget(task, 0.95, 0.050)
    assert c is not None
    # minimality: no smaller candidate meets the budget
    for smaller in task.dop_candidates():
        if smaller >= c:
            break
        assert model.bound("img_backbone", 0.95, smaller) > 0.050


def test_hardware_models():
    hw = simba_chip()
    assert hw.num_tiles == 128
    assert np.isclose(hw.tile_flops, 1.024e12)
    big = simba_chip(400)
    assert big.num_tiles == 400
    # realloc: hundreds of microseconds for MB-scale checkpoints
    lat = hw.realloc_latency(16e6, 64)
    assert 1e-4 < lat < 1e-3


def test_bound_ladder_and_batch_match_scalar_bounds():
    """The ladder and vectorized-batch evaluations of Eq. (1) must stay
    in lockstep with the scalar `bound()` path — including the edge
    cases (sensor tasks, zero-sigma work, rate<=0 I/O).  The autotuner
    ranks frontiers with the batch path while the compiler budgets with
    the scalar one; any drift silently desynchronizes them."""
    wf = make_ads_benchmark()
    model = LatencyModel.from_workflow(wf, simba_chip(400))
    # hand-built edge-case profiles alongside the benchmark's
    model.profiles["zero_sigma"] = TaskLatencyProfile(
        name="zero_sigma",
        work=LogNormal(2.0e9, 1.0),              # sigma == 0
        io=ShiftedExponential(5e-6, 0.0),        # rate <= 0
        sync_per_tile_s=1e-7,
    )
    names = tuple(model.profiles)
    for q in (0.5, 0.9, 0.95, 0.999):
        for c in (1, 2, 8, 32):
            scal = [model.bound(t, q, c) for t in names]
            batch = model.bound_batch(names, q, np.full(len(names), c))
            assert np.allclose(batch, scal, rtol=1e-12, atol=0.0), (q, c)
        for t in names:
            task = wf.tasks.get(t)
            cands = task.dop_candidates() if task is not None else (1, 4, 16)
            ladder = model.bound_ladder(t, q, tuple(cands))
            scal = tuple(
                model.profiles[t].latency_bound(q, c, model.hw.tile_flops)
                for c in cands
            )
            assert np.allclose(ladder, scal, rtol=1e-12, atol=0.0), (t, q)
