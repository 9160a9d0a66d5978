"""Structure-of-arrays jax backend vs the scalar reference engine.

The backend's contract is *distributional* equivalence, not
bit-identity (``docs/performance.md#soa-backend``): the SoA kernels
replace the event heap with discrete scheduling rounds, so individual
event timestamps shift at round granularity while the statistics the
paper's claims rest on must agree.  ``test_soa_contract.py`` holds
every nominal cell to it and ``test_soa_pins.py`` pins the round
loop's outputs; here are the kernel cache, the window-overflow retry,
the support predicates, the device sampling path, the allocator and
pick kernels, tp_driven's decision instants, and a property test over
random Markov scenarios mirroring ``test_batch.py``.
"""
import dataclasses
import os

import numpy as np
import pytest

from repro.core.baselines.tpdriven import TpDrivenPolicy
from repro.core.sim import soa
from repro.core.sim import soa_kernels as K
from repro.core.sim.batch import sample_trace_batch
from repro.core.sim.engine import Job, JobState
from repro.scenarios.runner import ScenarioSpec, run
from repro.scenarios.script import default_generator, get_scenario
from test_soa_contract import SEEDS, _pooled_latencies


# ---------------------------------------------------------------------------
# compile-cache identity: same shapes, different schedule constants
# ---------------------------------------------------------------------------
def test_kernel_cache_distinguishes_const_content():
    """Two cells over the same skeleton (same array shapes) but with
    different schedule constants must not share a compiled loop: the
    jit closure bakes the const arrays in at trace time, so a
    shape-only cache key silently replays the first cell's schedule
    (the figS_budget part-3 shape: one pinned drive, several
    portfolios/load factors in one process)."""
    from repro.scenarios.runner import _make_run_policy, _prepare_run

    spec_a = ScenarioSpec(scenario=get_scenario("commute"), policy="ads_tile")
    spec_b = dataclasses.replace(spec_a, load_factor=1.4)

    def _problem(spec):
        wf, model, sched, portfolio = _prepare_run(spec)
        return soa.build_problem(
            wf, model, sched, portfolio, _make_run_policy(spec, portfolio),
            spec.scenario, spec.scenario.duration_s, n_lanes=len(SEEDS),
        )

    pa, pb = _problem(spec_a), _problem(spec_b)
    # potency: the cells collide on a shape-only key...
    assert {k: v.shape for k, v in pa.const.items()} == {
        k: v.shape for k, v in pb.const.items()
    }
    assert pa.cfg == pb.cfg
    # ...and only the content digest tells them apart
    assert K._const_digest(pa.const) != K._const_digest(pb.const)

    K.clear_kernel_cache()
    fresh = run(spec_b, seeds=SEEDS, backend="soa", fallback=False)
    K.clear_kernel_cache()
    run(spec_a, seeds=SEEDS, backend="soa", fallback=False)  # warm the cache with A's consts
    got = run(spec_b, seeds=SEEDS, backend="soa", fallback=False)  # must not reuse A's loop
    for f, g in zip(fresh, got):
        assert f.chain_latencies == g.chain_latencies
        assert f.violation_rate == g.violation_rate
        assert f.effective_frac == g.effective_frac
        assert f.realloc_frac == g.realloc_frac


# ---------------------------------------------------------------------------
# window-lifetime overflow: detect, refuse, retry wider
# ---------------------------------------------------------------------------
def test_window_overflow_detected_and_retried():
    """A job that slides out of the job window unresolved (overload
    queueing past the E2E-deadline lifetime bound under the soft drop
    policy) must surface as SoaWindowOverflow, never as silently
    truncated reports; the runner retries with a wider window."""
    from repro.core.sim.trace import build_skeleton
    from repro.scenarios.runner import _prepare_run

    spec = ScenarioSpec(scenario=get_scenario("commute"), policy="tp_driven")
    wf, model, sched, portfolio = _prepare_run(spec)
    scen = spec.scenario
    duration = scen.duration_s

    base = soa.build_problem(
        wf, model, sched, portfolio, "tp_driven", scen, duration,
        n_lanes=len(SEEDS),
    )
    # shrink the window to ~4 ms: normal jobs outlive it, so they slide
    # out unresolved — the forced analogue of overload queueing delay
    tight = soa.SoaOptions(life_pad_s=-(base.life - 4e-3))
    problem = soa.build_problem(
        wf, model, sched, portfolio, "tp_driven", scen, duration,
        n_lanes=len(SEEDS), options=tight,
    )
    assert problem.life < base.life
    skel = build_skeleton(wf, scen, duration)
    btrace = sample_trace_batch(skel, model, scen, SEEDS, device=True)
    with pytest.raises(soa.SoaWindowOverflow):
        soa.run_problem(problem, btrace, SEEDS)

    # the runner widens and converges to non-truncated reports
    with pytest.warns(RuntimeWarning, match="SoA job window"):
        got = run(spec, seeds=SEEDS, backend="soa", fallback=False,
                  options=tight)
    want = run(spec, seeds=SEEDS, backend="soa", fallback=False)
    assert len(got) == len(SEEDS)
    for a, b in zip(want, got):
        assert soa.structural_invariants(a) == soa.structural_invariants(b)
        # truncation starves whole chains (violation rate ~1); the
        # widened rerun must sit at the default window's level
        assert abs(a.violation_rate - b.violation_rate) <= 0.05
        assert np.isclose(a.effective_frac, b.effective_frac, rtol=1e-2)


# ---------------------------------------------------------------------------
# support predicates + where the backend runs
# ---------------------------------------------------------------------------
def test_soa_supported_predicate():
    assert soa.soa_supported("cyc")
    assert soa.soa_supported("tp_driven", drop_policy="hard")
    assert not soa.soa_supported("unknown_policy")
    assert not soa.soa_supported("cyc", replan_mode="predictive")
    assert not soa.soa_supported("cyc", detection_delay_s=0.02)
    assert not soa.soa_supported("cyc", record=True)


def test_soa_sweep_runs_in_calling_process(monkeypatch):
    """An accelerator belongs to one process: a SoA sweep asked for
    two pool workers still runs every group here, where the caller's
    device is, instead of in workers that run on the CPU."""
    from repro.scenarios import runner, sweep

    pids = []
    real = runner._run_soa

    def spy(spec, seeds, options=None):
        pids.append(os.getpid())
        return real(spec, seeds, options)

    monkeypatch.setattr(runner, "_run_soa", spy)
    rows = sweep(2, policies=("cyc",), duration_s=0.3, seed=4, jobs=2,
                 backend="soa")
    assert len(rows) == 2
    assert pids == [os.getpid()] * 2


def test_soa_backend_rejects_unsupported_spec():
    spec = ScenarioSpec(
        scenario=get_scenario("commute"), policy="cyc", replan_mode="predictive"
    )
    with pytest.raises(soa.SoaUnsupported):
        run(spec, seeds=[0], backend="soa", fallback=False)


# ---------------------------------------------------------------------------
# device sampling path (stream contract on jnp)
# ---------------------------------------------------------------------------
def test_device_sampling_matches_numpy_path():
    spec = ScenarioSpec(scenario=get_scenario("commute"), policy="cyc")
    from repro.core.sim.trace import build_skeleton
    from repro.scenarios.runner import _prepare_run

    wf, model, _sched, _pf = _prepare_run(spec)
    skel = build_skeleton(wf, spec.scenario, spec.scenario.duration_s)
    host = sample_trace_batch(skel, model, spec.scenario, SEEDS)
    dev = sample_trace_batch(skel, model, spec.scenario, SEEDS, device=True)
    for field in ("work", "io", "sensor_lat"):
        a, b = getattr(host, field), getattr(dev, field)
        # integer hash is bit-identical; the float quantile transforms
        # may differ in the last ulp (XLA exp/log are not libm)
        assert np.allclose(a, b, rtol=1e-12, atol=1e-15), field


# ---------------------------------------------------------------------------
# allocator kernel vs the NumPy oracle
# ---------------------------------------------------------------------------
def test_ladder_grant_matches_reference():
    rng = np.random.default_rng(0)
    limit = rng.integers(0, 9, size=(5, 16)).astype(np.float32)
    cand = np.sort(rng.integers(0, 9, size=(5, 16, 4)), axis=-1).astype(np.float32)
    cand[..., 0] = 0.0
    want = K.ladder_grant_reference(limit, cand)
    import jax.numpy as jnp

    got = np.asarray(K._ladder_grant(jnp.asarray(limit), jnp.asarray(cand)))
    np.testing.assert_array_equal(want, got)
    got_p = np.asarray(
        K._ladder_grant_pallas(
            jnp.asarray(limit), jnp.asarray(cand), interpret=True
        )
    )
    np.testing.assert_array_equal(want, got_p)


@pytest.mark.parametrize("per_lane", [False, True])
def test_pallas_ladder_grant_blocks_match_jnp(per_lane):
    """The Pallas grant walks lanes in blocks (the last one partial
    here) with the ladder unrolled off the lane axis; it must equal the
    jnp select bit for bit, for one shared ladder per job (the round
    loop's case) and for per-lane ladders."""
    import jax.numpy as jnp

    R, W, C = K._GRANT_BLOCK_R + 24, 80, 6
    rng = np.random.default_rng(1)
    limit = rng.integers(-1, 40, size=(R, W)).astype(np.float32)
    shape = (R, W, C) if per_lane else (W, C)
    cand = np.sort(rng.integers(1, 33, size=shape), axis=-1).astype(np.float32)
    want = K.ladder_grant_reference(limit, cand)
    got_j = np.asarray(K._ladder_grant(jnp.asarray(limit), jnp.asarray(cand)))
    got_p = np.asarray(K._ladder_grant_pallas(
        jnp.asarray(limit), jnp.asarray(cand), interpret=True))
    np.testing.assert_array_equal(want, got_j)
    np.testing.assert_array_equal(want, got_p)


# ---------------------------------------------------------------------------
# per-element picks from small per-partition / per-rung tables
# ---------------------------------------------------------------------------
def _take_pick(table, idx):
    """The ``take_along_axis`` form that :func:`K._pick` replaces."""
    import jax.numpy as jnp

    full = jnp.broadcast_to(table, idx.shape + table.shape[-1:])
    return jnp.take_along_axis(full, idx[..., None], axis=-1)[..., 0]


@pytest.mark.parametrize("dtype", ["f32", "bool"])
@pytest.mark.parametrize("K_", [1, 4, 5, 6, 9, 29])
def test_pick_matches_take_along_axis(K_, dtype):
    """Lane-varying picks from a (R, 1, K) per-lane table (partitions,
    with ``own_of``'s pad column as the last one) and from a (1, W, K)
    per-job table (ladder rungs) equal the gather exactly, ``inf``
    included."""
    import jax.numpy as jnp

    R, W = 7, 33
    rng = np.random.default_rng(K_)
    idx = rng.integers(0, K_, size=(R, W)).astype(np.int32)
    idx[0, 0], idx[0, 1], idx[-1, -1] = 0, K_ - 1, K_ - 1  # K - 1: the pad
    for shape in ((R, 1, K_), (1, W, K_)):
        if dtype == "bool":
            table = rng.random(shape) < 0.5
        else:
            table = rng.normal(size=shape).astype(np.float32)
            table[rng.random(shape) < 0.3] = np.inf
            table[..., -1] = -0.0
        t, i = jnp.asarray(table), jnp.asarray(idx)
        got, want = np.asarray(K._pick(t, i)), np.asarray(_take_pick(t, i))
        assert got.dtype == want.dtype and got.shape == (R, W)
        np.testing.assert_array_equal(got, want)
        assert np.array_equal(np.signbit(got), np.signbit(want))


@pytest.mark.parametrize("policy", ["cyc", "tp_driven", "ads_tile"])
def test_round_loop_pick_is_bit_identical(policy, monkeypatch):
    """The round loop with the select-chain picks returns the same
    arrays, bit for bit, as with ``take_along_axis`` gathers, in every
    loop call of a run (overflow retries included)."""
    spec = ScenarioSpec(scenario=get_scenario("rate_churn"), policy=policy)
    seeds = list(range(8))
    real = K.simulate

    def outputs():
        calls = []

        def recording(cfg, const_np, lanes_np):
            out = real(cfg, const_np, lanes_np)
            calls.append(out)
            return out

        monkeypatch.setattr(K, "simulate", recording)
        K.clear_kernel_cache()
        # explicit options: both runs start from the default window,
        # not from a pad the first run's overflow retry remembered
        run(spec, seeds=seeds, backend="soa", fallback=False,
            options=soa.SoaOptions())
        monkeypatch.setattr(K, "simulate", real)
        return calls

    got = outputs()
    monkeypatch.setattr(K, "_pick", _take_pick)
    want = outputs()
    K.clear_kernel_cache()
    assert got and len(got) == len(want)
    for g, w in zip(got, want):
        assert g.keys() == w.keys()
        for k in g:
            assert np.array_equal(g[k], w[k], equal_nan=True), k


# ---------------------------------------------------------------------------
# property test over random Markov scenarios (mirrors test_batch.py)
# ---------------------------------------------------------------------------
try:
    from hypothesis import HealthCheck, given, settings, strategies as st
except ImportError:

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_random_scenarios_structurally_match():
        pass

else:

    @given(
        gen_seed=st.integers(0, 1_000),
        run_seed=st.integers(0, 10_000),
        duration=st.floats(0.3, 0.6),
        policy=st.sampled_from(["cyc", "tp_driven", "ads_tile"]),
    )
    @settings(
        deadline=None,
        max_examples=4,
        suppress_health_check=[HealthCheck.too_slow],
    )
    def test_property_random_scenarios_structurally_match(
        gen_seed, run_seed, duration, policy
    ):
        """Random scenario shapes keep the *exact* half of the
        contract: structural invariants match per seed (the KS half
        needs latency mass a 2-seed cell does not have)."""
        scen = default_generator().sample(duration, gen_seed)
        spec = ScenarioSpec(scenario=scen, policy=policy)
        seeds = [run_seed, run_seed + 1]
        got = run(spec, seeds=seeds, backend="soa", fallback=False)
        for s, rb in zip(seeds, got):
            [ra] = run(dataclasses.replace(spec, seed=int(s)),
                       backend="scalar")
            ia = soa.structural_invariants(ra)
            ib = soa.structural_invariants(rb)
            assert ia == ib, (gen_seed, policy, s)


# ---------------------------------------------------------------------------
# tp_driven walks at the scalar engine's decision instants
# ---------------------------------------------------------------------------
TP_KS_TOL = 0.03


@pytest.mark.parametrize("drop_policy", ["soft", "hard"])
def test_tp_driven_matches_the_oracle_on_rate_churn(drop_policy):
    """x1 ``rate_churn`` under the work-conserving baseline: tp_driven
    walks its quota at every queue change, and the SoA kernel walks at
    the same instants, so the drives agree with the scalar engine's far
    inside the round-coalescing envelope, reallocations and hard e2e
    drops included (a walk once a round missed it: KS 0.07, 0.8x the
    reallocations)."""
    spec = ScenarioSpec(scenario=get_scenario("rate_churn"), policy="tp_driven",
                        drop_policy=drop_policy)
    seeds = list(range(16))
    ref = run(spec, seeds=seeds, backend="lockstep")
    got = run(spec, seeds=seeds, backend="soa", fallback=False)
    for a, b in zip(ref, got):
        assert soa.structural_invariants(a) == soa.structural_invariants(b)
    ks = soa.ks_statistic(_pooled_latencies(ref), _pooled_latencies(got))
    assert ks <= TP_KS_TOL, ks
    for metric in ("violation_rate", "realloc_frac"):
        ci_ref = soa.mean_ci([getattr(r, metric) for r in ref])
        ci_got = soa.mean_ci([getattr(r, metric) for r in got])
        assert soa.intervals_overlap(ci_ref, ci_got, pad=1e-9), (
            metric, ci_ref, ci_got)
    for field in ("n_realloc", "n_dropped"):
        n_ref = np.mean([getattr(r, field) for r in ref])
        n_got = np.mean([getattr(r, field) for r in got])
        assert abs(n_got - n_ref) <= 0.15 * n_ref, (field, n_got, n_ref)


def test_tp_driven_does_not_move_with_the_round_length():
    """The round only batches tp_driven's instants: halving ``dt_s``
    leaves its reallocations where they were (a walk once a round made
    +42% of them at 0.5 ms)."""
    spec = ScenarioSpec(scenario=get_scenario("rate_churn"), policy="tp_driven")
    seeds = list(range(8))
    n = {
        dt: np.mean([r.n_realloc for r in run(
            spec, seeds=seeds, backend="soa", fallback=False,
            options=soa.SoaOptions(dt_s=dt))])
        for dt in (1e-3, 5e-4)
    }
    assert abs(n[5e-4] / n[1e-3] - 1.0) <= 0.10, n


def test_tp_driven_follows_the_scalar_through_hot_swaps_and_near_ties():
    """Two drives of the tp cell's deployment that used to leave the
    scalar engine's trajectory for good (half its reallocations, none of
    its rush-hour violations).  In the first, a runner started before
    the 1.2-s hot-swap keeps its earlier sub-deadline, so its lane's EDF
    order is not the round's; in the second, a quota rung a few
    microseconds short decided a walk, a margin that float32 times near
    1.45 s rounded the other way.  The kernel now takes every one of the
    scalar's decisions on both."""
    spec = ScenarioSpec(scenario=get_scenario("rate_churn"), policy="tp_driven")
    seeds = [28524854403661886, 28524854403661864]
    ref = [run(dataclasses.replace(spec, seed=s), backend="scalar")[0]
           for s in seeds]
    got = run(spec, seeds=seeds, backend="soa", fallback=False)
    for a, b in zip(ref, got):
        assert b.n_realloc == a.n_realloc
        assert b.violation_rate == a.violation_rate
        ks = soa.ks_statistic(_pooled_latencies([a]), _pooled_latencies([b]))
        assert ks <= 0.02, ks


def _hand_built_tp_problem():
    """Two jobs on one partition of 4 tiles, ladders (1, 2, 4), each
    readied by its own sensor inside the first 1-ms round: A at 0.2 ms,
    B at 0.6 ms.  Each takes 20 ms on one tile (5 ms on four), so
    nothing finishes in the three rounds."""
    f4, ms = np.float32, 1e-3
    N, S, C = 2, 1, 3
    const = {
        "release": np.zeros(N, f4),
        "e2e": np.full(N, 0.1, f4),
        "sync": np.zeros(N, f4),
        "ckpt": np.full(N, 1e6, f4),
        "preds": np.array([[2], [3]], np.int32),  # sensor columns
        "ert": np.zeros((S, N), f4),
        "sub": np.array([[0.04, 0.05]], f4),
        "tgt": np.array([[0.04, 0.05]], f4),
        "pdop": np.ones((S, N), f4),
        "part": np.zeros((S, N), f4),
        "cands": np.tile(np.array([1.0, 2.0, 4.0], f4), (S, N, 1)),
        "caps": np.array([[4.0]], f4),
        "hops": np.ones((S, 1), f4),
        "staged": np.zeros((S, 1), f4),
        "swap": np.zeros(S, bool),
        "t0": np.array([0.0, 1.0, 2.0], f4) * ms,
        "t1": np.array([1.0, 2.0, 3.0], f4) * ms,
        "seg": np.zeros(3, np.int32),
        "lo": np.zeros(3, np.int32),
        "entry": np.array([True, False, False]),
        "perm": np.tile(np.arange(N, dtype=np.int32), (3, 1)),
        "iperm": np.tile(np.arange(N, dtype=np.int32), (3, 1)),
    }
    cfg = K.KernelConfig(
        policy=K.POLICY_IDS["tp_driven"], R=1, W=N, C=C, PM=1, P=1,
        tile_flops=1.0, fixed_s=20e-6, decision_s=8e-6, per_hop_s=0.0,
        inv_bw=1.0 / 20e9,
    )
    lanes = {
        "work": np.full((1, N), 0.02, f4),
        "io": np.zeros((1, N), f4),
        # sensors A and B finish at 0.2 and 0.6 ms; the last column is
        # the resolved dummy
        "codes0": np.array([[np.inf, np.inf, 0.2 * ms, 0.6 * ms, 0.0]], f4),
    }
    return cfg, const, lanes


def _scalar_walk(policy, jobs, running, now):
    """``TpDrivenPolicy._reallocate`` on one unstalled partition of 4
    tiles holding ``jobs`` at ``now``; returns its (resizes, starts)."""
    from types import SimpleNamespace

    calls = []
    sim = SimpleNamespace(
        parts=[SimpleNamespace(stalled=False, capacity=4, running=running)],
        jobs={j.jid: j for j in jobs},
        hw=SimpleNamespace(tile_flops=1.0),
        eligible_jobs=lambda p, admitted_only=True: [
            j for j in jobs if j.state == JobState.READY],
        resize=lambda p, resize, starts=None: calls.append((resize, starts)),
    )
    policy._reallocate(sim, 0, now)
    return calls[0] if calls else ({}, {})


def test_tp_driven_walks_at_each_instant_of_a_hand_built_round():
    """Round 0 holds three instants: A ready at 0.2 ms, B ready at
    0.6 ms, and the end of the stall B's walk began; rounds 1 and 2 hold
    none.  The kernel walks at each instant of round 0 (three walks, one
    resize) and not at all in rounds 1 and 2, taking the decisions the
    scalar policy takes at those instants."""
    import jax
    from functools import partial

    ms = 1e-3
    cfg, const_np, lanes = _hand_built_tp_problem()
    const = {k: jax.numpy.asarray(v) for k, v in const_np.items()}
    const["work"] = jax.numpy.asarray(lanes["work"])
    const["io"] = jax.numpy.asarray(lanes["io"])
    body = jax.jit(K._build_loop(cfg, const).body)
    zf = partial(np.zeros, dtype=np.float32)
    fills = {K.F_FIN: np.inf, K.F_SUB: np.inf, K.F_TGT: np.inf,
             K.F_PART: -1.0, K.F_REM: 1.0}
    carry = (tuple(np.full((1, 2), fills.get(f, 0.0), np.float32)
                   for f in range(K.NFIELDS)),
             lanes["codes0"], zf((1, 1)), zf((1, 1)), zf((1, 1)),
             zf(1), zf(1), zf(1), zf(1), (zf((1, 2)), zf((1, 2))), zf((1, 1)),
             np.zeros_like(lanes["codes0"]))
    walks, resizes = [], []
    for r in range(3):
        carry = body(r, carry)
        walks.append(float(carry[8][0]))
        resizes.append(float(carry[5][0]))
    st, stall_end = carry[0], float(carry[2][0, 0])
    # the scalar policy at the same instants: A alone takes all four
    # tiles (quota 1, bumped 1 -> 2 -> 4); B's walk quotas both to one
    # tile and bumps both to two, shrinking A (a stall of 28 us plus 2 MB
    # at 20 GB/s) and starting B behind it; the resume's walk, on A's
    # progress synced at 0.6 ms, keeps both
    pol = TpDrivenPolicy()
    pol._cands = {"a": (1, 2, 4), "b": (1, 2, 4)}
    a = Job(0, "a", 0, 0, 0.0, False, 0.02, 0.0, 0.0, 0, 0.0, 0.04, 0.1, 1,
            state=JobState.READY)
    b = Job(1, "b", 0, 0, 0.0, False, 0.02, 0.0, 0.0, 0, 0.0, 0.05, 0.1, 1)
    assert _scalar_walk(pol, [a, b], {}, 0.2 * ms) == ({}, {0: 4})
    a.state, a.dop = JobState.RUNNING, 4
    b.state = JobState.READY
    assert _scalar_walk(pol, [a, b], {0: 4}, 0.6 * ms) == ({0: 2}, {1: 2})
    stall = 20e-6 + 8e-6 + 2e6 / 20e9
    a.dop, a.progress, b.state, b.dop = 2, 0.4 / 5.0, JobState.RUNNING, 2
    assert _scalar_walk(pol, [a, b], {0: 2, 1: 2}, 0.6 * ms + stall) == ({}, {})

    assert walks == [3.0, 3.0, 3.0]
    assert resizes == [1.0, 1.0, 1.0]
    assert stall_end == pytest.approx(0.6 * ms + stall, rel=1e-5)
    start, dop, fin = (np.asarray(st[f][0]) for f in (K.F_START, K.F_DOP, K.F_FIN))
    np.testing.assert_allclose(start, [0.2 * ms, 0.6 * ms], rtol=1e-5)
    np.testing.assert_array_equal(dop, [2.0, 2.0])
    # A ran 0.4 ms on four tiles, then resumes on two after the stall;
    # B starts when the stall ends
    np.testing.assert_allclose(
        fin, [0.6 * ms + stall + (1 - 0.08) * 0.01, 0.6 * ms + stall + 0.01],
        rtol=1e-5)

