"""Batched lockstep engine vs the scalar reference engine.

The contract under test is bit-identity: every lane of a seed-fan or
group ``run(..., backend="lockstep")`` must produce a
:class:`~repro.core.sim.engine.SimReport` exactly equal (via
``report_digest``, every float verbatim) to the same run through the
scalar backend.  Every bundled scenario without degradations x every
policy x both drop policies is checked here (``benchmarks.check_equivalence``
repeats the sweep in CI; ``test_degrade.py`` covers the degraded
scenario), plus the de-batching edge cases (unsupported lane, attached
recorder) and a property test over random scenarios/workloads.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.experiment import POLICIES
from repro.core.sim import batch as batch_mod
from repro.core.sim.batch import reports_identical
from repro.obs import TraceRecorder
from repro.scenarios.runner import ScenarioSpec, run
from repro.scenarios.script import (
    BUNDLED_SCENARIOS,
    default_generator,
    get_scenario,
)

SEEDS = [0, 7]
NOMINAL_SCENARIOS = tuple(
    name for name, s in BUNDLED_SCENARIOS.items() if not s.has_degradations
)


def _scalar(spec: ScenarioSpec, seed: int):
    return run(dataclasses.replace(spec, seed=int(seed)), backend="scalar")[0]


def _spy_scalar_lanes(monkeypatch):
    """Record every sim that de-batches to the scalar fallback lane."""
    seen = []
    orig = batch_mod._ScalarLane
    monkeypatch.setattr(
        batch_mod,
        "_ScalarLane",
        lambda sim: seen.append(sim) or orig(sim),
    )
    return seen


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("drop_policy", ["soft", "hard"])
@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("scenario", NOMINAL_SCENARIOS)
def test_batched_reports_bit_identical(scenario, policy, drop_policy):
    spec = ScenarioSpec(
        scenario=get_scenario(scenario), policy=policy, drop_policy=drop_policy
    )
    reports = run(spec, seeds=SEEDS, backend="lockstep")
    for s, rb in zip(SEEDS, reports):
        assert reports_identical(_scalar(spec, s), rb), (
            scenario,
            policy,
            drop_policy,
            s,
        )


def test_divergent_lane_falls_back_to_scalar(monkeypatch):
    # a predictive replanner is outside the fused cores' support set:
    # its lane must de-batch to the scalar driver (and only its lane),
    # while the whole batch stays bit-identical to per-run execution
    scen = get_scenario("calm_to_rush")
    specs = [
        ScenarioSpec(scenario=scen, policy="ads_tile", seed=3),
        ScenarioSpec(
            scenario=scen, policy="ads_tile", seed=3, replan_mode="predictive"
        ),
    ]
    seen = _spy_scalar_lanes(monkeypatch)
    reports = run(specs, backend="lockstep")
    assert len(seen) == 1
    assert seen[0].cfg.seed == 3
    assert not batch_mod.fast_lane_supported(seen[0])
    for spec, rb in zip(specs, reports):
        assert reports_identical(run(spec, backend="scalar")[0], rb)


def test_recorder_lane_debatches(monkeypatch):
    # recorder hooks live on engine paths the fused loop elides, so a
    # recorded lane runs scalar inside the lockstep loop — without
    # perturbing its own results or any other lane's
    spec = ScenarioSpec(scenario=get_scenario("calm_to_rush"), policy="ads_tile")
    seen = _spy_scalar_lanes(monkeypatch)
    reports = run(spec, seeds=SEEDS, backend="lockstep",
                  recorders={1: TraceRecorder()})
    assert [sim.cfg.recorder is not None for sim in seen] == [True]
    assert reports[0].attribution is None
    assert reports[1].attribution is not None
    for s, rb in zip(SEEDS, reports):
        assert reports_identical(_scalar(spec, s), rb)


def test_mixed_skeleton_batch_rejected():
    a = ScenarioSpec(scenario=get_scenario("calm_to_rush"), policy="cyc")
    b = ScenarioSpec(scenario=get_scenario("commute"), policy="cyc")
    with pytest.raises(ValueError, match="skeleton"):
        run([a, b], backend="lockstep")


# ---------------------------------------------------------------------------
# property test: random scenarios/workloads, scalar-vs-batched equality.
def test_ndtri_jnp_matches_numpy_at_stream_boundaries():
    """The stream contract's uniforms are ``(m + 0.5) * 2**-53``; the
    top draw's real value ``1 - 2**-54`` rounds to exactly 1.0 in
    binary64, where the NumPy ``ndtri`` array path returns ``+inf`` —
    the device mirror must agree on every reachable input, boundary
    included (not clip it to a finite tail value)."""
    import jax
    import jax.numpy as jnp

    from repro.core.latency_model import ndtri
    from repro.core.sim.batch import _ndtri_jnp

    top = (np.float64((1 << 53) - 1) + 0.5) * 2.0**-53
    assert top == 1.0  # the binary64 fact the boundary branch exists for
    bot = 0.5 * 2.0**-53  # the stream's smallest draw
    qs = np.array([bot, 1e-12, 0.02, 0.3, 0.99, 1.0 - 2.0**-52, top])
    with jax.enable_x64(True):
        got = np.asarray(_ndtri_jnp(jnp.asarray(qs)))
    want = ndtri(qs)
    assert want[-1] == np.inf and got[-1] == np.inf
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-15)


# Guarded import (not importorskip) so a missing hypothesis skips only
# this test, never the pinned equivalence tests above.
try:
    from hypothesis import HealthCheck, given, settings, strategies as st
except ImportError:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_random_scenarios_match_scalar():
        pass
else:
    @given(
        gen_seed=st.integers(0, 1_000),
        run_seed=st.integers(0, 10_000),
        duration=st.floats(0.3, 0.6),
        policy=st.sampled_from(["cyc", "tp_driven", "ads_tile"]),
        replicas=st.integers(1, 2),
    )
    @settings(
        deadline=None,
        max_examples=8,
        suppress_health_check=[HealthCheck.too_slow],
    )
    def test_property_random_scenarios_match_scalar(
        gen_seed, run_seed, duration, policy, replicas
    ):
        scen = default_generator().sample(duration, gen_seed)
        spec = ScenarioSpec(scenario=scen, policy=policy, cockpit_replicas=replicas)
        seeds = [run_seed, run_seed + 1]
        reports = run(spec, seeds=seeds, backend="lockstep")
        for s, rb in zip(seeds, reports):
            assert reports_identical(_scalar(spec, s), rb), (gen_seed, policy, s)
