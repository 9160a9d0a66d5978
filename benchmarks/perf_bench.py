"""Perf — simulator performance benchmark (jobs/s + sweep wall-clock).

Tracks the engine's speed headline over time so perf regressions are
visible in CI artifacts (``BENCH_sim.json`` via ``benchmarks.run
--out``).  Three measurements:

1. **Trace/job construction** — Simulator builds per second on a
   standard heavy workload (cockpit_replicas=4, 2 s horizon), both the
   single-build pattern and the paired-sweep pattern (one sampled
   trace shared across two policies, the steady state of ``sweep()``).
2. **Sampling kernel** — throughput of the batched counter-based trace
   sampler on the standard skeleton (jobs sampled per second; the
   legacy scalar ``RandomState`` reference it was once compared
   against is gone — the counter-based stream contract is the only
   sampling path).
3. **End-to-end sweep** — wall-clock for a pinned Monte-Carlo sweep
   (fixed 6-mode Markov generator, so the workload stays comparable as
   bundled defaults evolve), the figS_scenarios fleet view.
4. **Batched lockstep engine** — B-seed Monte-Carlo batch of one
   pinned Markov scenario through ``run(spec, seeds=...)`` (lockstep
   backend) vs the same seeds through a warm scalar loop
   (``perf_batch_*``; bit-identity between the two paths is asserted
   separately by ``benchmarks.check_equivalence``).
5. **SoA jax backend** — the same pinned scenario through
   ``run(spec, seeds=..., backend="soa")`` at R=8 and R=64
   (``perf_soa_*_r{8,64}``),
   steady-state per-run wall-clock with the jit compile reported
   separately (``check_equivalence --mode distributional`` asserts
   the statistical-equivalence side).

``PREPR_*`` constants are the pre-PR numbers measured on the reference
dev container when this benchmark was introduced (engine @ b7c00aa:
scalar per-job sampling, no skeleton cache); ``speedup_vs_prepr`` is
only meaningful on comparable hardware and is recorded for the PR's
acceptance trail, not as a portable metric.
"""
from __future__ import annotations

import dataclasses
import gc
import time

from repro.core.experiment import ExperimentSpec, build_stack, make_policy
from repro.core.sim import SimConfig, Simulator
from repro.core.sim.trace import build_skeleton, sample_trace
from repro.scenarios import sweep
from repro.scenarios.runner import ScenarioSpec, run as run_specs
from repro.scenarios.script import MarkovScenarioGenerator

from .common import emit

#: pre-PR reference numbers (dev container, engine @ b7c00aa)
PREPR_BUILD_JOBS_PER_S = 60_882.0
PREPR_SWEEP_8X2_S = 3.430

#: pinned 6-mode generator: the e2e workload must not drift when the
#: bundled DEFAULT_TRANSITIONS change
PERF_TRANSITIONS = {
    "urban": {
        "highway": 0.30,
        "parking": 0.13,
        "adverse_weather": 0.14,
        "night": 0.09,
        "rush_hour": 0.12,
        "urban": 0.22,
    },
    "highway": {
        "urban": 0.40,
        "adverse_weather": 0.15,
        "night": 0.10,
        "rush_hour": 0.05,
        "highway": 0.30,
    },
    "parking": {"urban": 0.90, "parking": 0.10},
    "adverse_weather": {"urban": 0.50, "highway": 0.30, "adverse_weather": 0.20},
    "night": {"urban": 0.40, "highway": 0.40, "night": 0.20},
    "rush_hour": {"urban": 0.55, "highway": 0.20, "rush_hour": 0.25},
}
PERF_DWELL = {
    "urban": 0.8,
    "highway": 1.0,
    "parking": 0.5,
    "adverse_weather": 0.7,
    "night": 0.9,
    "rush_hour": 0.6,
}


def _build_benchmark(duration: float, seed: int) -> None:
    spec = ExperimentSpec(
        policy="ads_tile", tiles=400, cockpit_replicas=4, duration_s=2.0, seed=seed
    )
    wf, _hw, model, compiler = build_stack(spec)
    sched = compiler.compile(model, wf)
    pol_a, pol_b = make_policy("ads_tile"), make_policy("tp_driven")
    reps = max(3, int(round(20 * duration)))

    # warm the skeleton/unroll caches (steady state of any sweep)
    Simulator(wf, model, sched, pol_a, SimConfig(duration_s=2.0, seed=0))

    t0 = time.perf_counter()
    n = 0
    for i in range(reps):
        cfg = SimConfig(duration_s=2.0, seed=seed + i)
        n += len(Simulator(wf, model, sched, pol_a, cfg).jobs)
    dt = time.perf_counter() - t0
    jps = n / dt
    emit(
        "perf_build_single",
        dt / reps * 1e6,
        f"jobs_per_s={jps:.0f};"
        f"prepr_ref={PREPR_BUILD_JOBS_PER_S:.0f};"
        f"speedup_vs_prepr={jps / PREPR_BUILD_JOBS_PER_S:.2f}",
    )

    # paired-sweep pattern: one trace, two policies
    t0 = time.perf_counter()
    n = 0
    for i in range(reps):
        skel = build_skeleton(wf, None, 2.0)
        tr = sample_trace(skel, model, None, seed + i)
        for pol in (pol_a, pol_b):
            cfg = SimConfig(duration_s=2.0, seed=seed + i, trace=tr)
            n += len(Simulator(wf, model, sched, pol, cfg).jobs)
    dt = time.perf_counter() - t0
    jps = n / dt
    emit(
        "perf_build_paired",
        dt / (2 * reps) * 1e6,
        f"jobs_per_s={jps:.0f};"
        f"speedup_vs_prepr={jps / PREPR_BUILD_JOBS_PER_S:.2f}",
    )

    # sampling kernel: batched counter-based draws, same skeleton
    skel = build_skeleton(wf, None, 2.0)
    t0 = time.perf_counter()
    for i in range(reps):
        sample_trace(skel, model, None, seed + i)
    dt_batched = time.perf_counter() - t0
    emit(
        "perf_sample_batched",
        dt_batched / reps * 1e6,
        f"jobs_per_s={skel.n * reps / dt_batched:.0f}",
    )


def _recorder_benchmark(duration: float, seed: int) -> None:
    """Flight-recorder cost on a pinned engine run: hooks compiled in
    but recorder detached (``perf_recorder_off``, the default every
    sweep pays) vs a :class:`~repro.obs.TraceRecorder` attached
    (``perf_recorder_on``).  The *off* row is the one the perf gate
    asserts on — the hooks' ``if rec is not None`` guards must stay
    invisible in the wall-clock."""
    from repro.obs import TraceRecorder

    spec = ExperimentSpec(
        policy="ads_tile", tiles=400, cockpit_replicas=4, duration_s=2.0, seed=seed
    )
    wf, _hw, model, compiler = build_stack(spec)
    sched = compiler.compile(model, wf)
    reps = max(3, int(round(10 * duration)))

    def loop(make_rec) -> float:
        t0 = time.perf_counter()
        for i in range(reps):
            pol = make_policy("ads_tile")
            cfg = SimConfig(duration_s=2.0, seed=seed + i, recorder=make_rec())
            Simulator(wf, model, sched, pol, cfg).run()
        return time.perf_counter() - t0

    loop(lambda: None)  # warm caches
    dt_off = loop(lambda: None)
    dt_on = loop(TraceRecorder)
    emit("perf_recorder_off", dt_off / reps * 1e6, f"seconds={dt_off:.3f}")
    emit(
        "perf_recorder_on",
        dt_on / reps * 1e6,
        f"overhead_pct={100.0 * (dt_on - dt_off) / dt_off:.1f}",
    )


def _sweep_benchmark(duration: float, seed: int) -> None:
    gen = MarkovScenarioGenerator(transitions=PERF_TRANSITIONS, mean_dwell_s=PERF_DWELL)
    n = max(2, int(round(8 * duration)))
    gc.collect()
    t0 = time.perf_counter()
    rows = sweep(
        n,
        policies=("ads_tile", "tp_driven"),
        duration_s=2.0,
        seed=seed,
        jobs=1,
        generator=gen,
    )
    dt = time.perf_counter() - t0
    derived = f"runs={len(rows)};seconds={dt:.3f}"
    if n == 8:
        # directly comparable to the recorded pre-PR wall-clock
        derived += (
            f";prepr_ref_s={PREPR_SWEEP_8X2_S:.3f}"
            f";speedup_vs_prepr={PREPR_SWEEP_8X2_S / dt:.2f}"
        )
    emit("perf_sweep_e2e", dt / max(len(rows), 1) * 1e6, derived)


#: lockstep per-run wall-clock measured by ``_batch_benchmark`` this
#: process, keyed by policy — lets ``_soa_benchmark`` derive a
#: same-machine, same-run speedup without re-measuring the baseline
_BATCH_US_PER_RUN: dict = {}


def _batch_benchmark(duration: float, seed: int) -> None:
    """Batched lockstep engine vs a warm scalar loop: one pinned Markov
    scenario (same 6-mode generator as ``perf_sweep_e2e``), B seeds per
    policy, both paths starting from warm skeleton/stack caches.  The
    ``us_per_call`` is the batched per-run wall-clock (the number the
    perf gate regression-checks); ``speedup_vs_scalar`` records how far
    the fused lanes beat the scalar loop on the *same* machine and run,
    so it is portable in a way ``speedup_vs_prepr`` is not.  The
    speedup is bounded well below the lane count by the bit-identity
    contract — every lane must replay the scalar engine's exact event
    stream — see docs/performance.md#batched-monte-carlo-engine for
    the ceiling analysis."""
    gen = MarkovScenarioGenerator(transitions=PERF_TRANSITIONS, mean_dwell_s=PERF_DWELL)
    scen = gen.sample(2.0, seed)
    b = max(2, int(round(8 * duration)))
    seeds = list(range(seed, seed + b))
    for pol, name in (("ads_tile", "perf_batch_ads"), ("tp_driven", "perf_batch_tp")):
        spec = ScenarioSpec(scenario=scen, policy=pol)
        # warm both paths (skeleton, stack, schedule caches)
        run_specs(spec, seeds=seeds[:2])
        run_specs(dataclasses.replace(spec, seed=seeds[0]))
        gc.collect()
        t0 = time.perf_counter()
        for s in seeds:
            run_specs(dataclasses.replace(spec, seed=s))
        dt_scalar = time.perf_counter() - t0
        t0 = time.perf_counter()
        run_specs(spec, seeds=seeds)
        dt_batch = time.perf_counter() - t0
        _BATCH_US_PER_RUN[pol] = dt_batch / b * 1e6
        emit(
            name,
            dt_batch / b * 1e6,
            f"batch={b};speedup_vs_scalar={dt_scalar / dt_batch:.2f};"
            f"scalar_s={dt_scalar:.3f};batch_s={dt_batch:.3f}",
        )


def _soa_benchmark(duration: float, seed: int) -> None:
    """Structure-of-arrays jax backend on the same pinned Markov
    scenario: R-seed cells at R=8 and R=64 through
    ``run(spec, seeds=..., backend="soa")``.  Each cell is measured
    twice — the first call
    pays the jit compile for that (policy, R) shape, the second is the
    steady state — and ``us_per_call`` reports the *steady* per-run
    wall-clock (the regression-gated number) with the compile cost in
    the derived fields, per the warm-up-excluded convention.
    ``speedup_vs_lockstep`` compares against ``_batch_benchmark``'s
    same-process lockstep per-run time; see
    docs/performance.md#soa-backend for why the single-core envelope
    of this ratio is modest (the round kernel's op-dispatch cost does
    not amortize with R on one core) and where the backend does win."""
    gen = MarkovScenarioGenerator(transitions=PERF_TRANSITIONS, mean_dwell_s=PERF_DWELL)
    scen = gen.sample(2.0, seed)
    for pol, name in (("ads_tile", "perf_soa_ads"), ("tp_driven", "perf_soa_tp")):
        spec = ScenarioSpec(scenario=scen, policy=pol)
        for runs in (8, 64):
            seeds = list(range(seed, seed + runs))
            gc.collect()
            t0 = time.perf_counter()
            run_specs(spec, seeds=seeds, backend="soa", fallback=False)
            dt_cold = time.perf_counter() - t0
            t0 = time.perf_counter()
            run_specs(spec, seeds=seeds, backend="soa", fallback=False)
            dt_warm = time.perf_counter() - t0
            derived = (
                f"runs={runs};compile_s={max(dt_cold - dt_warm, 0.0):.3f};"
                f"cold_s={dt_cold:.3f};warm_s={dt_warm:.3f}"
            )
            lockstep_us = _BATCH_US_PER_RUN.get(pol)
            if lockstep_us:
                derived += (
                    f";speedup_vs_lockstep="
                    f"{lockstep_us / (dt_warm / runs * 1e6):.2f}"
                )
            emit(f"{name}_r{runs}", dt_warm / runs * 1e6, derived)


def run(duration: float = 1.0, seed: int = 1) -> None:
    _build_benchmark(duration, seed)
    _recorder_benchmark(duration, seed)
    _sweep_benchmark(duration, seed)
    _batch_benchmark(duration, seed)
    _soa_benchmark(duration, seed)
