"""Reference seed fan: the lockstep engine over the copied scalar engine.

The same construction as the system's ``run(spec, seeds=...,
backend="lockstep")`` at the time the benchmark was defined (its
``_prepare_run``, ``_make_run_policy``, ``_sim_config`` and
``_run_lockstep_seeds``), for reactive replanning, the only mode the
benchmark's cells use.  Each lane is bit-identical to the scalar engine
run with that seed.
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence

from .core.experiment import ExperimentSpec, build_stack, make_policy
from .core.runtime import OnlineReplanner, SchedulePortfolio
from .core.sim import SimConfig, SimReport
from .core.sim.batch import LaneSimulator, run_batch, sample_trace_batch
from .core.sim.trace import build_skeleton
from .scenarios import Burst, ModeSegment, ScenarioScript, SensorDropout, get_mode

__all__ = ["RefSpec", "scenario_from_data", "run_seeds"]


@dataclasses.dataclass
class RefSpec(ExperimentSpec):
    """A deployment (the ``ExperimentSpec`` fields) driving one script."""

    scenario: ScenarioScript = None
    replan: bool = True


def scenario_from_data(d: dict) -> ScenarioScript:
    """A script from a traffic file's ``scenario`` object."""
    return ScenarioScript(
        name=d["name"],
        segments=tuple(ModeSegment(m, float(s)) for m, s in d["segments"]),
        bursts=tuple(Burst(**b) for b in d.get("bursts", ())),
        dropouts=tuple(SensorDropout(**x) for x in d.get("dropouts", ())),
    )


def run_seeds(spec: RefSpec, seeds: Sequence[int]) -> List[SimReport]:
    """One report per seed, each the scalar engine's for that seed."""
    scen = spec.scenario
    wf, _hw, model, compiler = build_stack(spec)
    initial_mode = scen.segments[0].mode
    wanted = scen.modes() if spec.replan else (initial_mode,)
    portfolio = SchedulePortfolio.compile(
        model, wf, {m: get_mode(m) for m in wanted}, compiler,
    )
    sched = portfolio.schedules[initial_mode]
    duration = scen.duration_s
    skel = build_skeleton(wf, scen, duration)
    btrace = sample_trace_batch(skel, model, scen, seeds)
    sims = []
    for k, s in enumerate(seeds):
        policy = make_policy(spec.policy)
        if spec.replan:
            policy.replanner = OnlineReplanner(portfolio)
        cfg = SimConfig(
            duration_s=duration,
            seed=int(s),
            drop_policy=spec.drop_policy,
            scenario=scen,
            trace=btrace.lane(k),
        )
        sims.append(LaneSimulator(wf, model, sched, policy, cfg))
    return run_batch(sims)
