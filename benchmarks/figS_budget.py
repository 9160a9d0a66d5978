"""Fig. S-budget — tile-budget autotuner: tiles saved vs work-conserving.

The paper's resource-efficiency headline is that ADS-Tile needs up to
~32 % fewer tiles than work-conserving baselines at the same service
level, because joint (quantile x DoP x partition) search plus isolation
lets it shed the overprovisioning the baselines need against
interference.  This suite reproduces the tiles-saved-vs-baseline curve
on the scenario subsystem:

1. The **work-conserving baseline** (Tp-driven, single shared bin)
   compiles its conservative full-chip portfolio; its simulated
   deadline-miss rate defines the *service target* both systems must
   meet.  (A budget-capped baseline is also swept for transparency —
   work-conserving tables collapse rather than compress: the
   autotuner's relaxed-q single-bin points trade a handful of tiles
   for order-of-magnitude worse miss rates.)
2. **ADS-Tile** walks a grid of predicted-miss targets through the
   autotuner (`SchedulePortfolio.compile(target_miss=...)`), each
   compiling the cheapest frontier point per mode, and keeps the
   fewest-tiles portfolio whose *simulated* miss rate still meets the
   baseline's service target on paired traces.

Two parts, two tile metrics (both reported; each part headlines the
one that matches its structure):

* ``rate_churn`` (scripted night -> urban -> rush-hour rate churn with
  a burst): **peak** reserved tiles — the provisioning headline, the
  scenario-world analogue of the paper's static tiles-saved figure.
* A Markov sweep of bursty congested-commute drives over the same
  sensor-rate-churn mode set: **mean** reserved tiles (time-weighted
  ``peak_tiles`` of the active table).  Per-mode tables release tiles
  during light segments; the work-conserving bin holds its full
  reservation for the whole drive by construction, so the mean is the
  honest fleet-scale comparison when drives are random.

Headline per part: ``saved_frac`` = 1 - ads_tile tiles / baseline
tiles, under ads_tile miss <= baseline miss (exactly paired job-level
traces).  ``--duration`` scales seeds / sampled drives, not per-drive
length.
"""
from __future__ import annotations

import dataclasses

from repro.core.experiment import build_stack
from repro.core.runtime import SchedulePortfolio
from repro.scenarios import ScenarioSpec, get_mode, get_scenario
from repro.scenarios.runner import _run_group, build_trace, run as run_specs
from repro.scenarios.script import MarkovScenarioGenerator

from .common import emit

#: predicted-miss targets walked from cheap to conservative; None is
#: the legacy most-conservative-feasible compile (always meets the
#: baseline target in practice, so the walk cannot come back empty)
TARGET_GRID = (0.45, 0.4, 0.35, 0.3, None)

#: transparency sweep of the capped work-conserving baseline
BASE_TARGETS = (0.45, 0.35)

#: part 2's drive distribution: a bursty congested commute over the
#: rate-churn mode set (15 -> 30 -> 60 Hz camera regimes), the regime
#: where per-mode tile budgets differ enough to matter
COMMUTE_TRANSITIONS = {
    "night": {"urban": 0.7, "rush_hour": 0.3},
    "urban": {"rush_hour": 0.5, "night": 0.5},
    "rush_hour": {"urban": 0.6, "night": 0.4},
}
COMMUTE_DWELL = {"night": 0.6, "urban": 0.6, "rush_hour": 0.8}
COMMUTE_BURST_PROB = 0.5
#: part 2 runs a 35 % heavier deployment: resource efficiency is a
#: statement about the capacity-bound regime — at light load any
#: full-chip baseline meets deadlines and there is nothing to save
COMMUTE_LOAD_FACTOR = 1.35

#: part 3 sweeps the load factor itself to trace the full
#: tiles-saved-vs-load curve (the paper's Fig. 13 analogue): from the
#: light regime (nothing to save) through part 2's operating point
#: into overload.  Cheap on the SoA backend — every grid point is an
#: R-seed cell of one pinned drive, so the jit compile is paid once
#: per policy shape and each point costs R kernel runs.
LOAD_GRID = (1.0, 1.15, 1.35, 1.5)
#: part 3's reduced autotuner walk per load point (the full
#: TARGET_GRID transparency sweep is part 2's job; the curve needs
#: the envelope: one relaxed point + the conservative fallback)
LOAD_TARGETS = (0.35, None)


def _portfolio_tiles(pf: SchedulePortfolio) -> int:
    """Tiles the portfolio provisions: the worst mode's reservation."""
    return max(p.tiles for p in pf.selected.values())


def _compile(spec: ScenarioSpec, mode_names, target) -> SchedulePortfolio:
    wf, _hw, model, compiler = build_stack(spec)
    modes = {m: get_mode(m) for m in mode_names}
    return SchedulePortfolio.compile(
        model, wf, modes, compiler, target_miss=target
    )


def _tag(target) -> str:
    return "cons" if target is None else f"t{int(round(target * 100)):02d}"


def _pick_cheapest(candidates, viol_base):
    """Fewest-tiles candidate ``(tiles, viol, target)`` whose simulated
    miss meets the baseline's.  If none qualifies the *lowest-miss*
    candidate backstops — never a cheap table that trades the service
    level away (the headline must stay an equal-or-better-miss claim)."""
    ok = [c for c in candidates if c[1] <= viol_base + 1e-12]
    if ok:
        return min(ok, key=lambda c: (c[0], c[1]))
    return min(candidates, key=lambda c: (c[1], c[0]))


def run(duration: float = 1.0, seed: int = 1) -> None:
    # -- part 1: rate_churn, paired seeds, peak-reservation metric ------
    scen = get_scenario("rate_churn")
    seeds = tuple(range(seed, seed + max(2, int(round(3 * duration)))))
    spec_ads = ScenarioSpec(scenario=scen, policy="ads_tile", seed=seed)
    spec_tp = ScenarioSpec(scenario=scen, policy="tp_driven", seed=seed)
    traces = {}
    for s in seeds:
        traces[s] = build_trace(dataclasses.replace(spec_ads, seed=s))

    def churn_stats(spec, pf):
        viol, mean_tiles = 0.0, 0.0
        for s in seeds:
            sp = dataclasses.replace(spec, seed=s, portfolio=pf)
            [r] = run_specs(sp, trace=traces[s])
            viol += r.violation_rate
            mean_tiles += r.tiles_reserved_mean
        return viol / len(seeds), mean_tiles / len(seeds)

    pf_base = _compile(spec_tp, scen.modes(), None)
    tiles_base = _portfolio_tiles(pf_base)
    viol_base, mean_base = churn_stats(spec_tp, pf_base)
    emit(
        "figS_budget_churn_base",
        tiles_base,
        f"tiles={tiles_base};mean_tiles={mean_base:.1f};"
        f"viol={viol_base:.4f};seeds={len(seeds)}",
    )
    for t in BASE_TARGETS:
        pf_t = _compile(spec_tp, scen.modes(), t)
        v, _m = churn_stats(spec_tp, pf_t)
        emit(
            f"figS_budget_churn_base_{_tag(t)}",
            _portfolio_tiles(pf_t),
            f"tiles={_portfolio_tiles(pf_t)};viol={v:.4f}",
        )

    candidates = []
    for t in TARGET_GRID:
        pf_t = _compile(spec_ads, scen.modes(), t)
        tiles = _portfolio_tiles(pf_t)
        v, m = churn_stats(spec_ads, pf_t)
        candidates.append((tiles, v, t))
        emit(
            f"figS_budget_churn_ads_{_tag(t)}",
            tiles,
            f"tiles={tiles};mean_tiles={m:.1f};viol={v:.4f}",
        )
    tiles_ads, viol_ads, t_pick = _pick_cheapest(candidates, viol_base)
    saved = 1.0 - tiles_ads / tiles_base
    emit(
        "figS_budget_churn_headline",
        saved * 1e6,
        f"tiles_ads={tiles_ads};tiles_base={tiles_base};"
        f"saved_frac={saved:.3f};viol_ads={viol_ads:.4f};"
        f"viol_base={viol_base:.4f};target={_tag(t_pick)}",
    )

    # -- part 2: bursty commute sweep, mean-reservation metric ----------
    gen = MarkovScenarioGenerator(
        transitions=COMMUTE_TRANSITIONS,
        mean_dwell_s=COMMUTE_DWELL,
        burst_prob=COMMUTE_BURST_PROB,
    )
    all_modes = sorted(gen.transitions)
    mode_defs = {m: get_mode(m) for m in all_modes}
    n = max(4, int(round(8 * duration)))
    base_spec = ScenarioSpec(
        scenario=scen,
        policy="tp_driven",
        seed=seed,
        mode_defs=mode_defs,
        load_factor=COMMUTE_LOAD_FACTOR,
    )
    pf_base = _compile(base_spec, all_modes, None)
    ads_pfs = {
        t: _compile(
            dataclasses.replace(base_spec, policy="ads_tile"), all_modes, t
        )
        for t in TARGET_GRID
    }

    rows = []
    for i in range(n):
        s_i = seed * 100003 + i
        script = gen.sample(2.0, seed=s_i)
        group = [
            ScenarioSpec(
                scenario=script,
                policy="tp_driven",
                seed=s_i,
                mode_defs=mode_defs,
                load_factor=COMMUTE_LOAD_FACTOR,
                portfolio=pf_base,
            )
        ]
        for t in TARGET_GRID:
            group.append(
                ScenarioSpec(
                    scenario=script,
                    policy="ads_tile",
                    seed=s_i,
                    mode_defs=mode_defs,
                    load_factor=COMMUTE_LOAD_FACTOR,
                    portfolio=ads_pfs[t],
                    target_miss=t,
                )
            )
        rows.extend(_run_group(group))

    stats = {}
    for row in rows:
        key = (str(row["policy"]), row["target_miss"])
        stats.setdefault(key, []).append(
            (float(row["violation_rate"]), float(row["tiles_reserved_mean"]))
        )

    def mean(xs):
        return sum(xs) / len(xs)

    viol_base = mean([v for v, _m in stats[("tp_driven", None)]])
    mean_base = mean([m for _v, m in stats[("tp_driven", None)]])
    emit(
        "figS_budget_markov_base",
        mean_base,
        f"tiles={_portfolio_tiles(pf_base)};mean_tiles={mean_base:.1f};"
        f"viol={viol_base:.4f};n={n}",
    )
    candidates = []
    for t in TARGET_GRID:
        v = mean([x for x, _m in stats[("ads_tile", t)]])
        m = mean([x for _v, x in stats[("ads_tile", t)]])
        candidates.append((m, v, t))
        emit(
            f"figS_budget_markov_ads_{_tag(t)}",
            m,
            f"tiles={_portfolio_tiles(ads_pfs[t])};mean_tiles={m:.1f};"
            f"viol={v:.4f}",
        )
    mean_ads, viol_ads, t_pick = _pick_cheapest(candidates, viol_base)
    saved = 1.0 - mean_ads / mean_base
    emit(
        "figS_budget_markov_headline",
        saved * 1e6,
        f"mean_tiles_ads={mean_ads:.1f};mean_tiles_base={mean_base:.1f};"
        f"saved_frac={saved:.3f};viol_ads={viol_ads:.4f};"
        f"viol_base={viol_base:.4f};target={_tag(t_pick)}",
    )

    # -- part 3: tiles-saved-vs-load curve (Fig. 13 analogue) -----------
    script3 = gen.sample(2.0, seed=seed * 100003)  # one pinned bursty drive
    seeds3 = list(range(seed, seed + n))

    def cell_stats(spec):
        """(mean violation rate, mean reserved tiles) over the R-seed
        cell on the SoA lanes (lockstep lanes via run()'s per-spec
        fallback where the SoA kernels do not apply; the curve is a
        statistical statement either way)."""
        reports = run_specs(spec, seeds=seeds3, backend="soa")
        return (
            mean([r.violation_rate for r in reports]),
            mean([r.tiles_reserved_mean for r in reports]),
        )

    curve = []
    for lf in LOAD_GRID:
        base3 = ScenarioSpec(
            scenario=script3,
            policy="tp_driven",
            seed=seed,
            mode_defs=mode_defs,
            load_factor=lf,
        )
        pf_b = _compile(base3, all_modes, None)
        viol_b, mean_b = cell_stats(dataclasses.replace(base3, portfolio=pf_b))
        cands = []
        for t in LOAD_TARGETS:
            pf_t = _compile(dataclasses.replace(base3, policy="ads_tile"), all_modes, t)
            v, m = cell_stats(
                dataclasses.replace(
                    base3, policy="ads_tile", portfolio=pf_t, target_miss=t
                )
            )
            cands.append((m, v, t))
        m_ads, v_ads, t_pick = _pick_cheapest(cands, viol_b)
        saved = 1.0 - m_ads / mean_b
        curve.append((lf, saved))
        emit(
            f"figS_budget_load_{int(round(lf * 100))}",
            saved * 1e6,
            f"load={lf};mean_tiles_ads={m_ads:.1f};"
            f"mean_tiles_base={mean_b:.1f};saved_frac={saved:.3f};"
            f"viol_ads={v_ads:.4f};viol_base={viol_b:.4f};"
            f"target={_tag(t_pick)};n={n};backend={backend3}",
        )
    emit(
        "figS_budget_load_curve",
        max(s for _lf, s in curve) * 1e6,
        "curve="
        + ",".join(f"{lf:g}:{s:.3f}" for lf, s in curve)
        + f";backend={backend3}",
    )
