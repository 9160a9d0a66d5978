"""CI equivalence gate: batched lockstep engine vs the scalar engine.

Tier-1 checks both contracts on every nominal cell
(``tests/test_batch.py``, ``tests/test_soa_contract.py``); this CLI
repeats the sweep in CI at its own seeds, writes a pass/fail table,
and adds the lockstep speedup floor.

Usage::

    python -m benchmarks.check_equivalence \
        [--mode bitwise|distributional] \
        [--seeds 0 7 123] [--policies cyc tp_driven ads_tile] \
        [--scenarios all] [--min-speedup 1.1] [--ks-tol 0.08]

For every bundled scenario x policy x pinned seed, the same run is
executed twice through :func:`repro.scenarios.runner.run` — once with
``backend="scalar"`` (the scalar reference engine) and once with
``backend="lockstep"`` (the lockstep batch engine, all seeds of a cell
in one batch) — and the two
:class:`~repro.core.sim.engine.SimReport` objects are compared through
:func:`repro.core.sim.batch.report_digest`.  The digest covers every
float in the report (latencies, violations, utilization, per-mode
tails), so a pass means **bit-identical** results, not "close enough":
any divergence in event ordering, rate arithmetic, or policy decisions
inside the fused lanes shows up here.

``--min-speedup`` additionally times one warm pinned batch (the
``perf_bench`` 6-mode Markov scenario, B=8, ads_tile) against the same
seeds through the scalar loop and fails when the batched path does not
clear the floor.  The floor is deliberately conservative (default
1.1x): shared CI runners are noisy and single-core, and the honest
fused-lane speedup envelope is documented in
``docs/performance.md#batched-monte-carlo-engine`` — this assertion
exists to catch the batched path silently degrading into
"scalar-with-overhead", not to certify a marketing number.

``--mode distributional`` gates the structure-of-arrays jax backend
instead: the SoA kernels replace the event heap with discrete
scheduling rounds, so bit-identity is out of reach *by design* and the
contract is statistical (docs/performance.md#soa-backend).  Per
scenario x policy cell, the pinned seed set runs through both the
lockstep engine (bit-identical to scalar, cheaper to drive) and
``run(spec, seeds=..., backend="soa", fallback=False)``, and the gate
asserts:

* **structural invariants** (job universe, seam spans, chain universe,
  reservation footprint) match exactly, per seed;
* the pooled chain-latency **KS statistic** stays under ``--ks-tol``
  (default 0.08 — cyc and ads_tile decide at each round's end, and
  their measured dt=1e-3 approximation envelope is 0.01-0.06;
  tp_driven decides at its queue-change instants and reads under 0.01;
  so the gate trips on regression, not on the known round-coalescing
  bias);
* per-cell **CI overlap** on violation rate, realloc waste and mean
  reserved tiles (normal-approximation intervals across seeds).

A pass/fail table is written to ``$GITHUB_STEP_SUMMARY`` when that
environment variable is set (the GitHub Actions job-summary panel) and
always printed to stdout.  Exit 1 on any mismatch or a missed speedup
floor, 0 otherwise.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time
from typing import List, Sequence

from repro.compile_cache import configure_compile_cache
from repro.core.sim.batch import report_digest
from repro.scenarios.runner import ScenarioSpec, run as run_specs
from repro.scenarios.script import (
    BUNDLED_SCENARIOS,
    MarkovScenarioGenerator,
    get_scenario,
)

DEFAULT_SEEDS = (0, 7, 123)
DEFAULT_POLICIES = ("cyc", "tp_driven", "ads_tile")


def run_cell(scenario: str, policy: str, seeds: Sequence[int]) -> List[bool]:
    """Per-seed bit-identity verdicts for one scenario x policy cell."""
    spec = ScenarioSpec(scenario=get_scenario(scenario), policy=policy)
    batched = run_specs(spec, seeds=list(seeds), backend="lockstep")
    out = []
    for s, rb in zip(seeds, batched):
        [rs] = run_specs(dataclasses.replace(spec, seed=int(s)), backend="scalar")
        out.append(report_digest(rs) == report_digest(rb))
    return out


def run_cell_distributional(
    scenario: str, policy: str, seeds: Sequence[int], ks_tol: float
) -> dict:
    """SoA-vs-scalar statistical verdicts for one scenario x policy
    cell (:func:`compare_distributional`).  The scalar side is driven
    through the lockstep engine, whose bit-identity to the scalar
    backend the bitwise mode of this gate pins separately."""
    spec = ScenarioSpec(scenario=get_scenario(scenario), policy=policy)
    ref = run_specs(spec, seeds=list(seeds), backend="lockstep")
    soa = run_specs(spec, seeds=list(seeds), backend="soa", fallback=False)
    return compare_distributional(ref, soa, ks_tol)


def compare_distributional(ref, soa, ks_tol: float) -> dict:
    """Verdicts of SoA reports ``soa`` against oracle reports ``ref`` of
    the same seeds: exact structural invariants per seed, pooled
    chain-latency KS, and CI overlap on the summary rates."""
    from repro.core.sim.soa import (
        intervals_overlap,
        ks_statistic,
        mean_ci,
        structural_invariants,
    )

    struct_ok = all(
        structural_invariants(a) == structural_invariants(b) for a, b in zip(ref, soa)
    )
    lat_ref = [x for r in ref for ls in r.chain_latencies.values() for x in ls]
    lat_soa = [x for r in soa for ls in r.chain_latencies.values() for x in ls]
    ks = ks_statistic(lat_ref, lat_soa)
    ci = {}
    for metric in ("violation_rate", "realloc_frac", "tiles_reserved_mean"):
        ci_ref = mean_ci([getattr(r, metric) for r in ref])
        ci_soa = mean_ci([getattr(r, metric) for r in soa])
        # zero-width intervals (deterministic metrics, single seeds)
        # still must touch: pad by a rounding epsilon only
        ci[metric] = (ci_ref, ci_soa, intervals_overlap(ci_ref, ci_soa, pad=1e-9))
    return {
        "struct_ok": struct_ok,
        "ks": ks,
        "ks_ok": ks <= ks_tol,
        "ci": ci,
        "ci_ok": all(ok for _r, _s, ok in ci.values()),
        "n": (len(lat_ref), len(lat_soa)),
    }


def measure_speedup(seeds: Sequence[int]) -> tuple:
    """``(scalar_s, batch_s)`` for the pinned perf-bench scenario."""
    from .perf_bench import PERF_DWELL, PERF_TRANSITIONS

    gen = MarkovScenarioGenerator(transitions=PERF_TRANSITIONS, mean_dwell_s=PERF_DWELL)
    spec = ScenarioSpec(scenario=gen.sample(2.0, 1), policy="ads_tile")
    run_specs(spec, seeds=list(seeds)[:2])  # warm caches for both paths
    run_specs(dataclasses.replace(spec, seed=int(seeds[0])))
    t0 = time.perf_counter()
    for s in seeds:
        run_specs(dataclasses.replace(spec, seed=int(s)))
    scalar_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    run_specs(spec, seeds=list(seeds))
    batch_s = time.perf_counter() - t0
    return scalar_s, batch_s


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--mode",
        choices=("bitwise", "distributional"),
        default="bitwise",
        help="bitwise: lockstep engine vs scalar (digest identity); "
        "distributional: SoA jax backend vs scalar (KS + CI overlap + "
        "structural invariants)",
    )
    ap.add_argument(
        "--ks-tol",
        type=float,
        default=0.08,
        help="distributional mode: max pooled chain-latency KS statistic "
        "(default 0.08)",
    )
    ap.add_argument(
        "--seeds",
        type=int,
        nargs="+",
        default=list(DEFAULT_SEEDS),
        help="pinned seeds per cell (default: 0 7 123)",
    )
    ap.add_argument(
        "--policies",
        nargs="+",
        default=list(DEFAULT_POLICIES),
        help="policies to sweep (default: all three)",
    )
    ap.add_argument(
        "--scenarios",
        nargs="+",
        default=["all"],
        help="bundled scenario names, or 'all'",
    )
    ap.add_argument(
        "--min-speedup",
        type=float,
        default=None,
        help="also assert batched/scalar wall-clock speedup "
        "on the pinned B=8 perf scenario (ads_tile)",
    )
    args = ap.parse_args(argv)
    configure_compile_cache()

    scenarios = (
        sorted(BUNDLED_SCENARIOS) if args.scenarios == ["all"] else args.scenarios
    )

    if args.mode == "distributional":
        lines = [
            "| scenario | policy | struct | KS (tol) | CI overlap |",
            "|---|---|---|---|---|",
        ]
        fails = 0
        total = 0
        for scen in scenarios:
            if get_scenario(scen).has_degradations:
                # the SoA kernels do not model degradation seams
                # (``soa_usable`` rejects these scripts); the bitwise
                # mode still covers them through the scalar lane
                lines.append(f"| {scen} | — | skipped (degradations) | — | — |")
                continue
            for pol in args.policies:
                v = run_cell_distributional(scen, pol, args.seeds, args.ks_tol)
                ok = v["struct_ok"] and v["ks_ok"] and v["ci_ok"]
                fails += 0 if ok else 1
                total += 1
                lines.append(
                    f"| {scen} | {pol} "
                    f"| {'OK' if v['struct_ok'] else '**FAIL**'} "
                    f"| {v['ks']:.4f} ({args.ks_tol}) "
                    f"{'OK' if v['ks_ok'] else '**FAIL**'} "
                    f"| {'OK' if v['ci_ok'] else '**FAIL**'} |"
                )
        lines.append("")
        lines.append(
            f"**{total - fails}/{total}** SoA-vs-scalar cells "
            f"distributionally equivalent (seeds {args.seeds})"
        )
        table = "\n".join(lines)
        print(table)
        summary = os.environ.get("GITHUB_STEP_SUMMARY")
        if summary:
            with open(summary, "a") as fh:
                fh.write("## SoA-backend distributional equivalence gate\n\n")
                fh.write(table + "\n")
        if fails:
            print(
                f"distributional gate failed: {fails} cell(s) out of the "
                "SoA equivalence envelope",
                file=sys.stderr,
            )
            return 1
        return 0

    seed_cols = " | ".join(f"seed {s}" for s in args.seeds)
    lines = [
        f"| scenario | policy | {seed_cols} |",
        "|---|---|" + "---|" * len(args.seeds),
    ]
    fails = 0
    for scen in scenarios:
        for pol in args.policies:
            verdicts = run_cell(scen, pol, args.seeds)
            fails += verdicts.count(False)
            cells = " | ".join("OK" if v else "**FAIL**" for v in verdicts)
            lines.append(f"| {scen} | {pol} | {cells} |")

    total = len(scenarios) * len(args.policies) * len(args.seeds)
    lines.append("")
    lines.append(f"**{total - fails}/{total}** scalar-vs-batched runs bit-identical")

    speed_ok = True
    if args.min_speedup is not None:
        scalar_s, batch_s = measure_speedup([1 + i for i in range(8)])
        speedup = scalar_s / batch_s
        speed_ok = speedup >= args.min_speedup
        verdict = "OK" if speed_ok else "**FAIL**"
        lines.append("")
        lines.append(
            f"Pinned B=8 ads_tile sweep: scalar {scalar_s:.3f}s, "
            f"batched {batch_s:.3f}s — **{speedup:.2f}x** "
            f"(floor {args.min_speedup:.2f}x) {verdict}"
        )

    table = "\n".join(lines)
    print(table)
    summary = os.environ.get("GITHUB_STEP_SUMMARY")
    if summary:
        with open(summary, "a") as fh:
            fh.write("## Batched-engine equivalence gate\n\n")
            fh.write(table + "\n")

    if fails:
        print(
            f"equivalence gate failed: {fails} run(s) diverged from the "
            "scalar engine",
            file=sys.stderr,
        )
        return 1
    if not speed_ok:
        print(
            "equivalence gate failed: batched sweep below the speedup "
            "floor (see docs/performance.md#batched-monte-carlo-engine)",
            file=sys.stderr,
        )
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
