# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: ``PYTHONPATH=src python -m benchmarks.run``.

One module per artifact of the paper's §V, plus the scenario suites:
  fig6  — Cyc./Tp-driven characterization (paper Fig. 6)
  fig11 — ablations: reservation, partitioning, their interplay (Fig. 11)
  fig12 — E2E tail latency + violation rate vs tiles (Fig. 12)
  fig13 — scaling: max chains / min tiles / waste (Fig. 13)
  figS  — driving scenarios: mode switches, replanning, MC sweeps
  table2 — scheduling-decision vs resharding overhead (Table II)

``--only fig11`` runs a subset; ``--duration`` scales simulated seconds
(default keeps the full harness under ~15 min on one CPU core);
``--jobs N`` runs independent suites in N worker processes (suite
output is buffered per process and printed in order; workers run on
the CPU, since an accelerator belongs to one process).
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys
import time
from pathlib import Path

from repro.compile_cache import configure_compile_cache
from repro.obs import metrics

from . import fig6_casestudy, fig11_ablation, fig12_e2e, fig13_scaling
from . import figS_budget, figS_degrade, figS_predict, figS_rates
from . import figS_scenarios, headroom, perf_bench, table2_overhead

SUITES = {
    "fig6": fig6_casestudy.run,
    "fig11": fig11_ablation.run,
    "fig12": fig12_e2e.run,
    "fig13": fig13_scaling.run,
    "figS": figS_scenarios.run,
    "figS_rates": figS_rates.run,
    "figS_predict": figS_predict.run,
    "figS_budget": figS_budget.run,
    "figS_degrade": figS_degrade.run,
    "perf": perf_bench.run,
    "table2": table2_overhead.run,
    "headroom": headroom.run,
}

#: CLI conveniences: the scenario suites also answer to their module names
ALIASES = {"figS_scenarios": "figS", "rates": "figS_rates",
           "predict": "figS_predict", "budget": "figS_budget",
           "degrade": "figS_degrade", "perf_bench": "perf"}


def _rows_from_csv(text: str) -> list:
    """Parse ``emit`` output back into structured rows (for --out)."""
    rows = []
    for line in text.splitlines():
        parts = line.split(",", 2)
        if len(parts) < 2 or parts[0] == "name":
            continue
        try:
            value = float(parts[1])
        except ValueError:
            continue
        rows.append({
            "name": parts[0],
            "us_per_call": value,
            "derived": parts[2] if len(parts) > 2 else "",
        })
    return rows


def _suite_worker(args: tuple) -> str:
    """Run one suite with stdout captured (process-pool entry point)."""
    name, duration, seed = args
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        SUITES[name](duration=duration, seed=seed)
    return buf.getvalue()


def _export_trace(path_str: str, duration: float, seed: int) -> None:
    """Record one rate_churn run and export a Perfetto/Chrome trace."""
    from repro.obs import TraceRecorder, export_chrome_trace
    from repro.scenarios import ScenarioSpec, get_scenario, run

    rec = TraceRecorder()
    spec = ScenarioSpec(
        scenario=get_scenario("rate_churn"), policy="ads_tile", seed=seed,
        duration_s=max(duration, 1.0),
    )
    [report] = run(spec, recorders={0: rec})
    path = Path(path_str)
    path.parent.mkdir(parents=True, exist_ok=True)
    export_chrome_trace(rec, str(path))
    att = report.attribution or {}
    print(f"# wrote {path} ({len(rec)} events, "
          f"{att.get('n_late', 0)} late chains)", file=sys.stderr)


def _run_campaign_cli(args) -> list:
    """Run (or resume) a sweep campaign from ``--campaign`` and emit
    its aggregate as CSV rows; returns the emitted text's rows.

    ``--campaign`` takes either a campaign-spec JSON or a manifest JSON
    written by a previous (possibly interrupted) invocation — resuming
    is just pointing the flag at the manifest (or rerunning the same
    spec against the same cache): cells with cached rows are not
    re-executed.  This is the entry the weekly extended-sweep CI job
    drives.
    """
    from repro.sweeps.executor import SubprocessShardExecutor
    from repro.sweeps.service import SweepFailure, run_campaign

    executor = None
    if args.campaign_shards and args.campaign_shards > 1:
        executor = SubprocessShardExecutor(
            num_shards=args.campaign_shards,
            jobs_per_shard=max(1, args.jobs),
        )
    try:
        result = run_campaign(
            args.campaign,
            cache_dir=args.campaign_cache,
            manifest_path=args.campaign_manifest,
            executor=executor,
            jobs=args.jobs if args.jobs > 1 else None,
        )
    except SweepFailure as exc:
        result = exc.result
        print(f"# campaign: {exc}", file=sys.stderr)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        from .common import emit_sweep_aggregate

        emit_sweep_aggregate(result.aggregate, "campaign")
        print(
            f"campaign_cells,{float(result.n_cells):.3f},"
            f"executed={result.n_executed};cached={result.n_cached};"
            f"failed={result.n_failed}"
        )
    out = buf.getvalue()
    sys.stdout.write(out)
    print(
        f"# campaign {result.campaign.name!r}: {result.n_cells} cells "
        f"({result.n_cached} cached, {result.n_executed} executed, "
        f"{result.n_failed} failed)",
        file=sys.stderr,
    )
    return _rows_from_csv(out)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names ('none' runs no suite "
                         "— useful with --trace-out)")
    ap.add_argument("--duration", type=float, default=1.0,
                    help="simulated seconds per experiment")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--jobs", type=int, default=1,
                    help="run independent suites in N worker processes")
    ap.add_argument("--out", default=None, metavar="FILE",
                    help="also write the rows as structured JSON "
                         "(consumed by benchmarks.make_tables)")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="record one rate_churn run with the flight "
                         "recorder and write a Perfetto/Chrome trace JSON")
    ap.add_argument("--campaign", default=None, metavar="FILE",
                    help="run/resume a sweep campaign: a campaign-spec "
                         "JSON or a manifest JSON from an earlier "
                         "(interrupted) run (see docs/sweeps.md); "
                         "combine with '--only none' to run it alone")
    ap.add_argument("--campaign-cache", default=".sweep-cache",
                    metavar="DIR",
                    help="content-addressed result cache for --campaign "
                         "(cells with cached rows are not re-executed)")
    ap.add_argument("--campaign-manifest", default=None, metavar="FILE",
                    help="write the resumable campaign manifest here "
                         "(default: <campaign-cache>/manifest.json)")
    ap.add_argument("--campaign-shards", type=int, default=0, metavar="N",
                    help="fan the campaign out over N worker "
                         "subprocesses via the manifest instead of the "
                         "in-process pool")
    args = ap.parse_args()
    configure_compile_cache()
    if args.campaign and args.campaign_manifest is None:
        args.campaign_manifest = str(
            Path(args.campaign_cache) / "manifest.json"
        )

    if args.only == "none":
        names = []
    else:
        names = args.only.split(",") if args.only else list(SUITES)
    names = [ALIASES.get(n, n) for n in names]
    unknown = [n for n in names if n not in SUITES]
    if unknown:
        ap.error(f"unknown suite(s) {unknown} (choose from {list(SUITES)})")
    if args.out or args.trace_out:
        # self-profiling: compile/sample/engine phase timers land in the
        # JSON "profile" section (parent process only — worker processes
        # profile themselves and are not aggregated here)
        metrics.enable()
    print("name,us_per_call,derived")
    outputs = []
    if args.jobs > 1 and len(names) > 1:
        from repro.scenarios.runner import parallel_map

        t0 = time.time()
        outputs = parallel_map(
            _suite_worker,
            [(n, args.duration, args.seed) for n in names],
            jobs=args.jobs,
        )
        for name, out in zip(names, outputs):
            sys.stdout.write(out)
            print(f"# {name} done", file=sys.stderr)
        print(f"# all suites done in {time.time()-t0:.1f}s", file=sys.stderr)
    else:
        for name in names:
            t0 = time.time()
            if args.out:
                out = _suite_worker((name, args.duration, args.seed))
                sys.stdout.write(out)
                outputs.append(out)
            else:
                SUITES[name](duration=args.duration, seed=args.seed)
            print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)

    campaign_rows = []
    if args.campaign:
        campaign_rows = _run_campaign_cli(args)

    if args.trace_out:
        _export_trace(args.trace_out, args.duration, args.seed)

    if args.out:
        path = Path(args.out)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps({
            "suites": names,
            "duration": args.duration,
            "seed": args.seed,
            "rows": _rows_from_csv("".join(outputs)) + campaign_rows,
            "profile": metrics.snapshot(),
        }, indent=2))
        print(f"# wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
