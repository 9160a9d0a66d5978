#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

Run from the root of a checkout, on the chip::

    python3 chipbench/calibrate.py --workload ck1.rate_churn.ads_tile \\
        --seeds 1 2 3 4 5 6 7 8 9 10 11 12 --control-seeds 13 14 15

For each seed it does what a run's check does at the cell's own size:
drives enough calls of R drives to fill the sample, draws the sample
from the seed, and compares it with the reference.  ``--seeds`` read the
program as it is (the lower readings); ``--control-seeds`` read the
control: the same program with every float32 plane that enters the
round loop rounded to bfloat16, the lower precision that a later change
might be tempted to store them in (the upper readings).  One JSON line
per seed goes to standard output, then one line with the largest sound
and the smallest control reading of each number.  The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import time
from pathlib import Path
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@contextlib.contextmanager
def bf16_planes():
    """Round every float32 array entering the round loop to bfloat16."""
    import ml_dtypes
    import numpy as np

    from repro.core.sim import soa_kernels as K

    simulate = K.simulate

    def q(a):
        if getattr(a, "dtype", None) == np.float32:
            return a.astype(ml_dtypes.bfloat16).astype(np.float32)
        return a

    def rounded(cfg, const, lanes):
        return simulate(cfg, {k: q(v) for k, v in const.items()},
                        {k: q(v) for k, v in lanes.items()})

    K.simulate = rounded
    try:
        yield
    finally:
        K.simulate = simulate


def readings(cell, seeds: List[int], tag: str) -> List[Dict[str, float]]:
    """One reading of every compared number per seed."""
    from harness import bench, cells
    from repro.scenarios import ScenarioSpec, run

    spec = ScenarioSpec(
        scenario=bench.program_scenario(cell.scenario), policy=cell.policy,
        **cell.spec_fields,
    )
    R = cell.drives_per_call
    n_calls = max(1, math.ceil(cell.sample_drives / R))
    problems = bench.Problems()
    problems.install()

    def setup_call(index):
        n0 = len(problems.built)
        batch = [cells.drive_seed(bench.SETUP_SEED, index, k, R) for k in range(R)]
        run(spec, seeds=batch, backend="soa", fallback=False)
        return problems.built[n0:]

    try:
        bench.settle(setup_call)
    finally:
        problems.uninstall()
    out = []
    for s in seeds:
        sample = bench.Sample(cell.sample_drives, s)
        t0 = time.perf_counter()
        try:
            for c in range(n_calls):
                batch = [cells.drive_seed(s, c, k, R) for k in range(R)]
                sample.offer(batch, run(spec, seeds=batch, backend="soa", fallback=False))
        except Exception as e:  # a control that crashes has failed: no reading
            print(json.dumps({"mode": tag, "seed": s, "failed": f"{type(e).__name__}: {e}"}),
                  flush=True)
            continue
        wall = time.perf_counter() - t0
        values = bench.check(cell, sample)
        line = {"mode": tag, "seed": s, "calls_s": wall, **values}
        print(json.dumps(line), flush=True)
        out.append(values)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH_DIR))
    from harness import cells
    from run import require_chips

    cell = cells.load(args.workload, ROOT)
    from repro.compile_cache import configure_compile_cache

    configure_compile_cache()
    require_chips(cell.chips)
    summary: Dict[str, Dict[str, float]] = {}
    if args.seeds:
        sound = readings(cell, args.seeds, "program")
        summary["lower"] = {k: max(v[k] for v in sound) for k in sound[0]}
    if args.control_seeds:
        with bf16_planes():
            ctrl = readings(cell, args.control_seeds, "control")
        if ctrl:
            summary["upper"] = {k: min(v[k] for v in ctrl) for k in ctrl[0]}
    print(json.dumps({"workload": cell.name, **summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
