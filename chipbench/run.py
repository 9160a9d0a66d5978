#!/usr/bin/env python3
"""Chip benchmark of the SoA Monte-Carlo path: one run of one cell.

Run from the root of a checkout::

    python3 chipbench/run.py --workload ck1.rate_churn.ads_tile \\
        --seed 7 --seconds 30 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix, each a data file under ``chipbench/``.  The run makes every
input from ``--seed``, warms up (set-up), drives the system for
``--seconds`` as one closed-loop caller, checks a sample of the drives
it returned against the frozen reference, and prints one JSON object as
the last line of standard output.  ``--trace 1`` profiles one whole
warm call besides, and reports the per-layer metrics instead of the
end-to-end ones.

It needs the chips the cell asks for, as JAX's default backend: where
JAX finds no TPU, or fewer chips, it exits non-zero and prints no
result.  JAX's persistent compilation cache is ``.jax_cache/`` in the
checkout, whatever the environment says, so that only a cell's first
run in a checkout compiles.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CACHE_DIR = ROOT / ".jax_cache"


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(n: int):
    """The first device, where JAX's default backend is a TPU with at
    least ``n`` chips; else exit non-zero."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chipbench: needs a TPU, JAX found {dev.platform!r} ({dev.device_kind})"
        )
    if len(devices) < n:
        raise SystemExit(f"chipbench: the cell needs {n} chips, JAX found {len(devices)}")
    return dev


def main(argv=None) -> int:
    t_start = time.perf_counter()
    args = parse(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH_DIR))
    from harness import bench, cells

    cell = cells.load(args.workload, ROOT)
    from repro.compile_cache import configure_compile_cache

    import jax

    configure_compile_cache()
    # every program goes to the cache, so a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    device = require_chips(cell.chips)
    bench.log(f"chipbench: {cell.name} seed {args.seed} on {device.platform} "
              f"{device.device_kind} x {len(jax.devices())}, jax {jax.__version__}")
    metric_names = [
        m["name"] for m in cells.benchmark(ROOT)["per_layer"]
        if cell.name in m.get("workloads", [cell.name])
    ]
    result = bench.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), device, t_start,
        metric_names,
    )
    if result is None:
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
