#!/usr/bin/env python3
"""Smoke run of the SoA Monte-Carlo path on one TPU.

Run it from the root of a checkout with nothing set::

    python chip_smoke.py

It puts ``src/`` on the import path itself, runs in this one process
and starts no other, and needs one TPU: where JAX finds none it exits
non-zero and prints no result.  The compile cache goes where
``repro.compile_cache`` says (``JAX_COMPILATION_CACHE_DIR`` if set,
else ``.jax_cache/`` in the checkout), so a second run of the same
checkout loads its programs instead of compiling them.

Phases, in order; the first that fails ends the run:

1. sampling -- ``sample_trace_batch(device=True)`` against the NumPy
   path on ``rate_churn`` for seeds 0..N_SEEDS-1;
2. main -- ``run(spec, seeds=range(N_SEEDS), backend="soa",
   fallback=False)`` twice for each of cyc, tp_driven and ads_tile on
   ``rate_churn`` at the default deployment (400 tiles, cockpit x1,
   2-s drives with rate hot-swaps);
3. correctness -- the chip's SoA reports of seeds 0..63 against the
   scalar oracle (the lockstep engine) and against the same SoA program
   run on this host's CPU backend, at the same seeds: structural
   invariants equal per seed, pooled chain-latency KS <= 0.08, and CI
   overlap on violation rate, realloc waste and reserved tiles;
4. Pallas -- the compiled ladder-grant kernel at the ads_tile shape
   (4096, 80, 6) against the jnp select and the NumPy reference, bit
   for bit.

The times it prints are a smoke, not a benchmark.  The last line of
standard output is one JSON object naming the device.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SCENARIO = "rate_churn"
POLICIES = ("cyc", "tp_driven", "ads_tile")
#: drives per SoA call.  At 4096 a v5e ran the warm call in 15.4 s for
#: cyc and 138 s for tp_driven, and ads_tile's full-horizon window did
#: not finish inside the smoke's time limit: their round loops take 7-17x
#: cyc's time per round (ROADMAP, queue 1).  256 keeps every phase inside
#: that limit; tests/test_tpu_compile.py compiles the loops at 4096.
N_SEEDS = 256
N_ORACLE = 64
KS_TOL = 0.08
#: device vs NumPy sampling: |device - numpy| <= SAMPLE_ATOL +
#: SAMPLE_RTOL * |numpy|.  The CPU meets rtol 1e-12
#: (``tests/test_soa.py``); a TPU emulates float64, and on a v5e its
#: exp/log put the work draws of seeds 0..4095 up to 2.6e-10 relative
#: off libm.
SAMPLE_RTOL = 1e-9
SAMPLE_ATOL = 1e-15
GRANT_SHAPE = (4096, 80, 6)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileCounter:
    """Counts XLA executables built (compiled or loaded from the
    persistent cache) and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self) -> None:
        from jax import monitoring

        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.n, self.seconds, self.cache_hits


def require_tpu():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
            f"({dev.device_kind})"
        )
    return dev


def phase_sampling(seeds) -> None:
    import numpy as np

    from repro.core.experiment import build_stack
    from repro.core.sim.batch import sample_trace_batch
    from repro.core.sim.trace import build_skeleton
    from repro.scenarios import ScenarioSpec, get_scenario

    spec = ScenarioSpec(scenario=get_scenario(SCENARIO), policy="cyc")
    wf, _hw, model, _compiler = build_stack(spec)
    scen = spec.scenario
    skel = build_skeleton(wf, scen, scen.duration_s)
    host = sample_trace_batch(skel, model, scen, seeds)
    t0 = time.perf_counter()
    dev = sample_trace_batch(skel, model, scen, seeds, device=True)
    dt = time.perf_counter() - t0
    log(f"[sampling] {len(seeds)} seeds x {skel.n} jobs, device pass "
        f"{dt!r} s (compile included)")
    bad = []
    for field in ("work", "io", "sensor_lat"):
        a = getattr(host, field)
        b = getattr(dev, field)
        diff = np.abs(a - b)
        nz = a != 0
        rel = float(np.max(diff[nz] / np.abs(a[nz]))) if nz.any() else 0.0
        ok = bool(np.all(diff <= SAMPLE_ATOL + SAMPLE_RTOL * np.abs(a)))
        log(f"[sampling] {field}: max relative difference {rel!r} "
            f"(bound rtol {SAMPLE_RTOL}, atol {SAMPLE_ATOL}) "
            f"{'ok' if ok else 'FAIL'}")
        if not ok:
            bad.append(field)
    if bad:
        raise SystemExit(f"chip_smoke: device sampling off the NumPy path: {bad}")


def phase_main(policy: str, seeds, device, counter):
    """Two SoA calls of one policy; returns the second call's reports."""
    from repro.core.sim import soa
    from repro.scenarios import ScenarioSpec, get_scenario, run

    spec = ScenarioSpec(scenario=get_scenario(SCENARIO), policy=policy)
    problems = []
    run_problem = soa.run_problem

    def recording(problem, btrace, lane_seeds):
        problems.append(problem)
        return run_problem(problem, btrace, lane_seeds)

    soa.run_problem = recording
    try:
        reports = None
        for call in ("cold", "warm"):
            problems.clear()
            n0, s0, h0 = counter.snapshot()
            t0 = time.perf_counter()
            reports = run(spec, seeds=seeds, backend="soa", fallback=False)
            wall = time.perf_counter() - t0
            n1, s1, h1 = counter.snapshot()
            p = problems[-1]
            drive_s = len(reports) * p.duration
            peak = (device.memory_stats() or {}).get("peak_bytes_in_use")
            log(f"[main] {policy} {call}: R={p.cfg.R} N={p.n_pad} W={p.cfg.W} "
                f"rounds={len(p.const['t0'])} round-loop attempts="
                f"{len(problems)} executables={n1 - n0} "
                f"({s1 - s0!r} s, persistent-cache hits {h1 - h0}) "
                f"wall={wall!r} s drive-s/wall-s={drive_s / wall!r} "
                f"peak_bytes_in_use={peak}")
    finally:
        soa.run_problem = run_problem
    if len(reports) != len(seeds):
        raise SystemExit(f"chip_smoke: {policy} returned {len(reports)} reports")
    return reports


def _gate(tag: str, policy: str, ref, got) -> bool:
    """Print the distributional verdicts of ``got`` against ``ref``;
    True when they hold."""
    from benchmarks.check_equivalence import compare_distributional

    v = compare_distributional(ref, got, KS_TOL)
    missed = [m for m, (_r, _g, ok) in v["ci"].items() if not ok]
    ok = v["struct_ok"] and v["ks_ok"] and not missed
    log(f"[correctness] {policy} vs {tag}: struct {v['struct_ok']} "
        f"KS {v['ks']!r} (tol {KS_TOL}) latencies {v['n']} "
        f"{'ok' if ok else 'FAIL'}")
    for m, (ci_ref, ci_got, overlap) in v["ci"].items():
        note = "" if overlap else " FAIL"
        log(f"[correctness]   {m}: {tag} CI {ci_ref} chip CI {ci_got}{note}")
    return ok


def phase_correctness(policy: str, soa_reports, oracle_seeds) -> None:
    """The chip's first ``len(oracle_seeds)`` lanes against the scalar
    oracle (the lockstep engine) and against the same SoA program on
    this host's CPU backend, both at the same seeds."""
    import jax

    from repro.scenarios import ScenarioSpec, get_scenario, run

    spec = ScenarioSpec(scenario=get_scenario(SCENARIO), policy=policy)
    chip = soa_reports[: len(oracle_seeds)]
    ref = run(spec, seeds=oracle_seeds, backend="lockstep")
    with jax.default_device(jax.devices("cpu")[0]):
        host = run(spec, seeds=oracle_seeds, backend="soa", fallback=False)
    ok_ref = _gate("oracle", policy, ref, chip)
    ok_host = _gate("cpu-soa", policy, host, chip)
    if not (ok_ref and ok_host):
        raise SystemExit(f"chip_smoke: {policy} outside the distributional gate")


def phase_pallas(shape, seed: int = 0) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.sim import soa_kernels as K

    R, W, C = shape
    rng = np.random.default_rng(seed)
    limit = rng.integers(-1, 40, size=(R, W)).astype(np.float32)
    cand = np.sort(rng.integers(1, 33, size=(R, W, C)), axis=-1).astype(np.float32)
    lim_d, cand_d = jnp.asarray(limit), jnp.asarray(cand)
    t0 = time.perf_counter()
    got = np.asarray(jax.jit(K._ladder_grant_pallas)(lim_d, cand_d))
    dt = time.perf_counter() - t0
    ref_j = np.asarray(jax.jit(K._ladder_grant)(lim_d, cand_d))
    ref_np = K.ladder_grant_reference(limit, cand)
    same_j = bool(np.array_equal(got, ref_j))
    same_np = bool(np.array_equal(got, ref_np))
    log(f"[pallas] ladder grant {shape}: equal to jnp {same_j}, to NumPy "
        f"{same_np} (first call {dt!r} s, compile included)")
    if not (same_j and same_np):
        raise SystemExit("chip_smoke: Pallas ladder grant differs")


def main() -> int:
    from repro.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    device = require_tpu()
    import jax

    counter = CompileCounter()
    log(f"chip_smoke: {device.platform} {device.device_kind} x "
        f"{len(jax.devices())}, jax {jax.__version__}, compile cache "
        f"{cache_dir} -- a smoke run, not a benchmark")
    t_start = time.perf_counter()
    seeds = list(range(N_SEEDS))
    phase_sampling(seeds)
    for policy in POLICIES:
        reports = phase_main(policy, seeds, device, counter)
        phase_correctness(policy, reports, list(range(N_ORACLE)))
    phase_pallas(GRANT_SHAPE)
    n, secs, hits = counter.snapshot()
    log(f"chip_smoke: done in {time.perf_counter() - t_start!r} s; "
        f"{n} executables built ({secs!r} s), {hits} from the "
        f"persistent cache")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
