"""One run of one cell: set-up, the measured window, the check.

The window is a closed loop with one caller, as a campaign script
drives the system: it calls ``repro.scenarios.run(spec, seeds=batch,
backend="soa", fallback=False)`` again and again, each call with R fresh
drive seeds, and waits for the reports before the next call.  Set-up
runs calls of the same shapes on seeds of its own, so that every
program is compiled (or loaded from the persistent cache) and the
runner's window-overflow retries have settled before the window opens.

After the window, a sample of the window's drives, drawn from the run
seed, is run again through the frozen reference (:mod:`refsim`), on the
host, and compared by :mod:`harness.gate`.
"""
from __future__ import annotations

import contextlib
import importlib.util
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from . import cells, gate, tracing
from .spans import CompileCounter, SpanRecorder

METRICS_DIR = cells.BENCH_DIR / "metrics"
#: the target whose calls are the problems a call builds (R, N, W, rounds)
BUILD_TARGET = ("repro.core.sim.soa", "build_problem")
#: set-up drives the same seeds in every run, so that every run of a
#: cell settles the runner's job window alike (how wide it must be
#: depends on the drives; the window's own drives are the run's)
SETUP_SEED = 0
#: set-up ends after this many calls in a row that ran the round loop
#: once (no window overflow), or at a window that spans the horizon
SETTLED_CALLS = 4
MAX_SETUP_CALLS = 8


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_metrics(names: List[str]) -> Dict[str, object]:
    """The reader module of each per-layer metric, by name."""
    out = {}
    for name in names:
        path = METRICS_DIR / f"{name}.py"
        spec = importlib.util.spec_from_file_location(f"chipbench_metric_{len(out)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[name] = mod
    return out


def program_scenario(d: dict):
    from repro.scenarios import Burst, ModeSegment, ScenarioScript, SensorDropout

    return ScenarioScript(
        name=d["name"],
        segments=tuple(ModeSegment(m, float(s)) for m, s in d["segments"]),
        bursts=tuple(Burst(**b) for b in d.get("bursts", ())),
        dropouts=tuple(SensorDropout(**x) for x in d.get("dropouts", ())),
    )


def reference_reports(cell: cells.Cell, seeds: List[int]):
    """The reference's reports of ``seeds`` (host CPU)."""
    if str(cells.BENCH_DIR) not in sys.path:
        sys.path.insert(0, str(cells.BENCH_DIR))
    from refsim.lockstep import RefSpec, run_seeds, scenario_from_data

    import jax

    spec = RefSpec(
        scenario=scenario_from_data(cell.scenario),
        policy=cell.policy,
        **cell.spec_fields,
    )
    try:
        host = jax.default_device(jax.devices("cpu")[0])
    except RuntimeError:  # no CPU backend: the reference is NumPy anyway
        host = contextlib.nullcontext()
    with host:
        return run_seeds(spec, seeds)


def settle(call: Callable[[int], List[dict]]) -> List[dict]:
    """Set-up calls ``call(0)``, ``call(1)``, ... until the runner's job
    window has settled; returns the problems they built."""
    built_all: List[dict] = []
    clean = 0
    for index in range(MAX_SETUP_CALLS):
        built = call(index)
        built_all.extend(built)
        clean = clean + 1 if len(built) == 1 else 0
        if clean == SETTLED_CALLS or (built and built[-1]["full_horizon"]):
            break
    return built_all


class Sample:
    """A uniform sample of at most ``k`` drives, drawn from the run seed
    as the calls return them (reservoir sampling), so that the window
    holds ``k`` reports and not every report it made."""

    def __init__(self, k: int, seed: int) -> None:
        self.k = k
        self.rng = np.random.default_rng(seed)
        self.seen = 0
        self.seeds: List[int] = []
        self.reports: list = []

    def offer(self, seeds: List[int], reports: list) -> None:
        for s, r in zip(seeds, reports):
            if len(self.reports) < self.k:
                self.seeds.append(s)
                self.reports.append(r)
            else:
                j = int(self.rng.integers(0, self.seen + 1))
                if j < self.k:
                    self.seeds[j], self.reports[j] = s, r
            self.seen += 1


def check(cell: cells.Cell, sample: Sample) -> Dict[str, float]:
    """The compared numbers of the sampled drives against the
    reference's drives of the same seeds."""
    t0 = time.perf_counter()
    ref = reference_reports(cell, sample.seeds)
    ref_s = time.perf_counter() - t0
    k = len(sample.seeds)
    log(f"[reference] {k} drives in {ref_s!r} s on the host = "
        f"{k * cell.duration_s / ref_s!r} drive-s/s")
    return gate.numbers(ref, sample.reports)


class Problems:
    """Records the shape of every problem the SoA path builds."""

    def __init__(self) -> None:
        self.built: List[Dict[str, float]] = []
        self._undo: Optional[Callable[[], None]] = None

    def install(self) -> bool:
        mod_name, attr = BUILD_TARGET
        try:
            mod = importlib.import_module(mod_name)
            fn = getattr(mod, attr)
        except (ImportError, AttributeError):
            return False
        built = self.built

        def recording(*args, **kwargs):
            p = fn(*args, **kwargs)
            built.append({
                "R": p.cfg.R, "N": p.n_real, "N_pad": p.n_pad, "W": p.cfg.W,
                "rounds": len(p.const["t0"]),
                "full_horizon": p.life >= p.duration,
            })
            return p

        setattr(mod, attr, recording)
        self._undo = lambda: setattr(mod, attr, fn)
        return True

    def uninstall(self) -> None:
        if self._undo:
            self._undo()
            self._undo = None


def run_cell(
    cell: cells.Cell,
    seed: int,
    seconds: float,
    trace: bool,
    device,
    t_start: float,
    metric_names: List[str],
) -> Optional[dict]:
    """The result line's object, or None where the run cannot stand
    (a problem of another size than the cell pins)."""
    import jax

    from repro.scenarios import ScenarioSpec, run

    counter = CompileCounter()
    spec = ScenarioSpec(
        scenario=program_scenario(cell.scenario), policy=cell.policy,
        **cell.spec_fields,
    )
    R = cell.drives_per_call
    metrics = load_metrics(metric_names)
    hooks: Dict[str, str] = {}
    for mod in metrics.values():
        hooks.update(getattr(mod, "HOOKS", {}))
    rec = SpanRecorder()
    rec.install(hooks)
    problems = Problems()
    have_problems = problems.install()

    def call(index: int, run_seed: int = seed):
        seeds = [cells.drive_seed(run_seed, index, k, R) for k in range(R)]
        n_built = len(problems.built)
        t0 = time.perf_counter()
        with rec.span("call"):
            try:
                reports = run(spec, seeds=seeds, backend="soa", fallback=False)
                err = None
            except Exception as e:  # a failed call is counted, not fatal
                reports, err = None, e
        wall = time.perf_counter() - t0
        built = problems.built[n_built:]
        shape = built[-1] if built else {}
        log(f"[call {index}] R={shape.get('R')} N={shape.get('N')} "
            f"W={shape.get('W')} rounds={shape.get('rounds')} round-loop "
            f"attempts={len(built)} wall={wall!r} s"
            + (f" FAILED: {type(err).__name__}: {err}" if err else ""))
        return seeds, reports, built

    try:
        # ---- set-up -------------------------------------------------
        # calls until one needs no window-overflow retry (or its window
        # already spans the horizon, which cannot overflow): the runner
        # then starts every later call from the window that proved wide
        # enough, and the measured window recompiles nothing
        c0 = counter.snapshot()
        t_setup0 = time.perf_counter()
        setup_built = settle(lambda index: call(index, SETUP_SEED)[2])
        setup_end = time.perf_counter()
        next_call = 0
        setup_s = setup_end - t_start
        c1 = counter.snapshot()
        if not have_problems or not setup_built:
            log("chipbench: no SoA problem was built in set-up; its size "
                "cannot be checked")
            return None
        if setup_built[-1]["N"] != cell.jobs:
            log(f"chipbench: the problem has N={setup_built[-1]['N']} jobs, "
                f"the cell pins N={cell.jobs}")
            return None
        setup_info = {
            "compile_s": c1[1] - c0[1],
            "executables": c1[0] - c0[0],
            "cache_hits": c1[2] - c0[2],
            "spans": rec.totals(t_setup0, setup_end),
        }
        log(f"[setup] {setup_s!r} s; {setup_info['executables']} executables "
            f"built in {setup_info['compile_s']!r} s, "
            f"{setup_info['cache_hits']} from the persistent cache")

        # ---- the traced call (--trace 1) ----------------------------
        trace_red = None
        traced_rounds = None
        if trace:
            log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            try:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(log_dir, profiler_options=options)
                try:
                    built = call(next_call)[2]
                finally:
                    jax.profiler.stop_trace()
                traced_rounds = sum(b["rounds"] for b in built)
                trace_red = tracing.reduce(tracing.events_from_profile(log_dir))
            finally:
                shutil.rmtree(log_dir, ignore_errors=True)
            next_call += 1

        # ---- the window --------------------------------------------
        cw0 = counter.snapshot()
        sample = Sample(cell.sample_drives, seed)
        attempted = failed = calls = 0
        w0 = time.perf_counter()
        while True:
            seeds, reports, _built = call(next_call)
            next_call += 1
            calls += 1
            attempted += len(seeds)
            if reports is None or len(reports) != len(seeds):
                failed += len(seeds)
            else:
                sample.offer(seeds, reports)
            del reports
            if time.perf_counter() - w0 >= seconds:
                break
        w1 = time.perf_counter()
        cw1 = counter.snapshot()
        window_s = w1 - w0
        drive_s = sample.seen * cell.duration_s
        log(f"[window] {calls} calls, {attempted} drives ({failed} failed) "
            f"in {window_s!r} s; executables built in the window: "
            f"{cw1[0] - cw0[0]} ({cw1[1] - cw0[1]!r} s)")
        stats = device.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        log(f"[memory] peak_bytes_in_use={peak}")
    finally:
        rec.uninstall()
        problems.uninstall()

    # ---- the check: a sample of the window's drives ----------------
    values = check(cell, sample) if sample.seen else {}
    checks = {
        name: {"value": values[name], "limit": limit}
        for name, limit in cell.limits.items() if name in values
    }
    correct = failed == 0 and bool(values) and gate.verdict(values, cell.limits)

    result = {
        "correct": bool(correct),
        "attempted": attempted,
        "failed": failed,
        "metrics": {},
        "device": {
            "platform": device.platform,
            "kind": device.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": peak,
        },
    }
    if not trace:
        result["metrics"] = {
            "drive_s_per_s": {"value": drive_s / window_s, "unit": "drive-s/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    else:
        ctx = {
            "window": {
                "seconds": window_s,
                "drive_s": drive_s,
                "spans": rec.totals(w0, w1),
            },
            "setup": setup_info,
            "trace": trace_red,
            "traced_rounds": traced_rounds,
        }
        for name, mod in metrics.items():
            value = mod.read(ctx)
            if value is not None:
                result["metrics"][name] = {"value": value, "unit": mod.UNIT}
        if trace_red is not None:
            result["device"]["busy_s"] = trace_red["busy_s"]
            result["device"]["window_s"] = trace_red["window_s"]
            result["breakdown"] = {
                "device_ops": trace_red["device_ops"],
                "idle_gaps": trace_red["idle_gaps"],
            }
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    result["checks"] = checks
    return result
