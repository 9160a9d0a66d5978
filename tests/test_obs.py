"""Observability tests (`repro.obs`).

Covers the flight recorder's four contracts:

* **Non-perturbation** — a run with a recorder attached produces a
  `SimReport` bit-identical to the same pinned run without one (the
  hooks observe, they never steer), and recorder-off runs are
  deterministic.
* **Export round-trip** — a recorded rate_churn run exports to a
  Chrome/Perfetto trace that validates against the checked-in
  `trace_schema.json`, survives a JSON round-trip, and keeps its
  structural invariants (paired flows, matched slice lanes, counters).
* **Attribution exactness** — for every missed chain of every bundled
  scenario, the four lateness components sum to the observed lateness
  to float tolerance.
* **Plumbing** — `summarize` rows and `aggregate_sweep` carry the
  attribution summary for recorded runs.

Plus unit tests for the dependency-free JSON-schema subset validator
and the metrics registry.
"""
import dataclasses
import json

import pytest

from repro.core.experiment import build_stack, make_policy
from repro.core.runtime import OnlineReplanner
from repro.core.sim import SimConfig, Simulator
from repro.obs import (
    EVENT_KINDS,
    SchemaError,
    TraceRecorder,
    attribute_misses,
    attribution_report,
    chrome_trace,
    export_chrome_trace,
    metrics,
    validate_trace,
)
from repro.obs.schema import load_schema, validate
from repro.scenarios import ScenarioSpec, get_scenario
from repro.scenarios.runner import (
    aggregate_sweep,
    build_trace,
    compile_portfolio,
    run,
    summarize,
    sweep,
)

BUNDLED = ("calm_to_rush", "commute", "night_storm", "rate_churn")


def _spec(name="rate_churn", policy="ads_tile", seed=1, **kw):
    return ScenarioSpec(
        scenario=get_scenario(name), policy=policy, seed=seed, **kw
    )


def _recorded_sim(name="rate_churn", policy="ads_tile", seed=1):
    """A finished scenario Simulator with its recorder (mirrors
    the runner's reactive-replan construction, which returns only the
    report)."""
    spec = _spec(name, policy, seed)
    wf, _hw, model, _compiler = build_stack(spec)
    portfolio = compile_portfolio(spec)
    sched = portfolio.schedules[spec.scenario.segments[0].mode]
    pol = make_policy(policy)
    pol.replanner = OnlineReplanner(portfolio)
    rec = TraceRecorder()
    sim = Simulator(
        wf, model, sched, pol,
        SimConfig(
            duration_s=spec.scenario.duration_s, seed=seed,
            scenario=spec.scenario, recorder=rec,
        ),
    )
    sim.run()
    return sim, rec


# ---------------------------------------------------------------------------
# non-perturbation
# ---------------------------------------------------------------------------
def test_recorder_does_not_perturb_pinned_reports():
    """Recorder attached vs detached: bit-identical `SimReport`s on the
    same pinned trace (the attribution field is runner-added metadata,
    not simulation output)."""
    spec = _spec("rate_churn")
    trace = build_trace(spec)
    spec = dataclasses.replace(spec, portfolio=compile_portfolio(spec))
    [off] = run(spec, trace=trace, backend="scalar")
    rec = TraceRecorder()
    [on] = run(spec, trace=trace, recorders={0: rec}, backend="scalar")
    assert len(rec) > 0
    d_off = dataclasses.asdict(off)
    d_on = dataclasses.asdict(on)
    assert d_off.pop("attribution") is None
    assert d_on.pop("attribution") is not None
    assert d_off == d_on


def test_disabled_recorder_runs_are_deterministic():
    """Two fresh recorder-off runs of one pinned spec agree bitwise."""
    spec = _spec("commute", seed=3)
    spec = dataclasses.replace(spec, portfolio=compile_portfolio(spec))
    a = dataclasses.asdict(run(spec, backend="scalar")[0])
    b = dataclasses.asdict(run(spec, backend="scalar")[0])
    assert a == b


# ---------------------------------------------------------------------------
# export round-trip
# ---------------------------------------------------------------------------
def test_trace_round_trips_through_schema(tmp_path):
    _sim, rec = _recorded_sim("rate_churn")
    assert all(e.kind in EVENT_KINDS for e in rec.events)
    path = tmp_path / "trace.json"
    doc = export_chrome_trace(rec, str(path))
    validate_trace(doc)  # in-memory form
    reloaded = json.loads(path.read_text())
    validate_trace(reloaded)  # disk round-trip
    assert reloaded["displayTimeUnit"] == "ms"

    evs = reloaded["traceEvents"]
    # every duration slice is non-negative and closed
    for e in evs:
        if e["ph"] == "X":
            assert e["dur"] >= 0
            assert e["ts"] >= 0
    # flow starts and ends come in matched pairs per id
    starts = {e["id"] for e in evs if e["ph"] == "s"}
    ends = {e["id"] for e in evs if e["ph"] == "f"}
    assert starts and starts == ends
    # counter tracks exist for tiles and realloc traffic
    counters = {e["name"] for e in evs if e["ph"] == "C"}
    assert any(c.startswith("tiles alloc p") for c in counters)
    assert "tiles reserved" in counters
    # per-partition lanes got thread metadata
    named = {e["args"]["name"] for e in evs if e["ph"] == "M"
             and e["name"] == "thread_name"}
    assert any(n.startswith("partition") for n in named)


def test_chrome_trace_meta_carries_run_context():
    _sim, rec = _recorded_sim("rate_churn")
    doc = chrome_trace(rec)
    meta = doc["otherData"]
    assert float(meta["duration_s"]) > 0
    assert int(meta["seed"]) == 1
    seams = list(rec.by_kind("rate_seam"))
    assert len(seams) == 2  # rate_churn: night -> urban -> rush_hour


# ---------------------------------------------------------------------------
# attribution
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", BUNDLED)
@pytest.mark.parametrize("policy", ("ads_tile", "tp_driven"))
def test_attribution_components_sum_to_lateness(name, policy):
    sim, rec = _recorded_sim(name, policy)
    misses = attribute_misses(sim, rec)
    late = list(rec.by_kind("deadline_miss"))
    assert len(misses) == len(late)
    for m in misses:
        assert m.lateness_s > 0
        total = (m.queueing_s + m.realloc_stall_s + m.restagger_s
                 + m.duration_tail_s)
        assert total == pytest.approx(m.lateness_s, abs=1e-9), m.chain
        # waiting components cannot be negative (only the tail can)
        assert m.queueing_s >= -1e-9
        assert m.realloc_stall_s >= -1e-9
        assert m.restagger_s >= -1e-9
        assert m.path[-1] == m.sink_jid


def test_attribution_report_totals_match_misses():
    sim, rec = _recorded_sim("rate_churn")
    misses = attribute_misses(sim, rec)
    rep = attribution_report(sim, rec)
    assert rep["n_late"] == len(misses)
    assert rep["lateness_s"] == pytest.approx(
        sum(m.lateness_s for m in misses)
    )
    comp = rep["components_s"]
    assert sum(comp.values()) == pytest.approx(rep["lateness_s"], abs=1e-6)
    if misses:
        worst = max(misses, key=lambda m: m.lateness_s)
        assert rep["worst_chain"] == worst.chain
        assert set(rep["by_chain"]) == {m.chain for m in misses}


def test_attribute_misses_requires_a_recorder():
    spec = _spec("rate_churn")
    wf, _hw, model, compiler = build_stack(spec)
    sched = compiler.compile(model, wf)
    sim = Simulator(wf, model, sched, make_policy("ads_tile"),
                    SimConfig(duration_s=0.2, seed=1))
    sim.run()
    with pytest.raises(ValueError):
        attribute_misses(sim)


# ---------------------------------------------------------------------------
# plumbing: summarize / sweep aggregation
# ---------------------------------------------------------------------------
def test_recorded_rows_aggregate_attribution():
    spec = _spec("rate_churn", record=True)
    [report] = run(spec, backend="scalar")
    assert report.attribution is not None
    row = summarize(spec, report)
    assert row["attribution"]["n_late"] == report.attribution["n_late"]

    rows = sweep(2, policies=("ads_tile",), duration_s=1.0, seed=1,
                 jobs=1, record=True)
    agg = aggregate_sweep(rows)["ads_tile"]
    att = agg["attribution"]
    assert att["n_recorded"] == 2
    assert att["n_late"] == sum(r["attribution"]["n_late"] for r in rows)
    assert set(att["components_s"]) == {
        "queueing", "realloc_stall", "restagger", "duration_tail"
    }
    # unrecorded sweeps carry no attribution block
    plain = aggregate_sweep(
        sweep(2, policies=("ads_tile",), duration_s=1.0, seed=1, jobs=1)
    )["ads_tile"]
    assert "attribution" not in plain


# ---------------------------------------------------------------------------
# the schema subset validator
# ---------------------------------------------------------------------------
def test_schema_validator_accepts_minimal_trace():
    validate_trace({
        "traceEvents": [
            {"ph": "i", "name": "x", "pid": 1, "ts": 0.0, "s": "g"},
        ],
        "displayTimeUnit": "ms",
    })


@pytest.mark.parametrize("doc", [
    {},                                           # missing required keys
    {"traceEvents": [], "displayTimeUnit": "ms"},  # minItems
    {"traceEvents": [{"ph": "i", "name": "x", "pid": 1}],
     "displayTimeUnit": "parsec"},                # enum
    {"traceEvents": [{"ph": "Z", "name": "x", "pid": 1}],
     "displayTimeUnit": "ms"},                    # ph enum
    {"traceEvents": [{"ph": "i", "name": "x", "pid": True}],
     "displayTimeUnit": "ms"},                    # bool is not an integer
    {"traceEvents": [{"ph": "i", "name": 3, "pid": 1}],
     "displayTimeUnit": "ms"},                    # name type
    {"traceEvents": [{"ph": "i", "pid": 1}],
     "displayTimeUnit": "ms"},                    # event missing required
    {"traceEvents": [{"ph": "i", "name": "x", "pid": 1}],
     "displayTimeUnit": "ms",
     "otherData": {"k": 3}},                      # additionalProperties type
])
def test_schema_validator_rejects(doc):
    with pytest.raises(SchemaError):
        validate_trace(doc)


def test_schema_validator_reports_paths():
    try:
        validate({"a": [1, "x"]},
                 {"type": "object",
                  "properties": {"a": {"type": "array",
                                       "items": {"type": "integer"}}}})
    except SchemaError as err:
        assert "$.a[1]" in str(err)
    else:  # pragma: no cover
        pytest.fail("expected SchemaError")


def test_checked_in_schema_loads():
    schema = load_schema()
    assert schema["required"] == ["traceEvents", "displayTimeUnit"]


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_metrics_disabled_is_a_noop():
    # other tests (e.g. the benchmark-CLI ones) may leave the
    # process-global registry enabled; this test owns its state
    metrics.enable(False)
    metrics.reset()
    assert not metrics.enabled()
    metrics.count("x")
    with metrics.phase("p"):
        pass
    snap = metrics.snapshot()
    assert snap == {"counters": {}, "phases": {}}


def test_metrics_counts_and_phases():
    metrics.reset()
    metrics.enable()
    try:
        metrics.count("hits")
        metrics.count("hits", 2)
        with metrics.phase("work"):
            pass
        with metrics.phase("work"):
            pass
        snap = metrics.snapshot(reset_after=True)
    finally:
        metrics.enable(False)
    assert snap["counters"] == {"hits": 3}
    work = snap["phases"]["work"]
    assert work["n"] == 2
    assert work["total_s"] >= 0
    assert work["mean_s"] == pytest.approx(work["total_s"] / 2)
    assert metrics.snapshot() == {"counters": {}, "phases": {}}


# ---------------------------------------------------------------------------
# the SoA path's phases and counters
# ---------------------------------------------------------------------------
#: the registry's phases on every SoA call, and inside each round-loop attempt
SOA_CALL_PHASES = ("soa_run", "stack_prepare", "trace_sample",
                   "trace_sample_draws", "trace_sample_fetch")
SOA_ATTEMPT_PHASES = ("soa_attempt", "soa_build", "soa_lanes", "soa_upload",
                      "soa_loop", "soa_fetch", "soa_assemble",
                      "soa_assemble_arrays", "soa_assemble_lanes")
SOA_LANES = 4


@pytest.fixture(scope="module")
def soa_call(tmp_path_factory):
    """One small SoA call with the registry on, under the profiler and
    with no round loop compiled yet, and the same call with it off."""
    import jax

    from repro.core.sim import soa
    from repro.core.sim import soa_kernels as K

    spec = _spec("rate_churn", "cyc")
    seeds = list(range(SOA_LANES))
    log_dir = str(tmp_path_factory.mktemp("soa_profile"))
    built = []
    real_build = soa.build_problem

    def recording(*args, **kwargs):
        built.append(real_build(*args, **kwargs))
        return built[-1]

    K.clear_kernel_cache()
    soa.build_problem = recording
    metrics.reset()
    metrics.enable()
    try:
        jax.profiler.start_trace(log_dir)
        try:
            on = run(spec, seeds=seeds, backend="soa", fallback=False)
        finally:
            jax.profiler.stop_trace()
        snap = metrics.snapshot(reset_after=True)
    finally:
        metrics.enable(False)
        soa.build_problem = real_build
    off = run(spec, seeds=seeds, backend="soa", fallback=False)
    return {"on": on, "off": off, "snap": snap, "built": built, "log_dir": log_dir}


def test_soa_reports_do_not_depend_on_the_registry(soa_call):
    from repro.core.sim.batch import reports_identical

    assert len(soa_call["on"]) == len(soa_call["off"]) == SOA_LANES
    for a, b in zip(soa_call["on"], soa_call["off"]):
        assert reports_identical(a, b)


def test_soa_call_records_every_phase_and_counter(soa_call):
    snap, [problem] = soa_call["snap"], soa_call["built"]
    phases = snap["phases"]
    for name in SOA_CALL_PHASES + SOA_ATTEMPT_PHASES:
        assert phases[name]["n"] == 1, name
    assert snap["counters"]["soa_attempts"] == 1
    assert snap["counters"]["soa_lanes"] == SOA_LANES
    assert snap["counters"]["soa_rounds"] == len(problem.const["t0"])
    assert snap["counters"]["soa_loop_builds"] == 1
    assert "soa_window_overflows" not in snap["counters"]
    # the parts of a call lie inside it
    assert phases["soa_loop"]["total_s"] <= phases["soa_attempt"]["total_s"]
    assert phases["soa_attempt"]["total_s"] <= phases["soa_run"]["total_s"]
    lanes = phases["soa_assemble_lanes"]["total_s"]
    assert lanes <= phases["soa_assemble"]["total_s"]


@pytest.mark.parametrize("policy", ["cyc", "tp_driven"])
def test_soa_counts_lane_rounds_resizes_and_walks(policy, monkeypatch):
    """Per round-loop call: its rounds times its lanes, its stalls
    (``n_realloc``) summed over lanes and, for tp_driven alone, the
    queue walks its lanes took."""
    from repro.core.sim import soa
    from repro.core.sim import soa_kernels as K

    calls = []
    real = K.simulate

    def recording(cfg, const_np, lanes_np):
        out = real(cfg, const_np, lanes_np)
        calls.append((len(const_np["t0"]) * lanes_np["work"].shape[0], out))
        return out

    monkeypatch.setattr(K, "simulate", recording)
    metrics.reset()
    metrics.enable()
    try:
        run(_spec("rate_churn", policy), seeds=list(range(SOA_LANES)),
            backend="soa", fallback=False, options=soa.SoaOptions())
        counters = metrics.snapshot(reset_after=True)["counters"]
    finally:
        metrics.enable(False)
    assert calls
    assert counters["soa_lane_rounds"] == sum(n for n, _o in calls)
    assert counters["soa_resizes"] == sum(int(o["n_realloc"].sum()) for _n, o in calls)
    if policy == "tp_driven":
        walks = counters["soa_tp_walks"]
        assert walks == sum(int(o["tp_walks"].sum()) for _n, o in calls)
        # the scalar engine walks in about 0.62 of a lane's rounds here
        assert 0.45 < walks / counters["soa_lane_rounds"] < 0.8
    else:
        assert "soa_tp_walks" not in counters


def test_soa_phases_are_profiler_spans(soa_call):
    import glob
    import os

    from jax.profiler import ProfileData

    [path] = glob.glob(os.path.join(soa_call["log_dir"], "**", "*.xplane.pb"),
                       recursive=True)
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    spans.setdefault(ev.name, []).append(
                        (plane.name, line.name, ev.start_ns, ev.end_ns,
                         dict(ev.stats)))
    [(plane, line, a, b, args)] = spans["repro.soa_attempt"]
    [problem] = soa_call["built"]
    assert args["W"] == problem.cfg.W
    assert args["rounds"] == len(problem.const["t0"])
    for name in ("repro.soa_loop", "repro.soa_assemble_lanes"):
        [(plane2, line2, a2, b2, _args)] = spans[name]
        assert (plane2, line2) == (plane, line)  # the same host thread
        assert a <= a2 <= b2 <= b
