"""The program's side of a traced call: spans, counters and named scopes.

``harness.program`` reduces the device operations of the round loop by
the named scope of their phase, names idle gaps by the program's own
spans, and reads the registry's phase totals over the window.  Checked
on a made-up trace, on the recorded TPU sample of the harness's own
reduction, and on a recorded TPU sample of a program with the scopes.
"""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from harness import bench, program, tracing

DATA = Path(__file__).resolve().parent / "data"
DEV, HOST = "/device:TPU:0", "/host:CPU"
BODY = "jit(run)/while/body/closed_call"
ROUND_METRICS = [f"round.{s}.device_us_per_round" for s in program.SCOPES]
SPAN_METRICS = ["transfer.ms_per_drive_s", "assemble.lanes.ms_per_drive_s"]


def made_up():
    ms = 1_000_000
    op = tracing.OPS_LINE
    return [
        (HOST, "t1", "chipbench.call", 0, 100 * ms, ""),
        (HOST, "t1", "repro.soa_run", 0, 100 * ms, ""),
        (HOST, "t1", "repro.soa_attempt", 10 * ms, 89 * ms, ""),
        (HOST, "t1", "repro.soa_loop", 15 * ms, 66 * ms, ""),
        (HOST, "t1", "repro.soa_fetch", 81 * ms, 9 * ms, ""),
        (HOST, "t1", "repro.soa_assemble", 90 * ms, 9 * ms, ""),
        (HOST, "t1", "repro.soa_assemble_lanes", 92 * ms, 7 * ms, ""),
        (DEV, tracing.MODULES_LINE, "jit_run(1)", 20 * ms, 60 * ms, ""),
        (DEV, op, "%fusion.9", 5 * ms, 2 * ms, "jit(_device_draws)/mul"),
        (DEV, op, "%while.1", 20 * ms, 60 * ms, "jit(run)/while"),
        (DEV, op, "%fusion.1", 20 * ms, 10 * ms, f"{BODY}/window/dynamic_slice"),
        (DEV, op, "%fusion.2", 30 * ms, 15 * ms,
         f"{BODY}/policy/alloc/while/body/closed_call/add"),
        (DEV, op, "%fusion.3", 45 * ms, 5 * ms, f"{BODY}/step/cond/branch_1_fun/select_n"),
        (DEV, op, "%fusion.4", 50 * ms, 10 * ms, f"{BODY}/apply/add"),
        (DEV, op, "%copy-start.1", 60 * ms, 2 * ms, ""),
        (DEV, op, "%fusion.5", 62 * ms, 18 * ms, f"{BODY}/window/dynamic_update_slice"),
        (DEV, op, "%fusion.6", 88 * ms, 1 * ms, "jit(run)/convert"),
    ]


def test_scopes_sum_leaf_ops_of_the_loop():
    red = program.reduce(made_up())
    assert red["window_s"] == pytest.approx(0.100)
    assert red["loop_s"] == pytest.approx(0.060)
    scopes = red["scopes"]
    assert scopes["window"] == pytest.approx(0.028)
    assert scopes["policy"] == pytest.approx(0.015)
    assert scopes["step"] == pytest.approx(0.005)
    assert scopes["apply"] == pytest.approx(0.010)
    # no name stack, or none of the body's scopes
    assert scopes[program.UNSCOPED] == pytest.approx(0.002)
    # the while's own event spans its body: not a leaf
    assert red["leaf_s"] == pytest.approx(sum(scopes.values()))
    assert red["leaf_s"] == pytest.approx(red["loop_s"])


def test_gaps_are_named_by_the_innermost_span_of_either_prefix():
    gaps = {name: s for name, s in program.reduce(made_up())["idle_gaps"]}
    assert gaps["soa_fetch"] == pytest.approx(0.008)  # 80..88 ms
    assert gaps["soa_assemble_lanes"] == pytest.approx(0.011)  # 89..100 ms
    assert gaps["soa_attempt"] == pytest.approx(0.013)  # 7..20 ms
    assert gaps["soa_run"] == pytest.approx(0.005)  # 0..5 ms


def test_device_ops_carry_their_scope_path():
    ops = dict(program.reduce(made_up())["device_ops"])
    assert ops["%while.1"] == pytest.approx(0.060)
    assert ops["window/%fusion.5"] == pytest.approx(0.018)
    assert ops["policy/alloc/%fusion.2"] == pytest.approx(0.015)
    assert ops["step/%fusion.3"] == pytest.approx(0.005)
    assert ops["%copy-start.1"] == pytest.approx(0.002)


@pytest.mark.parametrize("stack,path", [
    (f"{BODY}/policy/alloc/while/body/closed_call/add", ["policy", "alloc"]),
    (f"{BODY}/policy/ads/alloc/cumsum", ["policy", "ads", "alloc"]),
    (f"{BODY}/window/dynamic_slice", ["window"]),
    (f"{BODY}/policy/alloc/_class_prefix.<locals>.excl/cumsum", ["policy", "alloc"]),
    ("jit(run)/while/body/add", []),
    ("", []),
])
def test_scope_path(stack, path):
    assert program.scope_path(stack) == path


def test_hlo_op_names():
    text = (
        "HloModule jit_run, is_scheduled=true\n\n"
        "%fused_computation (param_0: f32[8]) -> f32[8] {\n"
        "  %param_0 = f32[8]{0} parameter(0)\n"
        f'  ROOT %add.1 = f32[8]{{0}} add(%param_0, %param_0), metadata={{op_name="{BODY}/apply/add"}}\n'
        "}\n\n"
        "ENTRY %main (p: f32[8]) -> f32[8] {\n"
        "  %p = f32[8]{0} parameter(0)\n"
        f'  ROOT %fusion.3 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%fused_computation, '
        f'metadata={{op_name="{BODY}/step/eq" stack_frame_id=4}}\n'
        "}\n"
    )
    names = program.hlo_op_names(text)
    assert names["%fusion.3"] == f"{BODY}/step/eq"
    assert names["%add.1"] == f"{BODY}/apply/add"
    assert names["%p"] == ""


def test_name_stacks_come_from_the_loop_that_ran():
    """A TPU trace names operations by instruction only: the loop's
    operations get their op_name from the compiled loop, others none."""
    bare = [e[:5] + ("",) for e in made_up()]
    asked = []

    def op_names(seen):
        asked.append(seen)
        return {e[2]: e[5] for e in made_up()}

    events = program.with_name_stacks(bare, op_names)
    [seen] = asked
    assert seen == {"%while.1", "%fusion.1", "%fusion.2", "%fusion.3",
                    "%fusion.4", "%copy-start.1", "%fusion.5"}
    assert program.reduce(events)["scopes"] == program.reduce(made_up())["scopes"]
    stacks = {e[2]: e[5] for e in events}
    assert stacks["%fusion.9"] == "" and stacks["%fusion.6"] == ""  # outside the loop
    # no loop in the trace: nothing to ask
    no_loop = [e for e in bare if program.LOOP_MODULE not in e[2]]
    assert program.with_name_stacks(no_loop, op_names) == no_loop
    assert len(asked) == 1


def test_recorded_sample_reduces_as_the_harness_does():
    """Without name stacks the program's reduction sees what the
    harness's does: the same window, loop time and operations."""
    events = [tuple(e) for e in json.loads((DATA / "trace_sample.json").read_text())]
    red = tracing.reduce(events)
    mine = program.reduce([e + ("",) for e in events], top=len(events))
    assert mine["window_s"] == pytest.approx(red["window_s"], abs=1e-12)
    loop = sum(s for name, (_n, s) in red["modules"].items() if program.LOOP_MODULE in name)
    assert mine["loop_s"] == pytest.approx(loop, abs=1e-12)
    ops = dict(mine["device_ops"])
    for name, secs in red["device_ops"]:
        assert ops[name] == pytest.approx(secs, abs=1e-12)
    assert mine["scopes"][program.UNSCOPED] == pytest.approx(mine["leaf_s"])
    assert all(mine["scopes"][s] == 0 for s in program.SCOPES)


def test_recorded_scoped_sample():
    """One warm call of the cyc round loop on a TPU v5e (ck1, R=64, the
    first millisecond of its 2,000 rounds kept), each operation of the
    loop named by the compiled loop's metadata: every body scope holds
    time, and the scopes with the unscoped rest are the leaf time."""
    events = [tuple(e) for e in json.loads((DATA / "trace_scopes_sample.json").read_text())]
    red = program.reduce(events)
    assert all(red["scopes"][s] > 0 for s in program.SCOPES)
    # leaf time of the loop's operations, by a plain pairwise test
    loop = [(s, s + d) for p, line, n, s, d, _x in events
            if line == tracing.MODULES_LINE and program.LOOP_MODULE in n]
    ops = [(s, s + d) for p, line, _n, s, d, _x in events
           if line == tracing.OPS_LINE and any(a <= s and s + d <= b for a, b in loop)]
    leaves = [(a, b) for a, b in ops
              if not any((a2, b2) != (a, b) and a <= a2 and b2 <= b for a2, b2 in ops)]
    leaf_s = sum(b - a for a, b in leaves) * 1e-9
    assert sum(red["scopes"].values()) == pytest.approx(leaf_s, rel=0.01)
    assert red["leaf_s"] <= red["loop_s"]
    names = dict(red["idle_gaps"])
    assert "soa_assemble_lanes" in names  # the program's span, not "assemble"


@pytest.mark.parametrize("name", ROUND_METRICS)
def test_round_metrics_read_the_recorded_sample(name, monkeypatch):
    events = [tuple(e) for e in json.loads((DATA / "trace_scopes_sample.json").read_text())]
    reader = program.Reader()
    reader.trace = program.reduce(events)
    monkeypatch.setattr(program, "READER", reader)
    ctx = {"trace": {"window_s": reader.trace["window_s"]}, "traced_rounds": 2000,
           "window": {"seconds": 1.0, "drive_s": 0.0, "spans": {}}}
    mod = bench.load_metrics([name])[name]
    assert mod.read(ctx) > 0


def test_metrics_read_the_reader():
    reader = program.Reader()
    reader.trace = program.reduce(made_up())
    reader.at_window = {"counters": {}, "phases": {}}
    ctx = {"trace": {"window_s": 0.100}, "traced_rounds": 10,
           "window": {"seconds": 1.0, "drive_s": 4.0, "spans": {}}}
    assert reader.scope_us_per_round(ctx, "window") == pytest.approx(2800.0)
    assert reader.scope_us_per_round(ctx, "apply") == pytest.approx(1000.0)
    # another call than the harness reduced, or no rounds: absent
    assert reader.scope_us_per_round(dict(ctx, trace={"window_s": 0.2}), "window") is None
    assert reader.scope_us_per_round(dict(ctx, traced_rounds=None), "window") is None
    # the program's spans are not in the window: absent
    assert reader.ms_per_drive_s(ctx, ("soa_upload",)) is None


def test_window_phases_are_a_difference_of_snapshots():
    from repro.obs import metrics

    metrics.reset()
    metrics.enable()
    try:
        with metrics.phase("soa_upload"):
            pass
        reader = program.Reader()
        reader.at_window = metrics.snapshot()
        for _ in range(3):
            with metrics.phase("soa_upload"):
                pass
            metrics.count("soa_attempts")
        win = reader.window()
        ctx = {"window": {"seconds": 1.0, "drive_s": 2.0, "spans": {}}}
        value = reader.ms_per_drive_s(ctx, ("soa_upload",))
    finally:
        metrics.enable(False)
        metrics.reset()
    assert win["counters"] == {"soa_attempts": 3}
    assert win["phases"]["soa_upload"][0] == 3
    assert value == pytest.approx(win["phases"]["soa_upload"][1] * 1e3 / 2.0)


@pytest.mark.parametrize("name", ROUND_METRICS + SPAN_METRICS)
def test_new_metrics_load_through_the_reader(name):
    mod = bench.load_metrics([name])[name]
    assert mod.HOOKS == {}
    assert hasattr(tracing.events_from_profile, "harness")


def test_reader_leaves_the_harness_reading_as_it_was(tmp_path):
    """The harness's events of a real profile are the same through the
    reader, and the reader's own events add the program's spans."""
    import jax
    import jax.numpy as jnp

    program.install()
    program.install()  # once per process
    f = jax.jit(lambda x: jnp.sin(x) * 2.0)
    x = jnp.ones((8, 8))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracing.CALL_SPAN):
        with jax.profiler.TraceAnnotation("repro.soa_loop"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    through = tracing.events_from_profile(str(tmp_path))
    assert through == tracing.events_from_profile.harness(str(tmp_path))
    assert tracing.CALL_SPAN in {e[2] for e in through}
    mine = program.events_from_profile(str(tmp_path))
    assert {e[2] for e in mine} >= {tracing.CALL_SPAN, "repro.soa_loop"}
    assert program.READER.at_window is not None


def test_registry_is_left_alone_outside_a_traced_run():
    assert not program.traced_run()


def test_compiled_loop_names_its_scopes():
    """The compiled round loop's text names each instruction's scope,
    and a loop without the trace's instructions is not taken."""
    from repro.scenarios import ScenarioSpec, get_scenario, run

    spec = ScenarioSpec(scenario=get_scenario("rate_churn"), policy="cyc")
    run(spec, seeds=[1, 2], backend="soa", fallback=False)
    names = program.compiled_loop_op_names(set())
    tops = {program.scope_path(n)[0] for n in names.values() if program.scope_path(n)}
    assert tops == set(program.SCOPES)
    assert program.compiled_loop_op_names({"%no-such-instruction"}) == {}
