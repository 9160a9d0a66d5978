"""A whole run with the timed path broken underneath reads not correct.

Each test skips the harness's look for a chip and drives the rest of a
run at a small size on the host CPU, with the round loop
(``soa_kernels.simulate``, under every layer the window drives) or its
inputs broken: the loop returns its initial state, half of the lanes
are simulated and copied over the rest, finish times are altered where
they are produced, or every float32 plane is rounded to bfloat16 (the
control).  A sound run of the same size reads correct.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np
import pytest

from harness import bench, cells

CELL = "ck1.rate_churn.cyc"
SEED = 2**31 + 11


def small_cell():
    cell = cells.load(CELL)
    return dataclasses.replace(
        cell, workload=dict(cell.workload, drives_per_call=4, sample_drives=4)
    )


def one_run(seconds=0.2):
    import jax

    names = [m["name"] for m in cells.benchmark()["per_layer"]]
    return bench.run_cell(
        small_cell(), SEED, seconds, False, jax.devices()[0], time.perf_counter(), names,
    )


@pytest.fixture(autouse=True)
def fresh_window_hint(monkeypatch):
    """Each run starts as a new process would: no job-window pad learnt."""
    from repro.scenarios import runner

    monkeypatch.setattr(runner, "_SOA_LIFE_PAD_HINT", {})


@pytest.fixture
def broken(monkeypatch):
    from repro.core.sim import soa_kernels as K

    real = K.simulate

    def install(fault):
        monkeypatch.setattr(K, "simulate", lambda cfg, const, lanes: fault(real, cfg, const, lanes))

    return install


def unchanged_state(real, cfg, const, lanes):
    out = real(cfg, const, lanes)
    init = {k: np.zeros_like(v) for k, v in out.items()}
    init["fin"] = np.full_like(out["fin"], np.inf)
    init["codes"] = np.asarray(lanes["codes0"])
    return init


def half_the_lanes(real, cfg, const, lanes):
    half = cfg.R // 2
    part = real(dataclasses.replace(cfg, R=half), const, {k: v[:half] for k, v in lanes.items()})
    return {k: np.concatenate([v, v], axis=0)[: cfg.R] for k, v in part.items()}


def altered_finish(real, cfg, const, lanes):
    from repro.core.sim import soa_kernels as K

    out = real(cfg, const, lanes)
    done = out["state"] == K.DONE
    out["fin"] = np.where(done, out["fin"] + np.float32(1e-3), out["fin"])
    return out


def test_sound_run_is_correct():
    res = one_run()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 4
    assert res["metrics"]["drive_s_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [unchanged_state, half_the_lanes, altered_finish],
                         ids=lambda f: f.__name__)
def test_broken_round_loop_is_not_correct(broken, fault):
    broken(fault)
    res = one_run()
    assert res is not None and not res["correct"], res


def test_bf16_control_is_not_correct():
    from calibrate import bf16_planes

    with bf16_planes():
        res = one_run()
    assert not res["correct"], res["checks"]
