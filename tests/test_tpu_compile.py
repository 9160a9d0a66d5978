"""Ahead-of-time compiles of the SoA hot path for one TPU v5e chip.

Nothing here runs on a chip: the TPU compiler, which is installed with
jax, compiles for a v5e that is described and not attached.  That
catches what interpret mode and the CPU backend cannot, such as a
Pallas block that exceeds the scoped VMEM limit or a program that does
not fit the device, before any chip time is spent.  Sizes are the
real ones: ``rate_churn`` at the default deployment and R = 4096 lanes.

The topology is described inside a module fixture, never while a
module is imported: only one process at a time may load the TPU
library, and every test worker imports every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.sim import batch, soa
from repro.core.sim import soa_kernels as K
from repro.core.sim.trace import _params_for, build_skeleton
from repro.scenarios.runner import (
    ScenarioSpec,
    _make_run_policy,
    _prepare_run,
)
from repro.scenarios.script import get_scenario

R = 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


#: compiled round loops by (policy, window), shared by the tests below
_LOOPS = {}


def _compiled_loop(policy, window, one_chip):
    key = (policy, window)
    if key not in _LOOPS:
        _LOOPS[key] = _compile_loop(policy, window, one_chip)
    return _LOOPS[key]


def _compile_loop(policy, window, one_chip):
    spec = ScenarioSpec(scenario=get_scenario("rate_churn"), policy=policy)
    wf, model, sched, portfolio = _prepare_run(spec)
    scen = spec.scenario
    pad = scen.duration_s if window == "horizon" else 0.0
    problem = soa.build_problem(
        wf, model, sched, portfolio, _make_run_policy(spec, portfolio),
        scen, scen.duration_s, n_lanes=R,
        options=soa.SoaOptions(life_pad_s=pad),
    )
    if window == "horizon":
        assert problem.cfg.W >= problem.n_real
    N = problem.n_pad
    A1 = N + len(problem.sen_jids) + 1
    if policy == "tp_driven":
        A1 *= 2  # its codes0 carries the codes' float32 remainders after them
    lanes = (
        _sds((R, N), jnp.float32, one_chip),
        _sds((R, N), jnp.float32, one_chip),
        _sds((R, A1), jnp.float32, one_chip),
    )
    return K.round_loop(problem.cfg, problem.const).lower(*lanes).compile()


@pytest.mark.parametrize(
    "policy,window",
    [
        ("cyc", "default"),
        ("tp_driven", "default"),
        ("ads_tile", "default"),
        # the widest loop the overflow retry can build (the window spans
        # the whole horizon); ads_tile reaches it on rate_churn
        ("ads_tile", "horizon"),
    ],
)
def test_round_loop_compiles_for_v5e(policy, window, one_chip):
    compiled = _compiled_loop(policy, window, one_chip)
    mem = compiled.memory_analysis()
    # the 16 GB of one v5e chip, with room for the caller's arrays
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 8 << 30


@pytest.mark.parametrize("policy", ["cyc", "tp_driven", "ads_tile"])
def test_round_loop_names_its_phases_for_v5e(policy, one_chip):
    """The executable keeps the name the benchmark's device metrics find
    (``jit_run``), and its instructions carry the round body's named
    scopes in their metadata."""
    text = _compiled_loop(policy, "default", one_chip).as_text()
    assert text.startswith("HloModule jit_run,")
    for scope in ("window", "step", "policy", "apply"):
        assert f"/while/body/closed_call/{scope}/" in text, scope


@pytest.mark.parametrize("policy", ["cyc", "tp_driven", "ads_tile"])
def test_round_loop_has_no_element_gathers_for_v5e(policy, one_chip):
    """A ``take_along_axis`` whose index varies per lane and per job
    becomes an element-by-element gather over the whole (R, W) window on
    a TPU.  The round loop picks per-partition and per-rung values with
    selects instead; only the seam hot-swap's argsort permutations,
    which run in segment-entry rounds alone, keep the gather."""
    text = _compiled_loop(policy, "default", one_chip).as_text()
    names = set(re.findall(r'op_name="([^"]*take_along_axis[^"]*)"', text))
    assert [n for n in names if "/step/cond/" not in n] == []


@pytest.mark.parametrize("per_lane", [False, True])
def test_pallas_ladder_grant_compiles_for_v5e(per_lane, one_chip):
    W, C = 80, 6
    cand = (R, W, C) if per_lane else (W, C)
    lowered = jax.jit(K._ladder_grant_pallas).lower(
        _sds((R, W), jnp.float32, one_chip),
        _sds(cand, jnp.float32, one_chip),
    )
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_x64_sampling_pass_compiles_for_v5e(one_chip):
    spec = ScenarioSpec(scenario=get_scenario("rate_churn"), policy="cyc")
    wf, model, _sched, _pf = _prepare_run(spec)
    scen = spec.scenario
    skel = build_skeleton(wf, scen, scen.duration_s)
    par = _params_for(skel, model, scen)

    def shapes(jobs):
        return {
            k: _sds(v.shape, v.dtype, one_chip) for k, v in jobs.items()
        }

    with jax.enable_x64(True):
        key = _sds((R, 1), np.uint64, one_chip)
        lowered = batch._device_draws.lower(
            key, key, key,
            shapes(batch._device_jobs(skel, par, skel.dnn_ix)),
            shapes(batch._device_jobs(skel, par, skel.sen_ix)),
        )
        lowered.compile()
