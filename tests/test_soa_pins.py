"""Pinned round-loop outputs of the SoA backend, on every nominal cell.

A change to ``soa_kernels.py`` made for speed alone must leave every
output of the round loop unchanged, bit for bit.  Each case runs one
cell at R = 8 from the default job window and hashes every array the
loop returns, in every loop call of the run (the window-overflow
retries included), against a digest taken before the change.
"""
import hashlib

import numpy as np
import pytest

from repro.core.sim import soa
from repro.core.sim import soa_kernels as K
from repro.scenarios.runner import ScenarioSpec, run
from repro.scenarios.script import get_scenario

#: (loop calls, 16-hex digest) of a run at R = 8 per (scenario, policy,
#: drop policy).  The four soft-drop cyc and ads_tile cells were taken
#: before tp_driven moved to its decision instants and have not moved
#: since; the other 28 were taken on the tree after report assembly
#: moved to whole-array reductions.  cyc terminates budget overruns at
#: the sub-deadline under either drop policy, so its two columns agree.
#: The digests hash raw float bytes from the CPU backend: they hold for
#: the pinned jax version (requirements.txt) only, and may move with
#: another jax, XLA or host instruction set even where the programs do
#: not
PINNED_LOOPS = {
    ("calm_to_rush", "cyc", "soft"): (1, "76046c6ebe520d8c"),
    ("calm_to_rush", "cyc", "hard"): (1, "76046c6ebe520d8c"),
    ("calm_to_rush", "cyc_s", "soft"): (2, "54baaa635a53bd53"),
    ("calm_to_rush", "cyc_s", "hard"): (1, "f183d9e8de42784a"),
    ("calm_to_rush", "tp_driven", "soft"): (1, "658e406fc67dabcf"),
    ("calm_to_rush", "tp_driven", "hard"): (1, "eb633a4fe9b44fb9"),
    ("calm_to_rush", "ads_tile", "soft"): (2, "aad1f19614795fb3"),
    ("calm_to_rush", "ads_tile", "hard"): (1, "d593183234fedbfa"),
    ("commute", "cyc", "soft"): (1, "ade6b8989ebb652d"),
    ("commute", "cyc", "hard"): (1, "ade6b8989ebb652d"),
    ("commute", "cyc_s", "soft"): (3, "8b35ad8ef0f429c9"),
    ("commute", "cyc_s", "hard"): (1, "38fb4192ca9c343e"),
    ("commute", "tp_driven", "soft"): (1, "3f61b447c88b61a0"),
    ("commute", "tp_driven", "hard"): (1, "7628983c60cd5afb"),
    ("commute", "ads_tile", "soft"): (2, "291578329beea112"),
    ("commute", "ads_tile", "hard"): (1, "2fc13a556a58c150"),
    ("night_storm", "cyc", "soft"): (1, "095b2d5122e98652"),
    ("night_storm", "cyc", "hard"): (1, "095b2d5122e98652"),
    ("night_storm", "cyc_s", "soft"): (3, "eb694540d9cedc08"),
    ("night_storm", "cyc_s", "hard"): (1, "8f91f58206095b03"),
    ("night_storm", "tp_driven", "soft"): (1, "f11c44d955d7f44a"),
    ("night_storm", "tp_driven", "hard"): (1, "5675bb06503ac442"),
    ("night_storm", "ads_tile", "soft"): (1, "15044cdcd2d8a5ca"),
    ("night_storm", "ads_tile", "hard"): (1, "ef6a86210372befa"),
    ("rate_churn", "cyc", "soft"): (1, "875d6163084ef997"),
    ("rate_churn", "cyc", "hard"): (1, "875d6163084ef997"),
    ("rate_churn", "cyc_s", "soft"): (3, "b162210764b37636"),
    ("rate_churn", "cyc_s", "hard"): (1, "a3d8ce3e20e80d3e"),
    ("rate_churn", "tp_driven", "soft"): (1, "466081c2197d3ea0"),
    ("rate_churn", "tp_driven", "hard"): (1, "36cd2dd0fc0f046a"),
    ("rate_churn", "ads_tile", "soft"): (2, "0e3daca9015d0f5e"),
    ("rate_churn", "ads_tile", "hard"): (1, "857ac48415738209"),
}
LOOP_KEYS = ("state", "ready_t", "deg", "start", "fin", "dop", "codes",
             "busy", "realloc", "n_realloc", "realloc_bytes", "dropped_work")


@pytest.mark.parametrize(("scenario", "policy", "drop_policy"), sorted(PINNED_LOOPS))
def test_round_loops_are_pinned(scenario, policy, drop_policy, monkeypatch):
    spec = ScenarioSpec(scenario=get_scenario(scenario), policy=policy,
                        drop_policy=drop_policy)
    calls = []
    real = K.simulate

    def recording(cfg, const_np, lanes_np):
        calls.append(real(cfg, const_np, lanes_np))
        return calls[-1]

    monkeypatch.setattr(K, "simulate", recording)
    K.clear_kernel_cache()
    run(spec, seeds=list(range(8)), backend="soa", fallback=False,
        options=soa.SoaOptions())
    h = hashlib.sha1()
    for out in calls:
        for k in LOOP_KEYS:
            a = np.ascontiguousarray(out[k])
            h.update(k.encode())
            h.update(str(a.dtype).encode())
            h.update(a.tobytes())
    assert (len(calls), h.hexdigest()[:16]) == PINNED_LOOPS[
        (scenario, policy, drop_policy)]
