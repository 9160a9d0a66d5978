"""setup.loop_attempts: round-loop runs in the set-up call.

One per attempt of the runner's SoA path: a job window that overflows
under overload is discarded and the round loop runs again, wider
(and compiles again).  Counted from the spans of
``soa_kernels.simulate`` during set-up; absent where that target is gone.
"""

UNIT = "runs"
HOOKS = {"loop": "repro.core.sim.soa_kernels:simulate"}


def read(ctx):
    spans = ctx["setup"]["spans"]
    if "loop" not in spans:
        return None
    return float(spans["loop"][0])
