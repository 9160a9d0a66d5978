"""round.policy.device_us_per_round: device microseconds per round in the round loop's allocation and grant.

From the profiler trace of one whole warm call: the device time of the
leaf operations of the round-loop executable whose name stack carries
the body scope ``policy`` (the policy pass: EDF allocation over the DoP ladder (with the Pallas grant), the work-conserving bumps and ads' Algorithm 2 phases;
``soa_kernels._build_loop``), over the rounds that the call's
round-loop attempts ran.  Reduced by ``harness.program``; absent where
no operation of the trace carries the scope.
"""
from harness import program

UNIT = "us/round"
HOOKS = {}
SCOPE = "policy"
program.install()


def read(ctx):
    return program.READER.scope_us_per_round(ctx, SCOPE)
