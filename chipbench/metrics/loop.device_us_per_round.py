"""loop.device_us_per_round: device microseconds per round of the round loop.

From the profiler trace of one whole warm call: the device time of the
round-loop executable (the ``XLA Modules`` events whose name carries
``LOOP_MODULE``: ``soa_kernels.round_loop`` jits a function named
``run``), over the rounds that the call's round-loop attempts ran.
Absent where the trace holds no such executable.
"""

UNIT = "us/round"
HOOKS = {}
LOOP_MODULE = "jit_run"


def read(ctx):
    red = ctx["trace"]
    rounds = ctx["traced_rounds"]
    if red is None or not rounds:
        return None
    secs = sum(s for name, (_n, s) in red["modules"].items() if LOOP_MODULE in name)
    if secs <= 0:
        return None
    return secs * 1e6 / rounds
