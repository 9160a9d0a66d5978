"""device.idle_share: share of one whole warm call in which the device ran nothing.

From the profiler trace: 1 - (union of device operations inside the
call) / (the call's host span), in percent, averaged over the chips.
Absent where the trace holds no call or no device operation.
"""

UNIT = "%"
HOOKS = {}


def read(ctx):
    red = ctx["trace"]
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
