"""loop.ms_per_drive_s: host milliseconds per simulated drive-second in round loop.

Layer: ``soa_kernels.simulate``: the jitted round loop with its transfers to and from the device.  Summed over the spans that began in the measured
window, over the drive-seconds the window completed.  Absent where the
span's target is gone or never ran.
"""

UNIT = "ms/drive-s"
HOOKS = {'loop': 'repro.core.sim.soa_kernels:simulate'}


def read(ctx):
    spans = ctx["window"]["spans"]
    if not any(s in spans for s in HOOKS) or ctx["window"]["drive_s"] <= 0:
        return None
    secs = sum(spans[s][1] for s in HOOKS if s in spans)
    return secs * 1e3 / ctx["window"]["drive_s"]
