"""prepare.ms_per_drive_s: host milliseconds per simulated drive-second in stack and portfolio.

Layer: the runner's ``_prepare_run``: workload stack and the per-mode GHA schedule portfolio, compiled on every call.  Summed over the spans that began in the measured
window, over the drive-seconds the window completed.  Absent where the
span's target is gone or never ran.
"""

UNIT = "ms/drive-s"
HOOKS = {'prepare': 'repro.scenarios.runner:_prepare_run'}


def read(ctx):
    spans = ctx["window"]["spans"]
    if not any(s in spans for s in HOOKS) or ctx["window"]["drive_s"] <= 0:
        return None
    secs = sum(spans[s][1] for s in HOOKS if s in spans)
    return secs * 1e3 / ctx["window"]["drive_s"]
