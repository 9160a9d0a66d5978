"""sample.ms_per_drive_s: host milliseconds per simulated drive-second in trace sampling.

Layer: ``sample_trace_batch(device=True)``: the x64 draws of every job of every drive.  Summed over the spans that began in the measured
window, over the drive-seconds the window completed.  Absent where the
span's target is gone or never ran.
"""

UNIT = "ms/drive-s"
HOOKS = {'sample': 'repro.scenarios.runner:sample_trace_batch'}


def read(ctx):
    spans = ctx["window"]["spans"]
    if not any(s in spans for s in HOOKS) or ctx["window"]["drive_s"] <= 0:
        return None
    secs = sum(spans[s][1] for s in HOOKS if s in spans)
    return secs * 1e3 / ctx["window"]["drive_s"]
