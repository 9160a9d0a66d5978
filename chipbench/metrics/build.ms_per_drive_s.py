"""build.ms_per_drive_s: host milliseconds per simulated drive-second in problem build.

Layer: ``soa.build_problem`` (round grid, windows, EDF permutations) and ``soa._lanes`` (lane padding).  Summed over the spans that began in the measured
window, over the drive-seconds the window completed.  Absent where the
span's target is gone or never ran.
"""

UNIT = "ms/drive-s"
HOOKS = {'build': 'repro.core.sim.soa:build_problem', 'lanes': 'repro.core.sim.soa:_lanes'}


def read(ctx):
    spans = ctx["window"]["spans"]
    if not any(s in spans for s in HOOKS) or ctx["window"]["drive_s"] <= 0:
        return None
    secs = sum(spans[s][1] for s in HOOKS if s in spans)
    return secs * 1e3 / ctx["window"]["drive_s"]
