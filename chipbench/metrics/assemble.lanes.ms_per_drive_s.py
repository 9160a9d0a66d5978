"""assemble.lanes.ms_per_drive_s: host milliseconds per simulated drive-second in report assembly's per-lane Python.

The program's own span ``soa_assemble_lanes`` (``repro.obs.metrics``):
the per-lane loop of ``soa._assemble_reports`` over sinks, chains and
modes, after its whole-array part (``soa_assemble_arrays``).  Summed
over the spans of the measured window, over the drive-seconds the
window completed.  Read in ``--trace 1`` runs, which enable the
registry (``harness.program``); absent where the program has no such
span.
"""
from harness import program

UNIT = "ms/drive-s"
HOOKS = {}
PHASES = ("soa_assemble_lanes",)
program.install()


def read(ctx):
    return program.READER.ms_per_drive_s(ctx, PHASES)
