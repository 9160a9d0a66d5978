"""transfer.ms_per_drive_s: host milliseconds per simulated drive-second in host-device transfers.

The program's own spans (``repro.obs.metrics`` phases): ``soa_upload``
(the lanes to the device), ``soa_fetch`` (the round loop's result
planes back) and ``trace_sample_fetch`` (the draws back), each waiting
for its copy while the registry is on.  Summed over the spans of the
measured window, over the drive-seconds the window completed.  Read in
``--trace 1`` runs, which enable the registry (``harness.program``);
absent where the program has no such spans.
"""
from harness import program

UNIT = "ms/drive-s"
HOOKS = {}
PHASES = ("soa_upload", "soa_fetch", "trace_sample_fetch")
program.install()


def read(ctx):
    return program.READER.ms_per_drive_s(ctx, PHASES)
