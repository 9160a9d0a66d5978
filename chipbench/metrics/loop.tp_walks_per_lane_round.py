"""loop.tp_walks_per_lane_round: tp_driven's queue walks per lane and round.

The program's own counters (``repro.obs.metrics``): ``soa_tp_walks``,
the walks the round loop took at tp_driven's queue-change instants
summed over lanes, over ``soa_lane_rounds``, the rounds it ran times
its lanes, both summed over the calls of the measured window.  The
scalar engine walks at about 0.62 of a lane's rounds on ``rate_churn``;
a loop that walks once a round reads 1.  Read in ``--trace 1`` runs,
which enable the registry (``harness.program``); absent where the
program has no such counters, or walks nowhere.
"""
from harness import program

UNIT = "walks/lane-round"
HOOKS = {}
program.install()


def read(ctx):
    win = program.READER.window()
    if win is None:
        return None
    counters = win["counters"]
    walks, lane_rounds = counters.get("soa_tp_walks"), counters.get("soa_lane_rounds")
    if not walks or not lane_rounds:
        return None
    return walks / lane_rounds
