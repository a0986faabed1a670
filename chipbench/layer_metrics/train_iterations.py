"""Trips of the batched L-BFGS ``while`` a train program
(``serving_stats()`` train_loop_trips ÷ train_programs): the iterations of
the program's slowest restart row — all slots' rows in a fused flush, all
devices' rows on a mesh. Left out where the program has no such counters or
the window trained nothing."""

from chipbench.lib import device_half


def read(evidence):
    counted = device_half.train_counters(evidence)
    return counted["train_loop_trips"] / counted["train_programs"] if counted else None
