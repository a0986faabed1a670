"""Loss evaluations a live row-iteration of the ARD train
(``serving_stats()`` train_evaluations ÷ train_row_iterations): one at the
line search's first step and one a halving, each a Cholesky at the cell's
pad; a row's evaluation of its start is in the numerator too. Left out where
the program has no such counters or the window trained nothing."""

from chipbench.lib import device_half


def read(evidence):
    counted = device_half.train_counters(evidence)
    if not counted or not counted["train_row_iterations"]:
        return None
    return counted["train_evaluations"] / counted["train_row_iterations"]
