"""Share of the row-trips the batched L-BFGS loop ran for a row that had
already stopped, in %: 100 x (1 - train_row_iterations ÷ train_row_trips) of
``serving_stats()`` — rows x trips is what lockstep ran (a padded slot's rows
too), the rows' own iterations what they needed. Left out where the program
has no such counters or the window trained nothing."""

from chipbench.lib import device_half


def read(evidence):
    counted = device_half.train_counters(evidence)
    if not counted or not counted["train_row_trips"]:
        return None
    return 100.0 * (1.0 - counted["train_row_iterations"] / counted["train_row_trips"])
