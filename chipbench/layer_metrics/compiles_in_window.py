"""XLA compile requests inside the window, from JAX's monitoring events
(persistent-cache hits included); expected 0."""


def read(evidence):
    return float(evidence["compiles_window"]["compiles"])
