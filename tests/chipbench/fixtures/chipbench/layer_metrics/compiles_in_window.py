"""A fixture for the contract tests: what a cell of a bucket-crossing
generator owes, XLA compiles inside the window, expected 0."""


def read(evidence):
    compiles = evidence.get("compiles_window")
    return None if compiles is None else float(compiles["compiles"])
