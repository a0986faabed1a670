"""Share of the window's suggest requests whose device programs ran on the
designers' whole-host mesh (``serving_stats()`` mesh_suggests over the
window ÷ requests, as ``batched_share`` divides), in %. A guard, like
``cache_warm_share``: under 100 the host served a suggest on one chip and
the cell's number is of something else. Nothing from a program without the
counter (a parent commit) or a window without a request."""


def read(evidence):
    suggests = evidence.get("stats_window", {}).get("mesh_suggests")
    requests = evidence.get("attempted")
    return 100.0 * suggests / requests if suggests is not None and requests else None
