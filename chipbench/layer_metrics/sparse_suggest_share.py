"""Share of the window's suggest requests that the sparse posterior served
(``serving_stats()`` sparse_suggests over the window ÷ requests, as
``mesh_suggest_share`` divides), in %. A guard, like ``cache_warm_share``:
under 100 a study of the cell was answered by the exact programs and the
cell's number is of something else. Nothing from a program without the
counter or a window without a request."""


def read(evidence):
    suggests = evidence.get("stats_window", {}).get("sparse_suggests")
    requests = evidence.get("attempted")
    return 100.0 * suggests / requests if suggests is not None and requests else None
