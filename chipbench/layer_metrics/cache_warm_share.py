"""Share of the window's ARD trains that started warm from the designer
cache (``serving_stats()`` warm_trains / (warm + cold)), in %."""


def read(evidence):
    stats = evidence["stats_window"]
    trains = stats["warm_trains"] + stats["cold_trains"]
    return 100.0 * stats["warm_trains"] / trains if trains else None
