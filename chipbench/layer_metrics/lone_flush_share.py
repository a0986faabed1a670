"""Share of the window's batch-executor flushes that held one real slot, in %:
handed back to the sequential path unprepared (``serving_stats()``
lone_handbacks) or run through the fused program with every other slot a
padded copy (lone_flushes), over batch_flushes. Left out where the program
has no such counters or nothing was flushed."""


def read(evidence):
    stats = evidence["stats_window"]
    flushes = stats.get("batch_flushes", 0)
    if "lone_handbacks" not in stats or "lone_flushes" not in stats or not flushes:
        return None
    return 100.0 * (stats["lone_handbacks"] + stats["lone_flushes"]) / flushes
