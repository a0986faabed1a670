"""The executor's counts of flushes that held one real slot, and its flush
and slot counts by bucket label (``vizier_batch_occupancy``): what
``chipbench``'s ``lone_flush_share`` and the fleet cell's line read."""

import threading
import time

from vizier_tpu.parallel.batch_executor import BatchExecutor
from vizier_tpu.serving.stats import ServingStats

from tests.parallel.test_batch_executor import FailPrepareStub, StubDesigner


def _suggest_in_threads(executor, jobs):
    """(designer, count) pairs, each on a thread of its own; a slot's own
    error stays with its thread."""

    def suggest(designer, count):
        try:
            executor.suggest(designer, count)
        except RuntimeError:
            pass

    threads = [threading.Thread(target=suggest, args=job) for job in jobs]
    for t in threads:
        t.start()
    return threads


def _wait_for(condition, seconds=10.0):
    deadline = time.monotonic() + seconds
    while not condition() and time.monotonic() < deadline:
        time.sleep(0.002)
    assert condition()


def test_a_lone_unprepared_slot_is_a_hand_back():
    stats = ServingStats()
    ex = BatchExecutor(max_batch_size=8, max_wait_ms=10, stats=stats)
    try:
        designer = StubDesigner(0.7)
        ex.suggest(designer, 1)
        snap = stats.snapshot()
        assert designer.sequential_calls == 1 and not designer.batched
        assert (snap["batch_flushes"], snap["lone_handbacks"], snap["lone_flushes"]) == (1, 1, 0)
    finally:
        ex.close()


def test_a_flush_that_met_is_not_lone():
    stats = ServingStats()
    ex = BatchExecutor(max_batch_size=3, max_wait_ms=5000, stats=stats)
    try:
        for t in _suggest_in_threads(ex, [(StubDesigner(v), 1) for v in (0.1, 0.2, 0.3)]):
            t.join(timeout=60)
        snap = stats.snapshot()
        assert (snap["batch_flushes"], snap["batched_suggests"]) == (1, 3)
        assert (snap["lone_handbacks"], snap["lone_flushes"]) == (0, 0)
    finally:
        ex.close()


def test_the_one_survivor_of_a_flush_runs_the_fused_program_alone():
    stats = ServingStats()
    ex = BatchExecutor(max_batch_size=2, max_wait_ms=5000, stats=stats)
    try:
        # The first arrival finds its bucket empty and stays unprepared; the
        # second prepares as it joins. The flush then prepares the first,
        # which raises: one real slot is left for the fused program.
        failing, sound = FailPrepareStub(0.1), StubDesigner(0.2)
        first = _suggest_in_threads(ex, [(failing, 1)])
        _wait_for(lambda: sum(ex.pending_counts().values()) == 1)
        second = _suggest_in_threads(ex, [(sound, 1)])
        for t in first + second:
            t.join(timeout=60)
        snap = stats.snapshot()
        assert sound.batched and sound.sequential_calls == 0
        assert (snap["batch_flushes"], snap["lone_flushes"], snap["lone_handbacks"]) == (1, 1, 0)
        assert (snap["batched_suggests"], snap["batch_slot_errors"]) == (1, 1)
    finally:
        ex.close()


def test_flushes_and_slots_are_counted_by_bucket_label():
    stats = ServingStats()
    ex = BatchExecutor(max_batch_size=2, max_wait_ms=300, stats=stats, metrics=stats.registry)
    try:
        # Two buckets (the suggestion count is part of a bucket's label): two
        # studies meet in one, a third waits out the window alone in the other.
        pair, lone = [StubDesigner(0.1), StubDesigner(0.2)], StubDesigner(0.3)
        for t in _suggest_in_threads(ex, [(pair[0], 1), (pair[1], 1), (lone, 2)]):
            t.join(timeout=60)
        series = {dict(key)["bucket"]: data for key, data in stats.registry.get("vizier_batch_occupancy").series_data().items()}
        assert set(series) == {"stub/t8/f1x0/m1/q1", "stub/t8/f1x0/m1/q2"}
        counts, flushes, slots = series["stub/t8/f1x0/m1/q1"]
        assert (flushes, slots, counts[0]) == (1, 2.0, 0)  # one flush of two real slots, none lone
        counts, flushes, slots = series["stub/t8/f1x0/m1/q2"]
        assert (flushes, slots, counts[0]) == (1, 1.0, 1)  # the first bucket of the histogram is occupancy 1
        snap = stats.snapshot()
        assert (snap["batch_flushes"], snap["lone_handbacks"], snap["lone_flushes"]) == (2, 1, 0)
        assert snap["batched_suggests"] == 2 and lone.sequential_calls == 1
    finally:
        ex.close()
