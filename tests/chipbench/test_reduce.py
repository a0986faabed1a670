"""The reduction from a profiler trace, and from samples and histogram
deltas, to the numbers the harness prints: on synthetic intervals, and on a
recording from this benchmark's own chip run."""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.lib import reduce  # noqa: E402
from chipbench.lib import stages  # noqa: E402
from chipbench.lib import trace_reduce as tr  # noqa: E402

# ``default20d.lone25``, seed 106, 0.33 s of the window on a TPU v5 lite
# (PR 23), thinned by dropping device operations under 2 us and all stats.
RECORDING = os.path.join(ROOT, "chipbench", "testdata", "lone25_slice.xplane.pb.gz")
ANNOTATIONS = stages.GAP_ANNOTATIONS  # what a run hands the reduction


@pytest.mark.parametrize(
    "intervals,merged",
    [
        ([], []),
        ([(0, 1)], [(0, 1)]),
        ([(0, 1), (1, 2)], [(0, 2)]),
        ([(2, 3), (0, 1)], [(0, 1), (2, 3)]),
        ([(0, 5), (1, 2), (4, 6)], [(0, 6)]),
        ([(1, 1), (3, 2)], []),
    ],
)
def test_merge_is_the_union(intervals, merged):
    assert tr.merge(intervals) == merged


@pytest.mark.parametrize(
    "busy,lo,hi,idle",
    [
        ([], 0, 10, [(0, 10)]),
        ([(0, 10)], 0, 10, []),
        ([(2, 3), (5, 7)], 0, 10, [(0, 2), (3, 5), (7, 10)]),
        ([(0, 4), (6, 10)], 0, 10, [(4, 6)]),
    ],
)
def test_gaps_are_the_complement(busy, lo, hi, idle):
    assert tr.gaps(busy, lo, hi) == idle
    assert tr.total(busy) + tr.total(idle) == hi - lo


def test_clip_and_overlap():
    assert tr.clip([(0, 2), (3, 9), (11, 12)], 1, 10) == [(1, 2), (3, 9)]
    assert tr.overlap([(0, 2), (3, 9)], 1, 4) == 2


@pytest.mark.parametrize(
    "gap,label",
    [((0, 4), "client.complete"), ((4, 8), "client.suggest"), ((8, 12), "none"), ((3.5, 4.5), "client.complete")],
)
def test_a_gap_is_named_after_what_covers_most_of_it(gap, label):
    host = {"client.complete": [(0, 4)], "client.suggest": [(4.1, 8.5)]}
    assert tr.attribute(gap, host) == label


def _host(recorded):
    """Host spans as ``read_xplane`` keeps them: those of the names a run
    asks for, every other recorded span dropped."""
    host = {name: [] for name in ANNOTATIONS}
    for name, span in recorded:
        if name in host:
            host[name].append(span)
    return {name: tr.merge(spans) for name, spans in host.items()}


def test_a_gap_takes_the_stage_that_covers_it_and_an_rpc_alone_names_nothing():
    # One request: the RPC span covers all of it, the stages its parts. The
    # device runs during device.wait only, and not for all of it.
    recorded = [
        ("client.suggest", (0.0, 8.0)),
        ("service.read", (0.2, 0.5)),
        ("policy.load_trials", (0.5, 2.0)),
        ("designer.prepare", (2.0, 3.0)),
        ("device.wait", (3.0, 5.0)),
        ("designer.decode", (5.0, 6.0)),
        ("client.complete", (8.0, 10.0)),
    ]
    ops = {"/device:TPU:0": [("m:%while.1 while", 3.2, 4.8)]}
    assert "client.suggest" not in ANNOTATIONS
    out = tr.reduce_intervals(ops, _host(recorded), 0.5, 10.0)
    # The gap before the device starts, 0.5-3.2: load_trials covers 1.5 of
    # its 2.7 s. The one after, 4.8-10.0: decode 1.0, complete 2.0 of 5.2 s,
    # and 6.0-8.0 inside the RPC with no stage.
    assert out["idle_gaps"] == [("client.complete", pytest.approx(5.2)), ("policy.load_trials", pytest.approx(2.7))]
    # A gap inside the RPC that no stage covers reads "none", whatever the
    # RPC's own span says: only leaves name a gap.
    assert tr.attribute((6.2, 7.8), _host(recorded)) == "none"
    assert tr.attribute((2.1, 2.9), _host(recorded)) == "designer.prepare"
    assert tr.attribute((4.8, 5.0), _host(recorded)) == "device.wait"
    assert set(out["idle_by_host_activity"]) <= set(ANNOTATIONS) | {"none"}


def test_reduce_intervals_on_a_synthetic_trace():
    ops = {
        "/device:TPU:0": [
            ("m:%while.1 while", 1.0, 3.0),  # a loop, and two operations inside it
            ("m:%fusion.2 fusion", 1.0, 1.5),
            ("m:%fusion.2 fusion", 2.0, 2.5),
            ("m:%copy.3 copy", 6.0, 7.0),
            ("m:%copy.3 copy", 9.5, 12.0),  # runs past the span: clipped
        ]
    }
    host = {"client.suggest": [(0.0, 3.2)], "client.complete": [(3.2, 5.9)]}
    out = tr.reduce_intervals(ops, host, 0.0, 10.0)
    assert out["busy_s"] == pytest.approx(2.0 + 1.0 + 0.5)
    assert out["span_s"] == 10.0
    assert tr.idle_share(out) == pytest.approx(65.0)
    assert dict(out["device_ops"]) == pytest.approx(
        {"m:%while.1 while": 2.0, "m:%fusion.2 fusion": 1.0, "m:%copy.3 copy": 1.5}
    )
    assert out["device_ops"][0][0] == "m:%while.1 while"
    assert out["idle_gaps"][0] == ("client.complete", pytest.approx(3.0))
    assert out["idle_by_host_activity"] == pytest.approx(
        {"client.suggest": 1.0, "client.complete": 3.0, "none": 2.5}
    )
    assert tr.busy_ms_per_request(out, seconds=20.0, requests=10) == pytest.approx(700.0)


def test_without_a_trace_the_readers_have_nothing():
    assert tr.idle_share(None) is None and tr.idle_share({}) is None
    assert tr.busy_ms_per_request({}, 10.0, 5) is None
    assert tr.busy_ms_per_request({"busy_s": 1.0, "span_s": 2.0}, 10.0, 0) is None
    assert tr.reduce_intervals({}, {}, 0.0, 1.0) == {}


@pytest.mark.parametrize(
    "hlo,short",
    [
        ("%while.26 = (s32[]{:T(128)}, f32[50,20]{0,1:T(8,128)S(1)}) while((s32[]{:T(128)}) %tuple.231), "
         "condition=%c, body=%b", "%while.26 while"),
        ("%copy-start.15 = (s32[2]{0:T(128)S(1)}, u32[]{:S(2)}) copy-start(s32[2]{0:T(128)} %gte.3)",
         "%copy-start.15 copy-start"),
        ("%fusion.12 = f32[8,128]{1,0:T(8,128)} fusion(f32[8]{0} %p), kind=kLoop", "%fusion.12 fusion"),
        ("broadcast_in_dim", "broadcast_in_dim"),
    ],
)
def test_an_operation_keeps_its_name_and_opcode(hlo, short):
    assert tr.short_name(hlo) == short


def test_the_recorded_slice_reduces_to_what_the_chip_run_printed():
    out = tr.reduce_trace(RECORDING, ANNOTATIONS)
    # The run itself printed busy 0.100757 s of a 0.325697 s span from the
    # unthinned trace; the thinning costs 0.06 ms.
    assert out["span_s"] == pytest.approx(0.325697, abs=1e-6)
    assert out["busy_s"] == pytest.approx(0.100757, abs=1e-4)
    assert tr.idle_share(out) == pytest.approx(69.06, abs=0.05)
    names = [name for name, _ in out["device_ops"]]
    assert len(names) == 10 and names[0] == "jit__suggest_batch:%while.82 while"
    assert all(name.startswith("jit_") for name in names)
    seconds = [s for _, s in out["device_ops"]]
    assert seconds == sorted(seconds, reverse=True) and seconds[0] == pytest.approx(0.05257, abs=1e-4)
    # One long gap after the suggest's programs, while the client completes
    # trials; the completes are too short to cover a quarter of it.
    assert out["idle_gaps"][0][1] == pytest.approx(0.2202, abs=1e-3)
    assert sum(out["idle_by_host_activity"].values()) == pytest.approx(out["span_s"] - out["busy_s"])


def test_a_trace_without_the_harness_mark_reduces_to_nothing(tmp_path):
    import gzip

    empty = tmp_path / "empty.xplane.pb.gz"
    with gzip.open(empty, "wb") as f:
        f.write(b"")
    assert tr.reduce_trace(str(empty), ANNOTATIONS) == {}


@pytest.mark.parametrize("q,want", [(50, 3), (95, 10), (100, 10), (10, 1)])
def test_percentile_is_nearest_rank(q, want):
    assert reduce.percentile([10, 1, 2, 3, 4], q) == want


def test_histogram_deltas_pool_and_interpolate():
    bounds = [0.1, 0.2, 0.4]
    before = {"h": {"bounds": bounds, "series": {"hop=service": ([1, 0, 0, 0], 1, 0.05)}}}
    after = {
        "h": {
            "bounds": bounds,
            "series": {
                "hop=service": ([1, 4, 0, 0], 5, 0.65),
                "hop=pythia": ([0, 0, 2, 0], 2, 0.6),
            },
        }
    }
    delta = reduce.histogram_delta(after, before)
    assert delta["h"]["series"]["hop=service"] == ([0, 4, 0, 0], 4, pytest.approx(0.6))
    counts, count, total = reduce.pooled(delta["h"], "hop=service")
    assert (counts, count) == ([0, 4, 0, 0], 4) and total == pytest.approx(0.6)
    assert reduce.pooled(delta["h"])[1] == 6
    assert reduce.bucket_quantile(bounds, counts, 50) == pytest.approx(0.15)
    assert reduce.bucket_quantile(bounds, [0, 0, 0, 3], 50) == 0.4  # past the last bound: clamped
    assert reduce.bucket_quantile(bounds, [0, 0, 0, 0], 50) is None
    assert reduce.counter_delta({"a": 5, "b": 1}, {"a": 2}) == {"a": 3, "b": 1}
