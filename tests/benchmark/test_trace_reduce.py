"""The reduction from a profiler trace to numbers, on a small trace in
the TPU profiler's layout (benchmark/testdata/trace_small.pbtxt: two
chips, a ``while`` that holds its body's fusions, programs, one host
thread with the benchmark's spans), and opcount.py against a hand count
for one lane. Nothing here touches a device or describes a topology."""

import json
import os

import pytest

from benchmark import opcount, run, trace_reduce

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
TRACE = os.path.join(REPO, "benchmark", "testdata", "trace_small.pbtxt")
MS = 1e-3


@pytest.fixture(scope="module")
def planes():
    return trace_reduce.load(TRACE)


@pytest.fixture(scope="module")
def reduced(planes):
    return trace_reduce.reduce(planes)


def test_the_trace_loads_as_planes_lines_and_events(planes):
    names = [p["name"] for p in planes]
    assert "/device:TPU:0" in names and "/host:CPU" in names
    dev0 = next(p for p in planes if p["name"] == "/device:TPU:0")
    assert {l["name"] for l in dev0["lines"]} == {
        "XLA Modules", "XLA Ops", "Async XLA Ops", "Steps"}
    name, start, dur = dev0["lines"][0]["events"][0]
    assert name.startswith("jit__verify_core_compact(")
    assert (start, dur) == pytest.approx((1 * MS + 4 * MS, 6 * MS))


def test_only_numbered_tpu_planes_are_chips(reduced):
    assert sorted(reduced["chips"]) == [0, 1]


def test_the_window_is_the_extent_of_the_benchmarks_spans(reduced):
    assert reduced["window_s"] == pytest.approx(25 * MS)


def test_busy_is_the_union_of_operation_intervals_per_chip(reduced):
    # chip 0: [4,4.4) [4.5,9.5) [17,17.4) [17.5,22.5); the fusions lie
    # inside the while and add nothing
    assert reduced["chips"][0]["busy_s"] == pytest.approx(10.8 * MS)
    assert reduced["chips"][0]["busy_share"] == pytest.approx(10.8 / 25)
    assert reduced["chips"][1]["busy_s"] == pytest.approx(3 * MS)
    assert reduced["busy_s"] == pytest.approx((10.8 + 3) / 2 * MS)


def test_operation_seconds_are_self_time_so_nothing_counts_twice(reduced):
    ops = {trace_reduce.short_name(k): v for k, v in reduced["ops"].items()}
    assert ops["%while.33"] == pytest.approx((5 - 2) * 2 * MS)
    assert ops["%fusion.7"] == pytest.approx((4 * 1 + 3) * MS)
    assert ops["%copy.1"] == pytest.approx(0.8 * MS)
    assert sum(reduced["ops"].values()) == pytest.approx(
        (10.8 + 3) * MS)  # = the chips' busy seconds
    top = trace_reduce.top(reduced["ops"], 2)
    assert [name for name, _ in top] == ["%fusion.7", "%while.33"]
    assert all(len(name) <= 96 for name, _ in top)


def test_program_seconds_by_name(reduced):
    assert trace_reduce.program_seconds(reduced, r"verify") == \
        pytest.approx((6 + 6 + 3) * MS)
    assert trace_reduce.program_seconds(reduced, r"merkle") == 0


def test_idle_gaps_go_to_the_host_span_that_covers_them(reduced):
    idle = reduced["idle_by_span"]
    assert idle["bench:submit"] == pytest.approx(4 * MS)
    assert idle["bench:wait_verdict"] == pytest.approx(9.2 * MS)
    assert idle[trace_reduce.UNATTRIBUTED] == pytest.approx(1 * MS)
    # every idle second is accounted for once: window - union of busy
    assert sum(idle.values()) == pytest.approx((25 - 10.8) * MS)
    assert reduced["spans"]["bench:submit"] == [2, pytest.approx(4 * MS)]


def test_an_explicit_window_clips_operations_and_gaps(planes):
    r = trace_reduce.reduce(planes, window=(1 * MS + 4 * MS, 1 * MS + 10 * MS))
    assert r["window_s"] == pytest.approx(6 * MS)
    assert r["chips"][0]["busy_s"] == pytest.approx(5.4 * MS)
    assert sum(r["idle_by_span"].values()) == pytest.approx(0.6 * MS)


def test_a_trace_without_a_device_plane_reduces_to_nothing(planes):
    host_only = [p for p in planes if not p["name"].startswith("/device")]
    assert trace_reduce.reduce(host_only) is None


@pytest.mark.parametrize("intervals,want", [
    ([(3, 4), (1, 2), (1.5, 3.2)], [(1, 4)]),
    ([(1, 2), (2, 3)], [(1, 3)]),
    ([(5, 5)], []),
])
def test_union(intervals, want):
    assert trace_reduce.union(intervals) == want


def test_gaps_and_nested_self_times():
    assert trace_reduce.gaps([(1, 2), (3, 4)], 0, 5) == [
        (0, 1), (2, 3), (4, 5)]
    assert trace_reduce.gaps([], 0, 5) == [(0, 5)]
    got = trace_reduce.self_times(
        [("while", 0, 10), ("f1", 1, 2), ("f2", 4, 3), ("x", 11, 1)])
    assert got == [("while", 0, 5), ("f1", 1, 2), ("f2", 4, 3), ("x", 11, 1)]


def test_attribution_gives_nested_spans_to_the_innermost():
    spans = [("bench:a", 0, 10), ("bench:b", 2, 4), ("bench:c", 12, 13)]
    got = trace_reduce.attribute([(1, 3), (3.5, 11), (11.5, 14)], spans)
    assert got == pytest.approx({
        "bench:a": 7.0, "bench:b": 1.5, "bench:c": 1.0,
        trace_reduce.UNATTRIBUTED: 2.5})


# --------------------------------------------------------------------------
# opcount.py against a hand count for one lane


def test_opcount_against_a_hand_count_for_one_lane():
    muls = opcount.field_muls()
    # decompress: y^2, d*y^2, v^3 (2), v^7 (2), u*v^7, the 2^252-3 power
    # (251 squarings + 11 products), x (2), v*x^2 (2), x*sqrt(-1)
    assert muls["decompress"] == 1 + 1 + 2 + 2 + 1 + 262 + 2 + 2 + 1 == 274
    # table: T of -A, one doubling (8), ten additions (9 each: cache +
    # add), twelve more cached forms
    assert muls["table"] == 1 + 8 + 10 * 9 + 12 == 111
    # ladder: 127 x (two doublings + one cached addition) x 8
    assert muls["ladder"] == 127 * 24 == 3048
    # encode: inversion (254 squarings + 11 products), x and y by 1/z
    assert muls["encode"] == 265 + 2
    assert sum(muls.values()) == 3700
    assert opcount.limb_macs_per_lane() == 3700 * 17 * 17 == 1_069_300
    assert opcount.int8_ops_per_lane() == 8_554_400
    assert opcount.hbm_bytes_per_lane() == 129
    assert opcount.hbm_bytes_per_lane(96) == 97


def test_the_roofline_is_compute_bound_on_the_v5e():
    with open(os.path.join(REPO, "benchmark", "peaks.json")) as fh:
        v5e = json.load(fh)["TPU v5 lite"]
    least = opcount.least_seconds_per_lane(v5e)
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(8_554_400 / 393e12)
    # a memory-starved part would be bound the other way
    slow = dict(v5e, hbm_bytes_per_s=1e9)
    assert opcount.least_seconds_per_lane(slow)["bound"] == "memory"


def _layer(name):
    return run.load_module("layers", name)


def test_the_trace_fed_readers_on_the_small_trace(reduced):
    def wire(lanes):
        return {"wire": {"lanes": {"single": lanes}, "phase_s": {}}}

    trace = dict(reduced, counters={"before": wire(100), "after": wire(228)})
    after = {"bench": {"device_kind": "TPU v5 lite"}}
    # 15 ms of verify programs over 128 lanes
    us = _layer("kernel_us_per_lane").read({}, after, trace)
    assert us == pytest.approx(15e3 / 128)
    share = _layer("ed25519_verify_roofline").read({}, after, trace)
    assert share == pytest.approx(100 * (8_554_400 / 393e12) * 1e6 / us)
    for name in ("kernel_us_per_lane", "ed25519_verify_roofline"):
        assert _layer(name).read({}, after, None) is None
    with pytest.raises(KeyError):
        _layer("ed25519_verify_roofline").read(
            {}, {"bench": {"device_kind": "TPU v9"}}, trace)
