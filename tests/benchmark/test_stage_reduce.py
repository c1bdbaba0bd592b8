"""The program's stages in a profiler trace (benchmark/stage_reduce.py),
on a small recorded trace in the TPU profiler's layout
(benchmark/testdata/trace_stages.pbtxt): one chip busy [6,10) and [35,38)
ms; the caller's thread with ``bench:request`` [0,20) and
``bench:verify_commit`` [30,40) and the stages that run on it; the flush
thread; the supervised dispatch's worker thread. Nothing here touches a
device."""

import os

import pytest

from benchmark import run_stages, stage_reduce, trace_reduce

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
TESTDATA = os.path.join(REPO, "benchmark", "testdata")
STAGES = os.path.join(TESTDATA, "trace_stages.pbtxt")
SMALL = os.path.join(TESTDATA, "trace_small.pbtxt")
MS = 1e-3


@pytest.fixture(scope="module")
def planes():
    return trace_reduce.load(STAGES)


@pytest.fixture(scope="module")
def reduced(planes):
    return stage_reduce.reduce(planes)


def test_the_window_is_the_extent_of_the_bench_spans_only(reduced):
    # cbft:commit.tally ends at 41 ms and does not stretch it
    assert reduced["window_s"] == pytest.approx(40 * MS)
    assert reduced["busy_s"] == pytest.approx(7 * MS)
    assert set(reduced["spans"]) == {"bench:request", "bench:verify_commit"}


@pytest.mark.parametrize("name,count,ms", [
    ("cbft:sched.submit", 1, 1.0),
    ("cbft:sched.assemble", 1, 0.5),
    ("cbft:sched.route", 1, 0.5),
    ("cbft:sup.supervise", 1, 11.0),
    ("cbft:sup.device", 1, 9.0),
    ("cbft:mesh.retire", 1, 7.0),
    ("cbft:sched.demux", 1, 3.0),       # past bench:request's end: counted
    ("cbft:host.verify", 1, 2.0),       # under no benchmark span: counted
    ("cbft:commit.sign_bytes", 1, 4.0),
    ("cbft:resident.retire", 2, 3.0),   # two chunks
    ("cbft:commit.tally", 1, 1.0),      # [39,41) clipped to the window
])
def test_stage_counts_and_seconds_clipped_to_the_window(
        reduced, name, count, ms):
    assert reduced["stages"][name] == [count, pytest.approx(ms * MS)]


def test_unstaged_is_the_open_request_time_no_stage_covers(reduced):
    assert reduced["bench_s"] == pytest.approx(30 * MS)
    # request: [0,.5) [1.5,2) [14,19); verify_commit: [30,30.5) [34.5,35)
    # [37,37.5) [38.5,39); stages on the other threads count as cover
    assert reduced["unstaged_s"] == pytest.approx((6 + 2) * MS)


def test_a_gap_goes_to_the_innermost_span_of_either_prefix(reduced, planes):
    idle = reduced["idle_by_span"]
    want = {
        "bench:request": 6.0, "cbft:sched.submit": 1.0,
        "cbft:sched.assemble": 0.5, "cbft:sched.route": 0.5,
        "cbft:sup.supervise": 2.0, "cbft:sup.device": 2.0,
        "cbft:mesh.retire": 3.0, "cbft:sched.demux": 3.0,
        trace_reduce.UNATTRIBUTED: 6.0, "cbft:host.verify": 2.0,
        "cbft:commit.sign_bytes": 4.0, "bench:verify_commit": 1.5,
        "cbft:resident.retire": 0.5, "cbft:commit.tally": 1.0,
    }
    assert set(idle) == set(want)
    for name, ms in want.items():
        assert idle[name] == pytest.approx(ms * MS), name
    assert sum(idle.values()) == pytest.approx((40 - 7) * MS)
    # the coarse view of the same gaps, as run.py reduces them today
    coarse = trace_reduce.reduce(planes)["idle_by_span"]
    assert coarse["bench:request"] == pytest.approx(16 * MS)
    assert coarse["bench:verify_commit"] == pytest.approx(7 * MS)


@pytest.mark.parametrize("path", [SMALL, STAGES])
def test_every_key_trace_reduce_had_keeps_its_value(path):
    loaded = trace_reduce.load(path)
    before = trace_reduce.reduce(loaded)
    after = stage_reduce.reduce(loaded)
    for key, value in before.items():
        if key == "idle_by_span" and path == STAGES:
            continue  # finer there, by design (pinned above)
        assert after[key] == value, key
    assert set(after) - set(before) == {"stages", "bench_s", "unstaged_s"}
    if path == SMALL:  # a trace of the parent: no stage in it
        assert after["stages"] == {} and after["unstaged_s"] == \
            pytest.approx(after["bench_s"])
        assert stage_reduce.metrics(after) == {}


@pytest.mark.parametrize("name,value,unit", [
    ("commit_sign_bytes_ms", 4.0, "ms"),
    ("resident_wait_ms", 3.0, "ms"),    # both chunks, per commit
    ("flush_host_ms", 4.0, "ms"),       # assemble + route + demux
    ("host_verify_ms", 2.0, "ms"),
    ("mesh_wait_ms", 7.0, "ms"),        # per device dispatch
    ("unstaged_share", 8 / 30 * 100, "%"),
])
def test_each_stage_metric_on_the_recorded_trace(reduced, name, value, unit):
    got = stage_reduce.metrics(reduced)[name]
    assert got == {"value": pytest.approx(value), "unit": unit}
    # and nothing to read without a trace, or with one that has no stage
    assert name not in stage_reduce.metrics(None)
    assert name not in stage_reduce.metrics(
        dict(reduced, stages={}, unstaged_s=reduced["bench_s"]))


def test_a_metric_whose_stage_is_missing_is_left_out(reduced):
    stages = {k: v for k, v in reduced["stages"].items()
              if k not in ("cbft:resident.retire", "cbft:sup.device")}
    got = stage_reduce.metrics(dict(reduced, stages=stages))
    assert "resident_wait_ms" not in got and "mesh_wait_ms" not in got
    assert {"commit_sign_bytes_ms", "flush_host_ms", "host_verify_ms",
            "unstaged_share"} <= set(got)


def test_a_capture_without_benchmark_spans_still_gives_its_stages(planes):
    """An operator's ProfilerCapture: the window is the extent of the
    device's operations, nothing is 'unstaged'."""
    host = next(p for p in planes if p["name"] == trace_reduce.HOST_PLANE)
    stripped = [p for p in planes if p is not host] + [{
        "name": host["name"],
        "lines": [{"name": l["name"], "events": [
            e for e in l["events"] if not e[0].startswith("bench:")
        ]} for l in host["lines"]],
    }]
    got = stage_reduce.reduce(stripped)
    assert got["window_s"] == pytest.approx((38 - 6) * MS)
    assert got["bench_s"] == 0 and got["unstaged_s"] == 0
    assert got["stages"]["cbft:mesh.retire"] == [1, pytest.approx(6 * MS)]
    assert "unstaged_share" not in stage_reduce.metrics(got)
    assert stage_reduce.metrics(got)["mesh_wait_ms"]["value"] == \
        pytest.approx(6.0)


def test_the_metric_table_names_layers_perf_md_lists():
    with open(os.path.join(REPO, "PERF.md")) as fh:
        perf = fh.read()
    rows = [(n, layer) for n, layer, _, _ in stage_reduce.PER_STAGE_MS]
    rows.append(stage_reduce.UNSTAGED)
    assert len({n for n, _ in rows}) == 6
    for name, layer in rows:
        assert name in perf and layer in perf, name


def test_run_stages_reduces_the_sub_window_with_the_stages(tmp_path):
    from jax.profiler import ProfileData

    log_dir = tmp_path / "trace"
    run_dir = log_dir / "plugins" / "profile" / "2026_01_01_00_00_00"
    run_dir.mkdir(parents=True)
    with open(STAGES) as fh:
        blob = ProfileData.text_proto_to_serialized_xspace(fh.read())
    (run_dir / "host.xplane.pb").write_bytes(blob)
    tracer = run_stages.StageTrace(None, str(log_dir), 0.0, 1.0)
    assert tracer.reduce() is None  # never started: no edges to read
    tracer.before, tracer.after = {"a": 1}, {"a": 2}
    reduced = tracer.reduce()
    assert reduced["counters"] == {"before": {"a": 1}, "after": {"a": 2}}
    assert reduced["stages"]["cbft:resident.retire"][0] == 2
    assert run_stages.StageTrace.last is reduced


def test_run_stages_refuses_a_cpu_platform_like_run_py(capsys):
    rc = run_stages.main(["--workload", "qa150-steady", "--seed", "1",
                          "--seconds", "1"])
    assert rc == run_stages.run.NO_TPU_EXIT != 0
    assert capsys.readouterr().out == ""
