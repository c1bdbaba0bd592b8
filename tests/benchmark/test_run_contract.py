"""run.py's side of the contract: the last line's keys, what counts as
failed and as incorrect, the refusal of a CPU platform, and one toy cell
end to end on the CPU platform through a real node, so that the counter
readers are held to the program's own names."""

import copy
import json
import os

import pytest

from benchmark import run
from benchmark.lib import stats

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
       "memory_peak_bytes": 123}


def _samples(statuses, loop="closed"):
    return {
        "loop": loop,
        "window_s": 2.0,
        "attempted": len(statuses),
        "requests": [(0.010 * (i + 1), 100, s)
                     for i, s in enumerate(statuses)],
        "cpu_units": [(0.08, 100)] * statuses.count("ok"),
        "extra_sigs": 0,
        "spans_s": {},
        "late_s": [],
    }


def _line(statuses, builds=0, device=TPU, chips=1, cell="qa150-blocksync"):
    summary = run.summarize(_samples(statuses), setup_s=12.5, cpu_s=0.4)
    names = run.load_json("benchmark", "workloads", cell + ".json")
    metrics = run.read_metrics("end_to_end", names["end_to_end"], summary)
    return run.result_line(summary, metrics, dict(device), builds, chips)


def test_the_last_line_has_exactly_the_contracts_keys():
    line = _line(["ok"] * 10)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True
    assert (line["attempted"], line["failed"]) == (10, 0)
    assert set(line["metrics"]) == {
        "verdict_p50_ms", "verified_sigs_per_s", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    assert line["metrics"]["verdict_p50_ms"]["value"] == pytest.approx(55.0)
    assert line["metrics"]["verified_sigs_per_s"]["value"] == 500.0
    assert line["metrics"]["setup_s"] == {"value": 12.5, "unit": "s"}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    json.dumps(line)
    traced = run.result_line(
        run.summarize(_samples(["ok"]), 1.0, 0.1), {}, dict(TPU), 0, 1,
        breakdown={"device_ops": [["fusion", 0.5]], "idle_gaps": []},
    )
    assert set(traced) == {"correct", "attempted", "failed", "metrics",
                           "device", "breakdown"}


def test_a_request_answered_by_a_fallback_is_failed_and_has_no_latency():
    line = _line(["ok", "ok", "fallback", "ok", "error"])
    assert (line["attempted"], line["failed"]) == (5, 2)
    assert line["correct"] is True  # no verdict was wrong
    # the median is over the three that were served: 10, 20, 40 ms
    assert line["metrics"]["verdict_p50_ms"]["value"] == pytest.approx(20.0)
    assert line["metrics"]["verified_sigs_per_s"]["value"] == 150.0


def test_a_request_never_answered_is_failed():
    samples = _samples(["ok", "ok"], loop="open")
    samples["attempted"] = 3
    summary = run.summarize(samples, 1.0, 0.1)
    assert (summary["attempted"], summary["failed"]) == (3, 1)


@pytest.mark.parametrize("statuses,builds,device,correct", [
    (["ok", "ok", "ok"], 0, TPU, True),
    (["ok", "mismatch", "ok"], 0, TPU, False),       # one flipped verdict
    (["ok", "ok", "ok"], 1, TPU, False),             # one build in window
    (["ok", "ok", "ok"], 0, dict(TPU, platform="cpu"), False),
    (["ok", "ok", "ok"], 0, dict(TPU, count=4), False),
])
def test_correct_is_false_on_a_wrong_verdict_a_build_or_another_device(
        statuses, builds, device, correct):
    assert _line(statuses, builds, device)["correct"] is correct


def test_a_mismatch_outside_the_requests_also_spoils_correct():
    samples = _samples(["ok"])
    samples["mismatches"] = 1  # a per-height commit, a health probe
    summary = run.summarize(samples, 1.0, 0.1)
    assert run.result_line(summary, {}, dict(TPU), 0, 1)["correct"] is False


def _layers(names, summary):
    """Per-layer readers that take the window as the caller saw it find
    it where a traced run puts it: under "bench" of the later snapshot."""
    return run.read_metrics("layers", names, {}, {"bench": summary}, None)


def test_p99_is_the_median_of_block_percentiles_and_needs_ten_blocks():
    """One stall of the host spoils one block of 300, not the figure."""
    names = ["verdict_p99_ms"]
    few = run.summarize(_samples(["ok"] * 2999), 1.0, 0.1)
    assert _layers(names, few) == {}
    samples = _samples(["ok"] * 3000)
    # every block: 297 requests of 1 ms, three of 9 ms
    samples["requests"] = [
        (0.009 if i % 100 == 50 else 0.001, 1, "ok") for i in range(3000)
    ]
    calm = _layers(names, run.summarize(samples, 1.0, 0.1))[
        "verdict_p99_ms"]["value"]
    assert 1.0 < calm <= 9.0
    # a one-second stall inside one block moves the whole window's p99
    # and leaves the block median where it was
    for i in range(600, 700):
        samples["requests"][i] = (1.0, 1, "ok")
    stalled = run.summarize(samples, 1.0, 0.1)
    assert stats.percentile(stalled["latency_ms"], 0.99) > 900
    assert _layers(names, stalled)[
        "verdict_p99_ms"]["value"] == pytest.approx(calm)


def test_an_open_loop_reports_no_throughput():
    summary = run.summarize(_samples(["ok"] * 3, loop="open"), 1.0, 0.1)
    assert run.read_metrics(
        "end_to_end", ["verified_sigs_per_s"], summary) == {}


def test_run_py_refuses_a_cpu_platform_with_no_last_line(capsys):
    rc = run.main(["--workload", "qa150-steady", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc == run.NO_TPU_EXIT != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "nothing was run" in out.err and "'cpu'" in out.err


def test_an_unknown_workload_is_an_error_not_a_default():
    with pytest.raises(SystemExit):
        run.resolve_cell("no-such-cell")


@pytest.fixture()
def _restore_process_state():
    """default_new_node with the tpu backend installs process-wide
    settings; put them back (as tests/test_chip_smoke.py does)."""
    yield
    from cometbft_tpu.crypto import batch as cryptobatch
    from cometbft_tpu.crypto.tpu import calibrate, keystore, mesh

    cryptobatch.set_default_backend("cpu")
    mesh.configure_chunk_cap(None)
    calibrate.set_table_path(None)
    keystore.default_store().invalidate()


def test_a_toy_cell_end_to_end_on_the_cpu_platform(
        monkeypatch, _restore_process_state):
    """The blocksync cell at toy size through a real node on the virtual
    CPU mesh: 2 blocks x 43 lanes of a 64-validator set, floor 64, so the
    windows take the device route. The profiler is left out (stopping it
    on the CPU platform takes a minute); everything else of a traced run
    runs, so every counter reader meets the program's real counters."""
    monkeypatch.delenv("CBFT_WARM_BOOT", raising=False)
    cell = run.resolve_cell("qa150-blocksync")
    cell = dict(cell, config=copy.deepcopy(cell["config"]),
                traffic=copy.deepcopy(cell["traffic"]))
    cell["config"].update(validators=64, replay_blocks=4)
    cell["config"]["crypto"].update(min_batch=64, max_chunk=128)
    cell["traffic"]["params"].update(blocks=2, forged_block=1)

    def start(self):
        self.before = self.plane.books.snapshot()
        self.started_at = run.time.monotonic()

    def stop(self):
        if not self.stopped and self.started_at is not None:
            self.after = self.plane.books.snapshot()
        self.stopped = True

    monkeypatch.setattr(run.SubWindowTrace, "_start", start)
    monkeypatch.setattr(run.SubWindowTrace, "stop", stop)
    monkeypatch.setattr(run.SubWindowTrace, "reduce", lambda self: None)
    monkeypatch.setitem(cell["cell"], "trace", {"after_s": 0.2,
                                                "seconds": 0.5})
    device = {"platform": "cpu", "kind": "cpu", "count": 8}
    line = run.run_cell(cell, 7, 2.0, True, device, expect_platform="cpu")
    assert line["correct"] is False  # not a TPU, and says so
    assert line["attempted"] >= 2 and line["failed"] == 0
    got = line["metrics"]
    # trace-fed readers found nothing to read and were left out, and so
    # was the host's cost per request where a loaded machine served
    # fewer than the ten requests its median asks for
    absent = {"kernel_us_per_lane", "ed25519_verify_roofline"}
    if line["attempted"] < 10:
        absent.add("host_cpu_ms_per_ksig")
    assert set(got) == set(cell["cell"]["layers"]) - absent
    assert got["compiles_in_window"]["value"] == 0
    assert got["device_lane_share"]["value"] > 50
    assert got["lanes_per_flush"]["value"] > 1
    assert got["pack_us_per_lane"]["value"] > 0
    assert got["device_leg_us_per_lane"]["value"] > 0
    assert got["queue_wait_mean_ms"]["value"] > 0
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_host_cpu_is_the_median_over_units_and_needs_ten():
    """The window's total swings with how many flushes the unseeded
    audit samples; the median over requests does not."""
    names = ["host_cpu_ms_per_ksig"]
    samples = _samples(["ok"] * 20)
    samples["cpu_units"] = [(0.08, 100)] * 18 + [(0.9, 100)] * 2  # 2 audited
    got = _layers(names, run.summarize(samples, 1.0, 3.24))
    assert got["host_cpu_ms_per_ksig"]["value"] == pytest.approx(800.0)
    assert got["host_cpu_ms_per_ksig"]["unit"] == "ms/ksig"
    samples["cpu_units"] = samples["cpu_units"][:9]
    assert _layers(names, run.summarize(samples, 1.0, 0.72)) == {}


class _Counter:
    def __init__(self, n=0.0):
        self.n = n

    def value(self):
        return self.n

    def with_labels(self, **_labels):
        return self


def _stub_node(**moved):
    """A node that has only the counters Books.fallbacks() reads."""
    from types import SimpleNamespace

    from benchmark.lib import books

    names = books.FALLBACK_COUNTERS + ("hedge_wins",)
    sup = SimpleNamespace(**{n: _Counter(moved.get(n, 0.0)) for n in names})
    sched = SimpleNamespace(cpu_fallbacks=_Counter(
        moved.get("cpu_fallbacks", 0.0)))
    return SimpleNamespace(
        verify_supervisor=SimpleNamespace(metrics=sup),
        verify_scheduler=SimpleNamespace(metrics=sched),
        crypto_backend=None,
    )


@pytest.mark.parametrize("counter,failed", [
    ("failures", True), ("watchdog_kills", True),
    ("sharded_fallbacks", True), ("indexed_fallbacks", True),
    ("triage_cpu_fallbacks", True), ("cpu_fallbacks", True),
    ("hedge_wins", False),
])
def test_a_broken_device_path_fails_a_request_and_a_hedge_win_does_not(
        counter, failed):
    """A hedge the host pool won is a late dispatch with a correct
    verdict: served, with its latency, and reported per layer."""
    from benchmark.lib import books, loops, plane as planelib

    node = _stub_node()
    plane = planelib.Plane(node)
    assert plane.fallbacks() == 0.0

    def serve(i):
        if i == 1:
            holder = (node.verify_scheduler.metrics
                      if counter == "cpu_fallbacks"
                      else node.verify_supervisor.metrics)
            getattr(holder, counter).n += 1
        return True

    samples = loops.closed_loop(plane, 0.05, 100, serve)
    statuses = [s for _, _, s in samples["requests"]]
    assert len(statuses) >= 3
    assert statuses[1] == ("fallback" if failed else "ok")
    assert set(statuses[:1] + statuses[2:]) == {"ok"}
    assert books.Books(node).fallbacks() == (1.0 if failed else 0.0)


def test_hedge_cpu_win_share_is_of_the_device_dispatches():
    def snap(dispatches, wins):
        return {"supervisor": {"device_dispatches": dispatches,
                               "hedge_wins_cpu": wins}}

    names = ["hedge_cpu_win_share"]
    got = run.read_metrics("layers", names, snap(10, 1), snap(254, 4), None)
    assert got["hedge_cpu_win_share"] == {
        "value": pytest.approx(100.0 * 3 / 244), "unit": "%"}
    calm = run.read_metrics("layers", names, snap(10, 1), snap(254, 1), None)
    assert calm["hedge_cpu_win_share"]["value"] == 0.0
    assert run.read_metrics("layers", names, snap(10, 1), snap(10, 1),
                            None) == {}
