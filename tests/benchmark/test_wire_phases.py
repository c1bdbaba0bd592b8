"""The flush-phase readers (benchmark/lib/wire_phases.py) on hand-made
snapshots, and ``run_phases.py``'s line from one toy cell on the CPU
platform: every reader the table lists for the cell yields a value from
the program's own books."""

import copy

import pytest

from benchmark import run, run_phases
from benchmark.lib import wire_phases

US = 1e-6


def _snap(lanes, **routes):
    """{"wire": ...} as books.Books.wire() gives it."""
    return {"wire": {"lanes": dict(lanes), "phase_s": {
        route: dict(phases) for route, phases in routes.items()
    }}}


BEFORE = _snap(
    {"single": 1000.0, "resident": 0.0},
    single={"queue": 1.0, "assemble": 0.1, "route": 0.1, "demux": 0.1,
            "lead": 2.0, "columns": 0.5, "tail": 0.2, "build_exposed": 1.0,
            "pack": 4.0},
    cpu={"queue": 50.0, "assemble": 9.0, "route": 9.0, "demux": 9.0},
)
AFTER = _snap(
    {"single": 3000.0, "resident": 2000.0},
    single={"queue": 1.0 + 8000 * US, "assemble": 0.1 + 1000 * US,
            "route": 0.1 + 200 * US, "demux": 0.1 + 800 * US,
            "lead": 2.0 + 12000 * US, "columns": 0.5 + 1400 * US,
            "tail": 0.2 + 400 * US, "build_exposed": 1.0 + 5000 * US,
            "pack": 4.0 + 16000 * US},
    resident={"lead": 4000 * US, "tail": 1200 * US, "fetch": 2400 * US,
              "build_exposed": 3000 * US, "pack": 6600 * US},
    cpu={"queue": 99.0, "assemble": 19.0, "route": 19.0, "demux": 19.0,
         "lead": 7.0},
)
# 4,000 lanes reached the device in between
WANT = {
    "queue_us_per_lane": 8000 / 4000,
    "flush_host_us_per_lane": (1000 + 200 + 800) / 4000,
    "lead_us_per_lane": (12000 + 4000) / 4000,
    "columns_us_per_lane": 1400 / 4000,
    "fetch_us_per_lane": 2400 / 4000,
    "build_exposed_share": 100.0 * (5000 + 3000) / (16000 + 6600 + 2400),
    "tail_us_per_lane": (400 + 1200) / 4000,
}


def test_the_table_holds_the_seven_readers():
    assert list(wire_phases.READERS) == list(WANT)


@pytest.mark.parametrize("name", list(WANT))
def test_a_reader_sums_every_route_but_the_hosts_over_the_lanes(name):
    reader = wire_phases.READERS[name]
    assert reader.read(BEFORE, AFTER, None) == pytest.approx(
        WANT[name], rel=1e-9)
    assert reader.NAME == name and reader.BETTER == "lower"
    assert reader.SOURCE == "program_counter"
    assert reader.MOVES == "verdict_p50_ms"
    assert reader.UNIT == ("%" if name.endswith("_share") else "us/lane")


@pytest.mark.parametrize("name", list(WANT))
def test_no_lanes_is_nothing_to_read(name):
    """qa150-steady: every flush is a cpu flush, no lane reached the
    device, and a cpu route's seconds are left out."""
    still = _snap(AFTER["wire"]["lanes"], **AFTER["wire"]["phase_s"])
    assert wire_phases.READERS[name].read(AFTER, still, None) is None
    host_only = _snap({}, cpu=AFTER["wire"]["phase_s"]["cpu"])
    assert wire_phases.READERS[name].read(
        _snap({}), host_only, None) is None


@pytest.mark.parametrize("name", list(WANT))
def test_a_program_without_the_phases_gives_nothing_and_does_not_raise(name):
    """The parent commit: launches on the books, no flush phase."""
    old = {"pack": 0.01, "h2d": 0.001, "compute": 0.002, "d2h": 0.03,
           "demux": 0.0004}
    before = _snap({"single": 0.0}, single={k: 0.0 for k in old})
    after = _snap({"single": 6464.0}, single=old, cpu={"demux": 0.1})
    got = wire_phases.READERS[name].read(before, after, None)
    if name == "flush_host_us_per_lane":
        # demux was always booked: the sum reads what there is of it
        assert got == pytest.approx(0.0004 / 6464 * 1e6)
    else:
        assert got is None


@pytest.mark.parametrize("cell,names", [
    ("qa150-blocksync", ["queue_us_per_lane", "flush_host_us_per_lane",
                         "lead_us_per_lane", "columns_us_per_lane",
                         "build_exposed_share", "tail_us_per_lane"]),
    ("qa150-blocksync-apply", ["queue_us_per_lane", "flush_host_us_per_lane",
                               "lead_us_per_lane", "columns_us_per_lane",
                               "tail_us_per_lane"]),
    ("light150-fleet", ["queue_us_per_lane", "flush_host_us_per_lane",
                        "lead_us_per_lane", "build_exposed_share",
                        "tail_us_per_lane"]),
    ("mega10k-commit", ["lead_us_per_lane", "fetch_us_per_lane",
                        "build_exposed_share", "tail_us_per_lane"]),
    ("mega10k-commit-x4", ["lead_us_per_lane", "fetch_us_per_lane",
                           "build_exposed_share", "tail_us_per_lane"]),
    ("qa150-steady", []),
])
def test_each_cell_gets_the_readers_the_issue_lists_for_it(cell, names):
    assert wire_phases.names_for(cell) == names


def test_flush_life_is_a_mean_by_phase_with_the_hosts_flushes_apart():
    rows = [
        {"route": "single", "lanes": 6464, "launches": 4, "life_ms": 70.0,
         "verify_ms": 60.0, "phases_ms": {
             "queue": 5.0, "assemble": 3.0, "route": 0.5, "lead": 9.0,
             "columns": 2.0, "stream": 50.0, "build_exposed": 5.0,
             "tail": 1.0, "demux": 1.5}},
        {"route": "single", "lanes": 6464, "launches": 4, "life_ms": 74.0,
         "verify_ms": 64.0, "phases_ms": {
             "queue": 7.0, "assemble": 3.0, "route": 0.5, "lead": 11.0,
             "columns": 2.4, "stream": 50.0, "build_exposed": 5.4,
             "tail": 1.0, "demux": 1.5}},
        {"route": "cpu", "lanes": 3, "launches": 0, "life_ms": 2.0,
         "verify_ms": 0.9, "phases_ms": {
             "queue": 1.0, "assemble": 0.04, "route": 0.01, "demux": 0.05}},
    ]
    got = run_phases.flush_life(rows)
    dev = got["device"]
    assert dev["records"] == 2 and dev["routes"] == ["single"]
    assert dev["life_ms"] == 72.0 and dev["sum_ms"] == pytest.approx(72.0)
    assert dev["phases_ms"]["lead"] == 10.0
    assert dev["phases_ms"]["columns"] == pytest.approx(2.2)
    assert got["host"]["records"] == 1
    assert got["host"]["sum_ms"] == pytest.approx(1.1)
    assert run_phases.flush_life([]) == {}


@pytest.fixture()
def _restore_process_state():
    yield
    from cometbft_tpu.crypto import batch as cryptobatch
    from cometbft_tpu.crypto.tpu import calibrate, keystore, mesh

    cryptobatch.set_default_backend("cpu")
    mesh.configure_chunk_cap(None)
    calibrate.set_table_path(None)
    keystore.default_store().invalidate()


def test_a_toy_cell_through_run_phases_on_the_cpu_platform(
        monkeypatch, _restore_process_state):
    """The blocksync cell at the toy size of test_run_contract.py, with
    no profiler: the end-to-end metrics, the cell's counter-fed layers and
    the six flush-phase metrics in one line, and the ledger's records of
    the windows' flushes adding up to their own lives."""
    monkeypatch.delenv("CBFT_WARM_BOOT", raising=False)
    cell = run.resolve_cell("qa150-blocksync")
    cell = dict(cell, config=copy.deepcopy(cell["config"]),
                traffic=copy.deepcopy(cell["traffic"]))
    cell["config"].update(validators=64, replay_blocks=4)
    cell["config"]["crypto"].update(min_batch=64, max_chunk=128)
    cell["traffic"]["params"].update(blocks=2, forged_block=1)
    device = {"platform": "cpu", "kind": "cpu", "count": 8}
    before = (run.load_module, run.SubWindowTrace)
    line = run_phases.phases_cell(cell, 7, 2.0, False, device,
                                  expect_platform="cpu")
    assert (run.load_module, run.SubWindowTrace) == before
    assert line["attempted"] >= 2 and line["failed"] == 0
    got = line["metrics"]
    for name in wire_phases.names_for("qa150-blocksync"):
        assert got[name]["value"] > 0, name
        assert got[name]["unit"] == wire_phases.READERS[name].UNIT
    # every launch of a one-launch flush is exposed: 100 but for rounding
    assert got["build_exposed_share"]["value"] <= 100.0 + 1e-9
    assert got["columns_us_per_lane"]["value"] < \
        got["lead_us_per_lane"]["value"]
    for name in ("verdict_p50_ms", "verified_sigs_per_s", "setup_s",
                 "pack_us_per_lane", "device_leg_us_per_lane",
                 "queue_wait_mean_ms", "compiles_in_window"):
        assert name in got, name
    assert "kernel_us_per_lane" not in got  # no session, no device trace
    assert "busy_s" not in line["device"] and "breakdown" not in line
    dev = line["flush_life"]["device"]
    assert dev["records"] >= 2 and dev["launches"] >= 1
    # warm-up flushes (a compile each) are among the records: the sum
    # is held to the records' own lives, not to the window's median
    assert abs(dev["sum_ms"] - dev["life_ms"]) <= max(
        0.05, 0.01 * dev["life_ms"])
