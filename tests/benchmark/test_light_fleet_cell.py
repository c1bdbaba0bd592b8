"""The light-fleet cell: its two request shapes held lane for lane to
what ``light.verifier.verify`` hands a backend, the Zipf assignment,
that a replayed header puts its lanes on the wire again, the readers on
empty books, and a toy cell end to end on the CPU platform with the
daemon on the host row verifier, also with the daemon stopped under it."""

import contextlib
import copy
import importlib
import threading
import time
import types

import pytest

from benchmark import run
from benchmark.lib import data
from benchmark.traffic import light_fleet

SEED = 2_150_000_041
# the toy sizes come from conftest.py, which completes
# test_traffic_shapes.py's table with them
TOY: dict = {}
NEW_READERS = ("fleet_requests_per_flush", "service_server_ms",
               "service_socket_ms", "indexed_lane_share",
               "register_per_request")


def _cell(**params):
    cell = run.resolve_cell("light150-fleet")
    cell = dict(cell, config=copy.deepcopy(cell["config"]),
                traffic=copy.deepcopy(cell["traffic"]))
    config, toy_params = TOY["light_fleet"]
    cell["config"].update(config)
    cell["traffic"]["params"].update(toy_params)
    cell["traffic"]["params"].update(params)
    return cell


def _stub_plane():
    return types.SimpleNamespace(
        span=lambda name: contextlib.nullcontext(),
        note=lambda msg: None, stop=lambda: None,
    )


# -- the shapes ------------------------------------------------------------


@pytest.fixture(scope="module")
def chain150():
    """One chain of the configuration's own size: 150 equal validators."""
    valset = data.make_valset(150, SEED, "shape")
    blocks = light_fleet.make_chain("light-shape", valset, 5000, 5, SEED,
                                    "shape")
    return valset[0], blocks


def _recorded(chain150, k, m):
    from cometbft_tpu.light import verifier
    from cometbft_tpu.proto.gogo import Timestamp

    vals, blocks = chain150
    rec = light_fleet.Recording()
    verifier.verify(
        blocks[k].signed_header, vals, blocks[m].signed_header, vals,
        10**18, Timestamp(data.timestamp(5010).seconds, 0), 10**10,
        backend=rec,
    )
    return [data.raw(call) for call in rec.calls]


def test_the_sequence_shape_is_the_quorum_prefix_in_one_round_trip(chain150):
    vals, blocks = chain150
    (lanes,) = _recorded(chain150, 0, 1)
    want = data.raw(data.quorum_prefix_items(
        vals, blocks[1].signed_header.commit, "light-shape"))
    assert len(lanes) == 101 and lanes == want


def test_the_skipping_shape_is_the_trusting_prefix_then_the_quorum_prefix(
        chain150):
    """51 lanes by address against the trusted set, then 101: with one
    set and every validator present they are the commit's first 51 and
    first 101 rows."""
    vals, blocks = chain150
    trusting, quorum = _recorded(chain150, 0, 3)
    commit = blocks[3].signed_header.commit
    want = data.raw(data.quorum_prefix_items(vals, commit, "light-shape"))
    assert len(trusting) == 51 and len(quorum) == 101
    assert quorum == want and trusting == want[:51]
    by_address = {v.address: v.pub_key.bytes() for v in vals.validators}
    assert [pk for pk, _, _ in trusting] == [
        by_address[cs.validator_address] for cs in commit.signatures[:51]]


def test_shape_lanes_reads_the_same_counts_from_the_program(chain150):
    vals, blocks = chain150
    chain = {"blocks": blocks, "adjacent": [(0, 1)], "skipping": [(0, 3)]}
    assert light_fleet.shape_lanes(chain) == {
        "adjacent": [101], "skipping": [51, 101]}


# -- the assignment --------------------------------------------------------


def test_the_zipf_assignment_is_the_traffic_files():
    params = run.load_json("benchmark", "traffic",
                           "fleet32-closed.json")["params"]
    split = light_fleet.zipf_split(32, 8, 1.0)
    assert split == [12, 6, 4, 3, 2, 2, 2, 1] == params["clients_per_chain"]
    config = run.load_json("benchmark", "configs", "light150.json")
    assert config["clients_per_chain"] == split
    assert config["clients"] == params["clients"] == 32
    assert config["chains"] == params["chains"] == 8
    clients = light_fleet.assignment(params)
    assert [c for c, _ in clients] == [
        c for c, n in enumerate(split) for _ in range(n)]
    assert [i for i, (_, skips) in enumerate(clients) if skips] == [
        i for i in range(32) if i % 4 == 3]
    assert sum(1 for _, skips in clients if not skips) == 24


def test_build_refuses_a_split_that_is_not_the_zipf_one():
    cell = _cell(clients_per_chain=[2, 2])
    with pytest.raises(ValueError, match="Zipf"):
        light_fleet.build(cell["config"], cell["traffic"]["params"], SEED)


def test_the_warm_flushes_reach_every_bucket_up_to_the_largest_flush():
    plan = {"lanes": {"adjacent": [101], "skipping": [51, 101]},
            "max_flush_lanes": 32 * 101}
    sizes = light_fleet.warm_sizes(plan)
    assert sizes == [64, 128, 256, 512, 1024, 2048, 3232]
    buckets = {1 << (n - 1).bit_length() for n in sizes}
    assert buckets == {64, 128, 256, 512, 1024, 2048, 4096}


# -- the readers -----------------------------------------------------------


@pytest.mark.parametrize("name", NEW_READERS)
@pytest.mark.parametrize("after", [
    {}, {"bench": {}}, {"bench": {"spans_s": {}}},
    {"bench": {"spans_s": {"fleet": {}}}},
    {"bench": {"spans_s": {"fleet": {"sched_requests": 0, "served": 0,
                                     "req_frames": 0, "client_rtts": 0}}}},
    # the parent's program: no server seconds, no client round trips
    {"bench": {"spans_s": {"fleet": {"served": 5, "client_rtts": 5}}}},
])
def test_the_new_readers_find_nothing_on_empty_books_and_do_not_raise(
        name, after):
    mod = importlib.import_module(f"benchmark.layers.{name}")
    assert mod.NAME == name
    assert mod.read({}, after, None) is None


def test_the_new_readers_read_the_fleets_books():
    fleet = {"sched_requests": 90, "sched_dispatches": 30, "served": 100,
             "served_s": 0.5, "client_rtts": 100, "client_rtt_s": 0.8,
             "lanes_indexed": 950, "lanes_compact": 50, "req_frames": 100,
             "register_frames": 2}
    after = {"bench": {"spans_s": {"fleet": fleet}}}
    got = {name: importlib.import_module(
        f"benchmark.layers.{name}").read({}, after, None)
        for name in NEW_READERS}
    assert got == {
        "fleet_requests_per_flush": 3.0,
        "service_server_ms": 5.0,
        "service_socket_ms": pytest.approx(3.0),
        "indexed_lane_share": 95.0,
        "register_per_request": 0.02,
    }


# -- the toy cell ----------------------------------------------------------


@pytest.fixture()
def _restore_process_state():
    yield
    from cometbft_tpu.crypto import batch as cryptobatch
    from cometbft_tpu.crypto.tpu import calibrate, keystore, mesh

    cryptobatch.set_default_backend("cpu")
    mesh.configure_chunk_cap(None)
    calibrate.set_table_path(None)
    keystore.default_store().invalidate()


def test_the_toy_cell_end_to_end_on_the_cpu_platform(
        monkeypatch, _restore_process_state):
    """2 chains of 8 validators, 4 clients (one skipping), through a real
    node and ``Daemon(backend="tpu")``, which on the CPU platform takes
    the host row verifier. The profiler is left out (as in
    test_run_contract.py); every counter reader meets the real books."""
    monkeypatch.delenv("CBFT_WARM_BOOT", raising=False)
    cell = _cell()

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
    line = run.run_cell(cell, SEED, 2.0, True, device, expect_platform="cpu")
    assert line["correct"] is False  # not a TPU, and says so
    assert line["attempted"] >= 8 and line["failed"] == 0
    got = line["metrics"]
    assert set(NEW_READERS) <= set(got)
    assert got["compiles_in_window"]["value"] == 0
    assert got["register_per_request"]["value"] == 0
    assert got["indexed_lane_share"]["value"] == 100
    assert got["fleet_requests_per_flush"]["value"] >= 1
    assert got["service_server_ms"]["value"] > 0
    assert got["service_socket_ms"]["value"] > 0


@pytest.fixture()
def toy_fleet():
    """A warmed toy fleet on a stub plane (no node: the daemon and its
    clients need none)."""
    from cometbft_tpu.crypto.tpu import keystore

    cell = _cell(request_timeout_s=2)
    plan = light_fleet.build(cell["config"], cell["traffic"]["params"], SEED)
    plane = _stub_plane()
    store = keystore.default_store()
    store.invalidate()
    evictions = store.snapshot()["stats"]["evictions"]
    warmed = light_fleet.warm(plane, plan)
    warmed["evictions_before"] = evictions
    yield plane, plan, warmed
    plane.stop()
    keystore.default_store().invalidate()


def test_warm_up_fails_at_once_where_the_daemon_cannot_hold_the_sets(
        monkeypatch):
    """A key store with room for one of the fleet's two validator sets
    (the parent of PR 30 had four slots for eight): no executable is
    built, no request is sent, the run says why and ends."""
    from cometbft_tpu.crypto.tpu import keystore

    store = keystore.default_store()
    store.invalidate()
    monkeypatch.setattr(store, "_max_host_keys", 8)
    cell = _cell(request_timeout_s=2)
    plan = light_fleet.build(cell["config"], cell["traffic"]["params"], SEED)
    plane = _stub_plane()
    try:
        with pytest.raises(AssertionError, match="holds 1 of the fleet's 2"):
            light_fleet.warm(plane, plan)
        assert plan["fleet"].books()["req_frames"] == 0
    finally:
        plane.stop()
        store.invalidate()


def test_a_replayed_header_puts_all_its_lanes_on_the_wire_again(toy_fleet):
    """Nothing on the client or in the service memoises a verdict: the
    same pair verified three times is three times the frames and the
    lanes at the daemon (the host row verifier's memo is the rung a TPU
    daemon never takes)."""
    plane, plan, _ = toy_fleet
    fleet = plan["fleet"]
    for i, sigs in ((0, 6), (3, 9)):  # a sequence and the skipping client
        chain, pairs, offset, lanes = light_fleet._pairs(plan, i)
        assert lanes == sigs
        pair = pairs[offset % len(pairs)]
        seen = []
        for _ in range(3):
            before = fleet.books()
            status, got = light_fleet.verify_once(
                plane, plan, fleet, i, chain["blocks"][pair[0]],
                chain["blocks"][pair[1]], chain["want"][pair])
            after = fleet.books()
            assert (status, got) == ("ok", "accept")
            seen.append((after["lanes_indexed"] - before["lanes_indexed"],
                         after["req_frames"] - before["req_frames"]))
        assert seen == [(sigs, 2 if sigs == 9 else 1)] * 3


def test_warm_up_refused_the_forged_headers_and_registered_once(toy_fleet):
    _, plan, warmed = toy_fleet
    # chain 0's sequence clients share one forgery; chain 1's only
    # client skips and meets two
    assert warmed["forged_refused"] == 3
    assert warmed["warm_not_ok"] == 0
    assert warmed["books"]["register_frames"] == 4
    assert warmed["books"]["keystore_evictions"] == warmed["evictions_before"]


def test_the_window_ends_within_its_bounds_when_the_daemon_is_stopped(
        toy_fleet):
    plane, plan, _ = toy_fleet
    fleet = plan["fleet"]
    threading.Timer(0.5, fleet.daemon.stop).start()
    t0 = time.monotonic()
    samples = light_fleet.drive(plane, plan, 2.0)
    assert time.monotonic() - t0 < 2.0 + plan["timeout_s"] * 2 + 10
    by_status = {}
    for _, _, status in samples["requests"]:
        by_status[status] = by_status.get(status, 0) + 1
    assert by_status.get("ok", 0) >= 1, by_status
    failed = {s for s in by_status if s != "ok"}
    assert failed and failed <= {"disconnected", "timeout", "error"}, \
        by_status
    assert "mismatch" not in by_status
    summary = run.summarize(samples, 1.0, 1.0)
    assert summary["failed"] == summary["attempted"] - by_status["ok"]
    assert summary["mismatches"] == 0
