"""The four-chip commit cell (mega10k-commit-x4): its two readers on
hand-built reductions, and the cell itself at toy size through a real
node on the suite's virtual CPU mesh, a fault domain a device, so that
the sharded resident path meets the counter readers and the harness
before any chip does."""

import copy

import pytest

from benchmark import run

CELL = "mega10k-commit-x4"


def _reduced(busy, ops):
    return {
        "window_s": 1.0,
        "chips": {n: {"busy_s": b, "busy_share": b} for n, b in
                  enumerate(busy)},
        "busy_s": sum(busy) / len(busy),
        "ops": ops,
        "programs": {},
        "idle_by_span": {},
        "spans": {},
    }


FUSION = ("%fusion.7 = s32[17,2048]{1,0:T(8,128)} fusion(s32[17,2048] "
          "%all-gather.1, u32[8,2048] %send.3), kind=kLoop")


def _read(name, trace):
    return run.read_metrics("layers", [name], {}, {}, trace)


def test_shard_busy_balance_is_the_least_busy_chip_over_the_busiest():
    got = _read("shard_busy_balance", _reduced([0.40, 0.30], {}))
    assert got["shard_busy_balance"] == {
        "value": pytest.approx(75.0), "unit": "%"}
    four = _reduced([0.5, 0.5, 0.25, 0.4], {})
    assert _read("shard_busy_balance", four)[
        "shard_busy_balance"]["value"] == pytest.approx(50.0)


def test_cross_chip_op_share_counts_collectives_by_name_not_by_operand():
    ops = {
        FUSION: 0.60,  # names a collective among its operands only
        "%while.2 = (s32[], u32[8,2048]) while(...)": 0.25,
        "%all-gather-start.1 = (u8[512], u8[2048]) all-gather-start(...)":
            0.05,
        "%all-gather-done.1 = u8[2048] all-gather-done(...)": 0.02,
        "collective-permute.4": 0.03,
        "%recv-done = token[] recv-done(...)": 0.05,
    }
    got = _read("cross_chip_op_share", _reduced([0.4, 0.3], ops))
    assert got["cross_chip_op_share"] == {
        "value": pytest.approx(15.0), "unit": "%"}
    local = {FUSION: 0.6, "%while.2 = ...": 0.4}
    assert _read("cross_chip_op_share", _reduced([0.4, 0.3], local))[
        "cross_chip_op_share"]["value"] == 0.0


@pytest.mark.parametrize("name", ["shard_busy_balance",
                                  "cross_chip_op_share"])
@pytest.mark.parametrize("trace", [
    None,                                       # an untraced run
    _reduced([0.4], {"%all-reduce.1": 0.1}),    # one chip
    _reduced([0.0, 0.0], {}),                   # nothing ran
    {"window_s": 1.0},                          # a reduction without the keys
], ids=["no-trace", "one-chip", "idle", "bare"])
def test_the_new_readers_find_nothing_to_read_and_do_not_raise(name, trace):
    assert _read(name, trace) == {}


def test_the_cell_is_the_one_chip_commit_cell_on_four_chips():
    one, four = run.resolve_cell("mega10k-commit"), run.resolve_cell(CELL)
    assert (one["chips"], four["chips"]) == (1, 4)
    assert four["traffic"] == one["traffic"]
    assert four["generator"].__name__ == one["generator"].__name__
    same = ("chain_id", "validators", "key_type", "voting_power",
            "sign_bytes", "pool_commits", "crypto", "reduced")
    for key in same:
        assert four["config"][key] == one["config"][key], key
    assert four["config"]["chips"] == 4
    assert four["config"]["guarantees"][:3] == one["config"]["guarantees"]
    assert four["cell"]["trace"] == one["cell"]["trace"]
    assert four["cell"]["end_to_end"] == one["cell"]["end_to_end"]
    assert set(four["cell"]["layers"]) == set(one["cell"]["layers"]) | {
        "shard_busy_balance", "cross_chip_op_share"}
    # the layout the file states is the program's rounding rule
    from cometbft_tpu.crypto.tpu import mesh

    layout = four["config"]["layout"]
    chunks = mesh.shard_chunks(10000, layout["chips_a_commit"], 8192, 64)
    assert [(c["padded_lanes"], c["lanes_a_chip"])
            for c in layout["launches_a_commit"]] == [
        (size, size // 4) for _, _, size in chunks]
    assert layout["padded_lanes_a_commit"] == sum(s for _, _, s in chunks)


@pytest.fixture()
def _restore_process_state():
    """default_new_node with the tpu backend installs process-wide
    settings; put them back (as tests/test_chip_smoke.py does)."""
    from cometbft_tpu.crypto.tpu import topology

    before = topology.default_topology()
    yield
    from cometbft_tpu.crypto import batch as cryptobatch
    from cometbft_tpu.crypto.tpu import calibrate, keystore, mesh

    cryptobatch.set_default_backend("cpu")
    mesh.configure_chunk_cap(None)
    calibrate.set_table_path(None)
    keystore.default_store().invalidate()
    topology.set_default_topology(before)


def test_the_toy_cell_end_to_end_on_the_virtual_mesh(
        monkeypatch, _restore_process_state):
    """300 validators, floor 64, chunk cap 256, a fault domain a virtual
    device: every commit is two sharded launches (256 and 44 -> 64 padded
    lanes over 8 devices). The profiler is left out, as in the blocksync
    toy cell; the counter-fed layers report, the trace-fed ones are
    absent, and the wire ledger has the mesh's real and padded lanes."""
    monkeypatch.delenv("CBFT_WARM_BOOT", raising=False)
    cell = run.resolve_cell(CELL)
    cell = dict(cell, config=copy.deepcopy(cell["config"]),
                traffic=copy.deepcopy(cell["traffic"]))
    cell["config"].update(validators=300)
    cell["config"]["crypto"].update(min_batch=64, max_chunk=256)
    assert cell["config"]["crypto"]["fault_domains"] == 0

    seen = {}

    def start(self):
        self.before = self.plane.books.snapshot()
        self.started_at = run.time.monotonic()
        seen["ledger"] = self.plane.node.wire_ledger
        seen["padded_before"] = dict(
            seen["ledger"].padded_lanes_by_route())

    def stop(self):
        if not self.stopped and self.started_at is not None:
            self.after = self.plane.books.snapshot()
            seen["padded_after"] = dict(
                seen["ledger"].padded_lanes_by_route())
            seen["profiles"] = seen["ledger"].snapshot()["profiles"]
        self.stopped = True

    monkeypatch.setattr(run.SubWindowTrace, "_start", start)
    monkeypatch.setattr(run.SubWindowTrace, "stop", stop)
    monkeypatch.setattr(run.SubWindowTrace, "reduce", lambda self: None)
    monkeypatch.setitem(cell["cell"], "trace", {"after_s": 0.2,
                                                "seconds": 0.5})
    device = {"platform": "cpu", "kind": "cpu", "count": 8}
    line = run.run_cell(cell, 2_150_000_011, 2.0, True, device,
                        expect_platform="cpu")
    assert line["correct"] is False  # not a TPU, and says so
    assert line["attempted"] >= 2 and line["failed"] == 0
    got = line["metrics"]
    absent = {"kernel_us_per_lane", "ed25519_verify_roofline",
              "shard_busy_balance", "cross_chip_op_share"}
    if line["attempted"] < 10:
        absent.add("host_cpu_ms_per_ksig")
    assert set(got) == set(cell["cell"]["layers"]) - absent
    assert got["compiles_in_window"]["value"] == 0
    assert got["device_lane_share"]["value"] > 90
    assert got["pack_us_per_lane"]["value"] > 0
    assert got["device_leg_us_per_lane"]["value"] > 0
    assert got["commit_host_ms"]["value"] > 0
    assert "busy_s" not in line["device"] and "breakdown" not in line
    # between the trace's edges every commit booked 300 real and 320
    # padded lanes, on the whole mesh
    padded = (seen["padded_after"]["resident"]
              - seen["padded_before"].get("resident", 0))
    assert padded > 0 and padded % 320 == 0
    resident = {(r["bucket"], r["device"]) for r in seen["profiles"]
                if r["route"] == "resident"}
    assert resident == {(256, "mesh:8"), (64, "mesh:8")}
