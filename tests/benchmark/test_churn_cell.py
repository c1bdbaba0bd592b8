"""The blocksync-churn cell: the plan a pure function of config, params
and seed; the schedule as the configuration states it; a request 16
heights whatever number of passes the program needs for them;
``correct`` false on a wrong app hash or validator set; the generator's
reactor and executor built with ``node.py``'s arguments; the three
readers; and the toy cell end to end on the CPU platform through a real
node."""

import contextlib
import copy
import types

import pytest

from benchmark import run
from benchmark.lib import chain as chainlib
from benchmark.lib import churn_reference
from benchmark.traffic import blocksync_churn as gen
from cometbft_tpu.crypto.batch import BackendSpec
from cometbft_tpu.crypto.scheduler import VerifyScheduler
from tests.benchmark.test_sync_cell import (
    _call_keywords, _restore_process_state,  # noqa: F401 - a fixture
)
from tests.conftest import blocksync_churn_toy, sync_plane

SEED = 2_150_000_123  # more than 32 signed bits hold
TOY: dict = {}  # tests/conftest.py puts the generator's toy sizes here
NEW_READERS = ("sync_window_blocks_per_pass",
               "sync_speculation_miss_lane_share",
               "sync_valset_update_ms_per_block")


def _toy():
    config, params = TOY["blocksync_churn"]
    return copy.deepcopy(config), copy.deepcopy(params)


@pytest.fixture(scope="module")
def plan():
    config, params = blocksync_churn_toy()
    return gen.build(config, params, SEED)


@pytest.fixture(scope="module")
def sched():
    s = VerifyScheduler(spec=BackendSpec("cpu"), flush_us=300)
    s.start()
    yield s
    s.stop()


# -- the plan ---------------------------------------------------------------


def _wire(plan):
    forks = [plan["forks"][k]["chain"].encoded for k in gen.KINDS]
    return (plan["chain"].encoded, forks, plan["chain"].records,
            plan["states"])


def test_the_plan_is_a_pure_function_of_config_params_and_seed(plan):
    again = gen.build(*_toy(), SEED)
    other = gen.build(*_toy(), SEED + 1)
    assert _wire(plan) == _wire(again)
    assert plan["chain"].seats == again["chain"].seats
    keys = lambda p: {v.pub_key.bytes() for v in p["valset"].validators}  # noqa: E731
    assert keys(plan) == keys(again)
    assert keys(plan).isdisjoint(keys(other))
    assert plan["chain"].encoded != other["chain"].encoded


def test_the_schedule_is_the_configurations(plan):
    chain = plan["chain"]
    config, _ = _toy()
    every = config["schedule"]["seat_every"]
    lo, hi = config["schedule"]["power_range"]
    hashes = [b.header.validators_hash for b in chain.blocks[1:]]
    assert hashes[0] == hashes[1]
    assert all(a != b for a, b in zip(hashes[1:], hashes[2:]))
    assert sorted(chain.seats) == list(range(every, chain.top + 1, every))
    for h in range(1, chain.top + 1):
        block, rec = chain.blocks[h], chain.records[h]
        updates = [churn_reference.parse_val_tx(tx) for tx in rec["val_txs"]]
        seat = 2 if h % every == 0 else 0
        assert len(updates) == config["schedule"]["power_changes"] + seat
        assert len(block.data.txs) == config["txs_per_block"] + len(updates)
        assert rec["new_keys"] == config["txs_per_block"]
        keys = [k for k, _ in updates]
        assert len(set(keys)) == len(keys)  # nobody is touched twice
        powers = sorted(p for _, p in updates)
        if seat:
            assert powers[0] == 0
            assert config["schedule"]["joiner_power"] in powers
            left, came = chain.seats[h]
            assert churn_reference.address_of(keys[-1]) == came
            assert came[0] < 0x40
        assert all(lo <= p <= hi for p in powers[1 if seat else 0:])
        assert chain.valsets[h].size() == config["validators"]
        assert len(chain.signers[h]) == config["validators"]
        assert [pv.get_pub_key().address() for pv in chain.signers[h]] == [
            v.address for v in chain.valsets[h].validators]


def test_each_validator_signs_its_own_seeded_time_by_commit_index(plan):
    chain = plan["chain"]
    for h in (1, 9, chain.top - 1):
        stamps = [cs.timestamp.to_unix_ns()
                  for cs in chain.commits[h].signatures]
        assert len(set(stamps)) == len(stamps) == plan["validators"]
        assert stamps == [chainlib.vote_time(SEED, h, i).to_unix_ns()
                          for i in range(len(stamps))]
        assert chain.blocks[h + 1].header.time > chain.blocks[h].header.time


def test_the_forged_seat_is_one_that_joined_inside_the_same_window(plan):
    case = plan["forks"]["seat"]
    joined = {came: h for h, (_, came) in plan["chain"].seats.items()}
    addr = plan["chain"].valsets[case["block"]].validators[
        case["lane"]].address
    # delivered inside the first window, two heights before it signs
    assert 1 <= joined[addr] <= case["block"] - 2 < 16
    assert case["want"]["refused"][0] == case["block"]


# -- the program under the generator ----------------------------------------


@pytest.mark.parametrize("blocks_a_pass,passes", [(1, 16), (5, 4), (16, 1)])
def test_a_request_is_16_heights_whatever_the_pass_count(
        sched, plan, blocks_a_pass, passes):
    """A program whose pass applies fewer blocks is asked again until
    the state has advanced by the window: the request is the same work."""
    node = gen.start_epoch(sync_plane(sched), plan)
    try:
        node.reactor.verify_window = blocks_a_pass  # the program's, not ours
        assert node.window == 16 and node.full_window() == 16
        node.await_window(10)
        applied, made = node.sync_heights(16)
        assert made == passes
        assert 16 <= applied == blocks_a_pass * passes
        assert node.agrees_with(plan["states"][applied], applied)
    finally:
        node.stop()


def test_the_timed_loop_counts_requests_of_a_window_each(sched, plan):
    got = gen.drive(sync_plane(sched), plan, 1.5)
    assert got["attempted"] == len(got["requests"]) >= 2
    assert {r[2] for r in got["requests"]} == {"ok"}
    # 16 blocks, then the 8 the chain has left, then a fresh epoch
    assert [r[1] for r in got["requests"][:3]] == [16 * 7, 8 * 7, 16 * 7]
    books = got["spans_s"]["sync"]
    assert books["epochs_finished"] >= 1
    assert books["blocks_applied"] == sum(r[1] for r in got["requests"]) // 7
    assert books["passes"] == got["attempted"]
    assert books["sync_one_calls"] == books["blocks_refused"] == 0
    assert books["seconds"]["exec.valset_update"] > 0


@pytest.mark.parametrize("spoil", ["app_hash", "validators",
                                   "next_validators", "validators_hash"])
def test_correct_goes_false_on_a_wrong_app_hash_or_set(sched, plan, spoil):
    """What the reference says of the state after the first request is
    changed in one place: that request is a mismatch, and the run's
    line is not correct."""
    states = dict(plan["states"])
    want = dict(states[16])
    if spoil in ("validators", "next_validators"):
        rows = list(want[spoil])
        addr, power, key = rows[0]
        rows[0] = (addr, power + 1, key)
        want[spoil] = rows
    else:
        want[spoil] = bytes(8) if spoil == "app_hash" else bytes(32)
    states[16] = want
    got = gen.drive(sync_plane(sched), dict(plan, states=states), 0.2)
    assert got["requests"][0][2] == "mismatch"
    summary = run.summarize(got, 1.0, 1.0)
    assert summary["mismatches"] >= 1
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert run.result_line(summary, {}, device, 0, 1)["correct"] is False


@pytest.mark.parametrize("healthy", [True, False])
def test_the_canary_runs_between_requests_and_a_failed_one_is_incorrect(
        sched, plan, healthy):
    """Where the plane has a supervisor, its canary is run between two
    requests (never inside one), again once ``health_probe_every_s``
    have passed; a probe that fails makes the run's line incorrect."""
    plane = sync_plane(sched)
    log = []

    @contextlib.contextmanager
    def span(name):
        log.append(("open", name))
        yield
        log.append(("close", name))

    def probe_now():
        log.append(("probe", None))
        return healthy

    plane.span = span
    plane.node = types.SimpleNamespace(
        verify_supervisor=types.SimpleNamespace(probe_now=probe_now))
    got = gen.drive(plane, dict(plan, health_probe_every_s=0.01), 0.6)
    assert {r[2] for r in got["requests"]} == {"ok"}
    n = len(got["requests"])
    assert n >= 2
    assert log == [
        ("open", "bench:health_probe"), ("probe", None),
        ("close", "bench:health_probe"),
        ("open", "bench:sync_request"), ("close", "bench:sync_request"),
    ] * n
    assert got["health_probes"] == (n if healthy else 0)
    assert got["mismatches"] == (0 if healthy else n)
    summary = run.summarize(got, 1.0, 1.0)
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert run.result_line(summary, {}, device, 0, 1)["correct"] is healthy
    # and none before the next is due
    del log[:]
    got = gen.drive(plane, dict(plan, health_probe_every_s=3600.0), 0.3)
    assert log.count(("probe", None)) == 1 and len(got["requests"]) >= 2


@pytest.mark.parametrize("callee", ["BlocksyncReactor", "BlockExecutor",
                                    "EvidencePool"])
def test_the_generator_builds_what_node_py_builds_with_its_arguments(callee):
    node_calls = _call_keywords("cometbft_tpu/node/node.py", callee)
    gen_calls = _call_keywords("benchmark/traffic/blocksync_churn.py", callee)
    assert len(node_calls) == len(gen_calls) == 1
    assert gen_calls == node_calls
    assert "verify_window" not in gen_calls[0][1]


def test_the_configuration_states_what_the_issue_asks_of_it():
    conf = run.load_json("benchmark", "configs", "qa150-churn.json")
    sync = run.load_json("benchmark", "configs", "qa150-sync.json")
    for key in ("source", "deployment", "schedule", "schedule_is", "assumed",
                "reduced", "guarantees", "on_device"):
        assert conf[key]
    assert conf["schedule"] == {"power_changes": 2, "power_range": [8, 12],
                                "seat_every": 8, "joiner_power": 10}
    assert (conf["validators"], conf["txs_per_block"], conf["tx_bytes"],
            conf["replay_blocks"]) == (150, 16, 1024, 256)
    assert conf["crypto"] == sync["crypto"]
    # no guarantee of the static chain's cell is given up
    assert len(conf["guarantees"]) == len(sync["guarantees"]) + 1
    assert set(sync["guarantees"][:4]) <= set(conf["guarantees"])
    assert any("never the set the lanes were chosen under" in g
               for g in conf["guarantees"])
    assert "verify_window" not in conf


# -- the readers ------------------------------------------------------------


def test_the_new_readers_find_nothing_on_a_program_without_the_books():
    parent = {"passes": 0, "blocks_applied": 0, "seconds": {}}
    for sync in ({}, parent,
                 # the parent's program: its passes and stages, no more
                 {"passes": 32, "blocks_applied": 32,
                  "seconds": {"sync.apply": 1.0, "exec.validate": 0.4}}):
        after = {"bench": {"spans_s": {"sync": sync}}}
        got = run.read_metrics("layers", list(NEW_READERS), {}, after, None)
        assert set(got) <= {"sync_window_blocks_per_pass"}
    assert got["sync_window_blocks_per_pass"]["value"] == 1.0
    for after in ({}, {"bench": {}}, {"bench": {"spans_s": {}}}):
        assert run.read_metrics("layers", list(NEW_READERS), {}, after,
                                None) == {}


def test_the_new_readers_read_the_reactors_books():
    sync = {"passes": 10, "blocks_applied": 152, "tally_lanes": 14000,
            "speculation_miss_lanes": 70,
            "seconds": {"exec.valset_update": 0.076}}
    after = {"bench": {"spans_s": {"sync": sync}}}
    got = run.read_metrics("layers", list(NEW_READERS), {}, after, None)
    assert {k: round(v["value"], 6) for k, v in got.items()} == {
        "sync_window_blocks_per_pass": 15.2,
        "sync_speculation_miss_lane_share": 0.5,
        "sync_valset_update_ms_per_block": 0.5,
    }
    assert [got[n]["unit"] for n in NEW_READERS] == [
        "blocks/pass", "%", "ms/block"]


# -- the toy cell through a real node ---------------------------------------


def test_the_toy_churn_cell_end_to_end_on_the_cpu_platform(
        monkeypatch, _restore_process_state):  # noqa: F811
    """10 validators, floor 64, launches of 128: a request's 16 x 7-9
    lanes take the device route on the virtual CPU mesh, its 10-lane
    commit checks and its apply-time lanes stay under the floor. The
    profiler is left out, as in test_sync_cell.py."""
    monkeypatch.delenv("CBFT_WARM_BOOT", raising=False)
    cell = run.resolve_cell("qa150-blocksync-churn")
    cell = dict(cell, config=copy.deepcopy(cell["config"]),
                traffic=copy.deepcopy(cell["traffic"]))
    config, params = _toy()
    cell["config"].update(config, validators=10, replay_blocks=33)
    cell["config"]["crypto"].update(min_batch=64, max_chunk=128)
    cell["traffic"]["params"].update(params)
    cell["traffic"]["params"]["forged"]["tail_lane"] = 9

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
    line = run.run_cell(cell, SEED, 3.0, True, device, expect_platform="cpu")
    assert line["correct"] is False  # not a TPU, and says so
    assert line["attempted"] >= 2 and line["failed"] == 0
    got = line["metrics"]
    absent = {"host_cpu_ms_per_ksig"} if line["attempted"] < 10 else set()
    assert set(got) == set(cell["cell"]["layers"]) - absent
    assert got["compiles_in_window"]["value"] == 0
    assert got["sync_window_blocks_per_pass"]["value"] > 8
    assert 0 < got["sync_speculation_miss_lane_share"]["value"] < 50
    assert got["sync_valset_update_ms_per_block"]["value"] > 0
    assert 5 < got["device_lane_share"]["value"] < 40
    assert got["device_leg_us_per_lane"]["value"] > 0
