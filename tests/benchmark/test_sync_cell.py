"""The blocksync-apply cell: the plan a pure function of config, params
and seed; per-validator timestamps; the plain reference on its own; that
nothing on the path memoizes a verdict across epochs; that the
generator's reactor and executor get exactly ``node.py``'s arguments and
no benchmark file sets ``verify_window``; the new readers; and the toy
cell end to end on the CPU platform through a real node."""

import ast
import copy
import os

import pytest

from benchmark import run
from benchmark.lib import chain as chainlib
from benchmark.lib import reference, sync_reference
from benchmark.traffic import blocksync_apply as gen
from tests.conftest import blocksync_apply_toy, sync_plane

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
SEED = 2_150_000_123  # more than 32 signed bits hold
TOY: dict = {}  # tests/conftest.py puts the generator's toy sizes here
NEW_READERS = ("sync_build_ms_per_block", "sync_verdict_wait_ms_per_block",
               "sync_validate_ms_per_block", "sync_exec_ms_per_block",
               "sync_store_ms_per_block")


def _toy():
    config, params = TOY["blocksync_apply"]
    return copy.deepcopy(config), copy.deepcopy(params)


@pytest.fixture(scope="module")
def plan():
    config, params = blocksync_apply_toy()
    return gen.build(dict(config), dict(params), SEED)


# -- the plan ---------------------------------------------------------------


def _wire(plan):
    """Everything of a plan that reaches the reactor or the reference."""
    forks = [plan["forks"][k]["chain"].encoded for k in ("prefix", "tail")]
    return (plan["chain"].encoded, forks, plan["chain"].records,
            plan["states"])


def test_the_plan_is_a_pure_function_of_config_params_and_seed(plan):
    config, params = _toy()
    again = gen.build(config, params, SEED)
    other = gen.build(*_toy(), SEED + 1)
    assert _wire(plan) == _wire(again)
    keys = lambda p: {v.pub_key.bytes() for v in p["valset"].validators}  # noqa: E731
    assert keys(plan) == keys(again)
    assert keys(plan).isdisjoint(keys(other))
    assert plan["chain"].encoded != other["chain"].encoded


def test_every_transaction_is_a_distinct_key_value_of_the_stated_size():
    txs = [tx for h in (1, 2) for tx in chainlib.make_txs(SEED, h, 396, 1024)]
    assert {len(tx) for tx in txs} == {1024}
    keys = [tx.split(b"=", 1)[0] for tx in txs]
    assert len(set(keys)) == len(txs) == 792
    assert txs == [tx for h in (1, 2)
                   for tx in chainlib.make_txs(SEED, h, 396, 1024)]


def test_each_validator_signs_its_own_seeded_time_and_the_median_is_the_blocks(
        plan):
    from cometbft_tpu.state import median_time

    chain = plan["chain"]
    for h in (1, 7, chain.top - 1):
        stamps = [cs.timestamp.to_unix_ns()
                  for cs in chain.commits[h].signatures]
        assert len(set(stamps)) == len(stamps) == plan["validators"]
        assert stamps == [chainlib.vote_time(SEED, h, i).to_unix_ns()
                          for i in range(len(stamps))]
        nxt = chain.blocks[h + 1]
        assert nxt.header.time == median_time(nxt.last_commit, chain.vals)
        assert nxt.header.time > chain.blocks[h].header.time
    assert {len(r) for r in chain.encoded[1:]} != {0}


# -- the plain reference ----------------------------------------------------


def test_the_references_app_hash_is_the_kvstores():
    from cometbft_tpu.abci.kvstore import _put_varint

    for keys in (0, 1, 63, 64, 396, 396 * 256, 2**31):
        assert sync_reference.app_hash(keys) == _put_varint(keys)


def test_the_reference_accepts_the_chain_and_refuses_each_fork_once(plan):
    vals = chainlib.plain_vals(plan["chain"].vals)
    top = plan["chain"].top
    assert sorted(plan["states"]) == list(range(top))
    for kind, refused_at in (("prefix", 5), ("tail", 10)):
        want = plan["forks"][kind]["want"]
        assert want["refused"][0] == refused_at
        assert want["accepted"][:refused_at - 1] == [True] * (refused_at - 1)
        assert not any(want["accepted"][refused_at - 1:])
        assert max(want["states"]) == refused_at - 1
        # in pure Python integers too (RFC 8032 5.1.7), on the blocks
        # around the forged precommit
        fork = plan["forks"][kind]["chain"]
        near = [None] + fork.records[1:refused_at + 2]
        slow = sync_reference.replay(
            vals, near,
            verify_many=lambda items: [reference.verify_py(*i)
                                       for i in items])
        assert slow["refused"] == want["refused"]


def test_the_reference_refuses_a_chain_that_does_not_continue(plan):
    vals = chainlib.plain_vals(plan["chain"].vals)
    records = list(plan["chain"].records[:8])
    records[4] = plan["forks"]["prefix"]["chain"].records[6]
    got = sync_reference.replay(vals, records)
    assert got["refused"][0] == 3  # block 4 is not what block 3's commit is for


# -- the program under the generator ----------------------------------------


def test_nothing_on_the_path_memoizes_a_verdict_across_epochs(plan):
    """The chain is re-synced from genesis in rotation. That stands for
    10,000 distinct blocks only while the second epoch verifies every
    lane the first one did."""
    from cometbft_tpu.crypto import batch as cryptobatch
    from cometbft_tpu.crypto.batch import BackendSpec, CPUBatchVerifier
    from cometbft_tpu.crypto.scheduler import VerifyScheduler

    lanes = []

    class Counting(CPUBatchVerifier):
        def verify(self):
            lanes.append(self.count())
            return super().verify()

    cryptobatch.register_backend("bench-sync-counting", Counting)
    sched = VerifyScheduler(spec=BackendSpec("bench-sync-counting"),
                            flush_us=200)
    sched.start()
    per_epoch = []
    try:
        for _ in range(2):
            del lanes[:]
            node = gen.start_epoch(sync_plane(sched), plan)
            try:
                while node.full_window():
                    node.await_window(10)
                    assert node.sync_pass() > 0
            finally:
                node.stop()
            per_epoch.append(sum(lanes))
    finally:
        sched.stop()
    blocks = plan["chain"].top - 1
    # a block: 5 light lanes, and its 7-lane LastCommit twice (none at 1)
    assert per_epoch == [blocks * 5 + (blocks - 1) * 2 * 7] * 2


def _call_keywords(path, callee):
    """[(positional count, sorted keyword names)] of every call of
    ``callee`` in the file."""
    with open(os.path.join(REPO, path)) as fh:
        tree = ast.parse(fh.read())
    return [
        (len(node.args), sorted(kw.arg for kw in node.keywords))
        for node in ast.walk(tree)
        if isinstance(node, ast.Call)
        and getattr(node.func, "id", getattr(node.func, "attr", "")) == callee
    ]


@pytest.mark.parametrize("callee", ["BlocksyncReactor", "BlockExecutor",
                                    "EvidencePool"])
def test_the_generator_builds_what_node_py_builds_with_its_arguments(callee):
    node_calls = _call_keywords("cometbft_tpu/node/node.py", callee)
    gen_calls = _call_keywords("benchmark/traffic/blocksync_apply.py", callee)
    assert len(node_calls) == len(gen_calls) == 1
    assert gen_calls == node_calls
    assert "verify_window" not in gen_calls[0][1]


def test_no_benchmark_file_sets_the_reactors_window():
    for root in ("benchmark", "tests/benchmark"):
        for dirpath, _, files in os.walk(os.path.join(REPO, root)):
            for f in files:
                if not f.endswith((".py", ".json")) or f == "test_sync_cell.py":
                    continue
                with open(os.path.join(dirpath, f)) as fh:
                    src = fh.read()
                if "BlocksyncReactor(" in src and f != "test_traffic_shapes.py":
                    assert "verify_window=" not in src, f
    conf = run.load_json("benchmark", "configs", "qa150-sync.json")
    assert "qa150.json's window_blocks 64" in conf["window_blocks"]
    assert "16" in conf["window_blocks"]


# -- the readers ------------------------------------------------------------


def test_the_new_readers_find_nothing_on_empty_books_and_do_not_raise():
    for after in ({}, {"bench": {}}, {"bench": {"spans_s": {}}},
                  {"bench": {"spans_s": {"sync": {}}}},
                  {"bench": {"spans_s": {"sync": {
                      "blocks_applied": 4, "seconds": {"sync.apply": 1.0}}}}}):
        assert run.read_metrics("layers", list(NEW_READERS), {}, after,
                                None) in ({}, )


def test_the_new_readers_read_the_reactors_books():
    sec = {"sync.build": 0.9, "sync.part_set": 0.5, "sync.submit": 0.1,
           "sync.verdict_wait": 0.2, "sync.validate": 1.0,
           "exec.validate": 1.1, "sync.save_block": 0.3, "sync.apply": 2.1}
    after = {"bench": {"spans_s": {"sync": {"blocks_applied": 100,
                                            "seconds": sec}}}}
    got = run.read_metrics("layers", list(NEW_READERS), {}, after, None)
    assert {k: round(v["value"], 6) for k, v in got.items()} == {
        "sync_build_ms_per_block": 5.0,
        "sync_verdict_wait_ms_per_block": 2.0,
        "sync_validate_ms_per_block": 21.0,
        "sync_exec_ms_per_block": 10.0,
        "sync_store_ms_per_block": 8.0,
    }
    assert {v["unit"] for v in got.values()} == {"ms/block"}


# -- the toy cell through a real node ---------------------------------------


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
    """10 validators (quorum prefix 7), floor 64, launches of 128: a
    pass's 16 x 7 light lanes take the device route on the virtual CPU
    mesh in one launch, its 10-lane commit checks stay under the floor.
    The profiler is left out, as in test_run_contract.py."""
    monkeypatch.delenv("CBFT_WARM_BOOT", raising=False)
    cell = run.resolve_cell("qa150-blocksync-apply")
    cell = dict(cell, config=copy.deepcopy(cell["config"]),
                traffic=copy.deepcopy(cell["traffic"]))
    config, params = _toy()
    cell["config"].update(config, validators=10, replay_blocks=33)
    cell["config"]["crypto"].update(min_batch=64, max_chunk=128)
    cell["traffic"]["params"].update(params)
    cell["traffic"]["params"]["forged"]["tail_lane"] = 8

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
    # the cell reports no trace-fed reader; the host's cost per pass
    # needs the ten passes its median asks for
    absent = {"host_cpu_ms_per_ksig"} if line["attempted"] < 10 else set()
    assert set(got) == set(cell["cell"]["layers"]) - absent
    assert set(NEW_READERS) <= set(got)
    assert all(got[n]["value"] > 0 for n in NEW_READERS)
    assert got["compiles_in_window"]["value"] == 0
    # 112 of a pass's 112 + 32 x 10 routed lanes ride the device, less
    # what a deadline flush cut off under the floor
    assert 5 < got["device_lane_share"]["value"] < 40
    assert got["device_leg_us_per_lane"]["value"] > 0
    assert got["queue_wait_mean_ms"]["value"] > 0
