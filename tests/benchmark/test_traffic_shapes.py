"""The traffic generators at toy sizes on the CPU platform, no node:
each plan is a pure function of (config, params, seed); the blocksync
requests are, lane for lane, what the reactor builds for the same
window; the steady schedule has the stated rates; an open loop is timed
from when a request was due."""

import contextlib
import json
import os
import time
from types import SimpleNamespace

import pytest

from benchmark import run
from benchmark.lib import data, reference

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
SEED = 11


def _generator(name):
    return run.load_module("traffic", name)


def _traffic(name):
    with open(os.path.join(REPO, "benchmark", "traffic", name + ".json")) as fh:
        return json.load(fh)


TOY = {
    "blocksync_window": (
        {"chain_id": "toy", "validators": 7, "replay_blocks": 6},
        {"blocks": 3, "first_height": 10, "forged_block": 1,
         "forged_lane": 2, "request_timeout_s": 5},
    ),
    "commit_loop": (
        {"chain_id": "toy", "validators": 9, "pool_commits": 2},
        {"first_height": 100, "corrupt_lane": 4},
    ),
    "steady_votes": (
        {"chain_id": "toy", "validators": 5, "steady_pool_heights": 2},
        dict(_traffic("steady-1s")["params"], first_height=50),
    ),
}


def _fingerprint(obj):
    """Everything in a plan that reaches the wire, as plain bytes."""
    if isinstance(obj, dict):
        return [(k, _fingerprint(v)) for k, v in sorted(obj.items())
                if k != "valset"]
    if isinstance(obj, (list, tuple)):
        return [_fingerprint(v) for v in obj]
    if hasattr(obj, "bytes") and callable(obj.bytes):
        return obj.bytes()
    if hasattr(obj, "encode") and not isinstance(obj, str):
        return obj.encode()
    return obj


@pytest.mark.parametrize("name", sorted(TOY))
def test_a_plan_is_a_pure_function_of_config_params_and_seed(name):
    gen = _generator(name)
    config, params = TOY[name]
    a = gen.build(dict(config), dict(params), SEED)
    b = gen.build(dict(config), dict(params), SEED)
    c = gen.build(dict(config), dict(params), SEED + 1)
    assert _fingerprint(a) == _fingerprint(b)
    keys = lambda p: [v.pub_key.bytes() for v in p["valset"].validators]  # noqa: E731
    assert keys(a) == keys(b)
    assert set(keys(a)).isdisjoint(keys(c))


def test_every_data_file_names_a_generator_and_all_its_parameters():
    for f in os.listdir(os.path.join(REPO, "benchmark", "traffic")):
        if not f.endswith(".json"):
            continue
        traffic = _traffic(f[:-5])
        toy_params = TOY[traffic["generator"]][1]
        assert set(traffic["params"]) == set(toy_params), f


# --------------------------------------------------------------------------
# blocksync: the benchmark's copy against the served shape


class _RecordingScheduler:
    """Stands where node.crypto_backend stands: records every submit and
    answers it valid."""

    def __init__(self):
        from cometbft_tpu.crypto.batch import BackendSpec

        self.spec = BackendSpec("tpu", min_batch=1024)
        self.calls = []

    def submit(self, items, subsystem=None, height=None):
        self.calls.append((list(items), subsystem, height))
        n = len(items)
        return SimpleNamespace(
            result=lambda timeout=None: (True, [True] * n)
        )


def _chain(vals, privs, chain_id, n_blocks):
    """n_blocks committed blocks through the real executor, as
    tests/test_blocksync.py builds them. → (genesis state factory, blocks)"""
    from cometbft_tpu.abci.client import LocalClient
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.libs.db import MemDB
    from cometbft_tpu.proto.gogo import Timestamp
    from cometbft_tpu.proxy import AppConnConsensus
    from cometbft_tpu.state import make_genesis_state
    from cometbft_tpu.state.execution import BlockExecutor
    from cometbft_tpu.state.store import Store
    from cometbft_tpu.types import test_util
    from cometbft_tpu.types.block import BlockID, Commit
    from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator

    doc = GenesisDoc(
        genesis_time=Timestamp(1_700_000_000, 0),
        chain_id=chain_id,
        validators=[
            GenesisValidator(v.address, v.pub_key, v.voting_power, "")
            for v in vals.validators
        ],
    )

    def fresh():
        state = make_genesis_state(doc)
        store = Store(MemDB())
        store.save(state)
        client = LocalClient(KVStoreApplication())
        client.start()
        return state, BlockExecutor(store, AppConnConsensus(client)), client

    state, executor, client = fresh()
    blocks = []
    last_commit = Commit(height=0, round=0)
    try:
        for h in range(1, n_blocks + 1):
            proposer = state.validators.validators[h % len(privs)].address
            block, parts = executor.create_proposal_block(
                h, state, last_commit, proposer
            )
            block_id = BlockID(block.hash(), parts.header())
            last_commit = test_util.make_commit(
                block_id, h, 0, state.validators, privs, chain_id,
                now=Timestamp(1_700_000_000 + h, 0),
            )
            state, _ = executor.apply_block(state, block_id, block)
            blocks.append(block)
    finally:
        client.stop()
    return fresh, blocks


def test_blocksync_requests_equal_what_the_reactor_builds_lane_for_lane():
    from cometbft_tpu.blocksync import BlocksyncReactor
    from cometbft_tpu.store import BlockStore
    from cometbft_tpu.libs.db import MemDB

    gen = _generator("blocksync_window")
    chain_id = "shape-chain"
    vals, privs = data.make_valset(7, SEED, "sync")
    fresh, blocks = _chain(vals, privs, chain_id, 5)
    state, executor, client = fresh()
    try:
        reactor = BlocksyncReactor(
            state, executor, BlockStore(MemDB()), fast_sync=False,
            verify_window=len(blocks),
        )
        recorder = _RecordingScheduler()
        reactor.crypto_backend = recorder
        reactor.pool = SimpleNamespace(
            peek_window=lambda n: blocks[:n],
            pop_request=lambda: None,
            height=1,
            max_peer_height=lambda: len(blocks),
        )
        new_state = reactor._try_sync_window(chain_id, state)
    finally:
        client.stop()
    # block i is verified by the commit the next block carries
    assert new_state.last_block_height == len(blocks) - 1
    commits = [b.last_commit for b in blocks[1:]]
    want = gen.window_requests(vals, commits, chain_id)
    assert len(recorder.calls) == len(want) == len(blocks) - 1
    for (items, subsystem, height), (w_height, w_items) in zip(
            recorder.calls, want):
        assert subsystem == gen.SUBSYSTEM
        assert height == w_height
        assert data.raw(items) == data.raw(w_items)
        # 7 equal validators: the quorum prefix is the first 5
        assert len(items) == 5


def test_the_forged_window_differs_in_one_lane_and_the_reference_sees_it():
    gen = _generator("blocksync_window")
    config, params = TOY["blocksync_window"]
    plan = gen.build(dict(config), dict(params), SEED)
    assert all(all(w) for w in plan["want"])
    assert plan["forged_want"] == [True, False, True]
    clean, forged = plan["windows"][0], plan["forged"]
    diff = [
        (b, i) for b in range(len(clean))
        for i, (x, y) in enumerate(zip(data.raw(clean[b][1]),
                                       data.raw(forged[b][1])))
        if x != y
    ]
    assert diff == [(params["forged_block"], params["forged_lane"])]
    assert plan["lanes_per_window"] == 3 * 5


def test_reachable_buckets_at_the_deployments_size():
    gen = _generator("blocksync_window")
    # 64 blocks x 101 lanes, floor 1,024, cap 8,192: a flush that clears
    # the floor holds 11..64 blocks and pads to 2,048, 4,096 or 8,192
    got = gen.reachable_buckets(101, 64, 1024, 8192)
    assert got == {2048: 11, 4096: 21, 8192: 41}
    assert gen.reachable_buckets(5, 3, 64, 128) == {}


def test_nothing_on_the_submit_path_memoizes_a_verdict():
    """The cells replay a small pool of pre-signed requests in rotation.
    That stands for distinct blocks only while the same request
    submitted twice is verified twice."""
    from cometbft_tpu.crypto import batch as cryptobatch
    from cometbft_tpu.crypto.batch import BackendSpec, CPUBatchVerifier
    from cometbft_tpu.crypto.scheduler import VerifyScheduler

    lanes = []

    class Counting(CPUBatchVerifier):
        def verify(self):
            lanes.append(self.count())
            return super().verify()

    cryptobatch.register_backend("bench-counting", Counting)
    gen = _generator("blocksync_window")
    config, params = TOY["blocksync_window"]
    plan = gen.build(dict(config), dict(params), SEED)
    sched = VerifyScheduler(spec=BackendSpec("bench-counting"), flush_us=200)
    sched.start()
    try:
        _, items = plan["windows"][0][0]
        for _ in range(3):
            ok, mask = sched.submit(
                items, subsystem=gen.SUBSYSTEM, height=10
            ).result(timeout=30)
            assert ok and all(mask)
    finally:
        sched.stop()
    assert lanes == [len(items)] * 3


# --------------------------------------------------------------------------
# steady state: the schedule, and timing from the due time


def test_the_steady_schedule_has_the_stated_mean_and_burst_rates():
    gen = _generator("steady_votes")
    params = _traffic("steady-1s")["params"]
    events = gen.schedule(params, 150, 4, SEED, 30.0)
    assert events == gen.schedule(params, 150, 4, SEED, 30.0)
    assert events != gen.schedule(params, 150, 4, SEED + 1, 30.0)
    votes = [e for e in events if e[1] == "vote"]
    commits = [e for e in events if e[1] == "commit"]
    assert len(votes) == 30 * 300 and len(commits) == 30
    assert len(votes) / 30.0 == 300.0  # the mean rate
    assert [e[0] for e in events] == sorted(e[0] for e in events)
    for k in range(30):
        in_height = [e for e in votes if k <= e[0] < k + 1]
        pre = [e for e in in_height if e[3] == gen.PREVOTE]
        com = [e for e in in_height if e[3] == gen.PRECOMMIT]
        assert len(pre) == len(com) == 150
        # each step's votes lie within 150 ms of its start: 1,000/s
        assert all(k <= e[0] < k + 0.150 for e in pre)
        assert all(k + 0.400 <= e[0] < k + 0.550 for e in com)
        assert sorted(e[4] for e in pre) == list(range(150))
    assert [e[0] for e in commits] == [float(k) for k in range(30)]
    # the pool rotates
    assert {e[2] for e in events} == {0, 1, 2, 3}


class _StubPlane:
    """A plane with no node: spans and ticks do nothing, no counter
    moves. The backend is the cpu backend's name."""

    backend = "cpu"

    def __init__(self):
        self.node = SimpleNamespace(verify_supervisor=SimpleNamespace(
            probe_now=lambda: True
        ))

    def span(self, name):
        return contextlib.nullcontext()

    def tick(self):
        pass

    def fallbacks(self):
        return 0.0

    @staticmethod
    def note(msg):
        pass


def test_an_open_loop_request_is_timed_from_when_it_was_due(monkeypatch):
    """A consumer that stalls makes the votes due meanwhile late by the
    stall, though each is verified in a blink once taken."""
    gen = _generator("steady_votes")
    config, params = TOY["steady_votes"]
    params = dict(params, height_s=0.5, spread_ms=100, precommit_at_ms=200,
                  drain_grace_s=5, health_probe_every_s=0.2)
    plan = gen.build(dict(config), params, SEED)
    plane = _StubPlane()
    gen.warm(plane, plan)
    stall = 0.3
    real = gen.verify_height_commit
    calls = []

    def slow_commit(plane_, plan_, k):
        calls.append(k)
        if len(calls) == 2:
            time.sleep(stall)  # the second height's block validation
        return real(plane_, plan_, k)

    monkeypatch.setattr(gen, "verify_height_commit", slow_commit)
    out = gen.drive(plane, plan, 1.0)
    assert out["loop"] == "open"
    assert out["attempted"] == 2 * 2 * 5 == len(out["requests"])
    assert all(status == "ok" for _, _, status in out["requests"])
    assert out["extra_sigs"] == 2 * 5 and out["mismatches"] == 0
    assert out["health_probes"] >= 2
    lat = [lat for lat, _, _ in out["requests"]]
    # the five prevotes of the second height were due during the stall
    assert sum(1 for x in lat if x > stall * 0.5) >= 5
    assert min(lat) < 0.05
    assert len(out["late_s"]) == out["attempted"]
    assert len(out["spans_s"]["commit150"]) == 2


def test_the_commit_loop_refuses_its_corrupted_commit_and_times_requests():
    gen = _generator("commit_loop")
    config, params = TOY["commit_loop"]
    plan = gen.build(dict(config), dict(params), SEED)
    assert plan["want"] == [True, True] and plan["corrupted_want"] is False
    plane = _StubPlane()
    assert gen.warm(plane, plan) == {"corrupted_refused": 1}
    out = gen.drive(plane, plan, 0.2)
    assert out["loop"] == "closed" and out["attempted"] >= 2
    assert all(s == "ok" and sigs == 9 for _, sigs, s in out["requests"])
    assert len(out["spans_s"]["verify_commit"]) == len(out["requests"])


def test_the_reference_agrees_with_itself_and_the_program_on_spoiled_lanes():
    vals, privs = data.make_valset(64, SEED, "ref")
    _, commit = data.make_commit(vals, privs, 3, SEED, "toy")
    items, spoiled = data.corrupt(data.commit_items(vals, commit, "toy"))
    assert len(spoiled) >= 5
    raw = data.raw(items)
    want = [i not in spoiled for i in range(len(items))]
    assert reference.verify_many(raw) == want
    assert [reference.verify_py(*r) for r in raw] == want
    if reference.verify_openssl(*raw[0]) is not None:
        assert [reference.verify_openssl(*r) for r in raw] == want
    from cometbft_tpu.crypto.batch import CPUBatchVerifier

    bv = CPUBatchVerifier()
    for pk, m, s in items:
        bv.add(pk, m, s)
    assert bv.verify()[1] == want
