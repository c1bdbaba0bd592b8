"""The blocksync reactor on a chain whose validator set moves at every
height, held to the plain reference (``benchmark/lib/churn_reference.py``)
on seeded chains of 7 validators: the window survives the change (lanes
speculated by address, the quorum walked at apply against the true set);
every signature the reference's walks need is verified, two epochs
alike; a forged precommit on a kept seat, on a seat that joined inside
the window and behind the quorum is refused or ignored as the reference
says; a speculation that stops short is made up at apply; and on a
static chain the requests are what they were."""

import collections
from types import SimpleNamespace

import pytest

from benchmark.lib import chain as chainlib
from benchmark.lib import churn_reference, data
from benchmark.traffic import blocksync_apply as apply_gen
from benchmark.traffic import blocksync_churn as gen
from cometbft_tpu.blocksync import reactor as reactor_mod
from cometbft_tpu.crypto.batch import BackendSpec
from cometbft_tpu.crypto.scheduler import VerifyScheduler
from tests.conftest import (
    blocksync_apply_toy, blocksync_churn_toy, sync_plane,
)

SEED = 2_150_000_077
NEW_COUNTERS = ("window_blocks", "valset_changes_in_window",
                "speculated_lanes", "tally_lanes", "speculation_miss_lanes")


@pytest.fixture(scope="module")
def sched():
    s = VerifyScheduler(spec=BackendSpec("cpu"), flush_us=300)
    s.start()
    yield s
    s.stop()


@pytest.fixture(scope="module")
def plan():
    config, params = blocksync_churn_toy()
    return gen.build(dict(config), dict(params), SEED)


def _sync_all(node, plan):
    """Requests until the chain's end. → (blocks, passes) a request."""
    out = []
    while node.full_window():
        node.await_window(10)
        applied, passes = node.sync_heights(node.full_window())
        assert applied > 0
        assert node.agrees_with(
            plan["states"][node.state.last_block_height], applied)
        out.append((applied, passes))
    return out


def test_the_window_survives_the_change_and_syncs_to_the_references_state(
        sched, plan):
    chain = plan["chain"]
    hashes = [b.header.validators_hash for b in chain.blocks[1:]]
    assert all(a != b for a, b in zip(hashes[2:], hashes[3:]))
    node = gen.start_epoch(sync_plane(sched), plan)
    try:
        # one pass a request: 16 blocks, then the 8 that are left
        assert _sync_all(node, plan) == [(16, 1), (8, 1)]
        top = chain.top - 1
        want = plan["states"][top]
        assert node.state.last_block_height == top == 24
        assert bytes(node.state.app_hash) == want["app_hash"]
        assert (chainlib.plain_vals(node.state.validators)["rows"]
                == want["validators"])
        assert node.state.validators.hash() == want["validators_hash"]
        # seats changed hands: the final set holds joiners
        genesis = {v.address for v in chain.vals.validators}
        final = {r[0] for r in want["next_validators"]}
        assert len(final) == 7
        assert final - genesis
        assert final - genesis <= {s[1] for s in chain.seats.values()}
        books = node.counters()
    finally:
        node.stop()
    assert books["passes"] == 2 and books["blocks_applied"] == 24
    assert books["blocks_refused"] == 0 and books["sync_one_calls"] == 0
    assert books["window_blocks"] == 24
    # the headers of blocks 3.. each carry a hash the one before did not
    assert books["valset_changes_in_window"] == 14 + 7
    assert books["speculated_lanes"] > books["tally_lanes"] > 0
    assert 0 < books["speculation_miss_lanes"] < books["tally_lanes"]
    sec = books["seconds"]
    for stage in ("sync.speculate", "sync.tally", "exec.valset_update"):
        assert sec[stage] > 0
    assert sec["sync.build"] >= sec["sync.part_set"] + sec["sync.speculate"]
    assert sec["sync.apply"] >= sec["exec.valset_update"]


def test_every_signature_the_reference_needs_is_verified_two_epochs_alike(
        plan):
    """Lanes recorded by a stand-in scheduler: what the reference's walks
    verify is verified by the program, nothing is remembered from one
    epoch (or height) to the next, and nothing here says how many times
    a lane may be checked."""
    seen = []

    class Recording:
        """Stands where node.crypto_backend stands: every request the
        reactor, validate_block and the executor hand the scheduler."""

        def __init__(self, inner):
            self.inner, self.spec = inner, inner.spec

        def submit(self, items, **kw):
            seen.extend((pk.bytes(), bytes(m), bytes(s)) for pk, m, s in items)
            return self.inner.submit(items, **kw)

    inner = VerifyScheduler(spec=BackendSpec("cpu"), flush_us=200)
    inner.start()
    epochs = []
    try:
        for _ in range(2):
            del seen[:]
            node = gen.start_epoch(sync_plane(Recording(inner)), plan)
            try:
                _sync_all(node, plan)
            finally:
                node.stop()
            epochs.append(collections.Counter(seen))
    finally:
        inner.stop()
    needed = []

    def verify_many(items):
        needed.extend(items)
        return churn_reference.reference.verify_many(items)

    chain = plan["chain"]
    churn_reference.replay(chainlib.plain_vals(chain.vals), chain.records,
                           verify_many=verify_many)
    assert len(needed) > 24 * 5
    assert epochs[0] == epochs[1]
    assert set(needed) <= set(epochs[0])


@pytest.mark.parametrize("kind,refused_by,at_apply", [
    ("prefix", "in the quorum prefix", False),
    ("seat", "in the quorum prefix", True),
    ("tail", "in the LastCommit", False),
])
def test_a_forged_precommit_is_refused_or_ignored_as_the_reference_says(
        sched, plan, kind, refused_by, at_apply):
    case = plan["forks"][kind]
    refused, why = case["want"]["refused"]
    assert refused_by in why
    assert refused == case["block"] + (kind == "tail")
    got = gen.sync_forged_chain(sync_plane(sched), plan, kind)
    assert got["applied"] == refused - 1 and got["agrees"]
    assert got["refused_count"] == 1
    assert got["stopped"] == got["byzantine"] == ["byzantine-0"]
    assert sorted(got["asked"]) == [refused, refused + 1]
    assert got["then_applied"] == got["then_window"] > 0
    assert got["then_agrees"]
    assert gen.check_forged_chain(plan, kind, got)["refused"] == refused
    if at_apply:
        # the seat joined inside the window: no lane was speculated for
        # it, the apply-time walk verified the forged signature itself
        addr = case["chain"].valsets[case["block"]].validators[
            case["lane"]].address
        assert addr in {seat[1] for seat in case["chain"].seats.values()}
        assert got["apply_time_lanes"] >= 1


def test_a_program_that_trusts_its_speculation_fails_warm_up(
        sched, plan, monkeypatch):
    """A walk that takes no lane it did not speculate lets the forged
    joiner through; the cell's warm-up says so."""
    real = reactor_mod.BlocksyncReactor._tally_speculated

    def trusting(self, chain_id, state, block_id, first, commit, lanes,
                 mask, scheduler):
        vals = state.validators.validators
        lanes = dict(lanes)
        for idx in range(len(vals)):
            lanes.setdefault(idx, (0, vals[idx].pub_key))
        return real(self, chain_id, state, block_id, first, commit, lanes,
                    [True] * len(vals), scheduler)

    monkeypatch.setattr(reactor_mod.BlocksyncReactor, "_tally_speculated",
                        trusting)
    got = gen.sync_forged_chain(sync_plane(sched), plan, "seat")
    assert got["applied"] >= plan["forks"]["seat"]["block"]
    with pytest.raises(AssertionError, match="the reference refuses"):
        gen.check_forged_chain(plan, "seat", got)


def test_a_joiner_inside_the_window_is_verified_at_apply(plan):
    """The seat that joins at height 10 (delivered at 8) signs commits
    10..16 of the first window under a key the state did not know when
    the lanes were chosen: those signatures are verified by the walk, in
    requests of their own."""
    calls = []

    class Recording:
        def __init__(self, inner):
            self.inner, self.spec = inner, inner.spec

        def submit(self, items, **kw):
            calls.append([(pk.bytes(), m, s) for pk, m, s in items])
            return self.inner.submit(items, **kw)

    inner = VerifyScheduler(spec=BackendSpec("cpu"), flush_us=300)
    inner.start()
    chain = plan["chain"]
    node = gen.start_epoch(sync_plane(Recording(inner)), plan)
    try:
        node.await_window(10)
        before = len(calls)
        assert node.sync_heights(16) == (16, 1)
        books = node.counters()
    finally:
        node.stop()
        inner.stop()
    joiner = chain.seats[8][1]
    assert joiner not in {v.address for v in chain.valsets[2].validators}
    wanted = set()
    for h in range(10, 17):
        vals = chain.valsets[h]
        if not vals.has_address(joiner):
            continue  # the seat changed hands again
        idx = [v.address for v in vals.validators].index(joiner)
        prefix = data.quorum_prefix_items(vals, chain.commits[h],
                                          plan["chain_id"])
        key = vals.validators[idx].pub_key.bytes()
        wanted |= {t for t in data.raw(prefix) if t[0] == key}
    assert wanted  # the joiner sits inside some of those walks
    # the window's 16 requests went out first, without the joiner's key
    window, later = calls[before:before + 16], calls[before + 16:]
    ahead = {t for call in window for t in call}
    assert not wanted & ahead
    assert wanted <= {t for call in later for t in call}
    assert books["speculation_miss_lanes"] >= len(wanted)


def test_a_speculation_that_stops_short_is_made_up_at_apply(
        sched, plan, monkeypatch):
    monkeypatch.setattr(reactor_mod, "SPECULATION_MARGIN", -0.5)
    node = gen.start_epoch(sync_plane(sched), plan)
    try:
        assert _sync_all(node, plan) == [(16, 1), (8, 1)]
        books = node.counters()
    finally:
        node.stop()
    assert books["sync_one_calls"] == 0 and books["blocks_refused"] == 0
    # half the quorum speculated: about half the walks' lanes are misses
    assert books["speculation_miss_lanes"] * 3 > books["tally_lanes"]


def test_on_a_static_chain_the_requests_are_what_they_were():
    """Lane for lane and request for request what the reactor submitted
    before it speculated: a block's quorum prefix under the state's set,
    one request a block, in block order; nothing is speculated and no
    new stage opens."""
    config, params = blocksync_apply_toy()
    plan = apply_gen.build(dict(config), dict(params), SEED)
    chain = plan["chain"]
    calls = []

    class Recording:
        spec = BackendSpec("tpu", min_batch=1024)

        def submit(self, items, subsystem=None, height=None):
            calls.append((data.raw(items), subsystem, height))
            n = len(items)
            return SimpleNamespace(
                result=lambda timeout=None: (True, [True] * n))

    inner = VerifyScheduler(spec=BackendSpec("cpu"), flush_us=300)
    inner.start()
    node = apply_gen.SyncNode(chain, inner)
    node.reactor.crypto_backend = Recording()
    try:
        for k in range(2):
            node.add_peer(f"honest-{k}")
        node.start()
        node.await_window(10)
        assert node.sync_pass() == 16
        books = node.counters()
    finally:
        node.stop()
        inner.stop()
    assert calls == [
        (data.raw(data.quorum_prefix_items(chain.vals, chain.commits[h],
                                           plan["chain_id"])),
         "blocksync", h)
        for h in range(1, 17)
    ]
    assert books["light_lanes_submitted"] == 16 * 5
    assert books["window_blocks"] == 16
    assert [books[c] for c in NEW_COUNTERS[1:]] == [0, 0, 0, 0]
    assert not {"sync.speculate", "sync.tally",
                "exec.valset_update"} & set(books["seconds"])
