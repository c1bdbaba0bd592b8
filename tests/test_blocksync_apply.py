"""The blocksync reactor with apply, held to the plain reference
(``benchmark/lib/sync_reference.py``) on seeded chains of 7 validators:
the healthy chain, a precommit forged inside the quorum prefix, one
forged outside it; the pass ``_pool_routine`` runs, its stages and its
books; a program that skips either commit check fails the cell's
warm-up; a device dispatch that dies leaves no future unanswered."""

import time
import types

import pytest

from benchmark.lib import sync_reference
from tests.conftest import blocksync_apply_toy, sync_plane
from benchmark.traffic import blocksync_apply as gen
from cometbft_tpu.crypto import batch as cryptobatch
from cometbft_tpu.crypto.batch import BackendSpec, CPUBatchVerifier
from cometbft_tpu.crypto.scheduler import VerifyScheduler

SEED = 2_150_000_077
TOY: dict = {}  # tests/conftest.py puts the generator's toy sizes here
SYNC_STAGES = {"sync.build", "sync.part_set", "sync.submit",
               "sync.verdict_wait", "sync.validate", "sync.save_block",
               "sync.apply"}
EXEC_STAGES = {"exec.validate", "exec.abci", "exec.commit",
               "exec.save_state"}


@pytest.fixture(scope="module")
def sched():
    s = VerifyScheduler(spec=BackendSpec("cpu"), flush_us=300)
    s.start()
    yield s
    s.stop()


@pytest.fixture(scope="module")
def plan():
    config, params = blocksync_apply_toy()
    return gen.build(dict(config), dict(params), SEED)


def _sync_all(node, plan):
    """Passes until the chain's end. → blocks applied, pass by pass."""
    out = []
    while node.full_window():
        node.await_window(10)
        applied = node.sync_pass()
        assert applied > 0
        assert node.agrees_with(
            plan["states"][node.state.last_block_height], applied)
        out.append(applied)
    return out


def test_the_healthy_chain_syncs_to_the_references_state(sched, plan):
    node = gen.start_epoch(sync_plane(sched), plan)
    try:
        assert _sync_all(node, plan) == [16, 8]
        top = plan["chain"].top - 1
        want = plan["states"][top]
        assert node.state.last_block_height == top == 24
        assert bytes(node.state.app_hash) == want["app_hash"]
        assert want["app_hash"] == sync_reference.app_hash(24 * 3)
        # every height read back whole, with the chain's hash and the
        # commit the next block carried
        for h in range(1, top + 1):
            block = node.block_store.load_block(h)
            assert block.hash() == plan["chain"].block_ids[h].hash
            seen = node.block_store.load_seen_commit(h)
            assert seen.block_id == plan["chain"].block_ids[h]
        books = node.counters()
    finally:
        node.stop()
    assert books["passes"] == 2 and books["blocks_applied"] == top
    assert books["blocks_refused"] == 0 and books["sync_one_calls"] == 0
    assert books["light_lanes_submitted"] == top * 5  # 5 of 7 a block
    assert set(books["seconds"]) == SYNC_STAGES | EXEC_STAGES
    assert all(v > 0 for v in books["seconds"].values())
    # apply holds its own stages, the window's build its part sets
    sec = books["seconds"]
    assert sec["sync.apply"] >= sum(sec[s] for s in EXEC_STAGES)
    assert sec["sync.build"] >= sec["sync.part_set"]


@pytest.mark.parametrize("kind,applied,refused_by", [
    ("prefix", 4, "in the quorum prefix"),
    ("tail", 9, "in the LastCommit"),
])
def test_a_forged_precommit_is_refused_where_the_reference_refuses_it(
        sched, plan, kind, applied, refused_by):
    got = gen.sync_forged_chain(sync_plane(sched), plan, kind)
    refused, why = plan["forks"][kind]["want"]["refused"]
    assert refused == applied + 1 and refused_by in why
    assert got["applied"] == applied and got["agrees"]
    assert got["refused_count"] == 1
    assert got["stopped"] == got["byzantine"] == ["byzantine-0"]
    # both heights went to the byzantine peer and then to another
    assert sorted(got["asked"]) == [refused, refused + 1]
    assert all(n == 2 for n in got["asked"].values())
    assert got["then_applied"] == got["then_window"] > 0
    assert got["then_agrees"]
    assert gen.check_forged_chain(plan, kind, got)["refused"] == refused


def test_a_program_that_skips_the_full_commit_check_fails_warm_up(
        sched, plan, monkeypatch):
    from cometbft_tpu.types.validator_set import ValidatorSet

    monkeypatch.setattr(ValidatorSet, "verify_commit",
                        lambda self, *a, **kw: None)
    got = gen.sync_forged_chain(sync_plane(sched), plan, "tail")
    assert got["applied"] > 9  # block 10 went in with a forged LastCommit
    with pytest.raises(AssertionError, match="the reference refuses"):
        gen.check_forged_chain(plan, "tail", got)


def test_a_program_that_skips_the_light_check_fails_warm_up(
        sched, plan, monkeypatch):
    from cometbft_tpu.types.validator_set import ValidatorSet

    class Credulous:
        """Answers the window's light lanes valid, unseen; every other
        request goes to the real scheduler."""

        spec = sched.spec

        def submit(self, items, subsystem=None, height=None):
            if subsystem != gen.SUBSYSTEM:
                return sched.submit(items, subsystem=subsystem,
                                    height=height)
            n = len(items)
            return types.SimpleNamespace(
                result=lambda timeout=None: (True, [True] * n))

    monkeypatch.setattr(ValidatorSet, "verify_commit_light",
                        lambda self, *a, **kw: None)
    got = gen.sync_forged_chain(sync_plane(Credulous()), plan, "prefix")
    # block 5 went in on a forged quorum; the full check of block 6's
    # LastCommit caught the precommit one block late
    assert got["applied"] == 5
    with pytest.raises(AssertionError, match="the reference refuses"):
        gen.check_forged_chain(plan, "prefix", got)


def test_the_pool_routine_runs_the_public_pass(sched, plan):
    """A reactor started the way a node starts it syncs the chain on its
    own thread, through ``sync_pass``."""
    node = gen.SyncNode(plan["chain"], sched)
    passes = []
    real = node.reactor.sync_pass
    node.reactor.sync_pass = lambda st: (passes.append(1), real(st))[1]
    try:
        for k in range(2):
            node.add_peer(f"honest-{k}")
        node.reactor.start()
        node.reactor.switch_to_fast_sync(node.state)
        deadline = time.monotonic() + 30
        top = plan["chain"].top - 1
        while (node.reactor.blocks_synced < top
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert node.block_store.height() == top
        assert node.reactor.sync_error is None
        books = node.reactor.sync_counters()
    finally:
        node.stop()
    assert passes and books["passes"] == len(passes)
    assert books["blocks_applied"] == top


def test_a_dispatch_that_dies_is_answered_by_the_host_and_the_pass_ends(
        plan):
    """Why ``_apply_window_pipelined`` waits without a timeout: the
    scheduler answers a flush whose backend died from the CPU."""

    class Dying(CPUBatchVerifier):
        def verify(self):
            raise RuntimeError("the device plane died")

    cryptobatch.register_backend("bench-dying", Dying)
    sched = VerifyScheduler(spec=BackendSpec("bench-dying"), flush_us=300)
    sched.start()
    node = gen.start_epoch(sync_plane(sched), plan)
    try:
        node.await_window(10)
        assert node.sync_pass() == 16
        assert node.agrees_with(plan["states"][16], 16)
        assert sched.metrics.cpu_fallbacks.value() > 0
    finally:
        node.stop()
        sched.stop()
