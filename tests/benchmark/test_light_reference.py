"""The program's ``light.verifier.verify`` against the plain light
reference (benchmark/lib/light_reference.py) on small seeded chains:
the same accept, or the same class of refusal, in every case, through
the CPU backend and through a RemoteVerifier on an in-process daemon."""

import os

import pytest

from benchmark.lib import data, light_reference as ref
from benchmark.traffic import light_fleet

SEED = 2_150_000_029
FIRST = 700
HOUR_NS = 3600 * light_fleet.SECOND_NS
PERIOD_NS = 14 * 24 * HOUR_NS
DRIFT_NS = 10 * light_fleet.SECOND_NS


def _valset(powers, tag, share=()):
    """A validator set with ``powers``; ``share`` are (pub key, signer)
    pairs of another set that lead this one's members."""
    from cometbft_tpu.crypto import ed25519
    from cometbft_tpu.types.priv_validator import MockPV
    from cometbft_tpu.types.validator import Validator
    from cometbft_tpu.types.validator_set import ValidatorSet

    privs = [pv for _, pv in share] + [
        MockPV(ed25519.gen_priv_key_from_secret(data.secret(SEED, tag, i)))
        for i in range(len(powers) - len(share))
    ]
    vals = ValidatorSet([
        Validator.new(pv.get_pub_key(), power)
        for pv, power in zip(privs, powers)
    ])
    by_addr = {pv.get_pub_key().address(): pv for pv in privs}
    return vals, [by_addr[v.address] for v in vals.validators]


class World:
    """Two chains of the same heights under one chain id: T's validator
    set, and U's, which shares T's first two validators."""

    def __init__(self, powers):
        self.chain_id = "light-ref"
        self.t = _valset(powers, "T")
        shared = list(zip(self.t[0].validators[:2], self.t[1][:2]))
        self.u = _valset(powers, "U", share=shared)
        self.t_blocks = light_fleet.make_chain(
            self.chain_id, self.t, FIRST, 6, SEED, "T")
        self.u_blocks = light_fleet.make_chain(
            self.chain_id, self.u, FIRST, 6, SEED, "U")
        newest = self.t_blocks[-1].signed_header.header.time
        self.now = type(newest)(newest.seconds + 3600, 0)


@pytest.fixture(scope="module", params=["equal", "unequal"])
def world(request):
    powers = [10] * 8 if request.param == "equal" else \
        [40, 25, 10, 10, 5, 5, 3, 2]
    return World(powers)


def _lanes(world, block, trusted_vals=None):
    """(lanes of the 2/3 prefix, lanes of the trusting prefix) as the
    program walks them, for planting a forgery inside or outside."""
    rec = light_fleet.Recording()
    vals = block.validator_set
    commit = block.signed_header.commit
    vals.verify_commit_light(world.chain_id, commit.block_id, commit.height,
                             commit, backend=rec)
    from cometbft_tpu.types.validator_set import Fraction

    (trusted_vals or vals).verify_commit_light_trusting(
        world.chain_id, commit, Fraction(1, 3), backend=rec)
    return len(rec.calls[0]), len(rec.calls[1])


def _commit_row(block, row, **changes):
    out = light_fleet.copy_commit(block)
    sig = out.signed_header.commit.signatures[row]
    for key, value in changes.items():
        setattr(sig, key, value)
    return out


def _double_vote(world, block):
    """The row behind the first one that a validator of T signed, given
    that validator's address too: T's validator votes twice."""
    rows = block.signed_header.commit.signatures
    first = next(i for i, cs in enumerate(rows)
                 if world.t[0].get_by_address(cs.validator_address)[1])
    return _commit_row(block, first + 1,
                       validator_address=rows[first].validator_address)


def _nil_row(world, block, row):
    """Row ``row`` as a signed precommit for nil."""
    from cometbft_tpu.types import test_util
    from cometbft_tpu.types.block import BlockID
    from cometbft_tpu.types.vote import SIGNED_MSG_TYPE_PRECOMMIT

    header = block.signed_header.header
    vote = test_util.make_vote(
        world.t[1][row], world.chain_id, row, header.height, 0,
        SIGNED_MSG_TYPE_PRECOMMIT, BlockID(), header.time)
    out = light_fleet.copy_commit(block)
    out.signed_header.commit.signatures[row] = vote.to_commit_sig()
    return out


def _absent_row(block, row):
    from cometbft_tpu.types.block import CommitSig

    out = light_fleet.copy_commit(block)
    out.signed_header.commit.signatures[row] = CommitSig.absent()
    return out


def cases(world):
    """{name: (trusted block, untrusted block, now, the class the test's
    author expects)}: the reference and the program must both give it."""
    t, u = world.t_blocks, world.u_blocks
    quorum, trusting = _lanes(world, t[3])
    last = world.t[0].size() - 1
    forge = lambda blk, lane: light_fleet.forge_block(  # noqa: E731
        blk, lane, world.chain_id, SEED)
    out = {
        "adjacent honest": (t[0], t[1], world.now, ref.ACCEPT),
        "skipping honest": (t[0], t[3], world.now, ref.ACCEPT),
        "adjacent forged inside the 2/3 prefix":
            (t[0], forge(t[1], quorum - 1), world.now, ref.INVALID_HEADER),
        "adjacent forged outside the 2/3 prefix":
            (t[0], forge(t[1], last), world.now, ref.ACCEPT),
        "skipping forged inside the trusting prefix":
            (t[0], forge(t[3], 0), world.now, ref.TRUSTING_COMMIT),
        "skipping forged between the two prefixes":
            (t[0], forge(t[3], quorum - 1), world.now, ref.INVALID_HEADER),
        "skipping forged outside both prefixes":
            (t[0], forge(t[3], last), world.now, ref.ACCEPT),
        "adjacent with an absent and a nil precommit":
            (t[0], _nil_row(world, _absent_row(t[1], last), last - 1),
             world.now, ref.ACCEPT),
        "skipping with an absent and a nil precommit":
            (t[0], _nil_row(world, _absent_row(t[3], last), last - 1),
             world.now, ref.ACCEPT),
        "adjacent with too many absent":
            (t[0], _absent_row(_absent_row(_absent_row(t[1], 0), 1), 2),
             world.now, ref.INVALID_HEADER),
        "skipping to a set that shares under 1/3 of the trusted power":
            (t[0], u[3], world.now, None),  # filled below
        "skipping with a double vote":
            (t[0], _double_vote(world, u[3]), world.now, None),
        "adjacent from an expired trusted header":
            (t[0], t[1], type(world.now)(
                t[0].signed_header.header.time.seconds
                + PERIOD_NS // light_fleet.SECOND_NS + 1, 0), ref.EXPIRED),
        "adjacent with a validators-hash break":
            (t[0], u[1], world.now, ref.INVALID_HEADER),
        "skipping to a header from the future":
            (t[0], t[5], type(world.now)(
                t[0].signed_header.header.time.seconds - 20, 0),
             ref.INVALID_HEADER),
    }
    # the two that depend on how much of T's power U's first rows hold
    shared = sum(v.voting_power for v in world.t[0].validators[:2])
    total = world.t[0].total_voting_power()
    name = "skipping to a set that shares under 1/3 of the trusted power"
    out[name] = out[name][:3] + (
        ref.ACCEPT if shared > total // 3 else ref.CANT_BE_TRUSTED,)
    first = max(v.voting_power for v in world.t[0].validators[:2])
    name = "skipping with a double vote"
    out[name] = out[name][:3] + (
        ref.ACCEPT if first > total // 3 else ref.TRUSTING_COMMIT,)
    return out


CASE_NAMES = sorted(cases(World([10] * 8)))


def _reference(world, trusted, untrusted, now):
    plain = lambda blk: light_fleet.plain_block(  # noqa: E731
        blk, world.chain_id)
    return ref.verify(
        plain(trusted), light_fleet.plain_vals(trusted.validator_set),
        plain(untrusted), light_fleet.plain_vals(untrusted.validator_set),
        PERIOD_NS, now.to_unix_ns(), DRIFT_NS,
    )


def _program(trusted, untrusted, now, backend):
    plan = {"trusting_period_ns": PERIOD_NS, "now": now,
            "max_clock_drift_ns": DRIFT_NS}
    return light_fleet.program_verdict(plan, backend, trusted, untrusted)


@pytest.mark.parametrize("name", CASE_NAMES)
def test_the_program_and_the_reference_agree_on_the_cpu_backend(world, name):
    trusted, untrusted, now, want = cases(world)[name]
    cls, why = _reference(world, trusted, untrusted, now)
    assert cls == want, why
    assert _program(trusted, untrusted, now, "cpu") == cls


def test_the_unequal_world_exercises_the_other_branch_of_the_two_cases():
    """With 40 + 25 of 100 in T's first two validators the shared rows
    pass 1/3 on their own; with equal powers they do not."""
    equal, unequal = cases(World([10] * 8)), cases(
        World([40, 25, 10, 10, 5, 5, 3, 2]))
    name = "skipping to a set that shares under 1/3 of the trusted power"
    assert equal[name][3] == ref.CANT_BE_TRUSTED
    assert unequal[name][3] == ref.ACCEPT
    assert equal["skipping with a double vote"][3] == ref.TRUSTING_COMMIT
    assert unequal["skipping with a double vote"][3] == ref.ACCEPT


@pytest.fixture(scope="module")
def remote():
    """A RemoteVerifier on an in-process daemon with the host row
    verifier."""
    from cometbft_tpu.crypto import service as svc
    from cometbft_tpu.crypto.scheduler import VerifyScheduler

    sched = VerifyScheduler(spec="cpu", flush_us=200,
                            row_verifier=svc.host_row_verifier())
    path = "/tmp/cbft-test-lightref-%d.sock" % os.getpid()
    service = svc.VerifyService(sched, "unix://" + path,
                                row_verifier=svc.host_row_verifier())
    sched.start()
    service.start()
    client = svc.RemoteVerifier("unix://" + path, tenant="light-ref",
                                timeout_ms=60_000)
    yield client
    client.close()
    service.stop()
    sched.stop()


@pytest.mark.parametrize("name", CASE_NAMES)
def test_the_program_and_the_reference_agree_through_a_remote_verifier(
        world, remote, name):
    trusted, untrusted, now, _ = cases(world)[name]
    marks = light_fleet.client_failures(remote.stats())
    cls, _ = _reference(world, trusted, untrusted, now)
    assert _program(trusted, untrusted, now, remote) == cls
    assert light_fleet.client_failures(remote.stats()) == marks, (
        "the client's local CPU answered"
    )
