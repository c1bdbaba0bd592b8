"""The plain reference for a chain whose validator set moves
(``benchmark/lib/churn_reference.py``) on its own: address, leaf and
root written out from their specifications against the program's; the
update rule (what the persistent kvstore returns, what the set does
with it, two heights on); and the replay of the toy chain and its
forks, in OpenSSL's arithmetic and in pure Python integers."""

import base64

import pytest

from benchmark.lib import chain as chainlib
from benchmark.lib import churn_chain, churn_reference as ref
from benchmark.lib import reference
from tests.conftest import blocksync_churn_toy

SEED = 2_150_000_311
SCHEDULE = blocksync_churn_toy()[0]["schedule"]


@pytest.fixture(scope="module")
def chain():
    return churn_chain.build("toy-churn", 7, 24, 3, 64, SCHEDULE, SEED)


def _key(i: int) -> bytes:
    return bytes([i]) * 32


def test_address_leaf_and_root_are_the_programs():
    from cometbft_tpu.crypto import ed25519, merkle
    from cometbft_tpu.types.validator import Validator

    for i, power in ((1, 10), (2, 1), (3, 300), (4, 2**40)):
        pk = ed25519.PubKeyEd25519(_key(i))
        assert ref.address_of(_key(i)) == pk.address()
        assert (ref.simple_validator(_key(i), power)
                == Validator.new(pk, power).bytes())
    for n in (0, 1, 2, 3, 5, 7, 8, 150):
        leaves = [bytes([k]) * (k % 5 + 1) for k in range(n)]
        assert ref.merkle_root(leaves) == merkle.hash_from_byte_slices(leaves)


def test_a_set_is_ordered_by_power_then_address_and_hashed_as_the_programs(
        chain):
    for h in (1, 3, 9, 25):
        vals = chain.valsets[h]
        plain = chainlib.plain_vals(vals)
        again = ref.make_set(list(reversed(plain["rows"])))
        assert again["rows"] == plain["rows"]
        assert again["hash"] == plain["hash"] == vals.hash()


def test_a_val_transaction_is_parsed_as_the_kvstore_parses_it():
    tx = churn_chain.val_tx(_key(7), 12)
    assert tx == b"val:" + base64.b64encode(_key(7)) + b"!12"
    assert ref.parse_val_tx(tx) == (_key(7), 12)
    assert ref.parse_val_tx(churn_chain.val_tx(_key(7), 0)) == (_key(7), 0)
    for bad in (b"val:AAAA", b"val:!!x", b"val:AAA!3", b"key=value"):
        assert ref.parse_val_tx(bad) is None


def test_end_block_returns_what_the_application_accepted():
    known = {_key(1), _key(2)}
    txs = [churn_chain.val_tx(_key(1), 9), churn_chain.val_tx(_key(5), 0),
           b"val:broken", churn_chain.val_tx(_key(3), 10),
           churn_chain.val_tx(_key(2), 0)]
    # removing a validator the application never heard of is refused
    assert ref.end_block_updates(known, txs) == [
        (_key(1), 9), (_key(3), 10), (_key(2), 0)]
    assert known == {_key(1), _key(3)}


def test_the_update_rule_is_upstreams():
    rows = [(ref.address_of(_key(i)), 10, _key(i)) for i in (1, 2, 3)]
    vals = ref.make_set(rows)
    assert ref.apply_updates(vals, []) is vals
    got = ref.apply_updates(vals, [(_key(2), 12), (_key(1), 0),
                                   (_key(4), 10)])
    assert [r[2] for r in got["rows"]][0] == _key(2)
    assert {r[2] for r in got["rows"]} == {_key(2), _key(3), _key(4)}
    assert got["hash"] != vals["hash"]
    for bad, why in (
        ([(_key(1), 9), (_key(1), 8)], "duplicate"),
        ([(_key(9), 0)], "failed to find"),
        ([(_key(1), -1)], "negative"),
        ([(_key(1), 0), (_key(2), 0), (_key(3), 0)], "empty set"),
    ):
        with pytest.raises(ValueError, match=why):
            ref.apply_updates(vals, bad)


def test_the_replay_derives_every_set_two_heights_after_its_updates(chain):
    got = ref.replay(chainlib.plain_vals(chain.vals), chain.records)
    assert got["refused"] is None and all(got["accepted"])
    assert sorted(got["states"]) == list(range(chain.top))
    sets = got["sets"]
    assert sets[1] == sets[2]
    for h in range(1, chain.top + 1):
        assert sets[h]["rows"] == chainlib.plain_vals(chain.valsets[h])["rows"]
        assert sets[h]["hash"] == chain.valsets[h].hash()
    for h in range(1, chain.top - 1):
        # block h's updates are exactly what separates V(h + 1), V(h + 2)
        delivered = {ref.address_of(k): p for k, p in
                     map(ref.parse_val_tx, chain.records[h]["val_txs"])}
        before = {r[0]: r[1] for r in sets[h + 1]["rows"]}
        after = {r[0]: r[1] for r in sets[h + 2]["rows"]}
        moved = {a: after.get(a, 0) for a in set(before) | set(after)
                 if before.get(a) != after.get(a)}
        assert moved == delivered and moved
        state = got["states"][h]
        assert state["validators"] == sets[h + 1]["rows"]
        assert state["next_validators"] == sets[h + 2]["rows"]
        assert state["validators_hash"] == sets[h + 1]["hash"]
        assert state["app_hash"] == ref.sync_reference.app_hash(3 * h)


def test_the_replay_refuses_a_header_that_names_another_set(chain):
    genesis = chainlib.plain_vals(chain.vals)
    for key, why in (("validators_hash", "another validator set"),
                     ("next_validators_hash", "another next validator set")):
        records = list(chain.records[:9])
        records[6] = dict(records[6], **{key: chain.records[5][key]})
        got = ref.replay(genesis, records)
        assert got["refused"] == (6, why)
        assert max(got["states"]) == 5


def test_the_replay_refuses_each_fork_where_its_forged_precommit_counts(
        chain):
    genesis = chainlib.plain_vals(chain.vals)
    seat = churn_chain.seat_lane(chain, 7)
    for block, lane, refused, inside in (
        (5, 3, 5, "in the quorum prefix"),
        (7, seat, 7, "in the quorum prefix"),
        (9, 6, 10, "in the LastCommit"),
    ):
        fork = churn_chain.fork(chain, block, lane, block + 6)
        got = ref.replay(genesis, fork.records)
        assert got["refused"][0] == refused and inside in got["refused"][1]
        assert got["accepted"][:refused - 1] == [True] * (refused - 1)
        assert not any(got["accepted"][refused - 1:])
        # in pure Python integers too (RFC 8032 5.1.7)
        slow = ref.replay(
            genesis, fork.records[:refused + 2],
            verify_many=lambda items: [reference.verify_py(*i)
                                       for i in items])
        assert slow["refused"] == got["refused"]


def test_the_light_walk_is_made_under_the_heights_own_powers(chain):
    """The quorum prefix of commit h under V(h) is not the one the
    genesis powers would give: a reference that kept the first set
    would walk other lanes."""
    got = ref.replay(chainlib.plain_vals(chain.vals), chain.records)
    differs = 0
    for h in range(3, chain.top):
        rows = got["sets"][h]["rows"]
        assert [r[0] for r in rows] == [
            v.address for v in chain.valsets[h].validators]
        differs += rows != got["sets"][1]["rows"]
    assert differs == chain.top - 3
