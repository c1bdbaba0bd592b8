"""Seeded chains whose validator set moves at every height: what a node
syncing a chain with staking is served.

``chain.py``'s chain with a schedule of validator updates on top, all
from ``seed``, delivered the way upstream's e2e testnets deliver theirs
(``[validator_update.<height>]`` in the manifest, ``val:<base64
key>!<power>`` transactions to the persistent kvstore, which returns
them from EndBlock; an update delivered at H acts at H + 2):

* every height carries ``power_changes`` transactions that each set one
  validator the application knows to another power of ``power_range``
  (so from height 3 on no two consecutive heights share a
  ``validators_hash``);
* every ``seat_every``-th height carries a seat replacement: one
  validator to power 0 and one new key at ``joiner_power`` (the set
  keeps its size, as a chain at its ``max_validators`` does);
* no validator is touched twice in one block (UpdateWithChangeSet
  refuses a duplicate address), and a joiner's power is left alone for
  ``seat_every`` heights after it joins;
* a joiner's key is the first of its seeded candidates whose address
  starts with two 0 bits: among its equals in power it sits in the
  first quarter of the set's order, inside the quorum walk, which is
  where a joiner costs a syncing node anything (and where the third
  forged chain of the cell's warm-up needs one);
* beside them ``txs_per_block`` key=value transactions of ``tx_bytes``.

Every precommit carries its validator's own seeded timestamp
(``chain.vote_time`` by commit index). The blocks are made by the
program's own ``State.make_block`` and applied by a ``BlockExecutor`` on
the cpu backend with a ``PersistentKVStoreApplication`` behind it that
got InitChain with the genesis set. ``signers[h]`` / ``valsets[h]`` are
V(h), the set that commits height h, in its own order.
"""

from __future__ import annotations

import base64
import random
from typing import Dict, List

from cometbft_tpu.abci import types as abci
from cometbft_tpu.abci.client import LocalClient
from cometbft_tpu.abci.kvstore import PersistentKVStoreApplication
from cometbft_tpu.crypto import ed25519
from cometbft_tpu.libs.db import MemDB
from cometbft_tpu.proto.keys import pub_key_to_proto
from cometbft_tpu.proxy import AppConnConsensus
from cometbft_tpu.state import make_genesis_state
from cometbft_tpu.state.execution import BlockExecutor
from cometbft_tpu.state.store import Store
from cometbft_tpu.types.block import Block, Commit
from cometbft_tpu.types.priv_validator import MockPV

from benchmark.lib import chain as chainlib
from benchmark.lib import data

VAL_PREFIX = b"val:"


class ChurnChain(chainlib.Chain):
    """``chain.Chain`` with the set that commits each height."""

    def __init__(self, chain_id: str, seed: int, vals, privs, doc):
        super().__init__(chain_id, seed, vals, privs, doc)
        self.signers: Dict[int, list] = {}
        self.valsets: Dict[int, object] = {}
        # {height: (address of the seat that left, of the one that came)}
        self.seats: Dict[int, tuple] = {}

    def add_churn(self, block, block_id, kv_txs: int) -> None:
        self.add(block, block_id, kv_txs)
        self.records[-1].update(
            next_validators_hash=bytes(block.header.next_validators_hash),
            val_txs=[bytes(tx) for tx in block.data.txs
                     if bytes(tx).startswith(VAL_PREFIX)],
        )


def val_tx(pub_key: bytes, power: int) -> bytes:
    return VAL_PREFIX + base64.b64encode(pub_key) + b"!%d" % power


def init_chain_request(doc) -> "abci.RequestInitChain":
    """InitChain with the genesis set, as the handshake sends it
    (consensus/replay.go ReplayBlocks): the persistent kvstore refuses to
    remove a validator it was never told of."""
    return abci.RequestInitChain(
        time=doc.genesis_time,
        chain_id=doc.chain_id,
        validators=[
            abci.ValidatorUpdate(pub_key_to_proto(gv.pub_key), gv.power)
            for gv in doc.validators
        ],
        initial_height=doc.initial_height,
    )


def joiner(seed: int, height: int) -> MockPV:
    k = 0
    while True:
        pv = MockPV(ed25519.gen_priv_key_from_secret(
            data.secret(seed, "churn-joiner", height, k)
        ))
        if pv.get_pub_key().address()[0] < 0x40:
            return pv
        k += 1


def updates_at(seed: int, height: int, members: Dict[bytes, int],
               joined: Dict[bytes, int], schedule: dict):
    """The validator updates block ``height`` carries. ``members``
    {address: power} is what the application knows before the block,
    ``joined`` {address: height} when each joiner came. → ([(address,
    new power)], joiner's signer or None); a seat that leaves has new
    power 0 and comes last."""
    rng = random.Random(
        int.from_bytes(data.secret(seed, "churn", height), "big")
    )
    lo, hi = schedule["power_range"]
    every = int(schedule["seat_every"])
    replace = every > 0 and height % every == 0
    settled = sorted(
        a for a in members if joined.get(a, -every) + every <= height
    )
    touched = rng.sample(settled, int(schedule["power_changes"]) + replace)
    leaver = touched.pop() if replace else None
    out = [
        (a, rng.choice([p for p in range(lo, hi + 1) if p != members[a]]))
        for a in touched
    ]
    if leaver is not None:
        out.append((leaver, 0))
    return out, (joiner(seed, height) if replace else None)


def build(chain_id: str, n_validators: int, n_blocks: int,
          txs_per_block: int, tx_bytes: int, schedule: dict,
          seed: int) -> ChurnChain:
    """``n_blocks`` blocks a node can apply and the one after, whose
    LastCommit verifies the last of them."""
    vals, privs = data.make_valset(n_validators, seed, "sync-churn")
    doc = chainlib.genesis_doc(vals, chain_id)
    chain = ChurnChain(chain_id, seed, vals, privs, doc)
    by_addr = {pv.get_pub_key().address(): pv for pv in privs}
    members = {v.address: v.voting_power for v in vals.validators}
    joined: Dict[bytes, int] = {}
    state = make_genesis_state(doc)
    store = Store(MemDB())
    store.save(state)
    client = LocalClient(PersistentKVStoreApplication())
    client.start()
    client.init_chain_sync(init_chain_request(doc))
    executor = BlockExecutor(store, AppConnConsensus(client))
    last_commit = Commit(height=0, round=0)
    try:
        for h in range(1, n_blocks + 2):
            signers = [by_addr[v.address] for v in state.validators.validators]
            chain.signers[h] = signers
            chain.valsets[h] = state.validators.copy()
            updates, new_pv = updates_at(seed, h, members, joined, schedule)
            txs = chainlib.make_txs(seed, h, txs_per_block, tx_bytes)
            for addr, power in updates:
                txs.append(val_tx(by_addr[addr].get_pub_key().bytes(), power))
                if power:
                    members[addr] = power
                else:
                    del members[addr]
            if new_pv is not None:
                addr = new_pv.get_pub_key().address()
                by_addr[addr] = new_pv
                members[addr] = int(schedule["joiner_power"])
                joined[addr] = h
                chain.seats[h] = (updates[-1][0], addr)
                txs.append(val_tx(new_pv.get_pub_key().bytes(),
                                  members[addr]))
            proposer = state.validators.validators[h % n_validators].address
            block, _ = state.make_block(h, txs, last_commit, [], proposer)
            block_id = chainlib.block_id_of(block)
            chain.add_churn(block, block_id, txs_per_block)
            if h > n_blocks:
                break
            last_commit = chainlib.make_commit(
                signers, block_id, h, seed, chain_id
            )
            chain.commits[h] = last_commit
            state, _ = executor.apply_block(state, block_id, block)
    finally:
        client.stop()
    return chain


def fork(chain: ChurnChain, forged_height: int, lane: int,
         upto: int) -> ChurnChain:
    """``chain.fork`` where every height has its own signers: heights
    1..forged_height as they are, then block forged_height + 1 carrying
    the commit with lane ``lane`` signed by somebody else, and every
    block up to ``upto`` re-made over the changed ids and committed by
    that height's whole set."""
    out = ChurnChain(chain.chain_id, chain.seed, chain.vals, chain.privs,
                     chain.doc)
    out.signers, out.valsets, out.seats = (chain.signers, chain.valsets,
                                           chain.seats)
    keep = forged_height + 1
    out.blocks = chain.blocks[:keep]
    out.block_ids = chain.block_ids[:keep]
    out.encoded = chain.encoded[:keep]
    out.records = chain.records[:keep]
    out.commits = {h: chain.commits[h] for h in range(1, forged_height)}
    last_commit = chainlib.forged_commit(chain, forged_height, lane)
    out.commits[forged_height] = last_commit
    for h in range(forged_height + 1, min(upto, chain.top) + 1):
        block = Block.decode(chain.blocks[h].encode())
        block.last_commit = last_commit
        block.header.last_commit_hash = last_commit.hash()
        block.header.last_block_id = out.block_ids[h - 1]
        block_id = chainlib.block_id_of(block)
        out.add_churn(block, block_id, chain.records[h]["new_keys"])
        last_commit = chainlib.make_commit(
            chain.signers[h], block_id, h, chain.seed, chain.chain_id
        )
        out.commits[h] = last_commit
    return out


def seat_lane(chain: ChurnChain, height: int) -> int:
    """The commit index, at ``height``, of the newest seat that joined
    far enough below it to sign there."""
    came = max(h for h in chain.seats if h + 2 <= height)
    addr = chain.seats[came][1]
    return [v.address for v in chain.valsets[height].validators].index(addr)
