"""Seeded chains of loaded blocks: what a syncing node is served.

From ``seed``: a validator set, ``n_blocks + 1`` blocks of
``txs_per_block`` distinct ``key=value`` transactions of ``tx_bytes``
bytes, each committed by every validator. Every precommit carries its
validator's OWN timestamp, drawn from the seed inside the height's
second, and a block's time is the weighted median of its LastCommit's
(``data.timestamp()`` gives a commit one timestamp; nothing here uses
it). Each block is also kept encoded as the ``BlockResponse`` a peer
sends, and flattened into the plain record the reference reads
(``sync_reference.py``: ints, bytes and tuples, nothing of the program's
types).

The blocks are made by the program's own ``State.make_block`` and
applied by a ``BlockExecutor`` on the cpu backend with a kvstore behind
it, so that app hashes and result hashes line up: they are the inputs
the served entry point takes, as ``data.py``'s commits are. The
program's modules are imported when this one is, on the thread that
loads the generator: the harness builds the plan on a thread of its own
beside the node's start, and two threads that import ``cometbft_tpu``'s
packages for the first time at once can meet half-made modules
(``KeyError: 'cometbft_tpu.types'``, chip run, PR 32).

``fork`` gives what a byzantine peer serves: the same chain from one
height on, with one precommit of one commit signed by somebody else and
every later block re-committed over the changed block ids.
"""

from __future__ import annotations

import hashlib
from typing import Dict, List, Optional

from cometbft_tpu.abci.client import LocalClient
from cometbft_tpu.abci.kvstore import KVStoreApplication
from cometbft_tpu.blocksync.messages import (
    BlockResponse, encode_blocksync_message,
)
from cometbft_tpu.crypto import ed25519
from cometbft_tpu.libs.db import MemDB
from cometbft_tpu.proto.gogo import Timestamp
from cometbft_tpu.proxy import AppConnConsensus
from cometbft_tpu.state import make_genesis_state
from cometbft_tpu.state.execution import BlockExecutor
from cometbft_tpu.state.store import Store
from cometbft_tpu.types import test_util
from cometbft_tpu.types.block import Block, BlockID, Commit
from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator
from cometbft_tpu.types.part_set import BLOCK_PART_SIZE_BYTES
from cometbft_tpu.types.vote import SIGNED_MSG_TYPE_PRECOMMIT

from benchmark.lib import data

GENESIS_SECONDS = 1_700_000_000
# a vote's timestamp lies in the first half of its height's second, so
# the medians of consecutive heights are strictly increasing
VOTE_SPREAD_NS = 500_000_000
FLAG_ABSENT, FLAG_COMMIT, FLAG_NIL = 1, 2, 3


def make_txs(seed: int, height: int, n: int, size: int) -> List[bytes]:
    """``n`` distinct ``key=value`` transactions of exactly ``size``
    bytes: the key names seed, height and index, the value is filler
    from a seeded stream (loadtime's payload is padding too)."""
    out = []
    for i in range(n):
        key = f"s{seed:x}h{height}i{i}=".encode()
        filler = hashlib.shake_128(
            data.secret(seed, "tx", height, i)
        ).hexdigest((size - len(key) + 1) // 2).encode()
        out.append(key + filler[: size - len(key)])
    return out


def vote_time(seed: int, height: int, idx: int):
    nanos = int.from_bytes(
        data.secret(seed, "vote-time", height, idx)[:8], "big"
    ) % VOTE_SPREAD_NS
    return Timestamp(GENESIS_SECONDS + height, nanos)


def make_commit(privs, block_id, height: int, seed: int, chain_id: str):
    """Every validator's precommit for ``block_id``, each with its own
    seeded timestamp."""
    sigs = [
        test_util.make_vote(
            pv, chain_id, i, height, 0, SIGNED_MSG_TYPE_PRECOMMIT, block_id,
            vote_time(seed, height, i),
        ).to_commit_sig()
        for i, pv in enumerate(privs)
    ]
    return Commit(height=height, round=0, block_id=block_id, signatures=sigs)


def genesis_doc(vals, chain_id: str):
    return GenesisDoc(
        genesis_time=Timestamp(GENESIS_SECONDS, 0),
        chain_id=chain_id,
        validators=[
            GenesisValidator(v.address, v.pub_key, v.voting_power, "")
            for v in vals.validators
        ],
    )


def block_id_of(block):
    parts = block.make_part_set(BLOCK_PART_SIZE_BYTES)
    return BlockID(block.hash(), parts.header())


def encode_response(block) -> bytes:
    """The bytes a peer puts on the blocksync channel for this block."""
    return encode_blocksync_message(BlockResponse(block))


def plain_vals(vals) -> dict:
    return {
        "hash": vals.hash(),
        "rows": [(v.address, v.voting_power, v.pub_key.bytes())
                 for v in vals.validators],
    }


def plain_id(block_id) -> tuple:
    psh = block_id.part_set_header
    return (bytes(block_id.hash), int(psh.total), bytes(psh.hash))


def plain_block(block, block_id, chain_id: str, new_keys: int) -> dict:
    """One height as the reference reads it. ``new_keys``: how many keys
    its transactions add to the kvstore."""
    commit = block.last_commit
    rows = []
    for idx, cs in enumerate(commit.signatures):
        if cs.is_absent():
            rows.append((FLAG_ABSENT, b"", b""))
            continue
        flag = FLAG_COMMIT if cs.for_block() else FLAG_NIL
        rows.append((flag, bytes(cs.signature),
                     commit.vote_sign_bytes(chain_id, idx)))
    return {
        "height": block.header.height,
        "id": plain_id(block_id),
        "last_block_id": plain_id(block.header.last_block_id),
        "validators_hash": bytes(block.header.validators_hash),
        "new_keys": new_keys,
        "last_commit": {
            "height": commit.height,
            "block_id": plain_id(commit.block_id),
            "rows": rows,
        },
    }


class Chain:
    """Heights 1..n_blocks + 1; index ``h`` of every list is height
    ``h`` (index 0 unused). ``commits[h]`` is the commit FOR height h,
    which block h + 1 carries as its LastCommit."""

    def __init__(self, chain_id: str, seed: int, vals, privs, doc):
        self.chain_id = chain_id
        self.seed = seed
        self.vals = vals
        self.privs = privs
        self.doc = doc
        self.blocks: List[Optional[object]] = [None]
        self.block_ids: List[Optional[object]] = [None]
        self.encoded: List[bytes] = [b""]
        self.records: List[Optional[dict]] = [None]
        self.commits: Dict[int, object] = {}

    @property
    def top(self) -> int:
        return len(self.blocks) - 1

    def add(self, block, block_id, new_keys: int) -> None:
        self.blocks.append(block)
        self.block_ids.append(block_id)
        self.encoded.append(encode_response(block))
        self.records.append(
            plain_block(block, block_id, self.chain_id, new_keys)
        )


def build(chain_id: str, n_validators: int, n_blocks: int,
          txs_per_block: int, tx_bytes: int, seed: int) -> Chain:
    """``n_blocks`` blocks a node can apply and the one after, whose
    LastCommit verifies the last of them."""
    vals, privs = data.make_valset(n_validators, seed, "sync-apply")
    doc = genesis_doc(vals, chain_id)
    chain = Chain(chain_id, seed, vals, privs, doc)
    state = make_genesis_state(doc)
    store = Store(MemDB())
    store.save(state)
    client = LocalClient(KVStoreApplication())
    client.start()
    executor = BlockExecutor(store, AppConnConsensus(client))
    last_commit = Commit(height=0, round=0)
    try:
        for h in range(1, n_blocks + 2):
            proposer = state.validators.validators[h % n_validators].address
            txs = make_txs(seed, h, txs_per_block, tx_bytes)
            block, _ = state.make_block(h, txs, last_commit, [], proposer)
            block_id = block_id_of(block)
            chain.add(block, block_id, len(txs))
            if h > n_blocks:
                break
            last_commit = make_commit(privs, block_id, h, seed, chain_id)
            chain.commits[h] = last_commit
            state, _ = executor.apply_block(state, block_id, block)
    finally:
        client.stop()
    return chain


def forged_commit(chain: Chain, height: int, lane: int):
    """The commit for ``height`` with lane ``lane`` signed by somebody
    else: a precommit "from" that validator which its key did not sign."""
    # through the wire and back: a fresh object, no cached hash
    commit = Commit.decode(chain.commits[height].encode())
    forger = ed25519.gen_priv_key_from_secret(
        data.secret(chain.seed, "forger")
    )
    msg = commit.vote_sign_bytes(chain.chain_id, lane)
    commit.signatures[lane].signature = forger.sign(msg)
    return commit


def fork(chain: Chain, forged_height: int, lane: int, upto: int) -> Chain:
    """What a byzantine peer serves: heights 1..forged_height as they
    are, then block forged_height + 1 carrying the forged commit, and
    every block up to ``upto`` re-made over the changed ids and
    committed by every validator, so that only the one forged precommit
    is wrong with this chain."""
    out = Chain(chain.chain_id, chain.seed, chain.vals, chain.privs,
                chain.doc)
    keep = forged_height + 1
    out.blocks = chain.blocks[:keep]
    out.block_ids = chain.block_ids[:keep]
    out.encoded = chain.encoded[:keep]
    out.records = chain.records[:keep]
    out.commits = {h: chain.commits[h] for h in range(1, forged_height)}
    last_commit = forged_commit(chain, forged_height, lane)
    out.commits[forged_height] = last_commit
    for h in range(forged_height + 1, min(upto, chain.top) + 1):
        block = Block.decode(chain.blocks[h].encode())
        block.last_commit = last_commit
        block.header.last_commit_hash = last_commit.hash()
        block.header.last_block_id = out.block_ids[h - 1]
        block_id = block_id_of(block)
        out.add(block, block_id, chain.records[h]["new_keys"])
        last_commit = make_commit(
            chain.privs, block_id, h, chain.seed, chain.chain_id
        )
        out.commits[h] = last_commit
    return out
