"""A launch's pack around the challenge call (PR 37): the resident,
indexed and keyed packs held bit for bit to a plain per-lane oracle,
on both sides of the native gate, and the wire ledger's books of the
call's lanes by path."""

import hashlib
import random

import numpy as np
import pytest

from cometbft_tpu import native
from cometbft_tpu.crypto import wire as wirelib
from cometbft_tpu.crypto.tpu import ed25519_batch as eb

L = 2**252 + 27742317777372353535851937790883648493


def _lanes(n, mixed, seed):
    """(pk u8[n,32], msgs, sigs): random rows; ``mixed`` spreads over
    the launch None signatures, None messages, 63- and 65-byte
    signatures and s >= L (the top byte of s set)."""
    rng = random.Random(seed)
    pk = np.frombuffer(rng.randbytes(32 * n), np.uint8).reshape(n, 32)
    msgs, sigs = [], []
    for i in range(n):
        s = bytearray(rng.randbytes(64))
        s[63] &= 0x0F  # s < 2^252 < L
        sig, msg = bytes(s), rng.randbytes(rng.randrange(0, 200))
        if mixed:
            kind = i % 7
            if kind == 1:
                sig = None
            elif kind == 2:
                msg = None
            elif kind == 3:
                sig = sig[:63]
            elif kind == 4:
                sig = sig + b"\x00"
            elif kind == 5:
                sig = sig[:63] + b"\xff"  # s >= L
        msgs.append(msg)
        sigs.append(sig)
    return pk, msgs, sigs


def _oracle(pk, msgs, sigs):
    """(R, S, h rows u8[n,32] each, valid) lane by lane: a lane is valid
    where its signature is 64 bytes, its message is there and s < L;
    an invalid lane's rows are zero but for the R and S of a 64-byte
    signature with s >= L."""
    n = len(msgs)
    rows = np.zeros((3, n, 32), np.uint8)
    valid = np.zeros(n, bool)
    for i in range(n):
        s, m = sigs[i], msgs[i]
        if s is None or m is None or len(s) != 64:
            continue
        rows[0, i] = np.frombuffer(s[:32], np.uint8)
        rows[1, i] = np.frombuffer(s[32:], np.uint8)
        if int.from_bytes(s[32:], "little") >= L:
            continue
        valid[i] = True
        h = int.from_bytes(
            hashlib.sha512(s[:32] + pk[i].tobytes() + m).digest(), "little"
        ) % L
        rows[2, i] = np.frombuffer(h.to_bytes(32, "little"), np.uint8)
    return rows, valid


def _words(rows):
    """u8[k,n,32] -> u32[8k,n]: each row's little-endian words."""
    return np.concatenate([r.view("<u4").T for r in rows], axis=0)


# 8 lanes: under the native gate (the hashlib loop); 300: over it
@pytest.mark.parametrize("n", [8, 300])
@pytest.mark.parametrize("mixed", [False, True], ids=["whole", "mixed"])
class TestPackParity:
    def test_prepare_rsh(self, n, mixed):
        pk, msgs, sigs = _lanes(n, mixed, seed=n)
        rows, valid = _oracle(pk, msgs, sigs)
        rsh, got_valid = eb._prepare_rsh(pk, msgs, sigs)
        assert rsh.dtype == np.uint32 and rsh.shape == (24, n)
        assert (rsh == _words(rows)).all()
        assert (got_valid == valid).all()

    def test_prepare_rsh_compact(self, n, mixed):
        pk, msgs, sigs = _lanes(n, mixed, seed=n + 1)
        rows, valid = _oracle(pk, msgs, sigs)
        rsh, got_valid = eb._prepare_rsh_compact(pk, msgs, sigs)
        want = np.concatenate([r.T for r in rows], axis=0)
        assert rsh.dtype == np.uint8 and (rsh == want).all()
        assert (got_valid == valid).all()

    def test_prepare_batch_compact(self, n, mixed):
        pk, msgs, sigs = _lanes(n, mixed, seed=n + 2)
        # the keyed route has every message; absent lanes are malformed
        # ones there: a None signature is a 0-byte one, a short key too
        msgs = [b"" if m is None else m for m in msgs]
        sigs = [b"" if s is None else s for s in sigs]
        keys = [pk[i].tobytes() for i in range(n)]
        if mixed:
            keys[6 % n] = keys[6 % n][:31]
        rows, valid = _oracle(pk, msgs, sigs)
        a_rows = pk.copy()
        for i, k in enumerate(keys):
            if len(k) != 32:
                rows[:, i] = 0
                a_rows[i] = 0
                valid[i] = False
            elif len(sigs[i]) != 64:
                a_rows[i] = 0
        wire, got_valid = eb.prepare_batch_compact(keys, msgs, sigs)
        want = np.concatenate([a_rows.T] + [r.T for r in rows], axis=0)
        assert wire.shape == (128, n) and (wire == want).all()
        assert (got_valid == valid).all()


@pytest.fixture
def ledger():
    led = wirelib.WireLedger()
    prev = wirelib.set_default_ledger(led)
    try:
        yield led
    finally:
        wirelib.set_default_ledger(prev)


@pytest.mark.parametrize(
    "n,lib,path",
    [
        (eb._NATIVE_CHALLENGE_MIN - 1, True, "python"),
        (3 * native._CHALLENGE_GRAIN, True, "native"),
        (3 * native._CHALLENGE_GRAIN, False, "python"),
    ],
    ids=["under-gate", "over-gate", "no-library"],
)
def test_challenge_books(ledger, monkeypatch, n, lib, path):
    """The wire ledger books a challenge call's lanes by the path that
    hashed them and the threads it ran on; a missing or stale library
    sends the lanes to the hashlib loop, on the caller's thread."""
    if lib and native.load_challenges() is None:
        pytest.skip("native challenges unavailable")
    if not lib:
        monkeypatch.setattr(native, "load_challenges", lambda: None)
    pk, msgs, sigs = _lanes(n, False, seed=3)
    rows, valid = _oracle(pk, msgs, sigs)
    sig_arr = np.frombuffer(b"".join(sigs), np.uint8).reshape(n, 64)
    h = eb._challenge_scalars(pk, sig_arr, msgs, valid)
    assert (h == rows[2]).all()
    snap = ledger.snapshot()
    threads = native.challenge_threads(n) if path == "native" else 1
    assert snap["challenge_calls"] == 1
    assert snap["challenge_lanes"] == {path: n}
    assert snap["challenge_threads"] == threads


def test_challenge_threads_from_lanes(monkeypatch):
    """One thread a grain of lanes, the caller's below two grains, at
    most the cores the process may run on."""
    grain = native._CHALLENGE_GRAIN
    monkeypatch.setattr(native.os, "sched_getaffinity", lambda pid: {0, 1, 2})
    assert [native.challenge_threads(k) for k in (
        0, 1, grain, 2 * grain - 1, 2 * grain, 3 * grain, 100 * grain
    )] == [1, 1, 1, 1, 2, 3, 3]
