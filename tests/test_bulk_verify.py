"""The batch-verifier boundary's bulk entry (crypto/batch.py).

``verify_many(items)`` and ``add`` x n + ``verify`` are one
implementation on the tpu backend and must answer alike: the same
``(ok, mask)``, the same ``host_lanes`` / ``device_lanes``, and the CPU
verifier's verdict on every lane. Then the mechanism a supervised
device dispatch rests on: a one-curve flush reaches the backend without
a single ``add`` and is booked as such; a duck-typed verifier that has
only ``add`` / ``verify`` is still driven lane by lane.

CPU platform: counts and verdicts only.
"""

import pytest

from cometbft_tpu.crypto import PubKey
from cometbft_tpu.crypto import batch as cryptobatch
from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto import secp256k1 as secp
from cometbft_tpu.crypto import sr25519 as sr
from cometbft_tpu.crypto.batch import (
    BackendSpec,
    CPUBatchVerifier,
    TPUBatchVerifier,
)

N = 12
FLOOR = 4  # ed25519 lanes from here up go to the device kernel
L = 2**252 + 27742317777372353535851937790883648493


class _OddKey(PubKey):
    """A key of no batch curve: verified serially, in place."""

    def __init__(self, tag: bytes):
        self._tag = tag

    def bytes(self) -> bytes:
        return self._tag

    def type(self) -> str:
        return "test/odd"

    def verify_signature(self, msg: bytes, sig: bytes) -> bool:
        return sig == self._tag + msg


def _ed_lane(i: int):
    k = ed.gen_priv_key_from_secret(b"bulk-%d" % i)
    m = b"bulk lane %d" % i
    return k.pub_key(), m, k.sign(m)


def _ed_items(n: int = N):
    return [_ed_lane(i) for i in range(n)]


def _forged(items, at):
    pk, m, s = items[at]
    items[at] = (pk, m, s[:10] + bytes([s[10] ^ 1]) + s[11:])
    return items


def _short_key(items, at):
    _, m, s = items[at]
    pk = ed.PubKeyEd25519(b"\x00" * 32)
    pk._bytes = b"\x07" * 31  # the constructor refuses it: a decoded lane might not
    items[at] = (pk, m, s)
    return items


def _short_sig(items, at):
    pk, m, s = items[at]
    items[at] = (pk, m, s[:63])
    return items


def _s_not_below_l(items, at):
    pk, m, s = items[at]
    s_int = int.from_bytes(s[32:], "little") + L
    items[at] = (pk, m, s[:32] + s_int.to_bytes(32, "little"))
    return items


def _mixed():
    items = _ed_items(6)
    for i in range(2):
        k = secp.gen_priv_key_from_secret(b"bulk-secp-%d" % i)
        m = b"secp lane %d" % i
        items.insert(2 * i + 1, (k.pub_key(), m, k.sign(m)))
    k = sr.gen_priv_key_from_secret(b"bulk-sr")
    items.insert(4, (k.pub_key(), b"sr lane", k.sign(b"sr lane")))
    items.append((_OddKey(b"odd"), b"odd lane", b"odd" + b"odd lane"))
    items.append((_OddKey(b"odd"), b"odd lane", b"not it"))
    return _forged(items, 0)


# name -> (items, the ed25519 floor): above FLOOR the ed lanes take the
# device kernel, below 1,000 the host pool; the other curves stay on the
# host either way (their kernels are test_tpu_secp's and test_tpu_sr25519's)
CASES = {
    "ed25519-above-floor": (_ed_items, FLOOR),
    "ed25519-below-floor": (_ed_items, 1000),
    "mixed-curves-ed-on-device": (_mixed, FLOOR),
    "mixed-curves-all-on-host": (_mixed, 1000),
    "one-unknown-curve": (
        lambda: [(_OddKey(b"k"), b"m", b"km"), (_OddKey(b"k"), b"m", b"x")],
        FLOOR,
    ),
    "empty": (list, FLOOR),
}
for _floor_name, _floor in (("device", FLOOR), ("host", 1000)):
    for _name, _spoil in (("short-key", _short_key), ("short-sig", _short_sig),
                          ("s-not-below-L", _s_not_below_l)):
        CASES[f"{_name}-{_floor_name}"] = (
            lambda spoil=_spoil: spoil(_ed_items(), 5), _floor)
    for _where, _at in (("first", 0), ("middle", N // 2), ("last", N - 1)):
        CASES[f"forged-{_where}-{_floor_name}"] = (
            lambda at=_at: _forged(_ed_items(), at), _floor)


def _tpu(floor):
    return TPUBatchVerifier(
        min_batch=floor, secp_min_batch=1000, slow_curve_min_batch=1000
    )


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_bulk_entry_answers_as_add_and_verify_do(case):
    build, floor = CASES[case]
    items = build()
    cpu = CPUBatchVerifier()
    for pk, m, s in items:
        cpu.add(pk, m, s)
    want = cpu.verify()

    one_by_one = _tpu(floor)
    for pk, m, s in items:
        one_by_one.add(pk, m, s)
    assert one_by_one.count() == len(items)
    got_added = one_by_one.verify()
    assert one_by_one.count() == 0

    bulk = _tpu(floor)
    got_bulk = bulk.verify_many(items)
    # the interface's default (what any backend without an entry of its
    # own runs) and the duck-typed driver say the same
    got_base = cryptobatch.BatchVerifier.verify_many(_tpu(floor), items)
    got_driven = cryptobatch.verify_flush(_tpu(floor), items)

    assert got_bulk == got_added == got_base == got_driven == want
    assert all(type(v) is bool for v in got_bulk[1])
    assert (bulk.host_lanes, bulk.device_lanes) == \
        (one_by_one.host_lanes, one_by_one.device_lanes)
    n_ed = sum(1 for pk, _, _ in items if pk.type() == ed.KEY_TYPE)
    device = n_ed if n_ed >= floor else 0
    assert (bulk.host_lanes, bulk.device_lanes) == (len(items) - device, device)
    curves = {pk.type() for pk, _, _ in items}
    assert bulk.single_curve == one_by_one.single_curve == (len(curves) == 1)


def test_bytes_like_lanes_are_normalised_as_add_does():
    items = _forged(_ed_items(), 3)
    loose = [(pk, bytearray(m), memoryview(s)) for pk, m, s in items]
    assert _tpu(FLOOR).verify_many(loose) == _tpu(FLOOR).verify_many(items)
    assert _tpu(1000).verify_many(loose) == _tpu(1000).verify_many(items)


def test_the_bulk_entry_takes_what_add_collected_first():
    items = _forged(_ed_items(), 1)
    bv = _tpu(FLOOR)
    for pk, m, s in items[:5]:
        bv.add(pk, m, s)
    ok, mask = bv.verify_many(items[5:])
    assert (ok, mask) == _tpu(FLOOR).verify_many(items)
    assert bv.count() == 0


@pytest.mark.parametrize("floor", [FLOOR, 1000])
@pytest.mark.parametrize("at", [0, N - 1])
def test_a_nil_key_raises_before_anything_is_dispatched(
        monkeypatch, floor, at):
    from cometbft_tpu.crypto.tpu import ed25519_batch, keystore

    def boom(*a, **k):
        raise AssertionError("dispatched a flush that holds a nil key")

    monkeypatch.setattr(ed25519_batch, "verify_batch", boom)
    monkeypatch.setattr(keystore, "verify_batch_indexed", boom)
    monkeypatch.setattr(ed, "verify_many", boom)
    items = _ed_items()
    items[at] = (None, items[at][1], items[at][2])
    with pytest.raises(ValueError, match="nil pubkey"):
        _tpu(floor).verify_many(items)
    with pytest.raises(ValueError, match="nil pubkey"):
        bv = _tpu(floor)
        for pk, m, s in items:
            bv.add(pk, m, s)


# --------------------------------------------------------------------------
# the supervised dispatch
# --------------------------------------------------------------------------


class _CountingTPU(TPUBatchVerifier):
    adds = 0

    def __init__(self):
        super().__init__(min_batch=FLOOR)

    def add(self, pub_key, msg, sig):
        _CountingTPU.adds += 1
        super().add(pub_key, msg, sig)


class _AddVerifyOnly:
    """No BatchVerifier: what a test double or an old plug-in looks like."""

    adds = 0

    def __init__(self):
        self._inner = CPUBatchVerifier()

    def add(self, pub_key, msg, sig):
        _AddVerifyOnly.adds += 1
        self._inner.add(pub_key, msg, sig)

    def verify(self):
        return self._inner.verify()


def _supervised(name, factory, items):
    from cometbft_tpu.crypto.supervisor import BackendSupervisor

    cryptobatch.register_backend(name, factory)
    sup = BackendSupervisor(spec=BackendSpec(name), audit_pct=0, hedge_pct=0)
    try:
        mask = sup.verify_items(items)
        return mask, sup.metrics
    finally:
        sup.stop()


def test_a_supervised_one_curve_flush_is_never_re_added():
    items = _ed_items()
    _CountingTPU.adds = 0
    mask, metrics = _supervised("bulk-counting-tpu", _CountingTPU, items)
    assert mask == [True] * N
    assert _CountingTPU.adds == 0
    assert metrics.device_dispatches.value() == 1
    assert metrics.single_curve_dispatches.value() == 1
    assert metrics.failures.value() == 0

    # a forged lane brings triage's passes: device dispatches too, each
    # of one curve, none re-added
    mask, metrics = _supervised(
        "bulk-counting-tpu", _CountingTPU, _forged(_ed_items(), 7))
    assert mask == [i != 7 for i in range(N)]
    assert _CountingTPU.adds == 0
    assert metrics.device_dispatches.value() > 1
    assert metrics.single_curve_dispatches.value() == \
        metrics.device_dispatches.value()

    # a mixed flush is a device dispatch and not a one-curve one
    mixed = _mixed()[1:]  # without its forged lane
    mask, metrics = _supervised("bulk-counting-tpu", _CountingTPU, mixed)
    assert mask == CPUBatchVerifier().verify_many(mixed)[1]
    assert _CountingTPU.adds == 0
    assert metrics.device_dispatches.value() >= 1
    assert metrics.single_curve_dispatches.value() == \
        metrics.device_dispatches.value() - 1
    assert metrics.host_lanes.value() >= 5


def test_a_verifier_with_only_add_and_verify_is_driven_lane_by_lane():
    items = _ed_items()
    _AddVerifyOnly.adds = 0
    mask, metrics = _supervised("bulk-add-verify-only", _AddVerifyOnly, items)
    assert mask == [True] * N
    assert _AddVerifyOnly.adds == N
    assert metrics.device_dispatches.value() == 1
    assert metrics.single_curve_dispatches.value() == 0
    assert metrics.failures.value() == 0

    _AddVerifyOnly.adds = 0
    mask, _ = _supervised(
        "bulk-add-verify-only", _AddVerifyOnly, _forged(_ed_items(), 7))
    assert mask == [i != 7 for i in range(N)]
    assert _AddVerifyOnly.adds >= N
