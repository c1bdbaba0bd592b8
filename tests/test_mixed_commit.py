"""A commit of a validator set whose seats hold ed25519 AND secp256k1
keys, through ValidatorSet.verify_commit on the tpu backend, on the CPU
platform: the ed25519 seats against their resident rows and the
secp256k1 seats on their kernel, in ONE stream (mesh.launch_parts), held
to the plain references (benchmark/lib/reference.py,
benchmark/lib/secp_reference.py) and to the cpu backend on every kind of
lane the benchmark's forged commits plant, and more. A one-curve set
keeps the resident path and its valset id."""

import copy
import hashlib

import pytest

from benchmark.lib import reference, secp_reference
from benchmark.traffic import mixed_commit as gen
from cometbft_tpu.crypto import batch as cryptobatch
from cometbft_tpu.crypto import secp256k1
from cometbft_tpu.crypto import wire as wirelib
from cometbft_tpu.crypto.batch import BackendSpec
from cometbft_tpu.crypto.tpu import ed25519_batch as eb
from cometbft_tpu.crypto.tpu import mesh
from cometbft_tpu.types.block import CommitSig
from cometbft_tpu.types.validator import Validator

SEED = 2_150_000_381  # more than 32 signed bits hold
CHAIN_ID = "mixed-chain"
HEIGHT = 9
N, N_SECP = 64, 32
TPU = BackendSpec("tpu", min_batch=1)
SECP_N = secp_reference.N


@pytest.fixture(scope="module")
def signed():
    vals, signers = gen.make_mixed_valset(N, N_SECP, SEED, "test")
    bid, commit = gen.make_commit(vals, signers, HEIGHT, SEED, CHAIN_ID)
    return vals, bid, commit


@pytest.fixture()
def ledger(monkeypatch):
    """A fresh wire ledger as the process default, the secp256k1 floor
    lowered so that the toy set's 32 secp256k1 lanes take the device."""
    monkeypatch.setenv("CBFT_TPU_SECP_MIN_BATCH", "1")
    led = wirelib.WireLedger()
    prev = wirelib.set_default_ledger(led)
    yield led
    wirelib.set_default_ledger(prev)


def _seat(vals, curve, k=0):
    return gen.forged_lane(vals, curve, k)


def _with_key(vals, seat, key_bytes):
    """The set with seat ``seat``'s key replaced (same address, power)."""
    bad = copy.copy(vals)
    bad.validators = list(vals.validators)
    v = vals.validators[seat]
    bad.validators[seat] = Validator(
        v.address, secp256k1.PubKeySecp256k1(key_bytes), v.voting_power, 0
    )
    return bad


def _spoil_sig(commit, seat, sig):
    bad = copy.deepcopy(commit)
    bad.signatures[seat].signature = sig
    return bad


def _case(signed, name):
    """→ (vals, commit) of one case."""
    vals, _, commit = signed
    ed_seat = _seat(vals, "ed25519", 5)
    secp_seat = _seat(vals, "secp256k1", 5)
    if name == "honest":
        return vals, commit
    if name == "ed25519_flip":
        return vals, gen.forge(commit, "ed25519_flip", ed_seat)
    if name == "secp256k1_flip":
        return vals, gen.forge(commit, "secp256k1_flip", secp_seat)
    if name == "high_s":
        return vals, gen.forge(commit, "secp256k1_high_s", secp_seat)
    sig = commit.signatures[secp_seat].signature
    if name == "r_zero":
        return vals, _spoil_sig(commit, secp_seat, bytes(32) + sig[32:])
    if name == "s_n":
        return vals, _spoil_sig(
            commit, secp_seat, sig[:32] + SECP_N.to_bytes(32, "big"))
    if name == "off_curve_x":
        x = next(x for x in range(5, 99) if secp_reference.decode_key(
            b"\x02" + x.to_bytes(32, "big")) is None)
        return _with_key(vals, secp_seat, b"\x02" + x.to_bytes(32, "big")), \
            commit
    if name == "x_not_below_p":
        return _with_key(
            vals, secp_seat, b"\x02" + secp_reference.P.to_bytes(32, "big")
        ), commit
    if name == "wrong_prefix":
        key = vals.validators[secp_seat].pub_key.bytes()
        return _with_key(vals, secp_seat, b"\x05" + key[1:]), commit
    if name == "absent_lanes":
        bad = copy.deepcopy(commit)
        for seat in (ed_seat, secp_seat, _seat(vals, "secp256k1", 40)):
            bad.signatures[seat] = CommitSig.absent()
        return vals, bad
    if name == "absent_and_flipped":
        bad = gen.forge(commit, "secp256k1_flip", _seat(vals, "secp256k1", 40))
        bad.signatures[secp_seat] = CommitSig.absent()
        return vals, bad
    raise KeyError(name)


CASES = ["honest", "ed25519_flip", "secp256k1_flip", "high_s", "r_zero",
         "s_n", "off_curve_x", "x_not_below_p", "wrong_prefix",
         "absent_lanes", "absent_and_flipped"]


def _reference_outcome(vals, commit):
    """verify_commit's outcome by the plain references: the first seat
    whose present lane either reference refuses, else the tally."""
    tallied = 0
    for seat, cs in enumerate(commit.signatures):
        if cs.is_absent():
            continue
        key = vals.validators[seat].pub_key.bytes()
        lane = (key, commit.vote_sign_bytes(CHAIN_ID, seat), cs.signature)
        ok = (reference.verify_many([lane]) if len(key) == 32
              else secp_reference.verify_many([lane]))[0]
        if not ok:
            return f"wrong signature (#{seat})"
        tallied += vals.validators[seat].voting_power
    if tallied <= vals.total_voting_power() * 2 // 3:
        return "insufficient voting power"
    return None


def _outcome(vals, bid, commit, backend):
    try:
        vals.verify_commit(CHAIN_ID, bid, HEIGHT, commit, backend=backend)
    except ValueError as exc:
        return str(exc)
    return None


@pytest.mark.parametrize("case", CASES)
def test_a_mixed_commit_is_one_stream_and_agrees_with_the_references(
        signed, ledger, monkeypatch, case):
    vals, commit = _case(signed, case)
    bid = signed[1]
    streams = []
    real = mesh.launch_parts

    def spy(parts, **kw):
        streams.append([(p.curve, p.n) for p in parts])
        return real(parts, **kw)

    def no_add(*a, **k):
        raise AssertionError("a mixed commit fell back to add()/verify()")

    monkeypatch.setattr(mesh, "launch_parts", spy)
    monkeypatch.setattr(cryptobatch, "new_batch_verifier", no_add)
    want = _reference_outcome(vals, commit)
    got = _outcome(vals, bid, commit, TPU)
    assert (got is None) == (want is None), (got, want)
    if want is not None:
        assert want in got
    assert streams == [[("ed25519", N - N_SECP), ("secp256k1", N_SECP)]]
    monkeypatch.undo()
    assert _outcome(vals, bid, commit, "cpu") == got
    books = ledger.curve_books()
    assert books["ed25519"]["device_lanes"] + books["secp256k1"][
        "device_lanes"] == N
    assert books["secp256k1"]["pack_s"] > 0
    assert ledger.lanes_by_route() == {"resident": N}


def test_a_curve_under_its_floor_is_verified_on_the_host(signed, ledger,
                                                          monkeypatch):
    """The secp256k1 floor at its default (256 lanes): the toy set's 32
    secp256k1 lanes go to the host pool, the ed25519 ones to the device,
    and a flipped secp256k1 lane is still found."""
    monkeypatch.delenv("CBFT_TPU_SECP_MIN_BATCH")
    vals, commit = _case(signed, "secp256k1_flip")
    want = _reference_outcome(vals, commit)
    assert want in _outcome(vals, signed[1], commit, TPU)
    books = ledger.curve_books()
    assert books["secp256k1"]["host_lanes"] == N_SECP
    assert books["secp256k1"]["device_lanes"] == 0
    assert books["ed25519"]["device_lanes"] == N - N_SECP


def test_the_stream_takes_a_launch_of_each_curve_in_turn(signed, ledger,
                                                          monkeypatch):
    """Launches of 64 lanes: 3 of each curve on a 150-seat set of 75 +
    75, issued ed25519, secp256k1, ed25519, ... with each launch's
    program and curve; the ledger books each launch to its curve."""
    monkeypatch.setattr(eb, "_LAUNCH_LANES", 32)
    from cometbft_tpu.crypto.tpu import secp256k1_batch as sb

    monkeypatch.setattr(sb, "_LAUNCH_LANES", 32)
    eb._keystore.invalidate()
    vals, signers = gen.make_mixed_valset(150, 75, SEED, "turns")
    bid, commit = gen.make_commit(vals, signers, HEIGHT, SEED, CHAIN_ID)
    calls = []
    real = mesh.launch_stream

    def spy(kernel, launches, build, n, **kw):
        calls.append([(s, e, z, prog[2]) for (s, e, z, *_), prog in zip(
            launches, kw["programs"])])
        return real(kernel, launches, build, n, **kw)

    monkeypatch.setattr(mesh, "launch_stream", spy)
    vals.verify_commit(CHAIN_ID, bid, HEIGHT, commit, backend=TPU)
    (launches,) = calls
    curves = [c for *_, c in launches]
    assert curves == ["ed25519", "secp256k1"] * 3
    # the secp256k1 seats' short launch first, padded to half a launch
    secp = [(s - 75, e - 75, z) for s, e, z, c in launches
            if c == "secp256k1"]
    assert secp == [(0, 11, 64), (11, 43, 64), (43, 75, 64)]
    assert ledger.curve_books()["secp256k1"]["device_lanes"] == 75
    eb._keystore.invalidate()


def test_a_one_curve_set_keeps_the_resident_path_and_its_valset_id(
        monkeypatch):
    """An ed25519 set: verify_valset_resident with the sha256 of the
    keys joined, and the index made once for the set, not each commit."""
    from benchmark.lib import data

    vals, privs = data.make_valset(40, SEED, "one")
    bid, commit = data.make_commit(vals, privs, HEIGHT, SEED, CHAIN_ID)
    seen = []
    real = eb.verify_valset_resident

    def spy(valset_id, pub_keys, msgs, sigs):
        seen.append((valset_id, list(pub_keys)))
        return real(valset_id, pub_keys, msgs, sigs)

    monkeypatch.setattr(eb, "verify_valset_resident", spy)
    made = []
    real_index = cryptobatch.CurveIndex

    def counting(validators):
        made.append(1)
        return real_index(validators)

    monkeypatch.setattr(cryptobatch, "CurveIndex", counting)
    for _ in range(2):
        vals.verify_commit(CHAIN_ID, bid, HEIGHT, commit, backend=TPU)
    keys = [v.pub_key.bytes() for v in vals.validators]
    assert seen == [(hashlib.sha256(b"".join(keys)).digest(), keys)] * 2
    assert made == [1]
    # a set that changes is indexed again
    vals.update_with_change_set([Validator.new(privs[0].get_pub_key(), 11)])
    vals.curve_index()
    assert made == [1, 1]


def test_only_a_chain_that_admits_secp256k1_warms_its_kernel(tmp_path):
    """The secp256k1 kernel is in the AOT registry, and a warm boot plans
    it only where the genesis consensus params admit secp256k1 keys: the
    launch's two buckets a chip, 1,024 and 2,048."""
    import json

    from cometbft_tpu.cmd.commands import _load_config
    from cometbft_tpu.cmd.commands import main as cli_main
    from cometbft_tpu.crypto.tpu import aot
    from cometbft_tpu.node import node as nodelib

    def secp_single(plan):
        return {t.bucket for t in plan
                if t.name == "secp256k1.verify" and not t.sharded}

    assert secp_single(aot.warmup_plan(floor=1024)) == set()
    assert secp_single(aot.warmup_plan(
        floor=1024, key_types=("ed25519", "secp256k1"))) == {1024, 2048}
    home = str(tmp_path / "home")
    assert cli_main(["--home", home, "init", "--chain-id", "mixed"]) == 0
    config = _load_config(home)
    assert tuple(nodelib._genesis_key_types(config)) == ("ed25519",)
    path = config.base.genesis_path()
    with open(path) as fh:
        doc = json.load(fh)
    params = doc.get("consensus_params") or {}
    params.setdefault("validator", {})["pub_key_types"] = [
        "ed25519", "secp256k1"]
    doc["consensus_params"] = params
    with open(path, "w") as fh:
        json.dump(doc, fh)
    assert tuple(nodelib._genesis_key_types(config)) == (
        "ed25519", "secp256k1")
