"""The resident commit as a stream of launches (ed25519_batch
verify_valset_resident fed a launch at a time by
ValidatorSet.verify_commit), on the CPU platform, on one device and on a
four-device virtual mesh.

A 300-validator commit under a launch size of 64 lanes a chip (the
constant patched down so that it binds at toy size) is five launches on
one device and 256 + 64 padded lanes on four: no multiple of the launch
size either way. The streamed path's per-lane mask is held to
verify_batch's and to the CPU's single-signature verdicts, with the
faults of each case spread over the launches; verify_commit's error is
held to the message the eager path of the parent commit raised on the
same commit (pinned below). Then the layout the constant gives at the
real sizes, against the warm ladder, and the order of the stages that
makes the overlap.
"""

import copy
import hashlib

import pytest

from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto.batch import BackendSpec
from cometbft_tpu.crypto.tpu import aot, ed25519_batch as eb, mesh, topology
from cometbft_tpu.libs import trace as tracelib
from cometbft_tpu.proto.gogo import Timestamp
from cometbft_tpu.types import test_util, validator_set
from cometbft_tpu.types.block import BlockID, CommitSig
from cometbft_tpu.types.validator_set import ErrNotEnoughVotingPowerSigned
from cometbft_tpu.types.vote import SIGNED_MSG_TYPE_PRECOMMIT

CHAIN_ID = "stream-chain"
N = 300
HEIGHT = 9
LAUNCH = 64  # lanes a chip, patched in for eb._LAUNCH_LANES
# shards -> the launches of 300 lanes: (start, end, padded lanes)
LAUNCHES = {
    1: [(0, 64, 64), (64, 128, 64), (128, 192, 64), (192, 256, 64),
        (256, 300, 64)],
    4: [(0, 256, 256), (256, 300, 64)],
}
TPU = BackendSpec("tpu", min_batch=1)

# case -> (spoiled lanes, absent lanes, nil-vote lanes)
CASES = {
    # one in every launch of both layouts, each at another offset
    "spoiled_in_every_launch": ([10, 70, 130, 200, 290], [], []),
    # launch edges on both layouts, and a spoiled lane behind them
    "absent_lanes": ([150], [5, 63, 64, 127, 255, 256, 299], []),
    # two templates, the nil one first met in launch 1 and again in the last
    "nil_votes_in_two_launches": ([], [], [3, 260]),
    "a_spoiled_nil_vote": ([260], [], [3, 260]),
    "not_enough_power": ([], [], list(range(1, 300, 2))),
    "all_valid": ([], [7], [8]),
}
# what ValidatorSet.verify_commit of the parent commit (9019f14, every
# sign-bytes built before the first launch) raised on the same commits,
# cpu and tpu backend alike
PARENT_ERRORS = {
    "spoiled_in_every_launch": (
        ValueError,
        "wrong signature (#10): 031830ED75EF622922A26B072FAA2AA1CE18A66CE50F"
        "DDC7E067E0416585A93B96AAC7426B593DF27AAA6DCC7C2DA8BDB72F5E4D6916C7"
        "047141EB9DB74A4207"),
    "absent_lanes": (
        ValueError,
        "wrong signature (#150): 6B424338FE8D7B17326A27462D6B5582D772A5CE39F"
        "E7BEEC7210BDFFE619C2597B0E2B33A4E8A31CF63A2443E2C8BDE943B3C6084F01"
        "337EC4F893EC027910C"),
    "nil_votes_in_two_launches": None,
    "a_spoiled_nil_vote": (
        ValueError,
        "wrong signature (#260): 497AE803216FED166F94D2A5A8A77A7310FD46855A2"
        "BB810E2FEF4B84111FCB095E3E984D554641E882221AFF21E4A2EBB15D4C3E35D3"
        "B66DD5DF8279360CC0F"),
    "not_enough_power": (
        ErrNotEnoughVotingPowerSigned,
        "invalid commit -- insufficient voting power: got 1500, needed more "
        "than 2000"),
    "all_valid": None,
}


@pytest.fixture(scope="module")
def signed():
    vals, privs = test_util.deterministic_validator_set(N, 10)
    return vals, privs, test_util.make_block_id()


def _commit(signed, case):
    vals, privs, bid = signed
    spoiled, absent, nil = CASES[case]
    commit = test_util.make_commit(
        bid, HEIGHT, 0, vals, privs, CHAIN_ID, now=Timestamp(1_700_000_000, 0))
    for lane in nil:
        commit.signatures[lane] = test_util.make_vote(
            privs[lane], CHAIN_ID, lane, HEIGHT, 0,
            SIGNED_MSG_TYPE_PRECOMMIT, BlockID(),
            Timestamp(1_700_000_001, lane)).to_commit_sig()
    for lane in absent:
        commit.signatures[lane] = CommitSig.absent()
    for lane in spoiled:
        sig = bytearray(commit.signatures[lane].signature)
        sig[lane % 32] ^= 1 << (lane % 8)
        commit.signatures[lane].signature = bytes(sig)
    return commit


@pytest.fixture(params=[1, 4], ids=["one_device", "four_device_mesh"])
def shards(request, monkeypatch):
    """A plane of one device (no shard plan) or of four fault domains on
    four of the suite's virtual devices, the launch size patched down."""
    before = topology.default_topology()
    topology.set_default_topology(
        topology.DeviceTopology.single() if request.param == 1
        else topology.DeviceTopology.virtual(request.param))
    monkeypatch.setattr(eb, "_LAUNCH_LANES", LAUNCH)
    monkeypatch.delenv("CBFT_TPU_MAX_CHUNK", raising=False)
    mesh.configure_chunk_cap(None)
    eb._keystore.invalidate()
    try:
        yield request.param
    finally:
        eb._keystore.invalidate()
        topology.set_default_topology(before)


def _cpu_verdict(pk, msg, sig):
    if msg is None or sig is None or len(pk) != 32:
        return False
    return ed.PubKeyEd25519(pk).verify_signature(msg, sig)


def _lane_truth(pks, msgs, sigs):
    """(the CPU's verdict a lane, verify_batch's over the present lanes)."""
    cpu = [_cpu_verdict(*lane) for lane in zip(pks, msgs, sigs)]
    present = [i for i, m in enumerate(msgs) if m is not None]
    batch = [False] * len(pks)
    for i, ok in zip(present, eb.verify_batch(
            [pks[i] for i in present], [msgs[i] for i in present],
            [sigs[i] for i in present])):
        batch[i] = bool(ok)
    return cpu, batch


def _assert_streamed(pks, msgs, sigs, source, shards):
    """verify_valset_resident fed by ``source`` against both truths, and
    the launches it was fed for."""
    asked = []

    def spy(start, end):
        asked.append((start, end))
        return source(start, end)

    vid = hashlib.sha256(b"".join(pks)).digest()
    got = [bool(x) for x in eb.verify_valset_resident(vid, pks, spy, sigs)]
    cpu, batch = _lane_truth(pks, msgs, sigs)
    assert [i for i in range(N) if got[i] != cpu[i]] == []
    assert [i for i in range(N) if got[i] != batch[i]] == []
    rv = eb._keystore.entry_for(vid)
    assert [(s, e, z) for s, e, z, _ in rv.chunks] == LAUNCHES[shards]
    assert asked == [(s, e) for s, e, _ in LAUNCHES[shards]]
    return got


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_streamed_mask_is_verify_batchs_and_the_cpus(
        signed, shards, case):
    vals, _, _ = signed
    commit = _commit(signed, case)
    spoiled, absent, _ = CASES[case]
    pks = [v.pub_key.bytes() for v in vals.validators]
    present = [i for i in range(N) if i not in absent]
    # the reference's messages: the single-vote path, a Vote a lane
    msgs = [commit.vote_sign_bytes(CHAIN_ID, i) if i in present else None
            for i in range(N)]
    sigs = [commit.signatures[i].signature if i in present else None
            for i in range(N)]
    source = validator_set._stream_lane_msgs(
        tracelib.NOOP_SPAN, commit, CHAIN_ID, present)
    got = _assert_streamed(pks, msgs, sigs, source, shards)
    assert [i for i in range(N) if not got[i]] == sorted(spoiled + absent)


def test_a_short_key_and_a_scalar_at_the_group_order_fail_their_lanes_only(
        signed, shards):
    vals, _, _ = signed
    commit = _commit(signed, "all_valid")
    _, absent, _ = CASES["all_valid"]
    pks = [v.pub_key.bytes() for v in vals.validators]
    pks[70] = pks[70][:31]
    pks[280] = pks[280] + b"\x00"
    present = [i for i in range(N) if i not in absent]
    for lane, s in ((40, eb.L), (270, eb.L + 1)):
        sig = commit.signatures[lane].signature
        commit.signatures[lane].signature = sig[:32] + s.to_bytes(32, "little")
    commit.signatures[200].signature = commit.signatures[200].signature[:63]
    msgs = [commit.vote_sign_bytes(CHAIN_ID, i) if i in present else None
            for i in range(N)]
    sigs = [commit.signatures[i].signature if i in present else None
            for i in range(N)]
    source = validator_set._stream_lane_msgs(
        tracelib.NOOP_SPAN, commit, CHAIN_ID, present)
    got = _assert_streamed(pks, msgs, sigs, source, shards)
    assert [i for i in range(N) if not got[i]] == [7, 40, 70, 200, 270, 280]


@pytest.mark.parametrize("case", sorted(CASES))
def test_verify_commit_raises_what_the_eager_path_raised(
        signed, shards, case, monkeypatch):
    vals, _, bid = signed
    commit = _commit(signed, case)
    calls = []
    real = eb.verify_valset_resident

    def spy(vid, pks, msgs, sigs):
        calls.append(callable(msgs))
        return real(vid, pks, msgs, sigs)

    monkeypatch.setattr(eb, "verify_valset_resident", spy)
    outcomes = []
    for backend in (TPU, "cpu"):
        try:
            vals.verify_commit(CHAIN_ID, bid, HEIGHT, commit, backend=backend)
            outcomes.append(None)
        except ValueError as exc:
            outcomes.append((type(exc), str(exc)))
    assert calls == [True]  # the tpu backend streamed, the cpu one did not
    assert outcomes[0] == outcomes[1]
    want = PARENT_ERRORS[case]
    if want is None:
        assert outcomes[0] is None
    else:
        assert outcomes[0] == want
        if want[0] is ValueError:  # the first spoiled lane, in valset order
            assert f"(#{min(CASES[case][0])})" in want[1]


def test_a_mixed_key_set_builds_every_message_and_takes_the_batch_verifier(
        signed, shards, monkeypatch):
    """The key-type scan comes after the source is made: the refused
    commit's lanes are then built at once, and verified as before."""
    from cometbft_tpu.crypto import secp256k1

    vals, privs, bid = signed
    commit = _commit(signed, "spoiled_in_every_launch")
    mixed = copy.copy(vals)
    mixed.validators = list(vals.validators)
    mixed.validators[299] = copy.copy(vals.validators[299])
    mixed.validators[299].pub_key = secp256k1.gen_priv_key_from_secret(
        b"odd one").pub_key()
    commit.signatures[299] = CommitSig.absent()
    monkeypatch.setattr(eb, "verify_valset_resident", None)  # never reached
    with pytest.raises(ValueError, match=r"wrong signature \(#10\)"):
        mixed.verify_commit(CHAIN_ID, bid, HEIGHT, commit, backend=TPU)


# -- the layout at the real sizes ---------------------------------------------


class _Plan:
    """What _build_resident reads of a shard plan."""

    def __init__(self, n_shards, jax_mesh):
        self.n_shards = n_shards
        self.mesh = jax_mesh


@pytest.fixture()
def real_launch(monkeypatch):
    monkeypatch.delenv("CBFT_TPU_MAX_CHUNK", raising=False)
    monkeypatch.delenv("CBFT_TPU_MIN_BATCH", raising=False)
    mesh.configure_chunk_cap(None)
    assert eb._LAUNCH_LANES == 2048
    assert mesh.chunk_cap(eb._MAX_CHUNK, eb._MIN_PAD) == 8192


def _launches(pks, nsh, monkeypatch):
    """The launches _build_resident lays the rows of ``pks`` (keys, or
    how many to make up) out in on a plan of ``nsh`` shards (the suite's
    first ``nsh`` virtual devices)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    plan = None
    if nsh > 1:
        plan = _Plan(nsh, Mesh(np.array(jax.devices()[:nsh]), ("batch",)))
    monkeypatch.setattr(mesh, "shard_plan", lambda topology=None: plan)
    if isinstance(pks, int):
        pks = [bytes([i % 251, i // 251]) * 16 for i in range(pks)]
    rv = eb._build_resident(pks)
    assert rv.plan is plan
    return rv


@pytest.mark.parametrize("nsh,want", [
    (1, [2048] * 5),
    (2, [4096, 4096, 2048]),
    (4, [8192, 2048]),
])
def test_ten_thousand_rows_are_launches_of_2048_lanes_a_chip(
        real_launch, monkeypatch, nsh, want):
    rv = _launches(10_000, nsh, monkeypatch)
    assert [size for _, _, size, _ in rv.chunks] == want
    assert all(size // nsh <= 2048 for _, _, size, _ in rv.chunks)
    assert rv.chunks[0][0] == 0 and rv.chunks[-1][1] == 10_000
    assert sum(e - s for s, e, _, _ in rv.chunks) == 10_000
    assert sum(size for _, _, size, _ in rv.chunks) == 10_240
    for _, _, size, a_dev in rv.chunks:
        assert a_dev.shape == (8, size)
        assert len(a_dev.addressable_shards) == nsh


@pytest.mark.parametrize("nsh", [1, 2, 4])
@pytest.mark.parametrize("n", [150, 1024, 4000, 10_000])
def test_every_launch_shape_is_a_target_of_the_warm_plan(
        real_launch, monkeypatch, n, nsh):
    """Zero compiles after warm: a commit is resident from the routing
    floor up, and the ladder starts at the floor's bucket (a chain of
    150 validators reaches this path only under a floor of 150)."""
    from cometbft_tpu.crypto import batch as cryptobatch

    floor = min(n, cryptobatch.ed25519_routing_floor())
    monkeypatch.setattr(mesh, "n_devices", lambda: nsh)
    warmed = {(t.bucket, t.sharded) for t in aot.warmup_plan(floor=floor)
              if t.name == "ed25519.verify_resident"}
    rv = _launches(n, nsh, monkeypatch)
    for _, _, size, _ in rv.chunks:
        assert (size, nsh > 1) in warmed, (n, nsh, size, sorted(warmed))


def test_the_indexed_view_does_not_depend_on_the_launch_size(
        real_launch, monkeypatch):
    import numpy as np

    pks = [ed.gen_priv_key_from_secret(b"indexed|%d" % i).pub_key().bytes()
           for i in range(299)] + [b"\x07" * 31]
    views = []
    for launch in (64, 2048):
        monkeypatch.setattr(eb, "_LAUNCH_LANES", launch)
        rv = _launches(pks, 1, monkeypatch)
        views.append((np.asarray(rv.table_dev), rv.index, rv.pk_ok.tolist(),
                      [size for _, _, size, _ in rv.chunks]))
    (small_table, small_index, small_ok, small), (table, index, ok, one) = views
    assert (small, one) == ([64] * 5, [512])
    assert table.shape == (512, 32) and np.array_equal(small_table, table)
    assert small_index == index and len(index) == 299
    assert small_ok == ok and ok[-1] is False


# -- the order that makes the overlap ----------------------------------------


def _stages(vals, bid, commit, backend):
    tracer = tracelib.Tracer(sample=1.0)
    root = tracer.start_span("request")
    with tracelib.use(root):
        vals.verify_commit(CHAIN_ID, bid, HEIGHT, commit, backend=backend)
    root.end()
    return tracer.recent()[0]["spans"]


def test_a_launchs_messages_are_built_after_the_launch_before_was_issued(
        signed, shards):
    vals, _, bid = signed
    spans = _stages(vals, bid, _commit(signed, "all_valid"), TPU)
    named = {}
    for s in spans:
        named.setdefault(s["name"], []).append(s)
    launches = LAUNCHES[shards]
    assert len(named["commit.sign_bytes"]) == 1
    assert named["commit.sign_bytes"][0]["tags"] == {
        "lanes": N - 1, "templates": 2}
    depth = mesh.pipeline_depth()
    flying = [min(k, depth) for k in range(len(launches))]
    assert [s["tags"] for s in named["commit.msgs_chunk"]] == [
        {"chunk": k, "lanes": e - s, "inflight": flying[k]}
        for k, (s, e, _) in enumerate(launches)]
    assert [s["tags"] for s in named["resident.pack"]] == [
        {"chunk": k, "inflight": flying[k]} for k in range(len(launches))]
    assert [(s["tags"]["chunk"], s["tags"]["inflight"])
            for s in named["resident.launch"]] == list(enumerate(flying))
    assert len(named["resident.retire"]) == len(launches)
    # selection closes, then launch k is built, packed and issued before
    # launch k + 1's messages are asked for
    order = sorted(
        (s for name in ("commit.sign_bytes", "commit.msgs_chunk",
                        "resident.pack", "resident.launch") for s in
         named[name]), key=lambda s: s["start_us"])
    assert [s["name"] for s in order] == ["commit.sign_bytes"] + [
        "commit.msgs_chunk", "resident.pack", "resident.launch"
    ] * len(launches)
    for before, after in zip(order, order[1:]):
        assert before["start_us"] + before["dur_us"] <= after["start_us"]
    first_launch = named["resident.launch"][0]
    second_build = named["commit.msgs_chunk"][1]
    assert (first_launch["start_us"] + first_launch["dur_us"]
            <= second_build["start_us"])


def test_under_the_floor_nothing_is_streamed(signed, shards):
    vals, _, bid = signed
    spans = _stages(vals, bid, _commit(signed, "all_valid"),
                    BackendSpec("tpu", min_batch=1000))
    names = [s["name"] for s in spans]
    assert names.count("commit.sign_bytes") == 1
    assert "commit.msgs_chunk" not in names
    assert not any(n.startswith("resident.") for n in names)
