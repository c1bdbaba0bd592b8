"""The one launch stream (mesh.launch_stream) under its entries:
dispatch_batch on one chip, dispatch_sharded on the suite's 8-device
mesh, the resident commit, the indexed key store, verifyd's rows, and
the keyed ed25519 flush (verify_batch), which streams through
dispatch_batch at the launch size the resident commit uses.

Every entry is driven over the same 200 lanes under a chunk cap of 64
(four launches, the last of 8 real lanes; verify_batch, cut by its launch
size, puts the 8 first), with a hook in whatever the entry asks for a
launch's host work, and held to the same four things:
the order of work, the cancel check between launches, the context a
failing launch carries, and what the wire ledger books. The two keyed
entries run a parity kernel that costs nothing to compile; the other
three run their ed25519 kernels at the 64-lane bucket against the CPU's
verdicts.
"""

import hashlib
import threading

import jax
import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto import service as servicelib
from cometbft_tpu.crypto import wire as wirelib
from cometbft_tpu.crypto.tpu import ed25519_batch as eb
from cometbft_tpu.crypto.tpu import keystore, mesh, topology
from cometbft_tpu.libs import trace as tracelib

N = 200
CAP = 64
SPANS = [(0, 64), (64, 128), (128, 192), (192, 200)]
# a flush cut by a launch size under the ceiling: the short launch first
SHORT_FIRST = [(0, 8), (8, 72), (72, 136), (136, 200)]
SPOILED = (5, 70, 199)
TABLE_KEYS = 40  # the indexed entry's valset: a 64-row table, lanes repeat


@jax.jit
def _parity(rows):
    return (rows.sum(axis=0) % 2) == 0


@pytest.fixture(scope="module")
def lanes():
    """200 signed lanes, three of them spoiled, and the CPU's verdicts."""
    keys = [ed.gen_priv_key_from_secret(b"stream|%d" % i) for i in range(N)]
    msgs = [b"launch stream lane %d" % i for i in range(N)]
    sigs = [k.sign(m) for k, m in zip(keys, msgs)]
    for i in SPOILED:
        sigs[i] = sigs[i][:7] + bytes([sigs[i][7] ^ 1]) + sigs[i][8:]
    return keys, msgs, sigs, [i not in SPOILED for i in range(N)]


class _Rows:
    """dispatch_rows' ``rows`` with a hook in the slice of a launch."""

    def __init__(self, arr, hook, spans):
        self._arr, self._hook, self.shape = arr, hook, arr.shape
        self._spans = spans

    def __getitem__(self, key):
        self._hook(self._spans.index((key[1].start, key[1].stop)))
        return self._arr[key]


class _Entry:
    """One caller of the stream: ``run(hook)`` sends the 200 lanes through
    it, ``hook(k)`` called when launch k's host work is asked for."""

    def __init__(self, route, device, prefix, sizes, run, want, spans=SPANS):
        self.route, self.device, self.prefix = route, device, prefix
        self.sizes, self.run, self.want = sizes, run, want
        self.spans = spans


def _keyed(dispatch):
    rng = np.random.default_rng(3)
    full = rng.integers(0, 100, size=(4, N)).astype(np.int32)

    def run(hook):
        def packed(start, end):
            hook(SPANS.index((start, end)))
            return [full[:, start:end]]

        return dispatch(_parity, packed, N, CAP, 8)

    return run, list((full.sum(axis=0) % 2) == 0)


ENTRIES = ["dispatch_batch", "dispatch_sharded", "verify_valset_resident",
           "verify_batch_indexed", "dispatch_rows", "verify_batch"]


@pytest.fixture(params=ENTRIES)
def entry(request, monkeypatch, lanes):
    keys, msgs, sigs, truth = lanes
    monkeypatch.setenv("CBFT_TPU_MAX_CHUNK", str(CAP))
    monkeypatch.delenv("CBFT_TPU_PIPELINE_DEPTH", raising=False)
    before = topology.default_topology()
    sharded = request.param == "dispatch_sharded"
    topology.set_default_topology(
        topology.DeviceTopology.virtual(8) if sharded
        else topology.DeviceTopology.single())
    eb._keystore.invalidate()
    try:
        if request.param == "dispatch_batch":
            def single(*args):
                with mesh.route_scope(mesh.ROUTE_SINGLE):
                    return mesh.dispatch_batch(*args)

            yield _Entry("single", "dev0", "mesh", [64, 64, 64, 8],
                         *_keyed(single))
        elif sharded:
            assert mesh.shard_plan().n_shards == 8
            yield _Entry("sharded", "mesh:8", "mesh", [64, 64, 64, 8],
                         *_keyed(mesh.dispatch_sharded))
        elif request.param == "verify_valset_resident":
            pks = [k.pub_key().bytes() for k in keys]
            vid = hashlib.sha256(b"".join(pks)).digest()

            def run(hook):
                def source(start, end):
                    hook(SPANS.index((start, end)))
                    return msgs[start:end]

                return eb.verify_valset_resident(vid, pks, source, sigs)

            yield _Entry("resident", "dev0", "resident", [64] * 4, run, truth)
        elif request.param == "verify_batch_indexed":
            monkeypatch.setattr(mesh, "n_devices", lambda: 1)
            table = [k.pub_key().bytes() for k in keys[:TABLE_KEYS]]
            eb._get_resident(hashlib.sha256(b"".join(table)).digest(), table)
            # lane i is signed by table key i % 40
            pks = [table[i % TABLE_KEYS] for i in range(N)]
            sigs40 = [keys[i % TABLE_KEYS].sign(msgs[i]) for i in range(N)]
            for i in SPOILED:
                sigs40[i] = sigs40[i][:7] + bytes([sigs40[i][7] ^ 1]) \
                    + sigs40[i][8:]
            real = eb._prepare_rsh_compact

            def run(hook):
                asked = []

                def prepare(pk_arr, lane_msgs, lane_sigs):
                    asked.append(None)
                    hook(len(asked) - 1)
                    return real(pk_arr, lane_msgs, lane_sigs)

                monkeypatch.setattr(eb, "_prepare_rsh_compact", prepare)
                return keystore.verify_batch_indexed(pks, msgs, sigs40)

            yield _Entry("indexed", "dev0", "mesh", [64] * 4, run, truth)
        elif request.param == "verify_batch":
            # the launch size is what cuts the flush, under a ceiling
            # that would hold it whole, and the short launch goes first
            monkeypatch.setenv("CBFT_TPU_MAX_CHUNK", str(4 * CAP))
            monkeypatch.setattr(eb, "_LAUNCH_LANES", CAP)
            pks = [k.pub_key().bytes() for k in keys]
            real = eb.prepare_batch_compact

            def run(hook):
                def prepare(lane_pks, lane_msgs, lane_sigs):
                    hook(SHORT_FIRST.index(
                        (pks.index(lane_pks[0]),
                         pks.index(lane_pks[-1]) + 1)))
                    return real(lane_pks, lane_msgs, lane_sigs)

                monkeypatch.setattr(eb, "prepare_batch_compact", prepare)
                with mesh.route_scope(mesh.ROUTE_SINGLE):
                    return eb.verify_batch(pks, msgs, sigs)

            yield _Entry("single", "dev0", "mesh", [64] * 4, run, truth,
                         spans=SHORT_FIRST)
        else:
            # verifyd's rows are cut as the keyed flush is (PR 30)
            monkeypatch.setenv("CBFT_TPU_MAX_CHUNK", str(4 * CAP))
            monkeypatch.setattr(eb, "_LAUNCH_LANES", CAP)
            wire, valid = eb.prepare_batch_compact(
                [k.pub_key().bytes() for k in keys], msgs, sigs)
            assert valid.all()
            yield _Entry(
                "service", "dev0", "mesh", [64] * 4,
                lambda hook: servicelib.dispatch_rows(
                    _Rows(wire, hook, SHORT_FIRST)),
                truth, spans=SHORT_FIRST)
    finally:
        eb._keystore.invalidate()
        topology.set_default_topology(before)


def _order(entry):
    """Build k, issue k, then build k + 1, never more than
    pipeline_depth() launches unretired: the ``inflight`` the stream
    writes on a launch's pack and launch stages is how many were issued
    and not yet retired when that launch's host work began."""
    asked = []
    tracer = tracelib.Tracer(sample=1.0)
    root = tracer.start_span("request")
    with tracelib.use(root):
        got = entry.run(asked.append)
    root.end()
    assert [bool(x) for x in got] == entry.want
    assert asked == [0, 1, 2, 3]
    spans = tracer.recent()[0]["spans"]
    named = {}
    for s in spans:
        named.setdefault(s["name"], []).append(s)
    depth = mesh.pipeline_depth()
    flying = [min(k, depth) for k in range(4)]
    assert flying == [0, 1, 2, 2]
    pack, launch, retire = (
        sorted(named[entry.prefix + "." + stage], key=lambda s: s["start_us"])
        for stage in ("pack", "launch", "retire"))
    assert [s["tags"] for s in pack] == [
        {"chunk": k, "inflight": flying[k]} for k in range(4)]
    nsh = 8 if entry.route == "sharded" else 1
    assert [s["tags"] for s in launch] == [
        {"chunk": k, "inflight": flying[k], "shards": nsh,
         "lanes_per_shard": entry.sizes[k] // nsh} for k in range(4)]
    assert len(retire) == 4
    # a launch's stages hang under its chunk span, which says what it held
    chunks = {s["span_id"]: s for s in named["chunk"]}
    assert [chunks[s["parent_id"]]["tags"]["chunk"] for s in pack] == [
        0, 1, 2, 3]
    assert [c["tags"]["n_sigs"] for c in named["chunk"]] == [
        end - start for start, end in entry.spans]
    assert [c["tags"]["pad"] for c in named["chunk"]] == entry.sizes
    ordered = sorted(pack + launch, key=lambda s: s["start_us"])
    assert [s["name"].rsplit(".", 1)[1] for s in ordered] == [
        "pack", "launch"] * 4
    # with launch k issued the oldest beyond the depth is retired, before
    # launch k + 1's host work begins
    for k in range(depth + 1, 4):
        done = retire[k - depth - 1]
        assert done["start_us"] + done["dur_us"] <= pack[k]["start_us"]


def _cancel(entry):
    """The watchdog's event, set while launch 0 is built, stops the
    stream at the next launch boundary and names the lanes left undone."""
    event = threading.Event()
    asked = []

    def hook(k):
        asked.append(k)
        event.set()

    with mesh.cancel_scope(event):
        with pytest.raises(mesh.DispatchCancelled) as exc:
            entry.run(hook)
    assert asked == [0]
    assert "before chunk 1" in str(exc.value)
    assert f"[{entry.spans[1][0]}:{N}] undone" in str(exc.value)


def _failure(entry):
    """A launch that fails says which launch it was, the lanes it held
    and where it ran; the launches before it were issued."""
    asked = []

    def hook(k):
        asked.append(k)
        if k == 1:
            raise ValueError("boom in launch 1")

    with pytest.raises(RuntimeError) as exc:
        entry.run(hook)
    assert asked == [0, 1]
    start, end = entry.spans[1]
    assert f"{entry.route} dispatch of chunk 1 (sigs [{start}:{end}]) on " \
        f"{entry.device} failed: boom in launch 1" == str(exc.value)
    assert isinstance(exc.value.__cause__, ValueError)


def _ledger(entry):
    """Each entry books its own route, the real lanes and the lanes they
    were padded to; only the two mesh entries book a per-call row."""
    ledger = wirelib.WireLedger(window=8)
    prev = wirelib.set_default_ledger(ledger)
    try:
        entry.run(lambda k: None)
    finally:
        wirelib.set_default_ledger(prev)
    assert ledger.lanes_by_route() == {entry.route: N}
    assert ledger.padded_lanes_by_route() == {
        entry.route: sum(entry.sizes)}
    snap = ledger.snapshot()
    assert snap["chunks"] == 4
    assert snap["dispatches"] == (
        1 if entry.route in ("single", "sharded") else 0)
    nsh = 8 if entry.route == "sharded" else 1
    assert {(r["route"], r["bucket"], r["device"])
            for r in snap["profiles"]} == {
        (entry.route, size // nsh, entry.device) for size in entry.sizes}
    # launches 1-3 were staged behind a launch in flight
    assert all(r["overlap"] is not None and r["overlap"] > 0
               for r in snap["profiles"])


ASPECTS = {"order": _order, "cancel": _cancel, "failure": _failure,
           "ledger": _ledger}


@pytest.mark.parametrize("aspect", sorted(ASPECTS))
def test_every_entry_is_the_one_stream(entry, aspect):
    ASPECTS[aspect](entry)


# a keyed flush of 290 lanes at a launch size of 256: the 34 lanes short
# of a launch go first and pad to half a launch, 128, and not to their own
# bucket, 64
KEYED_N, KEYED_LAUNCH = 290, 256
KEYED_SHORT = KEYED_N % KEYED_LAUNCH


@pytest.mark.parametrize("forged", [
    (), (KEYED_SHORT - 1,), (KEYED_SHORT,),
    (KEYED_SHORT - 1, KEYED_SHORT), (0, KEYED_N - 1),
], ids=["none", "last_of_launch_0", "first_of_launch_1", "both_sides",
        "first_and_last_lane"])
def test_a_keyed_flush_streams_at_the_launch_size(monkeypatch, forged):
    """verify_batch of more than one launch: launch 1 is packed with
    launch 0 in flight, the short launch goes first and pads to half a
    launch, a forged lane on either side of the launch boundary is
    refused and its neighbours are not, and the mask is the CPU
    verifier's."""
    keys = [ed.gen_priv_key_from_secret(b"keyed|%d" % i)
            for i in range(KEYED_N)]
    msgs = [b"keyed flush lane %d" % i for i in range(KEYED_N)]
    sigs = [k.sign(m) for k, m in zip(keys, msgs)]
    for i in forged:
        sigs[i] = sigs[i][:40] + bytes([sigs[i][40] ^ 4]) + sigs[i][41:]
    truth = [k.pub_key().verify_signature(m, s)
             for k, m, s in zip(keys, msgs, sigs)]
    assert [i for i, ok in enumerate(truth) if not ok] == sorted(forged)
    monkeypatch.delenv("CBFT_TPU_MAX_CHUNK", raising=False)
    monkeypatch.delenv("CBFT_TPU_PIPELINE_DEPTH", raising=False)
    mesh.configure_chunk_cap(None)
    monkeypatch.setattr(eb, "_LAUNCH_LANES", KEYED_LAUNCH)
    before = topology.default_topology()
    topology.set_default_topology(topology.DeviceTopology.single())
    tracer = tracelib.Tracer(sample=1.0)
    root = tracer.start_span("request")
    try:
        with tracelib.use(root), mesh.route_scope(mesh.ROUTE_SINGLE):
            got = eb.verify_batch(
                [k.pub_key().bytes() for k in keys], msgs, sigs)
    finally:
        root.end()
        topology.set_default_topology(before)
    assert [bool(x) for x in got] == truth
    spans = tracer.recent()[0]["spans"]
    chunks = sorted((s for s in spans if s["name"] == "chunk"),
                    key=lambda s: s["tags"]["chunk"])
    assert [(c["tags"]["n_sigs"], c["tags"]["pad"]) for c in chunks] == [
        (KEYED_SHORT, KEYED_LAUNCH // 2), (KEYED_LAUNCH, KEYED_LAUNCH)]
    pack = sorted((s for s in spans if s["name"] == "mesh.pack"),
                  key=lambda s: s["start_us"])
    assert [s["tags"] for s in pack] == [
        {"chunk": 0, "inflight": 0}, {"chunk": 1, "inflight": 1}]
