"""verifyd's row path after PR 31: a request's compact block is built
where its frame is admitted (the reader thread, ``svc.gather``) and a
flush only interleaves finished blocks (``sched.rows``).

Held here: the block and valid mask a flush hands the row verifier are
byte-identical to what the flush-side gather gave (``_old_*`` below is
that arithmetic, kept as this file's own plain reference), verdicts
match the host rung, a registered set that leaves the store between a
frame's admission and its flush changes no verdict, a stale indexed
frame is still refused at admission and served by the compact resend,
and the numpy calls the flush thread makes inside ``sched.rows`` do not
grow with the requests it carries. Counts and parity only: no clocks.
Runs on the virtual CPU mesh (conftest.py)."""

import hashlib
import sys
import threading
import time
import types

import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto import service as svc
from cometbft_tpu.crypto.tpu import keystore
from cometbft_tpu.libs import trace as tracelib

from test_service import _Daemon, _batch, _expected


# ---------------------------------------------------------------------------
# the plain reference: RowPayload.as_compact + np.concatenate as the flush
# thread ran them before PR 31
# ---------------------------------------------------------------------------


def _old_as_compact(kind, wire, idx=None, entry=None):
    n = int(wire.shape[1])
    if kind == svc.KIND_COMPACT:
        return wire, np.ones(n, dtype=bool)
    rows = entry.pk_arr[idx]
    valid = np.asarray(entry.pk_ok[idx], dtype=bool).copy()
    out = np.empty((svc.COMPACT_ROW_BYTES, n), np.uint8)
    out[:32] = rows.T
    out[32:] = wire
    return out, valid


def _old_flush(parts):
    """parts: [("compact", wire) | ("indexed", rsh, idx, entry) |
    ("triples", items)] -> (u8[128, N], bool[N])."""
    blocks, valids = [], []
    for part in parts:
        if part[0] == "compact":
            w, v = _old_as_compact(svc.KIND_COMPACT, part[1])
        elif part[0] == "indexed":
            w, v = _old_as_compact(svc.KIND_INDEXED, *part[1:])
        else:
            w, v = svc.pack_items_compact(part[1])
        blocks.append(w)
        valids.append(np.asarray(v, dtype=bool))
    if len(blocks) == 1:
        return blocks[0], valids[0]
    return np.concatenate(blocks, axis=1), np.concatenate(valids)


def _entry_of(pks):
    """A registered set in a store of this test's own."""
    store = keystore.DeviceKeyStore()
    vid = hashlib.sha256(b"".join(pks)).digest()[:svc.VALSET_ID_BYTES]
    return store.register(vid, pks)


def _compact_part(items):
    wire, valid = svc.pack_items_compact(items)
    assert valid.all()
    return ("compact", wire)


def _indexed_part(items, entry):
    rsh, idx, valid = svc.pack_items_indexed(items, entry.index)
    assert valid.all()
    return ("indexed", rsh, idx, entry)


def _request(part):
    """What the scheduler's flush holds for ``part``: a row request as
    admission builds it, or a triple rider."""
    if part[0] == "compact":
        return types.SimpleNamespace(
            rows=svc.RowPayload.from_compact(part[1].tobytes()), items=[]
        )
    if part[0] == "indexed":
        return types.SimpleNamespace(
            rows=svc.RowPayload.from_indexed(
                part[1].tobytes(), part[2], part[3]
            ),
            items=[],
        )
    return types.SimpleNamespace(rows=None, items=part[1])


def _case(name):
    """-> (parts, the items behind them in lane order, lanes refused for
    a bad registered key)."""
    if name == "compact":
        groups = [_batch(5, b"rc0", bad=(1,)), _batch(3, b"rc1")]
        return [_compact_part(g) for g in groups], sum(groups, []), set()
    if name == "indexed":
        groups = [_batch(6, b"ri0", bad=(4,)), _batch(6, b"ri0")]
        entry = _entry_of([svc._pk_bytes(pk) for pk, _, _ in groups[0]])
        # the second frame addresses the set out of order
        groups[1] = [groups[1][i] for i in (5, 0, 3)]
        return ([_indexed_part(g, entry) for g in groups],
                sum(groups, []), set())
    if name == "mixed":
        a, b, c = (_batch(4, b"rm0"), _batch(5, b"rm1", bad=(0,)),
                   _batch(3, b"rm2", bad=(2,)))
        entry = _entry_of([svc._pk_bytes(pk) for pk, _, _ in b])
        parts = [_compact_part(a), ("triples", c), _indexed_part(b, entry),
                 ("triples", a[:2])]
        return parts, a + c + b + a[:2], set()
    if name == "bad_key":
        items = _batch(5, b"rb0")
        pks = [svc._pk_bytes(pk) for pk, _, _ in items]
        # a registration whose key 2 is malformed: its row is zeroed and
        # pk_ok false, and the client's index still addresses it
        entry = _entry_of(pks[:2] + [pks[2][:31]] + pks[3:])
        rsh, _, valid = svc.pack_items_indexed(
            items, {pk: i for i, pk in enumerate(pks)}
        )
        assert valid.all()
        idx = np.arange(5, dtype=np.int32)
        return [("indexed", rsh, idx, entry)], items, {2}
    if name == "one_request":
        items = _batch(7, b"r1", bad=(6,))
        entry = _entry_of([svc._pk_bytes(pk) for pk, _, _ in items])
        return [_indexed_part(items, entry)], items, set()
    assert name == "requests_32"
    pool = _batch(8, b"r32", bad=(5,))
    entry = _entry_of([svc._pk_bytes(pk) for pk, _, _ in pool])
    groups = [
        [pool[(r + k) % 8] for k in range(1 + r % 4)] for r in range(32)
    ]
    parts = [
        _compact_part(g) if r % 5 == 4 else _indexed_part(g, entry)
        for r, g in enumerate(groups)
    ]
    return parts, sum(groups, []), set()


CASES = ("compact", "indexed", "mixed", "bad_key", "one_request",
         "requests_32")


class _Seen:
    """A row verifier that keeps what it was handed."""

    def __init__(self):
        self.blocks = []
        self.inner = svc.host_row_verifier()

    def __call__(self, rows):
        self.blocks.append(np.array(rows))
        return self.inner(rows)


class TestFlushParity:
    @pytest.mark.parametrize("name", CASES)
    def test_block_and_valid_are_what_the_flush_side_gather_gave(
            self, name):
        parts, items, refused = _case(name)
        want_full, want_valid = _old_flush(parts)
        batch = [_request(p) for p in parts]
        full, valid, prebuilt = svc.assemble_flush(batch)
        assert full.shape == want_full.shape == (128, len(items))
        assert full.dtype == np.uint8 and valid.dtype == np.bool_
        assert full.tobytes() == want_full.tobytes()
        assert valid.tobytes() == want_valid.tobytes()
        assert prebuilt == sum(p[0] != "triples" for p in parts)
        assert {i for i, v in enumerate(valid) if not v} == refused

    @pytest.mark.parametrize("name", CASES)
    def test_verdicts_match_the_host_rung(self, name):
        parts, items, refused = _case(name)
        want_full, _ = _old_flush(parts)
        seen = _Seen()
        mask = svc.verify_mixed_flush([_request(p) for p in parts], seen)
        want = [ok and i not in refused
                for i, ok in enumerate(_expected(items))]
        assert mask == want
        assert not all(want) or name == "compact"  # every case refuses one
        # one call, with the megabatch the old concatenation built
        assert len(seen.blocks) == 1
        assert seen.blocks[0].tobytes() == want_full.tobytes()

    @pytest.mark.parametrize("name", CASES)
    def test_as_compact_returns_what_admission_built(self, name):
        parts, _, _ = _case(name)
        for part in parts:
            if part[0] == "triples":
                continue
            want_rows, want_valid = _old_as_compact(
                svc.KIND_COMPACT if part[0] == "compact"
                else svc.KIND_INDEXED, *part[1:]
            )
            payload = _request(part).rows
            rows, valid = payload.as_compact()
            assert payload.n == want_rows.shape[1]
            assert rows.shape == want_rows.shape
            assert rows.tobytes() == want_rows.tobytes()
            assert valid.tobytes() == want_valid.tobytes()
            # the compact wire's own order underneath: a compact frame's
            # payload is the block, the same object
            assert payload.rows == want_rows.tobytes()
            if part[0] == "compact":
                wire = part[1].tobytes()
                assert svc.RowPayload.from_compact(wire).rows is wire

    def test_a_device_failure_goes_to_the_host_rung_and_is_counted(self):
        parts, items, _ = _case("mixed")
        calls = []

        def dead(rows):
            raise RuntimeError("device lost")

        mask = svc.verify_mixed_flush(
            [_request(p) for p in parts], dead,
            lambda exc, n: calls.append((repr(exc), n)),
        )
        assert mask == _expected(items)
        assert calls == [("RuntimeError('device lost')", len(items))]


class TestKeysAreCopiedAtAcceptance:
    def test_a_payload_owes_its_entry_nothing_once_built(self):
        items = _batch(6, b"rk0", bad=(3,))
        entry = _entry_of([svc._pk_bytes(pk) for pk, _, _ in items])
        part = _indexed_part(items, entry)
        want_full, want_valid = _old_flush([part])
        req = _request(part)
        # the entry's rows are overwritten after the frame was accepted
        # (harsher than an eviction, which only drops the reference)
        entry.pk_arr[:] = 0
        entry.pk_ok[:] = False
        full, valid, _ = svc.assemble_flush([req])
        assert full.tobytes() == want_full.tobytes()
        assert valid.tobytes() == want_valid.tobytes()
        assert svc.verify_mixed_flush(
            [req], svc.host_row_verifier()
        ) == _expected(items)

    def test_a_set_that_leaves_between_admission_and_flush_changes_nothing(
            self):
        gate = threading.Event()
        d = _Daemon("rows-evict", gate=gate)
        try:
            store = keystore.default_store()
            client = d.client("light-a")
            items = _batch(8, b"rows-evict", bad=(2,))
            pks = [svc._pk_bytes(pk) for pk, _, _ in items]
            want = _expected(items)
            vid = hashlib.sha256(b"".join(pks)).digest()[:16]
            client.register_valset(pks)
            gen = store.generation()
            # the first request's flush blocks in the verifier; the
            # second is admitted behind it and waits in the queue
            first = client.submit(items, subsystem="consensus")
            _wait(lambda: d.sched.queue_snapshot()["dispatches"] == 1)
            second = client.submit(items[::-1], subsystem="consensus")
            _wait(lambda: d.service.snapshot()["rows_prebuilt"] == 2)
            assert d.service.snapshot()["lanes"]["indexed"] == 16
            assert d.service.snapshot()["served"] == 0
            # the set LEAVES the store (an eviction does the same to the
            # generation), and its entry's rows are scribbled over
            entry = store.entry_for(vid, gen)
            assert store.invalidate(vid) == 1
            assert store.generation() == gen + 1
            entry.pk_arr[:] = 0
            gate.set()
            ok, mask = first.result(timeout=30)
            assert not ok and mask == want
            ok, mask = second.result(timeout=30)
            assert not ok and mask == want[::-1]
            # the second rode a flush of its own, joined after the set left
            assert d.sched.queue_snapshot()["dispatches"] == 2
            assert getattr(first, "reason", None) is None
            assert getattr(second, "reason", None) is None

            # the responses told the client the new generation: its next
            # submit registers again and goes indexed
            fut = client.submit(items, subsystem="consensus")
            ok, mask = fut.result(timeout=30)
            assert not ok and mask == want
            assert client.stats().get("registrations", 0) == 2
            assert d.service.snapshot()["lanes"]["indexed"] == 24

            # another set leaves behind the client's back: the frame it
            # stamps with the generation that just passed is refused where
            # it is admitted, and the same lanes are served as compact
            # rows: the protocol's recovery, no local CPU
            other = [
                ed.gen_priv_key_from_secret(b"rows-evict-other-%d" % i)
                .pub_key().bytes() for i in range(3)
            ]
            other_id = hashlib.sha256(b"".join(other)).digest()[:16]
            store.register(other_id, other)
            assert store.invalidate(other_id) == 1
            fut = client.submit(items, subsystem="consensus")
            ok, mask = fut.result(timeout=30)
            assert getattr(fut, "reason", None) is None
            assert not ok and mask == want
            assert client.stats().get("stale_resends", 0) == 1
            snap = d.service.snapshot()
            assert snap["stale_drops"] == 1
            assert snap["errors"].get("stale_generation", 0) == 1
            assert snap["lanes"].get("compact", 0) == 8
            assert snap["lanes"]["indexed"] == 24
            # the refused frame built nothing; the resend did
            assert snap["rows_prebuilt"] == snap["served"] == 4
        finally:
            gate.set()
            d.stop()


def _wait(cond, timeout=20.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.005)
    raise AssertionError("condition not reached")


# ---------------------------------------------------------------------------
# what the flush thread does inside sched.rows, counted
# ---------------------------------------------------------------------------


def _is_numpy_call(fn):
    mod = getattr(fn, "__module__", None) or ""
    if mod == "numpy" or mod.startswith("numpy."):
        return True
    owner = getattr(fn, "__self__", None)
    if isinstance(owner, (np.ndarray, np.generic)):
        return True
    return getattr(fn, "__objclass__", None) in (np.ndarray, np.generic)


def _calls_inside_sched_rows(batch):
    """C calls the calling thread makes between the entry and the exit of
    the ``sched.rows`` stage of one verify_mixed_flush -> (numpy calls,
    all C calls), by name."""
    label = tracelib.STAGE_PREFIX + "sched.rows"
    enter = tracelib.stage.__enter__.__code__
    leave = tracelib.stage.__exit__.__code__
    state = {"inside": False}
    numpy_calls, c_calls = [], []

    def hook(frame, event, arg):
        if event == "call" and frame.f_code in (enter, leave):
            if frame.f_locals["self"]._label == label:
                state["inside"] = frame.f_code is enter
        elif event == "c_call" and state["inside"]:
            name = getattr(arg, "__qualname__", repr(arg))
            c_calls.append(name)
            if _is_numpy_call(arg):
                numpy_calls.append(name)

    def verifier(rows):
        return np.ones(rows.shape[1], dtype=bool)

    sys.setprofile(hook)
    try:
        svc.verify_mixed_flush(batch, verifier)
    finally:
        sys.setprofile(None)
    return numpy_calls, c_calls


class TestFlushThreadCalls:
    def _row_requests(self, k):
        pool = _batch(8, b"rcount")
        entry = _entry_of([svc._pk_bytes(pk) for pk, _, _ in pool])
        return [
            _request(_compact_part(pool) if r % 3 == 2
                     else _indexed_part(pool, entry))
            for r in range(k)
        ]

    def test_numpy_calls_do_not_grow_with_the_requests(self):
        few, few_all = _calls_inside_sched_rows(self._row_requests(4))
        many, many_all = _calls_inside_sched_rows(self._row_requests(32))
        assert few == many == ["frombuffer", "ndarray.reshape", "frombuffer"]
        # two joins of finished blocks; what grows with the requests is
        # list bookkeeping and byte slices under the GIL (slices are
        # bytecode, not calls) and no call of another kind
        assert not set(many_all) - set(few_all)
        assert many_all.count("bytes.join") == 2
        assert few_all.count("bytes.join") == 2
        assert many_all.count("list.append") == 2 * 32

    def test_the_reference_arithmetic_does_grow(self):
        """The probe counts what it claims to: the flush-side gather's
        numpy calls, counted the same way, grow with the requests."""
        def count(k):
            pool = _batch(8, b"rcount")
            entry = _entry_of([svc._pk_bytes(pk) for pk, _, _ in pool])
            parts = [_indexed_part(pool, entry) for _ in range(k)]
            calls = []

            def hook(frame, event, arg):
                if event == "c_call" and _is_numpy_call(arg):
                    calls.append(arg)

            sys.setprofile(hook)
            try:
                _old_flush(parts)
            finally:
                sys.setprofile(None)
            return len(calls)

        assert count(32) > count(4) > 0

    def test_sched_rows_tags_say_what_the_flush_carried(self):
        ended = []
        tracer = tracelib.Tracer(sample=1.0, seed=5, on_span_end=ended.append)
        parts, items, _ = _case("mixed")
        root = tracer.start_span("dispatch")
        with tracelib.use(root):
            svc.verify_mixed_flush(
                [_request(p) for p in parts], svc.host_row_verifier()
            )
        root.end()
        (span,) = [s for s in ended if s.name == "sched.rows"]
        assert span.tags == {
            "requests": 4, "lanes": len(items), "prebuilt": 2,
        }

    def test_books_and_stages_of_a_served_fleet(self, monkeypatch):
        labels = []
        lock = threading.Lock()

        def note(label):
            with lock:
                labels.append((threading.current_thread().name, label))
            return None

        monkeypatch.setattr(tracelib, "_annotation", note)
        d = _Daemon("rows-books")
        try:
            clients = [d.client("light-%d" % i) for i in range(3)]
            items = _batch(6, b"rows-books", bad=(1,))
            pks = [svc._pk_bytes(pk) for pk, _, _ in items]
            clients[0].register_valset(pks)
            futs = [c.submit(items, subsystem="consensus")
                    for c in clients for _ in range(2)]
            for fut in futs:
                ok, mask = fut.result(timeout=30)
                assert not ok and mask == _expected(items)
                assert getattr(fut, "reason", None) is None
            snap = d.service.snapshot()
            assert snap["lanes"] == {"indexed": 12, "compact": 24}
            assert snap["rows_prebuilt"] == snap["served"] == 6
            assert d.service.metrics.rows_prebuilt is not None
        finally:
            d.stop()
        with lock:
            seen = list(labels)
        readers = [(t, lab) for t, lab in seen if t == "verify-service-r"]
        names = [lab for _, lab in readers]
        # a compact frame's payload is its block: only the two indexed
        # frames gather
        assert names.count("cbft:svc.gather") == 2
        assert names.count("cbft:svc.admit") == 6
        # the gather is a stage of its own INSIDE svc.admit
        for i, lab in enumerate(names):
            if lab == "cbft:svc.gather":
                assert "cbft:svc.admit" in names[:i]
        flush = [lab for t, lab in seen if lab == "cbft:sched.rows"]
        assert 1 <= len(flush) <= 6
        assert not [t for t, lab in seen
                    if lab == "cbft:svc.gather" and t != "verify-service-r"]

    def test_the_metrics_registry_exports_the_counter(self):
        from cometbft_tpu.libs.metrics import Registry

        reg = Registry()
        metrics = svc.ServiceMetrics(reg)
        d = _Daemon("rows-metric")
        d.service.metrics = metrics
        try:
            c = d.client("m")
            items = _batch(4, b"rows-metric")
            for _ in range(3):
                ok, _ = c.submit(items, subsystem="consensus").result(
                    timeout=30)
                assert ok
            assert metrics.rows_prebuilt.value() == 3
            assert d.service.snapshot()["rows_prebuilt"] == 3
        finally:
            d.stop()


class TestIsolatedDispatch:
    def test_coalesce_off_serves_what_admission_built(self):
        d = _Daemon("rows-iso", coalesce=False)
        try:
            client = d.client("iso")
            items = _batch(6, b"rows-iso", bad=(0, 5))
            pks = [svc._pk_bytes(pk) for pk, _, _ in items]
            ok, mask = client.submit(items, subsystem="consensus").result(
                timeout=30)
            assert not ok and mask == _expected(items)
            client.register_valset(pks)
            ok, mask = client.submit(items, subsystem="consensus").result(
                timeout=30)
            assert not ok and mask == _expected(items)
            snap = d.service.snapshot()
            assert snap["inline_dispatches"] == 2
            assert snap["lanes"] == {"compact": 6, "indexed": 6}
            assert snap["rows_prebuilt"] == snap["served"] == 2
        finally:
            d.stop()


class TestManyReaders:
    def test_eight_connections_under_a_short_switch_interval(self):
        """More reader threads than cores, the interpreter switching
        every 10 us: every block is built once, on its own connection's
        reader, and every verdict is its own request's."""
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        d = _Daemon("rows-stress")
        try:
            pool = _batch(8, b"rows-stress", bad=(3,))
            pks = [svc._pk_bytes(pk) for pk, _, _ in pool]
            want = _expected(pool)
            clients = [d.client("light-%d" % i) for i in range(8)]
            for c in clients[::2]:
                c.register_valset(pks)
            results = {}

            def run(i):
                order = [(i + k) % 8 for k in range(1 + i % 5)]
                futs = [
                    clients[i].submit([pool[j] for j in order],
                                      subsystem="consensus")
                    for _ in range(4)
                ]
                results[i] = [
                    (f.result(timeout=60)[1], [want[j] for j in order],
                     getattr(f, "reason", None))
                    for f in futs
                ]

            threads = [threading.Thread(target=run, args=(i,), daemon=True)
                       for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=90)
            assert not any(t.is_alive() for t in threads)
            assert sorted(results) == list(range(8))
            for rows in results.values():
                for mask, expect, reason in rows:
                    assert mask == expect and reason is None
            snap = d.service.snapshot()
            assert snap["rows_prebuilt"] == snap["served"] == 32
            assert d.service.pending_requests() == 0
        finally:
            sys.setswitchinterval(interval)
            d.stop()
