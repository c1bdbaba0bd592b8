"""The wire ledger's flush record (crypto/wire.FlushRecord, PR 34): a
flush's whole life as a partition of phases on one clock.

First the record on hand-made stamps and a fake clock (the identity, two
streams, a stream the answer did not wait for, what is kept after
close), then one real CPU-platform process: a device-routed and a
host-routed scheduler flush, a resident ``verify_commit`` and a canary
probe under one ledger. Last, that the five phases the ledger had
observe what they always did, and that ``libs/trace.stage`` serves the
one clock reading everything above is made of.
"""

import threading
import time

import pytest

from cometbft_tpu.crypto import wire as wirelib
from cometbft_tpu.libs import trace as tracelib
from cometbft_tpu.libs.metrics import Registry

CHAIN_ID = "flush-chain"
PARTITION = ("queue", "assemble", "route", "lead", "stream", "tail", "demux")
MS = 1_000_000  # ns


def _ledger(window=None):
    return wirelib.WireLedger(
        metrics=wirelib.Metrics(Registry()), window=window
    )


def _phase_sums(ledger):
    """{(route, phase): (sum seconds, count)} of phase_seconds."""
    from benchmark.lib import books

    out = {}
    for labels, tot in books.histogram_totals(
        ledger.metrics.phase_seconds
    ).items():
        lab = dict(labels)
        out[(lab["route"], lab["phase"])] = (tot["sum"], int(tot["count"]))
    return out


class FakeClock:
    """``time`` as wire.py and trace.py use it, on a hand-set counter."""

    def __init__(self, start_ns=5_000 * MS):
        self.now = start_ns

    def perf_counter_ns(self):
        return self.now

    def advance(self, ns):
        self.now += ns
        return self.now


@pytest.fixture
def clock(monkeypatch):
    fake = FakeClock()
    monkeypatch.setattr(wirelib, "time", fake)
    monkeypatch.setattr(tracelib, "time", fake)
    return fake


@pytest.fixture
def default_ledger():
    ledger = _ledger()
    prev = wirelib.set_default_ledger(ledger)
    try:
        yield ledger
    finally:
        wirelib.set_default_ledger(prev)


def _scheduled_flush(ledger, clock, *, streams=((7, 40),), done_after=45):
    """One scheduler-shaped life on the fake clock, in ms from the
    dispatch's entry: 3 of queue, 2 of assemble, 1 of route, the streams'
    (issue, retire) pairs, the verify back at ``done_after``, 0.5 of
    demux. → (entry, t_submit_ns, t_close_ns)."""
    t_submit = clock.now
    t_flush = clock.advance(3 * MS)
    t_lead = clock.advance(3 * MS)
    rec = ledger.open_flush(
        6464, t_submit, t_lead, queue=(t_flush - t_submit) / 1e9,
        assemble=2e-3,
    )
    rec.add("route", 1e-3)
    with wirelib.flush_scope(rec):
        wirelib.add_phase("columns", 2.5e-3)
        for issue, retire in streams:
            rec.note_issue(t_lead + issue * MS, "single")
            rec.add("build_exposed", 1.5e-3)
            rec.note_retire(t_lead + retire * MS, 4, 1616)
    t_done = t_lead + done_after * MS
    t_close = t_done + MS // 2
    entry = rec.close("single", t_done, t_close, demux=0.5e-3).entry()
    return entry, t_submit, t_close


def test_partition_adds_up_to_the_flushs_life_on_a_fake_clock(clock):
    ledger = _ledger()
    entry, t_submit, t_close = _scheduled_flush(ledger, clock)
    ph = entry["phases_ms"]
    assert ph == {
        "queue": 3.0, "assemble": 2.0, "route": 1.0, "lead": 7.0,
        "columns": 2.5, "stream": 33.0, "build_exposed": 1.5, "tail": 5.0,
        "demux": 0.5,
    }
    life_ms = (t_close - t_submit) / MS
    assert entry["life_ms"] == life_ms == 51.5
    assert sum(ph[p] for p in PARTITION) == pytest.approx(life_ms, abs=1e-9)
    assert entry["verify_ms"] == 45.0
    assert entry["route"] == "single" and entry["lanes"] == 6464
    assert entry["streams"] == 1 and entry["launches"] == 4


@pytest.mark.parametrize("phase,seconds", [
    ("queue", 3e-3), ("assemble", 2e-3), ("route", 1e-3), ("lead", 7e-3),
    ("columns", 2.5e-3), ("stream", 33e-3), ("build_exposed", 1.5e-3),
    ("tail", 5e-3),
])
def test_a_closed_record_observes_each_phase_once_under_its_route(
        clock, phase, seconds):
    ledger = _ledger()
    _scheduled_flush(ledger, clock)
    total, count = _phase_sums(ledger)[("single", phase)]
    assert count == 1
    assert total == pytest.approx(seconds, abs=1e-12)


def test_demux_is_the_schedulers_to_book_and_only_in_the_entry(clock):
    """close() keeps demux in the record and leaves the histogram and the
    EWMA rows to note_demux, which the scheduler calls as it always did."""
    ledger = _ledger()
    entry, _, _ = _scheduled_flush(ledger, clock)
    assert entry["phases_ms"]["demux"] == 0.5
    assert ("single", "demux") not in _phase_sums(ledger)
    assert ledger.demux_notes == 0


def test_two_streams_under_one_record_take_min_and_max(clock):
    ledger = _ledger()
    entry, _, _ = _scheduled_flush(
        ledger, clock, streams=((9, 30), (7, 25), (12, 41)), done_after=45
    )
    ph = entry["phases_ms"]
    assert ph["lead"] == 7.0          # the earliest issue
    assert ph["stream"] == 34.0       # ... to the latest retire
    assert ph["tail"] == 4.0
    assert ph["build_exposed"] == 4.5  # every stream's exposed build
    assert entry["streams"] == 3 and entry["launches"] == 12
    assert sum(ph[p] for p in PARTITION) == entry["life_ms"]


@pytest.mark.parametrize("streams,want", [
    # the device lost a hedge: its stream retires after the answer left
    (((7, 60),), {"lead": 7.0, "stream": 38.0, "tail": 0.0}),
    # ... or has not retired at all when the record closes
    (((7, None),), {"lead": 7.0, "stream": 38.0, "tail": 0.0}),
    # ... or was issued only after the answer left
    (((50, 70),), {"lead": 45.0, "stream": 0.0, "tail": 0.0}),
])
def test_a_stream_the_answer_did_not_wait_for_ends_where_the_verify_returned(
        clock, streams, want):
    ledger = _ledger()
    t_lead = clock.now
    rec = ledger.open_flush(lanes=8, t_lead_ns=t_lead)
    for issue, retire in streams:
        rec.note_issue(t_lead + issue * MS, "single")
        if retire is not None:
            rec.note_retire(t_lead + retire * MS, 1, 8)
    entry = rec.close("single", t_done_ns=t_lead + 45 * MS).entry()
    assert {p: entry["phases_ms"][p] for p in want} == want
    assert sum(want.values()) == entry["verify_ms"] == 45.0


def test_nothing_said_after_close_is_kept(clock):
    ledger = _ledger()
    entry, _, _ = _scheduled_flush(ledger, clock)
    rec = ledger.open_flush(lanes=1, t_lead_ns=clock.now)
    rec.note_issue(clock.advance(MS), "single")
    assert rec.close() is rec
    first = rec.entry()
    rec.add("columns", 1.0)
    rec.note_issue(clock.now - 10 * MS, "sharded")
    rec.note_retire(clock.advance(MS), 3, 99)
    assert rec.close() is None
    assert "columns" not in rec.seconds and rec.launches == 0
    assert rec.entry() == first and first["route"] == "single"
    assert ledger.flush_notes == 2
    assert ledger.flushes() == [entry, first]


def test_a_record_with_no_route_and_no_stream_books_nothing(clock):
    """verify_commit*'s own record around a commit that reached no device
    stream on its thread."""
    ledger = _ledger()
    rec = ledger.open_flush()
    rec.add("columns", 1e-3)
    clock.advance(3 * MS)
    assert rec.close() is None
    assert ledger.flush_notes == 0 and _phase_sums(ledger) == {}


def test_open_and_close_read_the_clock_once_each_when_given_no_stamp(clock):
    ledger = _ledger()
    rec = ledger.open_flush()
    assert rec.t_lead_ns == clock.now
    rec.note_issue(clock.advance(2 * MS), "resident")
    rec.note_retire(clock.advance(30 * MS), 5, 10_000)
    clock.advance(MS)
    entry = rec.close().entry()
    assert entry["route"] == "resident" and entry["lanes"] == 10_000
    assert entry["phases_ms"] == {"lead": 2.0, "stream": 30.0, "tail": 1.0}
    assert entry["life_ms"] == entry["verify_ms"] == 33.0


def test_flushes_are_bounded_by_the_window_and_served_in_the_snapshot(clock):
    ledger = _ledger(window=3)
    for _ in range(5):
        _scheduled_flush(ledger, clock)
    snap = ledger.snapshot()
    assert snap["flush_notes"] == 5
    assert len(snap["flushes"]) == 3
    assert snap["flushes"] == ledger.flushes()
    assert set(snap["flushes"][0]) == {
        "route", "lanes", "streams", "launches", "life_ms", "verify_ms",
        "phases_ms",
    }


def test_a_background_thread_opens_no_record(default_ledger):
    with tracelib.background():
        assert default_ledger.open_flush() is None
        assert wirelib.open_flush(lanes=3) is None
        with wirelib.own_flush() as rec:
            assert rec is None and wirelib.current_flush() is None
    assert wirelib.open_flush(lanes=3) is not None


def test_a_ledgerless_process_books_nothing():
    prev = wirelib.set_default_ledger(None)
    try:
        assert wirelib.open_flush(lanes=3) is None
        with wirelib.own_flush() as rec:
            assert rec is None
            wirelib.add_phase("columns", 1.0)  # no record: a no-op
            assert wirelib.current_flush() is None
    finally:
        wirelib.set_default_ledger(prev)


def test_flush_scope_nests_and_is_per_thread(default_ledger):
    outer, inner = default_ledger.open_flush(), default_ledger.open_flush()
    seen = []
    with wirelib.flush_scope(outer):
        with wirelib.flush_scope(inner):
            assert wirelib.current_flush() is inner
            with wirelib.flush_scope(None):
                assert wirelib.current_flush() is None
        assert wirelib.current_flush() is outer
        t = threading.Thread(
            target=lambda: seen.append(wirelib.current_flush())
        )
        t.start()
        t.join()
        # an entry point under a record that is there opens none of its own
        with wirelib.own_flush() as own:
            assert own is None and wirelib.current_flush() is outer
    assert seen == [None] and wirelib.current_flush() is None


def test_own_flush_closes_under_the_route_of_the_stream_that_ran(
        default_ledger, clock):
    with wirelib.own_flush() as rec:
        assert wirelib.current_flush() is rec
        rec.note_issue(clock.advance(2 * MS), "resident")
        rec.note_retire(clock.advance(8 * MS), 2, 2048)
        clock.advance(MS)
    assert wirelib.current_flush() is None
    (entry,) = default_ledger.flushes()
    assert entry["route"] == "resident"
    assert entry["phases_ms"] == {"lead": 2.0, "stream": 8.0, "tail": 1.0}
    with pytest.raises(ValueError):
        with wirelib.own_flush():
            raise ValueError("wrong signature")  # closed all the same
    assert wirelib.current_flush() is None


# --------------------------------------------------------------------------
# the phases the ledger had


CHUNK = dict(pack_s=0.004, h2d_s=0.0015, compute_s=0.0007, d2h_s=0.009,
             hidden_s=0.001)


def _feed(ledger, **extra):
    for _ in range(6):
        ledger.note_chunk("single", "dev0", 2048, 2000, 262144,
                          padded_lanes=2048, **CHUNK, **extra)
    ledger.note_demux("single", 6464, 0.0004)
    ledger.note_dispatch("single", "dev0", 2000, 0.02, 0.004, 0.0015,
                         0.0007, 0.009, 0.001, 262144, 1, **extra)


@pytest.mark.parametrize("phase,each,count", [
    ("pack", 0.004, 6), ("h2d", 0.0015, 6), ("compute", 0.0007, 6),
    ("d2h", 0.009, 6), ("demux", 0.0004, 1),
])
@pytest.mark.parametrize("fetch_s", [None, 0.003])
def test_the_five_old_phases_observe_what_they_did(phase, each, count,
                                                   fetch_s):
    ledger = _ledger()
    _feed(ledger, **({} if fetch_s is None else {"fetch_s": fetch_s}))
    sums = _phase_sums(ledger)
    total, n = sums[("single", phase)]
    assert n == count
    assert total == pytest.approx(each * count, rel=1e-12)
    want = {("single", p) for p in wirelib.PHASES}
    if fetch_s is not None:
        want.add(("single", "fetch"))
        assert sums[("single", "fetch")] == (
            pytest.approx(fetch_s * 6, rel=1e-12), 6)
    assert set(sums) == want


def test_fetch_moves_no_profile_prediction_or_lane_count():
    plain, fetched = _ledger(), _ledger()
    _feed(plain)
    _feed(fetched, fetch_s=0.003)
    for bucket in (512, 2048, 8192, 65536):
        assert fetched.predict_ms("single", bucket) == \
            plain.predict_ms("single", bucket)
    assert fetched.cost_profile().predict_ms("single", 2048) == \
        pytest.approx(15.2, rel=1e-9)
    a, b = plain.snapshot(), fetched.snapshot()
    for key in ("profiles", "demux", "lanes", "padded_lanes", "chunks"):
        assert a[key] == b[key]
    assert set(wirelib.CHUNK_PHASES) == {"pack", "h2d", "compute", "d2h"}


def test_coverage_counts_fetch():
    plain, fetched = _ledger(), _ledger()
    _feed(plain)
    _feed(fetched, fetch_s=0.003)
    assert plain.snapshot()["recent"][-1]["coverage"] == pytest.approx(
        0.0152 / 0.02, abs=1e-4)
    row = fetched.snapshot()["recent"][-1]
    assert row["fetch_ms"] == 3.0
    assert row["coverage"] == pytest.approx(0.0182 / 0.02, abs=1e-4)


# --------------------------------------------------------------------------
# the one clock reading


def test_a_stage_serves_its_own_reading(clock):
    st = tracelib.stage("unit.region")
    assert st.seconds == 0.0
    with st:
        assert st.t0_ns == clock.now
        assert st.seconds == 0.0  # open: nothing to serve yet
        clock.advance(1_234_567)
    assert st.t1_ns - st.t0_ns == 1_234_567
    assert st.seconds == pytest.approx(1.234567e-3, abs=1e-15)


def test_a_stage_that_raises_is_timed_like_any_other(clock):
    st = tracelib.stage("unit.region")
    with pytest.raises(RuntimeError):
        with st:
            clock.advance(2 * MS)
            raise RuntimeError("body")
    assert st.seconds == 2e-3


def test_stage_seconds_book_holds_exactly_the_stages_reading():
    book = tracelib.StageSeconds()
    total = 0.0
    for _ in range(5):
        st = book.stage("sync.apply")
        with st:
            time.sleep(0.001)
        assert st.seconds >= 0.001
        total += st.seconds
    assert book.snapshot() == {"sync.apply": pytest.approx(total, abs=1e-15)}


def test_two_stages_in_a_row_leave_only_the_statements_between_them():
    first, second = tracelib.stage("unit.a"), tracelib.stage("unit.b")
    with first:
        pass
    with second:
        pass
    assert first.t0_ns <= first.t1_ns <= second.t0_ns <= second.t1_ns
    assert second.t0_ns - first.t1_ns < 50_000


# --------------------------------------------------------------------------
# one real CPU-platform process


def _fixture_commit(n=6):
    from cometbft_tpu.types import test_util

    vals, privs = test_util.deterministic_validator_set(n, 10)
    bid = test_util.make_block_id()
    commit = test_util.make_commit(bid, 5, 0, vals, privs, CHAIN_ID)
    items = [
        (v.pub_key, commit.vote_sign_bytes(CHAIN_ID, i),
         commit.signatures[i].signature)
        for i, v in enumerate(vals.validators)
    ]
    return vals, bid, commit, items


@pytest.fixture(scope="module")
def process():
    """A device-routed flush, a host-routed flush, a resident commit and
    a canary probe, each after a warm-up of its own, under one ledger.
    → {"device" | "host" | "commit": (entry, caller's ns around it),
    "probe": (flush_notes, phase sums) before and after it}."""
    from cometbft_tpu.crypto.batch import BackendSpec
    from cometbft_tpu.crypto.scheduler import VerifyScheduler
    from cometbft_tpu.crypto.supervisor import BackendSupervisor

    vals, bid, commit, items = _fixture_commit()
    ledger = _ledger()
    prev = wirelib.set_default_ledger(ledger)
    dev_spec = BackendSpec("tpu", min_batch=1)  # floor lowered: device
    host_spec = BackendSpec("tpu", min_batch=1000)  # under the floor: host
    sup = BackendSupervisor(spec=dev_spec, audit_pct=0)
    host_sup = BackendSupervisor(spec=host_spec, audit_pct=0)
    dev = VerifyScheduler(spec=dev_spec, supervisor=sup, flush_us=100)
    host = VerifyScheduler(spec=host_spec, supervisor=host_sup, flush_us=100)
    dev.start()
    host.start()
    out = {}

    def timed(name, call):
        call()  # warm: executables, threads
        n = ledger.flush_notes
        t0 = time.perf_counter_ns()
        call()
        t1 = time.perf_counter_ns()
        assert ledger.flush_notes == n + 1, name
        out[name] = (ledger.flushes()[-1], t1 - t0)

    try:
        timed("commit", lambda: vals.verify_commit(
            CHAIN_ID, bid, 5, commit, backend=dev_spec))
        timed("device", lambda: dev.submit(items).result(timeout=300))
        timed("host", lambda: host.submit(items).result(timeout=300))
        before = (ledger.flush_notes, _phase_sums(ledger))
        assert sup.probe_now()
        out["probe"] = (before, (ledger.flush_notes, _phase_sums(ledger)))
    finally:
        dev.stop()
        host.stop()
        sup.stop()
        host_sup.stop()
        wirelib.set_default_ledger(prev)
    return out


def test_a_real_flushs_phases_add_up_to_its_life(process):
    """Last future set less oldest submit, to 1 % or 50 us: what falls
    between two stages is a handful of statements."""
    entry, outer_ns = process["device"]
    ph = entry["phases_ms"]
    assert set(PARTITION) | {"columns", "build_exposed"} == set(ph)
    total = sum(ph[p] for p in PARTITION)
    assert abs(total - entry["life_ms"]) <= max(
        0.05, 0.01 * entry["life_ms"])
    # and the life is the caller's own view of it, less the submit call
    # in front and the wake-up behind
    assert entry["life_ms"] <= outer_ns / 1e6
    assert outer_ns / 1e6 - entry["life_ms"] < 250.0
    assert entry["route"] == "single" and entry["lanes"] == 6
    assert entry["streams"] >= 1 and entry["launches"] >= 1
    # columns and the exposed build lie inside lead
    assert ph["columns"] + ph["build_exposed"] <= ph["lead"]


def test_a_host_routed_flush_closes_under_cpu_without_lead(process):
    entry, outer_ns = process["host"]
    assert entry["route"] == "cpu"
    assert set(entry["phases_ms"]) == {"queue", "assemble", "route", "demux"}
    assert entry["streams"] == 0 and entry["launches"] == 0
    # the host verify is in the record, in no phase
    booked = sum(entry["phases_ms"].values())
    assert abs(booked + entry["verify_ms"] - entry["life_ms"]) <= 0.05
    assert entry["life_ms"] <= outer_ns / 1e6


def test_the_resident_path_opens_its_own_record(process):
    entry, outer_ns = process["commit"]
    assert entry["route"] == "resident" and entry["lanes"] == 6
    ph = entry["phases_ms"]
    assert set(ph) == {"lead", "stream", "build_exposed", "tail"}
    # entry to return: the record is the call, but for the decorator
    assert ph["lead"] + ph["stream"] + ph["tail"] == pytest.approx(
        entry["life_ms"], abs=1e-3)
    assert entry["life_ms"] <= outer_ns / 1e6
    assert outer_ns / 1e6 - entry["life_ms"] < 5.0
    # the launch's messages were fetched under commit.msgs_chunk, whose
    # reading is the ledger's fetch phase (one observation a launch, two
    # commits) and lies inside the exposed build
    fetched, n = process["probe"][1][1][("resident", "fetch")]
    assert n == 2 and 0.0 < fetched
    assert fetched / n * 1e3 < 2 * ph["build_exposed"]


def test_a_probe_books_launch_phases_and_nothing_of_a_flush(process):
    (notes0, sums0), (notes1, sums1) = process["probe"]
    assert notes1 == notes0
    moved = {k for k in sums1 if sums1[k] != sums0.get(k)}
    assert moved, "the probe's launch is on the books as it always was"
    assert {phase for _, phase in moved} <= set(wirelib.CHUNK_PHASES)
