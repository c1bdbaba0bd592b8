"""libs/trace.stage: request-path stages in the profiler's own trace.

One ``jax.profiler`` session on the CPU platform around a
``verify_commit`` through the resident path, a scheduler flush routed to
the host and one routed to the device path (plus a canary probe and an
async audit, which are background and must write nothing); the
``.xplane.pb`` is read back with ``benchmark.trace_reduce.load`` and
every stage of the closed list is looked up by name, caller and thread.
Then the primitive on its own: no jax, unsampled, exceptions, the
flight recorder's view, and what a stage costs with no session running.
"""

import json
import sys
import threading
import time

import pytest

from cometbft_tpu.libs import trace as tracelib

CHAIN_ID = "stage-chain"
PREFIX = tracelib.STAGE_PREFIX

# stage -> (the span it must lie inside, the thread it must be on):
# "caller" is the thread that asked, "flush" the scheduler's worker,
# "worker" the supervisor's dispatch thread. The bench:* spans are the
# test's own, opened around each request on the caller's thread.
STAGES = {
    "commit.sign_bytes": ("bench:commit", "caller"),
    "commit.valset_id": ("bench:commit", "caller"),
    "commit.msgs_chunk": ("bench:commit", "caller"),
    "resident.pack": ("bench:commit", "caller"),
    "resident.launch": ("bench:commit", "caller"),
    "resident.retire": ("bench:commit", "caller"),
    "commit.tally": ("bench:commit", "caller"),
    "sched.submit": ("bench:device", "caller"),
    "sched.assemble": ("bench:device", "flush"),
    "sched.route": ("bench:device", "flush"),
    "sched.demux": ("bench:device", "flush"),
    "sup.supervise": ("bench:device", "flush"),
    "sup.device": (PREFIX + "sup.supervise", "worker"),
    "sup.columns": (PREFIX + "sup.device", "worker"),
    "mesh.pack": (PREFIX + "sup.device", "worker"),
    "mesh.launch": (PREFIX + "sup.device", "worker"),
    "mesh.retire": (PREFIX + "sup.device", "worker"),
    "host.verify": ("bench:host", "flush"),
}


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
def capture(tmp_path_factory):
    """→ {"events": [(name, line index, start_s, end_s)] of the bench:
    and cbft: spans on /host:CPU, "dumps": the sampled tracer's
    flight recorder}."""
    import jax

    from benchmark import trace_reduce
    from cometbft_tpu.crypto.batch import BackendSpec
    from cometbft_tpu.crypto.scheduler import VerifyScheduler
    from cometbft_tpu.crypto.supervisor import BackendSupervisor

    vals, bid, commit, items = _fixture_commit()
    tracer = tracelib.Tracer(sample=1.0, buffer=64)
    dev_spec = BackendSpec("tpu", min_batch=1)  # floor lowered: device
    host_spec = BackendSpec("tpu", min_batch=1000)  # under the floor: host
    sup = BackendSupervisor(spec=dev_spec, audit_pct=100, tracer=tracer)
    host_sup = BackendSupervisor(spec=host_spec, audit_pct=0)
    dev = VerifyScheduler(spec=dev_spec, supervisor=sup, tracer=tracer,
                          flush_us=100)
    host = VerifyScheduler(spec=host_spec, supervisor=host_sup,
                           tracer=tracer, flush_us=100)
    dev.start()
    host.start()
    log_dir = str(tmp_path_factory.mktemp("stage_trace"))
    try:
        # warm every executable outside the session
        vals.verify_commit(CHAIN_ID, bid, 5, commit, backend=dev_spec)
        assert dev.submit(items).result(timeout=300)[0]
        assert host.submit(items).result(timeout=300)[0]
        assert sup.probe_now()
        _wait_for(lambda: sup.metrics.audits.value() >= 1)
        audits = sup.metrics.audits.value()
        tracer.clear()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1  # annotations, not every XLA thunk
        jax.profiler.start_trace(log_dir, profiler_options=options)
        try:
            span = jax.profiler.TraceAnnotation
            with span("bench:commit"):
                vals.verify_commit(CHAIN_ID, bid, 5, commit,
                                   backend=dev_spec)
            with span("bench:device"):
                assert dev.submit(items).result(timeout=300)[0]
            with span("bench:host"):
                assert host.submit(items).result(timeout=300)[0]
            with span("bench:background"):
                assert sup.probe_now()
                _wait_for(lambda: sup.metrics.audits.value() > audits)
        finally:
            jax.profiler.stop_trace()
    finally:
        dev.stop()
        host.stop()
        sup.stop()
        host_sup.stop()
    planes = trace_reduce.load(trace_reduce.find_xplane(log_dir))
    host_plane = next(
        p for p in planes if p["name"] == trace_reduce.HOST_PLANE
    )
    events = [
        (name, i, start, start + dur)
        for i, line in enumerate(host_plane["lines"])
        for name, start, dur in line["events"]
        if name.startswith((PREFIX, "bench:"))
    ]
    return {"events": events, "dumps": tracer.recent()}


def _wait_for(cond, timeout_s=60.0):
    deadline = time.monotonic() + timeout_s
    while not cond():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.01)


def _named(capture, name):
    return [e for e in capture["events"] if e[0] == name]


def _inside(capture, outer_name, inner):
    return any(
        o[2] <= inner[2] and inner[3] <= o[3]
        for o in _named(capture, outer_name)
    )


@pytest.mark.parametrize("stage", sorted(STAGES))
def test_each_stage_is_on_the_host_plane_inside_its_caller_on_its_thread(
        capture, stage):
    outer, thread = STAGES[stage]
    caller_line = _named(capture, "bench:commit")[0][1]
    found = [e for e in _named(capture, PREFIX + stage)
             if _inside(capture, outer, e)]
    assert found, f"no {PREFIX}{stage} inside {outer}"
    lines = {e[1] for e in found}
    assert len(lines) == 1, f"{stage} on several threads: {lines}"
    line = lines.pop()
    if thread == "caller":
        assert line == caller_line
    else:
        assert line != caller_line
        # the flush thread and the dispatch worker are two threads
        other = "sup.device" if thread == "flush" else "sup.supervise"
        others = {e[1] for e in _named(capture, PREFIX + other)
                  if _inside(capture, "bench:device", e)}
        assert line not in others


def test_commit_sign_bytes_opens_once_a_commit(capture):
    """The batch build stays ONE stage a commit (not one a template or a
    lane): its count is the per-commit divisor of resident_wait_ms."""
    found = [e for e in _named(capture, PREFIX + "commit.sign_bytes")
             if _inside(capture, "bench:commit", e)]
    assert len(found) == 1


@pytest.mark.parametrize("entry", ["verify_commit", "verify_commit_light",
                                   "verify_commit_light_trusting"])
@pytest.mark.parametrize("nil_lane,templates", [(None, 1), (4, 2)])
def test_commit_sign_bytes_is_tagged_with_lanes_and_templates(
        entry, nil_lane, templates):
    """What a commit built, on the flight-recorder span only: lanes, and
    1 template (the commit's block id) or 2 (a nil precommit among the
    lanes, which only the full verify_commit selects)."""
    from cometbft_tpu.types import test_util
    from cometbft_tpu.types.block import BlockID
    from cometbft_tpu.types.validator_set import Fraction
    from cometbft_tpu.types.vote import SIGNED_MSG_TYPE_PRECOMMIT

    vals, bid, commit, _ = _fixture_commit()
    if nil_lane is not None:
        _, privs = test_util.deterministic_validator_set(6, 10)
        commit.signatures[nil_lane] = test_util.make_vote(
            privs[nil_lane], CHAIN_ID, nil_lane, 5, 0,
            SIGNED_MSG_TYPE_PRECOMMIT, BlockID()).to_commit_sig()
    tracer = tracelib.Tracer(sample=1.0)
    root = tracer.start_span("request")
    with tracelib.use(root):
        if entry == "verify_commit":
            vals.verify_commit(CHAIN_ID, bid, 5, commit, backend="cpu")
        elif entry == "verify_commit_light":
            vals.verify_commit_light(CHAIN_ID, bid, 5, commit, backend="cpu")
        else:
            vals.verify_commit_light_trusting(
                CHAIN_ID, commit, Fraction(1, 3), backend="cpu")
    root.end()
    (stage,) = [s for s in tracer.recent()[0]["spans"]
                if s["name"] == "commit.sign_bytes"]
    if entry == "verify_commit":
        assert stage["tags"] == {"lanes": 6, "templates": templates}
    else:  # for-block lanes only, up to the speculative quorum
        assert stage["tags"]["templates"] == 1
        assert 1 <= stage["tags"]["lanes"] <= 5


def test_the_traced_stages_are_the_closed_list(capture):
    names = {e[0] for e in capture["events"] if e[0].startswith(PREFIX)}
    assert names == {PREFIX + s for s in STAGES}


def test_background_work_writes_no_annotation(capture):
    """The canary probe dispatches through sup.device and mesh.*, the
    async audit through host.verify: neither is what a request waits
    for, and a gap goes to the span opened last on ANY thread."""
    (_, _, lo, hi), = _named(capture, "bench:background")
    during = [e for e in capture["events"]
              if e[0].startswith(PREFIX) and e[3] > lo and e[2] < hi]
    assert during == []
    # and the audit of the traced device flush wrote none either
    assert len(_named(capture, PREFIX + "host.verify")) == 1


def test_a_sampled_trace_shows_the_same_stage_names(capture):
    """The flight recorder's view (what tools/trace_report.py renders):
    stages under an installed span appear under their stage names,
    nested as the profiler shows them."""
    names = {s["name"] for t in capture["dumps"] for s in t["spans"]}
    assert {"sched.route", "host.verify", "mesh.pack", "mesh.launch",
            "mesh.retire"} <= names
    assert not names & {"cpu", "wire_pack", "wire_h2d", "wire_compute",
                        "wire_d2h"}
    # spans their owner makes keep their names, and the chunk's stages
    # hang under the chunk
    assert {"request", "dispatch", "supervise", "device", "chunk"} <= names
    for trace in capture["dumps"]:
        by_id = {s["span_id"]: s for s in trace["spans"]}
        for s in trace["spans"]:
            if s["name"].startswith("mesh."):
                assert by_id[s["parent_id"]]["name"] == "chunk"

    from tools import trace_report

    table = {r["stage"] for r in trace_report.stage_table(capture["dumps"])}
    assert {"mesh.retire", "host.verify", "sched.route"} <= table


# -- the primitive on its own ------------------------------------------------


class _FakeAnnotation:
    opened = []

    def __init__(self, name):
        self.name = name
        self.exited = None
        _FakeAnnotation.opened.append(self)

    def __exit__(self, etype, exc, tb):
        self.exited = etype
        return False


@pytest.fixture()
def fake_annotation(monkeypatch):
    _FakeAnnotation.opened = []
    monkeypatch.setattr(tracelib, "_annotation_cls", _FakeAnnotation)
    return _FakeAnnotation.opened


def test_without_jax_loaded_no_annotation_and_still_the_recorder_span(
        monkeypatch):
    monkeypatch.setattr(tracelib, "_annotation_cls", None)
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    tracer = tracelib.Tracer(sample=1.0)
    root = tracer.start_span("request")
    with tracelib.use(root):
        cm = tracelib.stage("sched.route", n=3)
        with cm as span:
            assert cm._ann is None
            assert span.name == "sched.route" and span.tags == {"n": 3}
            assert tracelib.current_span() is span
        assert tracelib.current_span() is root
    root.end()
    assert tracelib._annotation_cls is None and "jax" not in sys.modules
    assert [s["name"] for s in tracer.recent()[0]["spans"]] == [
        "request", "sched.route"]


def test_unsampled_it_yields_the_noop_span_and_still_annotates(
        fake_annotation):
    tracer = tracelib.Tracer(sample=0.0)
    with tracelib.use(tracer.start_span("request")):
        with tracelib.stage("sched.route", n=3) as span:
            assert span is tracelib.NOOP_SPAN
            span.set_tag("k", 1)  # keeps working
    with tracelib.stage("commit.tally") as span:  # no span installed
        assert span is tracelib.NOOP_SPAN
    assert [a.name for a in fake_annotation] == [
        "cbft:sched.route", "cbft:commit.tally"]
    assert tracer.recent() == []


def test_an_exception_in_the_body_closes_both_and_propagates(
        fake_annotation):
    tracer = tracelib.Tracer(sample=1.0)
    root = tracer.start_span("request")
    with tracelib.use(root):
        with pytest.raises(KeyError):
            with tracelib.stage("sched.demux"):
                raise KeyError("boom")
        assert tracelib.current_span() is root
    root.end()
    (ann,) = fake_annotation
    assert ann.exited is KeyError
    demux = tracer.recent()[0]["spans"][1]
    assert demux["name"] == "sched.demux" and "boom" in demux["tags"]["error"]


def test_tracing_that_fails_never_fails_the_body(monkeypatch):
    class Broken:
        def __init__(self, name):
            raise RuntimeError("profiler is gone")

    monkeypatch.setattr(tracelib, "_annotation_cls", Broken)
    with tracelib.stage("sched.route") as span:
        assert span is tracelib.NOOP_SPAN

    class BrokenExit(_FakeAnnotation):
        def __exit__(self, *exc):
            raise RuntimeError("profiler is gone")

    monkeypatch.setattr(tracelib, "_annotation_cls", BrokenExit)
    with tracelib.stage("sched.route"):
        pass


def test_a_callers_own_span_is_used_and_noop_means_annotation_only(
        fake_annotation):
    tracer = tracelib.Tracer(sample=1.0)
    own = tracer.span("supervise", state="ok")  # a root: nothing installed
    with tracelib.stage("sup.supervise", span=own) as span:
        assert span is own and tracelib.current_span() is own
        own.end(outcome="device_ok")  # the body's end wins
        # annotation only: the installed span stays the current one
        with tracelib.stage("sup.device", tracelib.NOOP_SPAN) as inner:
            assert inner is tracelib.NOOP_SPAN
            assert tracelib.current_span() is own
    assert tracelib.current_span() is None
    assert [a.name for a in fake_annotation] == [
        "cbft:sup.supervise", "cbft:sup.device"]
    (trace,) = tracer.recent()
    assert [s["name"] for s in trace["spans"]] == ["supervise"]
    assert trace["spans"][0]["tags"]["outcome"] == "device_ok"


def test_background_suppresses_the_annotation_not_the_span(fake_annotation):
    tracer = tracelib.Tracer(sample=1.0)
    root = tracer.start_span("audit")
    assert not tracelib.in_background()
    with tracelib.background(), tracelib.use(root):
        assert tracelib.in_background()
        with tracelib.stage("host.verify") as span:
            assert span.name == "host.verify"
        # what a worker re-applies from its spawner's thread
        seen = []
        t = threading.Thread(target=lambda: seen.append(
            tracelib.in_background()))
        t.start()
        t.join(10)
        assert seen == [False]  # thread-local: carried by hand
        with tracelib.background(False):
            assert tracelib.in_background()  # never lifts an outer mark
    assert not tracelib.in_background()
    root.end()
    assert fake_annotation == []
    assert [s["name"] for s in tracer.recent()[0]["spans"]] == [
        "audit", "host.verify"]


def test_ten_thousand_stages_with_no_session_cost_under_5us_each():
    """jax loaded, no profiler session: one inactive annotation and one
    no-op span a stage. Generous, CI-safe; the measured figure is in
    PERF.md beside each cell's stages per request."""
    import jax  # noqa: F401 - the annotation must be the real one

    assert tracelib._annotation("cbft:warm") is not None
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(10_000):
            with tracelib.stage("sched.route"):
                pass
        best = min(best, (time.perf_counter() - t0) / 10_000)
    print(json.dumps({"stage_off_cost_us": best * 1e6}))
    assert best < 5e-6
