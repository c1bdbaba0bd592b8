"""Device-mesh plumbing for the crypto plane — batch parallelism over
signatures as a first-class component (SURVEY.md §2.16).

The gossip network stays on CPU/TCP; the DEVICE plane scales by
sharding the signature batch (the trailing lane axis of every kernel
input) across whatever devices are visible:

* single host, multiple chips — one mesh axis ("batch") over ICI;
* multiple hosts — initialize `jax.distributed` first
  (`maybe_init_distributed`, driven by the standard JAX env vars or
  [crypto] coordinator config), then the SAME mesh spans all hosts'
  devices and XLA routes the all-gather of the verdict mask over
  ICI within a host and DCN across hosts. No NCCL/MPI: collectives are
  compiled into the program.

Every device dispatch is one `launch_stream`: `dispatch_batch` (one
chip, or every visible device when nothing placed the batch),
`dispatch_sharded` (the healthy fault domains' sub-mesh) and the
resident, indexed and service entries that call it with rows of their
own.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
import warnings
from typing import Any, Callable, NamedTuple, Optional, Sequence

from cometbft_tpu.libs import trace as _trace

# the CPU fallback platform can't honor buffer donation and warns on
# every dispatch; install the filter ONCE here — per-dispatch
# warnings.catch_warnings() would mutate process-global filter state
# from multiple threads (warmup + consensus both dispatch)
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable"
)

_mtx = threading.Lock()
_cached = None


# --- cancellable dispatch entry ---------------------------------------------
# An XLA dispatch cannot be interrupted once issued, but the chunk loop
# CAN stop between chunks. The supervisor's watchdog (crypto/
# supervisor.py) abandons a wedged dispatch thread and sets its cancel
# event; the zombie then exits at the next chunk boundary instead of
# grinding through the rest of the batch against a dead device.

_cancel_local = threading.local()


class DispatchCancelled(RuntimeError):
    """The dispatch's cancel event fired (watchdog abandoned it)."""


def current_cancel_event() -> Optional[threading.Event]:
    """The cancel event installed on THIS thread, if any."""
    return getattr(_cancel_local, "event", None)


class cancel_scope:
    """Context manager installing ``event`` as this thread's dispatch
    cancel event; dispatch_batch checks it at every chunk boundary."""

    def __init__(self, event: threading.Event):
        self._event = event
        self._prev = None

    def __enter__(self):
        self._prev = getattr(_cancel_local, "event", None)
        _cancel_local.event = self._event
        return self._event

    def __exit__(self, *exc_info):
        _cancel_local.event = self._prev
        return False


# --- dispatch route (cpu / single-chip / sharded mesh) ----------------------
# The scheduler decides per coalesced flush which rung of the routing
# ladder a batch takes (see calibrate.shard_min_batch for the learned
# crossover); the supervisor installs the decision on the dispatching
# thread, same pattern as cancel_scope. No route installed = legacy
# behavior: dispatch_batch auto-shards over the full mesh when more
# than one device is visible.

ROUTE_SINGLE = "single"    # force one chip even when a mesh is visible
ROUTE_SHARDED = "sharded"  # the healthy-sub-mesh megabatch path

_route_local = threading.local()


def current_route() -> Optional[str]:
    """The dispatch route installed on THIS thread, if any."""
    return getattr(_route_local, "route", None)


class route_scope:
    """Context manager installing ``route`` (ROUTE_SINGLE /
    ROUTE_SHARDED / None) as this thread's dispatch route; nests."""

    def __init__(self, route: Optional[str]):
        self._route = route
        self._prev = None

    def __enter__(self):
        self._prev = getattr(_route_local, "route", None)
        _route_local.route = self._route
        return self._route

    def __exit__(self, *exc_info):
        _route_local.route = self._prev
        return False


def parse_route(raw: Optional[str]) -> Optional[str]:
    """Parse one CBFT_MESH_ROUTE value: ROUTE_SINGLE / ROUTE_SHARDED
    for a pin, None for auto/unset (size routing), ValueError on
    anything else. Pure — the scheduler's parse-once pin cache and
    route_override share it."""
    if raw is None:
        return None
    raw = raw.strip().lower()
    if raw in ("", "auto"):
        return None
    if raw in (ROUTE_SINGLE, ROUTE_SHARDED):
        return raw
    raise ValueError(
        f"CBFT_MESH_ROUTE={raw!r} must be auto, single, or sharded"
    )


def route_override() -> Optional[str]:
    """Operator A/B override of the scheduler's routing decision:
    CBFT_MESH_ROUTE=auto|single|sharded (auto/unset = learned
    crossover)."""
    return parse_route(os.environ.get("CBFT_MESH_ROUTE"))


def maybe_init_distributed() -> bool:
    """Initialize jax.distributed for a multi-host verification plane
    when the operator configured one. Runs automatically on first mesh
    construction (batch_mesh), before any device set is cached.

    Config: either the standard JAX env (JAX_COORDINATOR_ADDRESS +
    JAX_NUM_PROCESSES/JAX_PROCESS_ID, auto-detected by
    jax.distributed.initialize()) or the explicit CBFT_TPU_COORDINATOR /
    CBFT_TPU_NUM_PROCESSES / CBFT_TPU_PROCESS_ID trio — the CBFT vars
    are only passed when set, so they never override the JAX ones.
    Single-host runs (no coordinator configured) skip this entirely.
    → True if a multi-process runtime is active."""
    addr_cbft = os.environ.get("CBFT_TPU_COORDINATOR")
    addr_jax = os.environ.get("JAX_COORDINATOR_ADDRESS")
    if not addr_cbft and not addr_jax:
        return False
    import jax

    kwargs = {}
    if addr_cbft:
        kwargs["coordinator_address"] = addr_cbft
        if os.environ.get("CBFT_TPU_NUM_PROCESSES"):
            kwargs["num_processes"] = int(os.environ["CBFT_TPU_NUM_PROCESSES"])
        if os.environ.get("CBFT_TPU_PROCESS_ID"):
            kwargs["process_id"] = int(os.environ["CBFT_TPU_PROCESS_ID"])
    try:
        jax.distributed.initialize(**kwargs)
    except Exception as exc:
        if jax.process_count() > 1:
            return True  # already initialized (idempotent restart)
        if addr_cbft:
            # the operator EXPLICITLY configured a multi-host plane:
            # failing to form it must stop the node, not degrade into a
            # silently split cluster verifying on disjoint hosts
            raise RuntimeError(
                f"CBFT_TPU_COORDINATOR={addr_cbft!r} is set but "
                f"jax.distributed.initialize failed: {exc}"
            ) from exc
        import sys

        print(
            "cometbft-tpu: ambient JAX_COORDINATOR_ADDRESS present but "
            f"jax.distributed.initialize failed ({exc}); continuing "
            "single-host",
            file=sys.stderr,
        )
        return False
    return jax.process_count() > 1


def batch_mesh():
    """One 1-D mesh over every visible device, cached. The batch axis is
    the only parallel axis the crypto plane needs — signatures are
    embarrassingly parallel; collectives appear only for the output
    gather."""
    global _cached
    with _mtx:
        if _cached is not None:
            return _cached
        maybe_init_distributed()  # must run before the device set is read
        import jax
        import numpy as np
        from jax.sharding import Mesh

        devs = np.array(jax.devices())
        _cached = Mesh(devs, ("batch",))
        return _cached


def live_devices() -> Optional[list]:
    """The mesh's jax devices if this process already holds them (some
    dispatch or start-up gate built the mesh), else None. For observers
    — the memory plane's poll, snapshots — which must never be the call
    that takes the accelerator: a cpu-backend node beside a verifyd that
    owns the chip polls its memory plane too."""
    with _mtx:
        cached = _cached
    return list(cached.devices.flat) if cached is not None else None


def n_devices() -> int:
    # via batch_mesh so maybe_init_distributed runs BEFORE the first
    # jax.devices() call — initialize() refuses to run once any backend
    # is up, and verify_batch's device-count probe is the first touch
    return int(batch_mesh().devices.size)


_plane = None


def device_plane() -> dict:
    """What jax gave THIS process — the one that will dispatch — resolved
    once: platform, device kind and count as jax reports them, the
    runtime versions, and where the compile cache lives. The first call
    takes the accelerator (a chip belongs to one process), so nothing
    that is not going to dispatch may call it. /debug/verify, the verifyd
    snapshot and the smoke all print this record."""
    global _plane
    with _mtx:
        if _plane is not None:
            return _plane
    devs = list(batch_mesh().devices.flat)
    import jax
    import jaxlib

    from cometbft_tpu.crypto.tpu import aot

    try:
        from importlib import metadata

        libtpu = metadata.version("libtpu")
    except Exception:  # noqa: BLE001 - not installed on CPU-only hosts
        libtpu = None
    plane = {
        "platform": devs[0].platform,
        "device_kind": getattr(devs[0], "device_kind", "?"),
        "n_devices": len(devs),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
        "compile_cache_dir": aot.compile_cache_dir(),
    }
    with _mtx:
        _plane = plane
    return plane


def resolved_plane() -> Optional[dict]:
    """device_plane()'s record once this process holds its devices
    (live_devices), else None — for snapshots, which must never be the
    call that takes the accelerator."""
    return device_plane() if live_devices() is not None else None


def require_accelerator(what: str) -> dict:
    """Start-up gate of everything that was CONFIGURED for the device
    (``[crypto] backend = "tpu"``, ``verifyd --backend tpu``): jax must
    have found a TPU. The one exception is a JAX_PLATFORMS whose FIRST
    entry is cpu — how the tests and the e2e runner ask for the virtual
    CPU mesh. Anything else (no libtpu, a chip held by another process
    and jax falling back — which is what a ``tpu,cpu`` list resolving to
    cpu means) is a start-up error naming the platform found, never a
    quiet CPU verifier behind a device name."""
    plane = device_plane()
    if plane["platform"] == "tpu":
        return plane
    asked = os.environ.get("JAX_PLATFORMS", "")
    if asked.split(",")[0].strip().lower() == "cpu":
        return plane
    raise RuntimeError(
        f"{what} asks for the tpu backend but jax found platform "
        f"{plane['platform']!r} ({plane['device_kind']}, "
        f"{plane['n_devices']} device(s)); set JAX_PLATFORMS=cpu to run "
        "the kernels on the CPU platform on purpose"
    )


# [crypto] max_chunk, installed by node start (configure_chunk_cap).
# Module state rather than an env var so in-process multi-node setups
# don't leak one node's tuning into another via the process environment
# — though the cap tunes the LINK, so differing values on one host are
# a configuration smell; last configure wins.
_configured_cap: Optional[int] = None


def configure_chunk_cap(cap: Optional[int]) -> None:
    """Install the [crypto] max_chunk default for every curve kernel.
    An explicitly-set CBFT_TPU_MAX_CHUNK env var still wins (operator
    A/B override, same precedence as the min_batch knob)."""
    global _configured_cap
    _configured_cap = cap


def resolve_chunk_cap(default: int, min_pad: int) -> int:
    """Resolve the node-wide dispatch chunk cap, BEFORE any per-device
    OOM shrink: CBFT_TPU_MAX_CHUNK (validated) beats the configured
    [crypto] max_chunk beats the caller's per-curve default; the winner
    is rounded UP to a power of two, so the dispatched bucket always
    equals a padded shape and warmup covers it. One knob governs every
    curve kernel — the cap tunes a property of the LINK (per-dispatch
    cost vs bytes), not of a curve."""
    raw = os.environ.get("CBFT_TPU_MAX_CHUNK")
    if raw is None:
        if _configured_cap is None:
            cap = default
        else:
            # config is validated at load (config.validate_basic); a cap
            # below the curve's minimum pad just means "smallest bucket"
            cap = max(int(_configured_cap), min_pad)
    else:
        try:
            cap = int(raw)
        except ValueError:
            raise ValueError(
                f"CBFT_TPU_MAX_CHUNK={raw!r} is not an integer"
            ) from None
        if cap < min_pad:
            raise ValueError(
                f"CBFT_TPU_MAX_CHUNK={cap} is below the minimum pad {min_pad}"
            )
    size = min_pad
    while size < cap:
        size *= 2
    return size


def chunk_cap(default: int, min_pad: int) -> int:
    """The resolved cap halved once per active OOM shrink level of the
    DEFAULT device (topology device 0), never below min_pad — a
    RESOURCE_EXHAUSTED device keeps serving smaller chunks instead of
    being abandoned wholesale. Per-device callers use
    DeviceHandle.chunk_cap (crypto/tpu/topology.py) instead."""
    return max(min_pad, resolve_chunk_cap(default, min_pad)
               >> chunk_shrink_levels())


# --- OOM-adaptive chunk cap (runtime shrink / hysteretic recovery) ----------
# A device raising RESOURCE_EXHAUSTED is not broken — it is over-chunked
# (HBM pressure from another tenant, a bigger-than-calibrated pad, a
# fragmented allocator). The supervisor halves the effective cap and
# retries instead of striking the breaker; the cap recovers one doubling
# per N clean dispatches (hysteresis: one stray OOM must not oscillate
# the chunk size).
#
# The shrink ladder is PER FAULT DOMAIN (crypto/tpu/topology.py
# DeviceHandle) — one over-chunked chip must not shrink its healthy
# neighbors' dispatches. The module-level functions below are the
# single-device shim: they delegate to the default topology's device 0,
# so pre-topology callers and tests see the exact old behavior.

MAX_SHRINK_LEVELS = 6  # 8192 → 128 floor; min_pad clamps earlier anyway


def _shim_device():
    """Device 0 of the process-default topology — the fault domain the
    legacy module-global chunk-cap API maps onto."""
    from cometbft_tpu.crypto.tpu import topology

    return topology.default_topology().device(0)


def chunk_shrink_levels() -> int:
    """How many halvings are applied to the default device's cap."""
    return _shim_device().chunk_shrink_levels()


def shrink_chunk_cap() -> bool:
    """Halve the default device's effective chunk cap after an OOM.
    → True if a level was added, False at the floor (the caller should
    then treat the OOM as persistent)."""
    return _shim_device().shrink_chunk_cap()


def note_clean_dispatch(recover_n: int) -> bool:
    """Record one clean dispatch on the default device; after
    ``recover_n`` consecutive clean dispatches one shrink level is
    removed. → True when a level was recovered on this call."""
    return _shim_device().note_clean_dispatch(recover_n)


def reset_chunk_shrink() -> None:
    """Drop the DEFAULT TOPOLOGY's shrink state — every device, not just
    device 0 (supervisor stop, tests, chaos harness setup)."""
    from cometbft_tpu.crypto.tpu import topology

    topology.default_topology().reset_runtime_state()


def effective_chunk_cap(default: int = 8192, min_pad: int = 64) -> int:
    """The cap dispatch_batch would use right now (gauge fodder)."""
    return chunk_cap(default, min_pad)


def pipeline_depth() -> int:
    """How many chunk dispatches may be in flight before the oldest is
    retired. 2 = double buffering: the host packs/transfers chunk N+1
    while the device computes chunk N — the measured win (two pipelined
    8k chunks beat one 16k dispatch ~1.8× on the round-5 shared chip,
    MAXCHUNK16K.jsonl) — while staging memory stays bounded at two
    chunks' wire. Deeper pipelines buy nothing once transfer and compute
    overlap (the link is the bottleneck) and cost HBM per stage."""
    raw = os.environ.get("CBFT_TPU_PIPELINE_DEPTH")
    if raw is None:
        return 2
    try:
        depth = int(raw)
    except ValueError:
        raise ValueError(
            f"CBFT_TPU_PIPELINE_DEPTH={raw!r} is not an integer"
        ) from None
    if depth < 1:
        raise ValueError(f"CBFT_TPU_PIPELINE_DEPTH={depth} must be >= 1")
    return depth


def placement(handle):
    """The jax device a topology.DeviceHandle stands for, or None when
    the handle has no chip of its own: the single-domain topology (jax's
    default placement IS its chip) and virtual domains (logical only).
    A member of a detected mesh is device ``index`` of jax's list."""
    from cometbft_tpu.crypto.tpu import topology

    if handle is None or handle.kind != topology.KIND_MESH:
        return None
    devs = list(batch_mesh().devices.flat)
    return devs[handle.index] if handle.index < len(devs) else None


def run_single(kernel, args, donate_from: int = 0, device=None):
    """Run `kernel` single-device through the AOT executable registry
    with args [donate_from:] donated — the per-chunk staging buffers
    are single-use, so XLA reuses their space instead of holding input
    + workspace live together (matters at the 8k-lane chunks). The registry (crypto/tpu/aot.py) keys by stable
    kernel name + exact arg shapes + fingerprints — never by id(), which
    CPython reuses after GC — and is what warm boot pre-populates, so a
    warmed bucket never pays trace+compile here. ``device`` (a jax
    device, see placement) runs the program on that chip: the args must
    already live there."""
    from cometbft_tpu.crypto.tpu import aot

    return aot.default_registry().call(
        kernel, list(args), donate_from=donate_from, sharded=False,
        device=device,
    )


def launch_stream(kernel, launches, build, n: int, *, where, prefix: str,
                  route: str, device_label: str, domains=(),
                  donate_from: int = 0, fetch=None, curve: str = "ed25519",
                  programs=None):
    """THE loop from "here are lanes" to "here is the mask": every device
    dispatch of the crypto plane (dispatch_batch, dispatch_sharded, the
    resident commit, the indexed key store, verifyd's rows) is this
    stream with its own inputs. → (mask bool[n], the call's phase
    totals: note_dispatch's keyword arguments).

    ``launches`` is the one rounding rule's [(start, end, size, *lead)]
    (shard_chunks): lanes [start, end) of the batch padded to ``size``;
    ``lead`` are arguments already on the device that lead the kernel
    call and outlive it (the resident pubkey rows, the indexed table), so
    ``donate_from`` counts past them. ``build(start, end)`` gives the
    launch's host arrays, trailing axis = its real lanes; it is asked
    once a launch, in order, when that launch is next, and which lanes it
    found malformed stays its caller's business. ``fetch(chunk, start,
    end, inflight)``, where given, gathers what build will pack and is
    no packing itself (a commit's sign-bytes, under a stage of their
    own) and returns that stage's seconds: the wire ledger's ``fetch``
    phase. ``curve`` names whose lanes the launches carry (the wire
    ledger's book by curve); ``programs``, where a stream mixes curves
    (launch_parts), gives each launch its own ``(kernel, donate_from,
    curve)`` in place of those three. ``where`` is the placement: a jax
    Mesh (arguments sharded on
    the trailing axis, the sharded executable) or one jax device / None
    for jax's default (run_single). ``domains`` are the labels of the
    fault domains the lanes are attributed to, in shard order: a shard
    span and a telemetry-hub row each, and the wire ledger's bucket is
    the lanes of one (none given: of the launch).

    One order of work: build launch k, issue it (device_put and the call
    both return before the device is done), retire the oldest beyond
    pipeline_depth(), then build launch k+1. So a launch's host work and
    H2D run behind the device's work on the launches in flight; on the
    v5e this order beat building every lane first 100.48 to 128.12 ms a
    10,000-lane commit (PERF.md, PR 27). The thread's cancel event is
    checked at every launch boundary; a launch that fails says which it
    was and which lanes it held.

    One clock: a launch's ``pack`` / ``h2d`` / ``compute`` / ``d2h`` are
    the readings its three stages take anyway (libs/trace.stage), split
    once inside ``.launch`` between device_put and the call. The flush
    record this thread works for (crypto/wire.current_flush; none on a
    background thread) is stamped with launch 0's issue and the last
    retire, and is told the build seconds no launch in flight hid."""
    from collections import deque

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

    from cometbft_tpu.crypto import telemetry as _telemetry
    from cometbft_tpu.crypto import wire as _wirelib
    from cometbft_tpu.crypto.tpu import aot

    if isinstance(where, Mesh):
        nsh = int(where.devices.size)
        registry = aot.default_registry()

        def put(a):
            # numpy rows go host -> each shard's chip; jnp.asarray first
            # would land the whole launch on chip 0 and make the
            # placement a chip-to-chip copy
            return jax.device_put(a, NamedSharding(
                where, PS(*([None] * (a.ndim - 1) + ["batch"]))
            ))

        def call(kernel, donate_from, args):
            return registry.call(
                kernel, args, donate_from=donate_from, sharded=True,
                mesh=where,
            )
    else:
        nsh = 1

        def put(a):
            return jax.device_put(jnp.asarray(a), where)

        def call(kernel, donate_from, args):
            return run_single(
                kernel, args, donate_from=donate_from, device=where
            )

    hub = _telemetry.default_hub()
    ledger = _wirelib.default_ledger()
    flush = _wirelib.current_flush()
    depth = pipeline_depth()
    cancel = current_cancel_event()
    # an executable a launch has to build first (registry miss) is host
    # work: its seconds are left out of the compute phase and of the wall
    # the ledger prices routes with
    clock = aot.build_clock()
    built0 = clock.total()
    t_wall0 = time.perf_counter()
    tot = {"wall_s": 0.0, "fetch_s": 0.0, "pack_s": 0.0, "h2d_s": 0.0,
           "compute_s": 0.0, "d2h_s": 0.0, "hidden_s": 0.0, "wire_bytes": 0,
           "chunks": 0}
    last_retire_ns = 0
    out = np.zeros(n, bool)
    inflight: "deque" = deque()
    n_domains = max(1, len(domains))

    @contextlib.contextmanager
    def launch_context(what, chunk, start, end, span, shard_spans):
        """Ends the launch's spans with the error and says which launch
        it was; a cancellation passes through as it is."""
        try:
            yield
        except Exception as exc:  # noqa: BLE001 - per-launch context for triage
            cancelled = isinstance(exc, DispatchCancelled)
            for s in (*shard_spans, span):
                s.end(error="cancelled" if cancelled else repr(exc))
            if cancelled:
                raise
            raise RuntimeError(
                f"{route} {what} of chunk {chunk} (sigs [{start}:{end}]) "
                f"on {device_label} failed: {exc}"
            ) from exc

    def retire(slot):
        nonlocal last_retire_ns
        chunk, start, end, size, mask, span, shard_spans, phases, \
            lane_curve = slot
        # np.asarray blocks until the device finishes this launch (and,
        # sharded, gathers the mask's slices from the chips): the wait
        # the stage reads IS the device-time attribution of the span
        waited = _trace.stage(prefix + ".retire", span=span.child(
            prefix + ".retire", shards=nsh, lanes_per_shard=size // nsh,
        ))
        with launch_context("retire", chunk, start, end, span, shard_spans), \
                waited:
            out[start:end] = np.asarray(mask)[: end - start]
        last_retire_ns = waited.t1_ns
        wait_ns = waited.t1_ns - waited.t0_ns
        tot["d2h_s"] += wait_ns / 1e9
        if ledger is not None:
            ledger.note_chunk(
                route, device_label, size // n_domains, end - start,
                d2h_s=wait_ns / 1e9, padded_lanes=size, curve=lane_curve,
                **phases,
            )
        for s in (*shard_spans, span):
            s.end(device_wait_ns=wait_ns)

    def drain(keep):
        while len(inflight) > keep:
            retire(inflight.popleft())

    def issue(chunk, start, end, size, lead, program):
        """Builds and issues one launch -> its in-flight slot. A function
        of its own, so that the launch's staging (the built and padded
        host arrays, the donated device buffers) is released when it
        returns, while the device works, and not after the last retire
        (kept until then, qa150-blocksync read 3 % slower: PERF.md, PR 28)."""
        real = end - start
        flying = len(inflight)
        span = _trace.child_of_current(
            "chunk", chunk=chunk, n_sigs=real, shards=nsh
        )
        fetch_s = 0.0
        with launch_context("dispatch", chunk, start, end, span, ()):
            if fetch is not None:
                fetch_s = fetch(chunk, start, end, flying) or 0.0
            packed = _trace.stage(prefix + ".pack", span=span.child(
                prefix + ".pack", chunk=chunk, inflight=flying,
            ))
            with packed:
                padded = []
                for a in build(start, end):
                    p = np.zeros(a.shape[:-1] + (size,), a.dtype)
                    p[..., :real] = a
                    padded.append(p)
            built = clock.total()
            # the ISSUE cost: both calls return before the device is done;
            # only the launch's own staging is donated
            issued = _trace.stage(prefix + ".launch", span=span.child(
                prefix + ".launch", shards=nsh, lanes_per_shard=size // nsh,
                chunk=chunk, inflight=flying,
            ))
            with issued:
                placed = [put(p) for p in padded]
                # the one reading no stage takes: it splits the launch
                # stage between the transfer's issue and the call's
                t_h2d = time.perf_counter_ns()
                mask = call(program[0], program[1], lead + placed)
        if flush is not None:
            if chunk == 0:
                flush.note_issue(issued.t1_ns, route)
            if not flying:
                # nothing was in flight: the device waited for this build
                flush.add("build_exposed", fetch_s + packed.seconds)
        # attribution comes after the issue: nothing the device does not
        # need stands between a launch's pack and its start
        per = size // n_domains
        shard_spans = []
        for si, label in enumerate(domains):
            lanes = max(0, min(per, real - si * per))
            shard_spans.append(span.child(
                "shard", device=label, shard=si, n_sigs=lanes, pad=per,
            ))
            if hub is not None:
                hub.note_chunk(label, lanes, per)
        h2d_s = (t_h2d - packed.t1_ns) / 1e9
        phases = {
            "fetch_s": fetch_s,
            "pack_s": packed.seconds,
            "h2d_s": h2d_s,
            "compute_s": max(
                0.0, (issued.t1_ns - t_h2d) / 1e9 - (clock.total() - built)
            ),
            # a transfer issued while an earlier launch was in flight paid
            # no wall time of its own (only launch 0's H2D is exposed)
            "hidden_s": h2d_s if flying else 0.0,
            "wire_bytes": sum(int(p.nbytes) for p in padded),
        }
        for key, v in phases.items():
            tot[key] += v
        tot["chunks"] += 1
        span.set_tag("pad", size)
        span.set_tag("wire_bytes", phases["wire_bytes"])
        for key in ("pack_s", "h2d_s", "compute_s", "hidden_s"):
            span.set_tag(key[:-2] + "_ns", int(phases[key] * 1e9))
        # host time of the launch: pack + H2D issue + kernel issue
        span.set_tag("host_ns", int(
            (phases["pack_s"] + h2d_s + phases["compute_s"]) * 1e9
        ))
        return (chunk, start, end, size, mask, span, shard_spans, phases,
                program[2])

    if programs is None:
        programs = [(kernel, donate_from, curve)] * len(launches)
    for chunk, ((start, end, size, *lead), program) in enumerate(
            zip(launches, programs)):
        if cancel is not None and cancel.is_set():
            raise DispatchCancelled(
                f"{route} dispatch cancelled before chunk {chunk} "
                f"(sigs [{start}:{n}] undone)"
            )
        inflight.append(issue(chunk, start, end, size, lead, program))
        drain(depth)
    drain(0)
    if flush is not None and tot["chunks"]:
        flush.note_retire(last_retire_ns, tot["chunks"], n)
    tot["wall_s"] = max(
        0.0, time.perf_counter() - t_wall0 - (clock.total() - built0)
    )
    return out, tot


class StreamPart(NamedTuple):
    """One curve's lanes of a stream that mixes curves (launch_parts):
    its program (``kernel``, ``donate_from`` counted past the launch's
    ``lead``), its ``launches`` [(start, end, size, *lead)] over its own
    ``n`` lanes, launch_stream's ``build`` and ``fetch`` over the same
    lanes, and ``valid``, bool[n], which ``build`` clears for the lanes
    it found malformed."""

    curve: str
    kernel: Any
    donate_from: int
    launches: list
    build: Callable
    fetch: Optional[Callable]
    n: int
    valid: Any


def launch_parts(parts: Sequence[StreamPart], *, where, prefix: str,
                 route: str, device_label: str, domains=()):
    """The parts' launches as ONE launch_stream, a launch of each part in
    turn (round robin, in the order given), so that one curve's build
    runs behind the device's work on the other's launches; the curve is
    a dimension of the launch. → ([mask bool[part.n] & part.valid per
    part], the stream's phase totals)."""
    import numpy as np

    offsets, total = [], 0
    for part in parts:
        offsets.append(total)
        total += part.n
    queues = [[(offsets[i] + start, offsets[i] + end, size, *lead)
               for start, end, size, *lead in part.launches]
              for i, part in enumerate(parts)]
    launches, owners = [], []
    for turn in range(max((len(q) for q in queues), default=0)):
        for i, q in enumerate(queues):
            if turn < len(q):
                launches.append(q[turn])
                owners.append(i)
    by_start = {launch[0]: i for launch, i in zip(launches, owners)}

    def build(start, end):
        i = by_start[start]
        return parts[i].build(start - offsets[i], end - offsets[i])

    def fetch(chunk, start, end, inflight):
        i = by_start[start]
        part = parts[i]
        if part.fetch is None:
            return 0.0
        return part.fetch(chunk, start - offsets[i], end - offsets[i],
                          inflight)

    programs = [(parts[i].kernel, parts[i].donate_from, parts[i].curve)
                for i in owners]
    out, tot = launch_stream(
        parts[0].kernel if parts else None, launches, build, total,
        where=where, prefix=prefix, route=route, device_label=device_label,
        domains=domains, fetch=fetch, programs=programs,
    )
    masks = [np.asarray(out[offsets[i]:offsets[i] + part.n]) & part.valid
             for i, part in enumerate(parts)]
    return masks, tot


def _slices_of(packed):
    """dispatch_batch's ``packed`` as the stream's ``build``: the callable
    itself, or slices of the pre-packed arrays' trailing axis."""
    if callable(packed):
        return packed
    return lambda start, end: [a[..., start:end] for a in packed]


def _note_dispatch(route: str, device_label: str, n: int, tot: dict) -> None:
    """The per-call row of a mesh entry in the wire ledger."""
    from cometbft_tpu.crypto import wire as _wirelib

    ledger = _wirelib.default_ledger()
    if ledger is not None and tot["chunks"]:
        ledger.note_dispatch(route, device_label, n, **tot)


def dispatch_batch(kernel, packed, n: int, max_chunk: int, min_pad: int,
                   device=None, launch: Optional[int] = None,
                   curve: str = "ed25519"):
    """Chunk-pad-dispatch of a batch verify kernel (used by all three
    curve entries): cuts the batch into launches of at most the chunk
    cap, pads each launch's trailing batch axis to a power of two
    (shard_chunks), runs them as one launch_stream and gathers the
    boolean masks.

    ``max_chunk`` is the CEILING of a launch ([crypto] max_chunk, halved
    by the OOM-shrink ladder and the memory guard). ``launch``, where the
    curve entry has measured one, is the SIZE of a launch in lanes a
    chip, applied below the ceiling: a batch of more lanes is a stream
    of such launches, each packed while the ones before it run; the one
    short launch goes first and pads to at least half a launch, so the
    shapes a batch of any size can reach are two a chip (shard_chunks'
    ``short_floor``). With none given a launch is as large as the
    ceiling allows.

    ``curve`` is whose lanes they are (the wire ledger's book by curve).
    ``device`` is an optional topology.DeviceHandle naming the fault
    domain this dispatch runs against; when omitted the thread's
    device_scope (installed by the supervisor) is consulted, and with
    neither the default device-0 chunk cap applies. The handle selects
    whose OOM-shrink ladder caps the chunk size and, when it is a member
    of a detected mesh, the chip the chunks are placed and run on
    (placement) — so a fault domain's breaker judges its own chip.

    `packed` is either a list of pre-packed arrays (trailing axis = the
    full batch) or a callable ``(start, end) -> list`` producing one
    chunk's arrays on demand — the callable form lets the caller's host
    packing (SHA-512 hashing, merlin transcripts, scalar inversions) for
    chunk i+1 overlap the device's transfer+compute of chunk i, since
    jax dispatch returns before the result is ready. Staging buffers are
    donated."""
    route = current_route()
    if route == ROUTE_SHARDED:
        plan = shard_plan()
        if plan is not None:
            return dispatch_sharded(
                kernel, packed, n, max_chunk, min_pad, plan=plan
            )
        # the mesh shrank under us (quarantine left <2 usable devices):
        # fall through to the single-device path rather than failing
        route = ROUTE_SINGLE
    if device is None:
        from cometbft_tpu.crypto.tpu import topology

        device = topology.current_device()
    # pre-dispatch memory guard (crypto/tpu/memory.py): project this
    # dispatch's footprint and clamp the chunk cap BEFORE the allocator
    # can fail — the reactive OOM rung stays as the last resort. The
    # guarded cap lands on the device handle, so the chunk_cap reads
    # below already include it. Device-less dispatches guard (and cap)
    # against the module shim's device 0, matching the telemetry shim.
    from cometbft_tpu.crypto.tpu import memory as _memory

    _plane = _memory.default_plane()
    _guard_dev = device if device is not None else _shim_device()
    _kernel_name = getattr(kernel, "__name__", "kernel")
    if _plane is not None:
        # the guard projects the largest launch this dispatch can issue
        _plane.refresh_guard(
            _guard_dev, max_chunk, min_pad, kernel=_kernel_name,
            launch=launch,
        )
        _mem_baseline = _plane.device_view(_guard_dev).get("bytes_in_use")
    else:
        _mem_baseline = None
    if device is not None:
        max_chunk = device.chunk_cap(max_chunk, min_pad)
    else:
        max_chunk = chunk_cap(max_chunk, min_pad)
    _dev_label = device.label if device is not None else "dev0"
    # ROUTE_SINGLE pins the program to one chip even when a mesh is
    # visible (the scheduler's below-crossover rung), and so does a fault
    # domain that owns a chip. With neither, and more than one device
    # visible, the batch goes over the full mesh with no shard plan: its
    # own ledger label, its lanes booked to the one fault domain there is.
    where, nsh, _wire_route = placement(device), 1, ROUTE_SINGLE
    if route != ROUTE_SINGLE and where is None and n_devices() > 1:
        where, nsh, _wire_route = batch_mesh(), n_devices(), "auto"
    cap = max_chunk if launch is None else min(max_chunk, launch * nsh)
    chunks = shard_chunks(
        n, nsh, cap, min_pad, short_floor=0 if launch is None else cap // 2
    )
    out, tot = launch_stream(
        kernel, chunks, _slices_of(packed), n, where=where, prefix="mesh",
        route=_wire_route, device_label=_dev_label, domains=(_dev_label,),
        curve=curve,
    )
    _note_dispatch(_wire_route, _dev_label, n, tot)
    if _plane is not None and chunks:
        # post-dispatch model correction: the observed allocation peak
        # over the pre-dispatch baseline calibrates the per-(kernel,
        # bucket) footprint model, at the largest launch issued.
        # Best-effort — a stats failure must never fail a dispatch that
        # already produced its mask.
        try:
            _plane.observe_dispatch(
                _guard_dev, _kernel_name,
                max(size for _, _, size in chunks) // nsh,
                baseline_in_use=_mem_baseline,
            )
        except Exception:  # noqa: BLE001 - observability only
            pass
    return out


def _pow2(n: int, floor: int) -> int:
    size = max(1, int(floor))
    while size < n:
        size *= 2
    return size


def shard_chunks(n: int, n_shards: int, cap: int, min_pad: int,
                 short_floor: int = 0):
    """THE rounding rule of a batch launched over ``n_shards`` chips:
    → [(start, end, size)], one entry a launch, lanes [start, end) of
    the batch padded to ``size`` lanes, ``size // n_shards`` on each
    chip. ``cap`` bounds the REAL lanes of one launch IN TOTAL, whatever
    the shard count; a launch pads to a power of two (floored at
    min_pad), rounded up to a multiple of n_shards so the shards are
    equal. A 10,000-lane commit on four chips under the 8,192 cap is
    8,192 + 2,048 padded lanes in two launches, 2,048 and 512 a chip.

    ``short_floor`` (dispatch_batch, where a launch size is given: half
    a launch) shapes a batch of MORE than one launch as a stream: the
    launch that is short of ``cap`` lanes pads to at least
    ``short_floor``, so the stream's shapes are that and ``cap`` and
    nothing between min_pad and there (the executables a stream of any
    length needs are the ones its first two lengths built), and it goes
    FIRST, since the host work in front of the first launch is the part
    of a stream nothing hides. A 6,464-lane blocksync window under a cap
    of 2,048 is 1,024 + 3 x 2,048 padded lanes; on the v5e that read
    79.3 ms a window against 83.1 with the short launch last, 88.9 with
    it padded to 2,048 and 107.9 in one launch of 8,192 (PERF.md, PR 29).
    A batch of one launch, and any batch without the floor, rounds as
    ever.

    The resident commit (ed25519_batch._build_resident), dispatch_batch,
    dispatch_sharded, the indexed key store, verifyd's rows and warm
    boot (aot.warmup_plan, through shard_bucket) all round here, so a
    warmed ladder covers every shape a dispatch can produce: the
    zero-compiles-after-warm guarantee rests on there being one rule.
    With one shard it is the single-device rule (pow2 bucket, chunks of
    ``cap``)."""
    n_shards = max(1, int(n_shards))
    cap = max(1, int(cap))
    n = max(0, int(n))
    short = n % cap if short_floor and n > cap else 0
    bounds = [(0, short)] if short else []
    bounds += [(start, min(start + cap, n)) for start in range(short, n, cap)]
    out = []
    for start, end in bounds:
        floor = max(min_pad, short_floor) if end == short else min_pad
        size = -(-_pow2(end - start, floor) // n_shards) * n_shards
        out.append((start, end, size))
    return out


def shard_bucket(n: int, n_shards: int, min_pad: int) -> int:
    """Total padded lanes of ONE launch of ``n`` real lanes over
    ``n_shards`` chips (shard_chunks with the cap out of the way)."""
    n = max(1, int(n))
    return shard_chunks(n, n_shards, n, min_pad)[0][2]


# --- sharded dispatch plan ---------------------------------------------------
# Which fault domains participate in a sharded dispatch, decided ONCE
# per topology generation and cached: quarantining a domain bumps the
# topology's generation counter, so the next dispatch re-slices the
# mesh over the survivors instead of tripping the whole plane. The
# handle list comes from topology.healthy_devices() (stable index
# order), so every thread observing the same generation builds the
# identical mesh.


class ShardPlan:
    """An immutable slice of the topology for one sharded-dispatch
    epoch: the participating healthy fault domains (deterministic index
    order) and the jax Mesh over their backing devices."""

    def __init__(self, generation: int, handles, jax_mesh):
        self.generation = int(generation)
        self.handles = list(handles)
        self.mesh = jax_mesh
        self.n_shards = len(self.handles)

    def labels(self):
        return [h.label for h in self.handles]


_plan_mtx = threading.Lock()
_plan_cache = None  # (topology, generation, Optional[ShardPlan])


def shard_plan(topology=None):
    """The current sharded-dispatch plan for ``topology`` (default: the
    process default), or None when sharded execution is not possible —
    fewer than two healthy fault domains backed by distinct visible jax
    devices (e.g. a virtual multi-domain topology over one real chip).
    Cached per (topology, generation)."""
    from cometbft_tpu.crypto.tpu import topology as topolib

    topo = topology if topology is not None else topolib.default_topology()
    gen = topo.generation()
    global _plan_cache
    with _plan_mtx:
        cached = _plan_cache
    if cached is not None and cached[0] is topo and cached[1] == gen:
        return cached[2]
    full = batch_mesh()  # may init jax.distributed; never under _plan_mtx
    jax_devs = list(full.devices.flat)
    healthy = [h for h in topo.healthy_devices() if h.index < len(jax_devs)]
    if len(healthy) < 2:
        plan = None
    elif len(healthy) == len(jax_devs) and len(topo) == len(jax_devs):
        # full-strength mesh: reuse the cached process mesh so the AOT
        # registry key (mesh device set) matches warm boot's
        plan = ShardPlan(gen, healthy, full)
    else:
        import numpy as np
        from jax.sharding import Mesh

        plan = ShardPlan(
            gen, healthy,
            Mesh(np.array([jax_devs[h.index] for h in healthy]), ("batch",)),
        )
    with _plan_mtx:
        _plan_cache = (topo, gen, plan)
    return plan


def sharded_available(topology=None) -> bool:
    """True when a sharded dispatch is currently possible (>= 2 healthy
    fault domains backed by distinct jax devices) — the scheduler's
    routing gate."""
    try:
        return shard_plan(topology) is not None
    except Exception:  # noqa: BLE001 - routing probe must never raise
        return False


def dispatch_sharded(kernel, packed, n: int, max_chunk: int, min_pad: int,
                     topology=None, plan=None, donate_from: int = 0):
    """The production multi-device megabatch path: chunk-pad-dispatch
    with every chunk's trailing batch axis sharded over the HEALTHY
    fault domains of the topology (NamedSharding on the "batch" mesh
    axis, limbs replicated).

    Same contract as dispatch_batch — ``packed`` is pre-packed arrays or
    a ``(start, end) -> list`` callable, one launch_stream, staging
    buffers donated — plus the sharded specifics: a launch's lane count
    IN TOTAL is capped by the MINIMUM chunk cap over the participating
    devices (each device's OOM-shrink ladder and memory-plane guard clamp
    it), chunks and padding follow the one rounding rule (shard_chunks),
    and per-shard child spans attribute the work to each fault domain.
    The wire ledger buckets sharded work by the per-shard lane count and
    labels the whole mesh as one "device": the link is what the ledger
    models, and all shards ride the same host egress. Quarantined
    domains are excluded by the ShardPlan; a topology generation bump
    re-slices on the next call."""
    if plan is None:
        plan = shard_plan(topology)
    if plan is None:
        # no usable multi-device mesh: serve the batch on the single-
        # device path (route pinned so dispatch_batch cannot bounce back)
        with route_scope(ROUTE_SINGLE):
            return dispatch_batch(kernel, packed, n, max_chunk, min_pad)
    from cometbft_tpu.crypto.tpu import memory as _memory

    nsh = plan.n_shards
    _kernel_name = getattr(kernel, "__name__", "kernel")
    _plane = _memory.default_plane()
    _baselines = {}
    launch_cap = None
    for h in plan.handles:
        if _plane is not None:
            _plane.refresh_guard(h, max_chunk, min_pad, kernel=_kernel_name)
            _baselines[h.label] = _plane.device_view(h).get("bytes_in_use")
        cap = h.chunk_cap(max_chunk, min_pad)
        launch_cap = cap if launch_cap is None else min(launch_cap, cap)
    chunks = shard_chunks(n, nsh, launch_cap, min_pad)
    _wire_dev = f"mesh:{nsh}"
    out, tot = launch_stream(
        kernel, chunks, _slices_of(packed), n, where=plan.mesh,
        prefix="mesh", route=ROUTE_SHARDED, device_label=_wire_dev,
        domains=plan.labels(), donate_from=donate_from,
    )
    _note_dispatch(ROUTE_SHARDED, _wire_dev, n, tot)
    if _plane is not None and chunks:
        # per-device model correction: each shard served at most this
        # many lanes of this kernel; best-effort, never fails a dispatch
        max_bucket = max(size for _, _, size in chunks) // nsh
        for h in plan.handles:
            try:
                _plane.observe_dispatch(
                    h, _kernel_name, max_bucket,
                    baseline_in_use=_baselines.get(h.label),
                )
            except Exception:  # noqa: BLE001 - observability only
                pass
    return out
