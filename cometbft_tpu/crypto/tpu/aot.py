"""Compile economics for the device verify plane — AOT shape-bucket
precompilation (ROADMAP item 2).

First dispatch used to pay the whole XLA pipeline in-line: ~17 s of
trace+compile on one chip (BENCH_onchip_probe ``compile_and_run_s``) and
~103 s for the 8-way sharded program on the virtual CPU mesh — again on
every restart, every new pow2 shape bucket, and every topology change.
A validator that must vote within a round cannot absorb that. This
module makes every executable the verify path can need exist BEFORE
traffic arrives:

* ``ExecutableRegistry`` — the one home for compiled verify programs,
  keyed by (kernel stable name, arg shape bucket, donation spec,
  topology fingerprint, backend fingerprint). Lowering and compilation
  are explicit (``jax.jit(...).lower(shapes).compile()``), observable
  (``verify_aot_*`` metrics, ``aot_compile`` trace spans), deduplicated
  across racing threads, and bounded (LRU). It replaces the
  ``id(kernel)``-keyed ``_sharded_kernels`` / ``_donating_kernels``
  dicts in mesh.py — ``id()`` is reusable after GC, so a collision
  could silently run the WRONG executable; stable names cannot collide
  that way (see ``stable_kernel_name``).

* Fingerprints — a registry entry compiled against one machine or one
  topology is never trusted on another: the backend fingerprint (jax
  version + platform + device kind + device count) guards against the
  stale-machine-feature reloads seen in MULTICHIP_r05.json, and the
  topology fingerprint invalidates on fault-domain changes. A
  mismatched entry is discarded and recompiled, never run.

* Warm boot — ``run_warm_boot`` pre-lowers and compiles the pow2
  bucket ladder a routed flush can pad to ([crypto] min_batch's bucket
  … max_chunk; single-device and sharded variants for the current
  topology) in priority order: the canary's bucket, the commit-p50
  bucket, the megabatch cap last, refined by measured per-bucket compile
  seconds from the calibration table when available. ``start_warm_boot``
  runs it on a background thread the supervisor's warmup canary joins
  before declaring HEALTHY; ``[crypto] warm_boot = eager|background|off``
  (env ``CBFT_WARM_BOOT`` wins) controls the mode.

After a completed warm boot, a dispatch at ANY bucket in the ladder
(single-device or sharded) is a registry hit: zero new XLA compilations
on the hot path — the acceptance contract tests/test_tpu_aot.py pins.
"""

from __future__ import annotations

import hashlib as _hashlib
import os
import pickle as _pickle
import threading
import time
import warnings
import weakref
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from cometbft_tpu.libs import trace as _trace
from cometbft_tpu.libs.metrics import Registry

SUBSYSTEM = "verify_aot"

# the CPU platform can't honor buffer donation and warns per compile;
# same process-wide filter mesh.py installs (registry compiles can
# happen before mesh is imported — tools/warm_cache.py)
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable"
)


# --------------------------------------------------------------------------
# Stable kernel identity.
#
# The old mesh caches keyed executables by id(kernel). CPython reuses an
# object's id after it is garbage-collected, so a short-lived kernel
# (tests, a reloaded module) could collide with a LIVE cache entry and
# silently run the wrong executable. Names here are derived from the
# kernel's qualified name plus a per-object serial: a dead object's
# serial is never reused, and liveness is checked through a weakref, so
# an id collision is detected instead of trusted.

_name_mtx = threading.Lock()
# id(inner) -> (name, weakref-or-None, strong-ref-or-None)
_name_by_id: Dict[int, Tuple[str, Any, Any]] = {}
_name_serials: Dict[str, int] = {}
# explicit registrations (register_kernel): name -> _KernelReg; holds a
# strong reference so registered kernels' ids stay valid forever
_registered: "OrderedDict[str, _KernelReg]" = OrderedDict()


def unwrap_kernel(kernel) -> Any:
    """The traceable inner function of a (possibly jitted) kernel."""
    return getattr(kernel, "_fun", None) or getattr(
        kernel, "__wrapped__", kernel
    )


class _KernelReg:
    """One explicitly-registered kernel: its stable name, the warmup
    shape template (bucket -> arg (shape, dtype) list) and the default
    donation spec the dispatch layer uses for it."""

    __slots__ = ("name", "kernel", "bucket_shapes", "donate_from",
                 "forced", "launch")

    def __init__(self, name, kernel, bucket_shapes, donate_from, forced,
                 launch):
        self.name = name
        self.kernel = kernel
        self.bucket_shapes = bucket_shapes
        self.donate_from = donate_from
        self.forced = forced
        self.launch = launch


def register_kernel(
    name: str,
    kernel,
    bucket_shapes: Optional[Callable[[int], List[Tuple[tuple, Any]]]] = None,
    donate_from: int = 0,
    forced: bool = False,
    launch: Optional[int] = None,
) -> None:
    """Bind ``kernel`` to a stable ``name`` and (optionally) a warmup
    shape template: ``bucket_shapes(bucket)`` returns the kernel's arg
    (shape, dtype) list for a padded batch bucket. Registered kernels
    are what ``warmup_plan`` pre-compiles; registration holds a strong
    reference, so the name can never be re-assigned by id reuse.
    ``forced`` marks the kernel the supervisor's canary and triage
    dispatch BELOW the routing floor (force_device): its smallest bucket
    is warmed first, ahead of the ladder. ``launch`` is the size, in
    lanes, of the largest launch the kernel's entry issues on one chip
    where that is below the chunk cap (the entry streams a larger batch
    as launches of that size): the warm ladder ends there for the
    kernel's single-device executables."""
    inner = unwrap_kernel(kernel)
    with _name_mtx:
        _registered[name] = _KernelReg(
            name, kernel, bucket_shapes, donate_from, forced, launch,
        )
        _name_by_id[id(inner)] = (name, None, inner)


def stable_kernel_name(kernel) -> str:
    """A name for ``kernel`` that survives GC-driven id reuse: explicit
    registration wins; otherwise module.qualname plus a serial that is
    assigned once per live object and never reused after it dies."""
    inner = unwrap_kernel(kernel)
    with _name_mtx:
        ent = _name_by_id.get(id(inner))
        if ent is not None:
            name, ref, strong = ent
            alive = strong if strong is not None else (
                ref() if ref is not None else None
            )
            if alive is inner:
                return name
            # id reuse after GC: drop the stale binding, assign fresh
            del _name_by_id[id(inner)]
        base = "{}.{}".format(
            getattr(inner, "__module__", "?"),
            getattr(inner, "__qualname__", repr(type(inner).__name__)),
        )
        serial = _name_serials.get(base, 0)
        _name_serials[base] = serial + 1
        name = base if serial == 0 else f"{base}#{serial}"
        try:
            ref = weakref.ref(inner)
            strong = None
        except TypeError:  # not weakrefable: pin it (same as registered)
            ref, strong = None, inner
        _name_by_id[id(inner)] = (name, ref, strong)
        return name


# the key types a validator set may hold where the node's consensus
# params say nothing else (types/params.py ValidatorParams' default)
DEFAULT_KEY_TYPES = ("ed25519",)
# the key types a kernel's name may start with (``secp256k1.verify``)
CURVES = ("ed25519", "secp256k1", "sr25519")


def registered_kernels(
    key_types: Sequence[str] = DEFAULT_KEY_TYPES,
) -> List[_KernelReg]:
    """Warmup-eligible registrations: those with a shape template, but a
    curve's kernel (its name starts with the key type) whose key type
    is not among ``key_types``: no validator key of the node's chain
    can reach it, and it is not worth a compile per bucket."""

    def admitted(name: str) -> bool:
        curve = name.split(".", 1)[0]
        return curve not in CURVES or curve in key_types

    with _name_mtx:
        return [
            r for r in _registered.values()
            if r.bucket_shapes and admitted(r.name)
        ]


# --------------------------------------------------------------------------
# Fingerprints.


def backend_fingerprint() -> str:
    """Identity of the machine/runtime an executable was compiled
    against: jax version, platform, device kind, and device count. A
    registry entry whose recorded fingerprint differs from the current
    one is discarded — a stale-machine-feature reload (MULTICHIP_r05)
    must recompile, never run."""
    import jax

    devs = jax.devices()
    d = devs[0]
    return "{}:{}:{}:{}".format(
        jax.__version__,
        d.platform,
        getattr(d, "device_kind", "?"),
        len(devs),
    )


def topology_fingerprint(topology=None) -> str:
    """Identity of the fault-domain topology the executable serves —
    registry entries do not survive a topology change."""
    if topology is None:
        from cometbft_tpu.crypto.tpu import topology as topolib

        topology = topolib.default_topology()
    return topology.fingerprint()


# --------------------------------------------------------------------------
# Metrics (verify_aot_* family, same shape as verify_supervisor_*).


class Metrics:
    """AOT observability, exported as ``verify_aot_*`` through the
    node's Prometheus registry."""

    def __init__(self, registry: Optional[Registry] = None):
        r = registry if registry is not None else Registry()
        self.registry_hits = r.counter(
            SUBSYSTEM, "registry_hits",
            "Dispatches served by an already-compiled registry executable.",
        )
        self.registry_misses = r.counter(
            SUBSYSTEM, "registry_misses",
            "Dispatches that found no compiled executable for their "
            "(kernel, bucket, topology, backend) key — each one pays a "
            "trace+compile (or waits on a racing one).",
        )
        self.compiles = r.counter(
            SUBSYSTEM, "compiles",
            "Executable builds (lower+compile), by trigger "
            "(warmup|dispatch).",
        )
        self.compile_seconds = r.counter(
            SUBSYSTEM, "compile_seconds",
            "Total seconds spent in explicit lower+compile.",
        )
        self.exec_store_hits = r.counter(
            SUBSYSTEM, "exec_store_hits",
            "Registry misses served by deserializing a disk-persisted "
            "executable — no trace, no lower, no XLA compile.",
        )
        self.exec_store_misses = r.counter(
            SUBSYSTEM, "exec_store_misses",
            "Registry misses with no usable disk-persisted executable "
            "(absent, corrupt, or store disabled) — a fresh compile.",
        )
        self.exec_store_save_failures = r.counter(
            SUBSYSTEM, "exec_store_save_failures",
            "Fresh compiles whose executable could not be serialized to "
            "the disk store (the next boot recompiles them).",
        )
        self.exec_store_discards = r.counter(
            SUBSYSTEM, "exec_store_discards",
            "Disk-persisted executables thrown away on load: unreadable, "
            "not deserializable, or loaded but failing their first run "
            "(each one then costs a fresh compile).",
        )
        self.compile_fallbacks = r.counter(
            SUBSYSTEM, "compile_fallbacks",
            "Compiles that failed once (corrupt/truncated persistent-"
            "cache entry, transient backend error) and succeeded on the "
            "fresh-compile retry.",
        )
        self.invalidations = r.counter(
            SUBSYSTEM, "invalidations",
            "Registry entries discarded because their backend or "
            "topology fingerprint no longer matches the live plane.",
        )
        self.evictions = r.counter(
            SUBSYSTEM, "evictions",
            "Registry entries evicted by the LRU size bound.",
        )
        self.warmup_seconds = r.gauge(
            SUBSYSTEM, "warmup_seconds",
            "Wall seconds the last warm boot spent compiling the ladder.",
        )
        self.warmup_executables = r.gauge(
            SUBSYSTEM, "warmup_executables",
            "Executables the last warm boot left resident in the registry.",
        )
        self.warmup_state = r.gauge(
            SUBSYSTEM, "warmup_state",
            "Warm-boot phase: 0=not started, 1=running, 2=done, "
            "3=stopped/failed.",
        )

    @classmethod
    def nop(cls) -> "Metrics":
        return cls(None)


# --------------------------------------------------------------------------
# The disk executable store.
#
# jax's persistent compilation cache only skips the XLA BACKEND compile;
# tracing and lowering still run on every boot, and they dominate the
# warm path (~3 s per executable for the ed25519 jaxpr on CPU — the
# coldboot stage measured a 3× warm speedup where ≥5× is the bar).
# Persisting the SERIALIZED compiled executable (jax.experimental.
# serialize_executable) skips all three stages: a warm boot is a read +
# deserialize per executable. Entries are keyed by the full registry key
# — fingerprints included — so a file from another machine, topology, or
# jax version is never even looked up; a corrupt or truncated file
# degrades to a fresh compile with a warning, never a crash or a wrong
# executable.


# Bumped when what a stored blob means changes; older files are then
# never looked up. 2: entries are loaded onto their own device assignment
# and proven to run when loaded (ExecutableStore.load). 3: field.sq is a
# squaring (153 limb products), so every ed25519 / sr25519 program
# changed, and the key holds nothing of a program: kernel name, shapes
# and fingerprints are those of the executables a checkout upgraded in
# place still has in its store. 4: secp_field's product is slices and
# adds off the CPU platform, so the secp256k1 program changed. 5: the
# secp256k1 ladder is one Pallas kernel off the CPU platform
# (secp_ladder), a Mosaic call inside the same program.
_STORE_FORMAT = 5


class ExecutableStore:
    """Disk persistence of serialized compiled executables.
    ``on_discard`` is called once per entry thrown away on load."""

    def __init__(self, root: str,
                 on_discard: Optional[Callable[[], None]] = None):
        self.root = root
        self._on_discard = on_discard

    def _path(self, key: tuple) -> str:
        digest = _hashlib.sha256(
            repr((_STORE_FORMAT, key)).encode()
        ).hexdigest()
        return os.path.join(self.root, digest + ".aotexe")

    def load(self, key: tuple, devices: Sequence[Any], args: Sequence[Any]):
        """The deserialized executable for ``key`` loaded onto
        ``devices`` — the executable's own device assignment (one device
        for a single-device program, the mesh's for a sharded one);
        deserialize_and_load otherwise spreads it over every device of
        the backend — and PROVEN: run once on zeros of ``args``' shapes.
        On XLA:CPU (jax 0.9.0) an executable the persistent compilation
        cache handed back serializes into a blob that loads and then
        fails its first call ("Function ... not found"); trusted, it
        would turn a warm boot into dispatch failures the supervisor
        answers from the CPU. None when absent; an entry that is
        unreadable, does not deserialize or does not run is discarded
        with a warning and None is returned — the caller compiles."""
        path = self._path(key)
        try:
            with open(path, "rb") as fh:
                payload, in_tree, out_tree = _pickle.load(fh)
        except FileNotFoundError:
            return None
        except Exception as exc:  # noqa: BLE001 - corrupt/truncated entry
            return self._discard(path, key, f"is unreadable ({exc!r})")
        try:
            from jax.experimental import serialize_executable as _se

            loaded = _se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=list(devices),
            )
        except Exception as exc:  # noqa: BLE001 - stale/incompatible blob
            return self._discard(
                path, key, f"failed to deserialize ({exc!r})"
            )
        try:
            import jax
            import numpy as np

            zeros = [
                jax.device_put(np.zeros(a.shape, a.dtype), sh)
                for a, sh in zip(args, loaded.input_shardings[0])
            ]
            jax.block_until_ready(loaded(*zeros))
        except Exception as exc:  # noqa: BLE001 - loads, does not run
            return self._discard(path, key, f"does not run ({exc!r})")
        return loaded

    def save(self, key: tuple, compiled) -> bool:
        """Serialize ``compiled`` under ``key``, atomically (tmp +
        rename — readers never see a torn entry). → False when the
        executable does not serialize or the disk refuses it: that only
        costs the NEXT boot a compile, but the caller counts it
        (``verify_aot_exec_store_save_failures``) so a backend whose
        executables never persist is visible."""
        path = self._path(key)
        try:
            from jax.experimental import serialize_executable as _se

            blob = _pickle.dumps(_se.serialize(compiled))
            os.makedirs(self.root, exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "wb") as fh:
                fh.write(blob)
            os.replace(tmp, path)
            return True
        except Exception as exc:  # noqa: BLE001 - persistence is optional
            warnings.warn(
                f"aot executable for {key[0]} was not persisted ({exc!r})",
                RuntimeWarning,
                stacklevel=2,
            )
            return False

    def _discard(self, path: str, key: tuple, why: str) -> None:
        warnings.warn(
            f"aot executable store entry for {key[0]} {why}; "
            "recompiling fresh",
            RuntimeWarning,
            stacklevel=3,
        )
        try:
            os.remove(path)
        except OSError:
            pass
        if self._on_discard is not None:
            self._on_discard()
        return None


_store_mtx = threading.Lock()
_configured_store_root: Optional[str] = None


def configure_exec_store(root: Optional[str]) -> None:
    """Pin the executable store location (tools, tests); "" disables
    persistence. None reverts to the default resolution."""
    global _configured_store_root
    with _store_mtx:
        _configured_store_root = root


# the directory that holds the cometbft_tpu package: a fixed path for a
# given installation, which is what a cache key needs (a node home is a
# fresh temp directory in every test and smoke run, so a cache kept
# there never hits)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))
)))


def compile_cache_dir() -> str:
    """THE placement of the jax persistent compilation cache, and the
    only code that touches it: with JAX_COMPILATION_CACHE_DIR set the
    operator has placed it and jax reads the variable itself — nothing
    is set here; otherwise ``<checkout>/.jax_cache``. Idempotent; every
    process that compiles verify kernels (node, verifyd, tools, tests)
    calls this before its first compile and sets no cache of its own."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def exec_store_root() -> str:
    """Where serialized executables live: the configured root (tools,
    tests), else an ``aot_exec`` sibling inside the compile cache."""
    with _store_mtx:
        if _configured_store_root is not None:
            return _configured_store_root
    return os.path.join(compile_cache_dir(), "aot_exec")


def _current_store(on_discard=None) -> Optional[ExecutableStore]:
    root = exec_store_root()
    return ExecutableStore(root, on_discard) if root else None


# --------------------------------------------------------------------------
# Build time is host time.
#
# A registry miss traces, lowers and compiles (45-85 s an executable on
# a v5e) or loads a stored one, on the thread that dispatches. None of it
# is the device's doing, so whoever times a dispatch — the supervisor's
# watchdog, the wire ledger's compute phase — reads the dispatching
# thread's clock here and leaves those seconds out.


class BuildClock:
    """Seconds one thread has spent inside registry misses (building,
    loading, or waiting on a racing build), the one in progress
    included. Written by its own thread, read by any."""

    __slots__ = ("seconds", "since")

    def __init__(self) -> None:
        self.seconds = 0.0
        self.since: Optional[float] = None  # monotonic start, while inside

    def total(self) -> float:
        since = self.since
        running = time.monotonic() - since if since is not None else 0.0
        return self.seconds + running


_build_tls = threading.local()


def build_clock() -> BuildClock:
    """The calling thread's BuildClock."""
    clock = getattr(_build_tls, "clock", None)
    if clock is None:
        clock = _build_tls.clock = BuildClock()
    return clock


# --------------------------------------------------------------------------
# The executable registry.


class _InFlight:
    __slots__ = ("event", "compiled", "error")

    def __init__(self):
        self.event = threading.Event()
        self.compiled = None
        self.error: Optional[BaseException] = None


class ExecutableRegistry:
    """Compiled-executable cache for the dispatch layer.

    ``call(kernel, args)`` looks up the executable for the args' exact
    (padded-bucket) shapes and runs it; a miss lowers and compiles
    explicitly — outside any jit implicit path — and caches the result.
    ``warm`` compiles without running (the warm-boot entry). Concurrent
    misses on one key compile once (followers wait on the leader).
    Entries are LRU-bounded and fingerprint-guarded."""

    def __init__(
        self,
        max_entries: int = 256,
        metrics: Optional[Metrics] = None,
        logger=None,
    ):
        self._mtx = threading.Lock()
        self._entries: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._inflight: Dict[tuple, _InFlight] = {}
        self._max_entries = max(1, int(max_entries))
        self.metrics = metrics if metrics is not None else Metrics.nop()
        self._logger = logger
        self._last_fps: Optional[Tuple[str, str]] = None
        # plain int alongside the labeled verify_aot_compiles series —
        # labeled children don't roll up into the parent counter
        self._compile_count = 0
        # one record per executable this registry built or loaded —
        # what a boot cost, bucket by bucket (stats()["builds"])
        self._builds: List[dict] = []

    # -- introspection -------------------------------------------------------

    def set_metrics(self, metrics: Metrics) -> None:
        self.metrics = metrics

    def stats(self) -> Dict[str, float]:
        with self._mtx:
            entries = len(self._entries)
        return {
            "entries": entries,
            "hits": self.metrics.registry_hits.value(),
            "misses": self.metrics.registry_misses.value(),
            "compiles": self._compile_count,
            "builds": list(self._builds),
            "invalidations": self.metrics.invalidations.value(),
            "evictions": self.metrics.evictions.value(),
        }

    @property
    def compile_count(self) -> int:
        return self._compile_count

    def clear(self) -> None:
        with self._mtx:
            self._entries.clear()

    def __len__(self) -> int:
        with self._mtx:
            return len(self._entries)

    # -- keying --------------------------------------------------------------

    @staticmethod
    def _shape_key(args: Sequence[Any]) -> tuple:
        return tuple(
            (tuple(int(d) for d in a.shape), str(a.dtype)) for a in args
        )

    def _key(self, kernel, shape_key, donate_from, sharded, mesh=None,
             device=None):
        bfp = backend_fingerprint()
        tfp = topology_fingerprint()
        self._note_fps(bfp, tfp)
        # sharded executables are additionally keyed by the mesh's exact
        # device set: a re-sliced (quarantine-shrunk) sub-mesh compiles
        # and caches separately from the full-strength program — running
        # an 8-way executable on a 7-device mesh would be wrong, not slow
        if sharded:
            if mesh is None:
                from cometbft_tpu.crypto.tpu import mesh as mesh_mod

                mesh = mesh_mod.batch_mesh()
            mkey = tuple(
                int(getattr(d, "id", i))
                for i, d in enumerate(mesh.devices.flat)
            )
        elif device is not None:
            # a single-device program PLACED on one chip of a mesh (a
            # fault domain's own dispatch): compiled for that device's
            # assignment, so it keys apart from the default placement
            mkey = (int(device.id),)
        else:
            mkey = None
        return (
            stable_kernel_name(kernel),
            shape_key,
            int(donate_from),
            bool(sharded),
            tfp,
            bfp,
            mkey,
        ), bfp, tfp

    def _note_fps(self, bfp: str, tfp: str) -> None:
        """On a fingerprint change (topology swap, test-injected backend
        change), discard every entry compiled against the old plane —
        a mismatched executable is recompiled, never trusted."""
        with self._mtx:
            if self._last_fps == (bfp, tfp):
                return
            self._last_fps = (bfp, tfp)
            stale = [
                k for k, (_, ebfp, etfp) in self._entries.items()
                if ebfp != bfp or etfp != tfp
            ]
            for k in stale:
                del self._entries[k]
        for _ in stale:
            self.metrics.invalidations.add()

    # -- lookup / compile ----------------------------------------------------

    def lookup(
        self,
        kernel,
        args: Sequence[Any],
        donate_from: int = 0,
        sharded: bool = False,
        trigger: str = "dispatch",
        mesh=None,
        device=None,
    ):
        """The compiled executable for ``args``' exact shapes, compiling
        on miss. ``args`` may be concrete arrays or ShapeDtypeStructs.
        ``mesh`` names the device mesh a sharded executable runs over
        (default: the full batch_mesh); ``device`` the jax device a
        single-device executable is placed on (default: jax's default
        placement) — both part of the cache key."""
        shape_key = self._shape_key(args)
        key, bfp, tfp = self._key(
            kernel, shape_key, donate_from, sharded, mesh=mesh,
            device=device,
        )
        with self._mtx:
            ent = self._entries.get(key)
            if ent is not None:
                self._entries.move_to_end(key)
                hit = True
            else:
                hit = False
                fut = self._inflight.get(key)
                leader = fut is None
                if leader:
                    fut = self._inflight[key] = _InFlight()
        if hit:
            self.metrics.registry_hits.add()
            return ent[0]
        self.metrics.registry_misses.add()
        clock = build_clock()
        clock.since = time.monotonic()
        try:
            return self._serve_miss(
                kernel, key, bfp, tfp, fut, leader, args, donate_from,
                sharded, trigger, mesh, device,
            )
        finally:
            clock.seconds += time.monotonic() - clock.since
            clock.since = None

    def _serve_miss(self, kernel, key, bfp, tfp, fut, leader, args,
                    donate_from, sharded, trigger, mesh, device):
        """The slow half of lookup: wait on the racing build, or build
        (load from the store, else compile) and publish the entry."""
        if not leader:
            fut.event.wait()
            if fut.error is not None:
                raise RuntimeError(
                    f"registry compile of {key[0]} failed in a racing "
                    f"thread: {fut.error}"
                ) from fut.error
            return fut.compiled
        try:
            compiled = self._load_or_compile(
                kernel, key, args, donate_from, sharded, trigger,
                mesh=mesh, device=device,
            )
            fut.compiled = compiled
        except BaseException as exc:
            fut.error = exc
            raise
        finally:
            with self._mtx:
                self._inflight.pop(key, None)
            fut.event.set()
        with self._mtx:
            self._entries[key] = (compiled, bfp, tfp)
            self._entries.move_to_end(key)
            evicted = 0
            while len(self._entries) > self._max_entries:
                self._entries.popitem(last=False)
                evicted += 1
        for _ in range(evicted):
            self.metrics.evictions.add()
        return compiled

    def call(
        self,
        kernel,
        args: Sequence[Any],
        donate_from: int = 0,
        sharded: bool = False,
        mesh=None,
        device=None,
    ):
        """Run ``kernel`` on ``args`` through the registry (the
        dispatch-layer entry — mesh.launch_stream, on one chip through
        mesh.run_single)."""
        compiled = self.lookup(
            kernel, args, donate_from=donate_from, sharded=sharded,
            mesh=mesh, device=device,
        )
        return compiled(*args)

    def warm(
        self,
        kernel,
        shapes: Sequence[Tuple[tuple, Any]],
        donate_from: int = 0,
        sharded: bool = False,
        mesh=None,
    ) -> float:
        """Pre-lower and compile one (kernel, bucket, variant) without
        running it. → compile wall seconds (0.0 when already resident)."""
        import jax

        sds = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in shapes]
        t0 = time.perf_counter()
        before = self._compile_count
        self.lookup(
            kernel, sds, donate_from=donate_from, sharded=sharded,
            trigger="warmup", mesh=mesh,
        )
        if self._compile_count == before:
            return 0.0
        return time.perf_counter() - t0

    def _load_or_compile(
        self, kernel, key, args, donate_from, sharded, trigger, mesh=None,
        device=None,
    ):
        """Serve a registry miss: deserialize from the disk executable
        store when a fingerprint-matched entry exists (no trace, no
        compile), else compile fresh and persist for the next boot."""
        store = _current_store(self.metrics.exec_store_discards.add)
        if store is not None:
            span = _trace.child_of_current(
                "aot_load", kernel=key[0], bucket=_bucket_of(args),
                sharded=sharded, topology=key[4], trigger=trigger,
            )
            t0 = time.perf_counter()
            compiled = store.load(
                key, _execution_devices(sharded, mesh, device), args
            )
            if compiled is not None:
                secs = round(time.perf_counter() - t0, 3)
                span.end(cache_hit=True, seconds=secs)
                self.metrics.exec_store_hits.add()
                self._note_build(key, args, sharded, "store", secs)
                return compiled
            span.end(cache_hit=False)
            self.metrics.exec_store_misses.add()
        else:
            self.metrics.exec_store_misses.add()
        compiled = self._compile(
            kernel, key, args, donate_from, sharded, trigger, mesh=mesh,
            device=device,
        )
        if store is not None and not store.save(key, compiled):
            self.metrics.exec_store_save_failures.add()
        return compiled

    def _compile(self, kernel, key, args, donate_from, sharded, trigger,
                 mesh=None, device=None):
        """Explicit jit(...).lower(shapes).compile() with one fresh-
        compile retry: a corrupted or truncated persistent-cache entry
        (or a transient backend hiccup) must degrade to a fresh compile
        with a warning — never crash the dispatch, never return a wrong
        executable."""
        name, bucket = key[0], _bucket_of(args)
        span = _trace.child_of_current(
            "aot_compile", kernel=name, bucket=bucket, sharded=sharded,
            topology=key[4], trigger=trigger, cache_hit=False,
        )
        t0 = time.perf_counter()
        try:
            try:
                compiled = self._build(
                    kernel, args, donate_from, sharded, mesh=mesh,
                    device=device,
                )
            except Exception as exc:  # noqa: BLE001 - retry fresh once
                warnings.warn(
                    f"aot compile of {name} bucket {bucket} failed "
                    f"({exc!r}); retrying with a fresh compile",
                    RuntimeWarning,
                    stacklevel=2,
                )
                if self._logger is not None:
                    self._logger.error(
                        "aot compile failed; retrying fresh",
                        kernel=name, bucket=bucket, err=str(exc),
                    )
                compiled = self._build(
                    kernel, args, donate_from, sharded, mesh=mesh,
                    device=device,
                )
                self.metrics.compile_fallbacks.add()
        except Exception as exc:  # noqa: BLE001
            span.end(error=repr(exc))
            raise
        secs = time.perf_counter() - t0
        span.end(seconds=round(secs, 3))
        with self._mtx:
            self._compile_count += 1
        self.metrics.compiles.with_labels(trigger=trigger).add()
        self.metrics.compile_seconds.add(secs)
        self._note_build(key, args, sharded, trigger, round(secs, 3))
        return compiled

    def _note_build(self, key, args, sharded, source, secs) -> None:
        with self._mtx:
            self._builds.append({
                "kernel": key[0], "bucket": _bucket_of(args),
                "sharded": bool(sharded), "source": source, "seconds": secs,
            })

    def _build(self, kernel, args, donate_from, sharded, mesh=None,
               device=None):
        import jax

        inner = unwrap_kernel(kernel)
        sds = [
            a if isinstance(a, jax.ShapeDtypeStruct)
            else jax.ShapeDtypeStruct(a.shape, a.dtype)
            for a in args
        ]
        donate = tuple(range(int(donate_from), len(sds)))
        if sharded:
            from cometbft_tpu.crypto.tpu import mesh as mesh_mod
            from jax.sharding import NamedSharding, PartitionSpec as PS

            m = mesh if mesh is not None else mesh_mod.batch_mesh()
            in_shardings = tuple(
                NamedSharding(m, PS(*([None] * (len(s.shape) - 1) + ["batch"])))
                for s in sds
            )
            jitted = jax.jit(
                inner,
                in_shardings=in_shardings,
                out_shardings=NamedSharding(m, PS("batch")),
                donate_argnums=donate,
            )
        elif device is not None:
            from jax.sharding import SingleDeviceSharding

            placed = SingleDeviceSharding(device)
            jitted = jax.jit(
                inner,
                in_shardings=tuple(placed for _ in sds),
                out_shardings=placed,
                donate_argnums=donate,
            )
        else:
            jitted = jax.jit(inner, donate_argnums=donate)
        return jitted.lower(*sds).compile()


def _execution_devices(sharded: bool, mesh, device) -> List[Any]:
    """The device assignment an executable under this key was compiled
    for — what the disk store must load it back onto."""
    if sharded:
        if mesh is None:
            from cometbft_tpu.crypto.tpu import mesh as mesh_mod

            mesh = mesh_mod.batch_mesh()
        return list(mesh.devices.flat)
    if device is not None:
        return [device]
    import jax

    return [jax.local_devices()[0]]


def _bucket_of(args) -> int:
    """The batch bucket of an arg list = the trailing axis of its last
    arg (arg 0 of the indexed kernel is the valset table)."""
    try:
        return int(args[-1].shape[-1])
    except Exception:  # noqa: BLE001 - scalar/odd kernels
        return 0


# -- process-default registry (mirrors topology.default_topology) ------------

_reg_mtx = threading.Lock()
_default_registry: Optional[ExecutableRegistry] = None


def default_registry() -> ExecutableRegistry:
    """The process-wide registry the mesh dispatch layer uses. Node
    start swaps in real metrics via set_metrics()."""
    global _default_registry
    with _reg_mtx:
        if _default_registry is None:
            _default_registry = ExecutableRegistry()
        return _default_registry


def reset_default_registry() -> None:
    """Drop every cached executable (tests, topology teardown)."""
    with _reg_mtx:
        if _default_registry is not None:
            _default_registry.clear()


# --------------------------------------------------------------------------
# The pow2 bucket ladder and the warm-boot plan.

_MIN_PAD = 64
_DEFAULT_CAP = 8192


def _pow2_at_least(n: int, lo: int = _MIN_PAD) -> int:
    size = lo
    while size < n:
        size *= 2
    return size


def bucket_ladder(
    floor: Optional[int] = None,
    cap: Optional[int] = None,
    min_pad: int = _MIN_PAD,
) -> List[int]:
    """The pow2 buckets a ROUTED flush can pad to, in warm-boot priority
    order: from the bucket of the routing floor ([crypto] min_batch —
    below it the router keeps a flush on the host) up to the chunk cap
    ([crypto] max_chunk). The commit-p50 bucket (the floor's) first,
    then the rest — cheapest measured compile first when the calibration
    table has per-bucket compile seconds, ascending size otherwise —
    with megabatch (the cap) last.

    The two settings that bound routing bound the ladder. Buckets below
    the floor are reached only by the canary and small triage passes
    (warmup_plan adds that one bucket for the kernels they dispatch), by
    the remainder chunk of a flush larger than the cap and by triage
    over many lanes; the last two compile on first use — outside the
    dispatch watchdog (BuildClock) — and persist in the executable
    store. A kernel whose entry launches below the cap (register_kernel's
    ``launch``) is warmed on one chip only up to that launch
    (warmup_plan): at the defaults 1,024 and 2,048 of the ladder's four
    buckets, 5 executables on one chip with the canary's, at ~50 s
    apiece cold on a v5e."""
    from cometbft_tpu.crypto.tpu import calibrate

    if cap is None:
        from cometbft_tpu.crypto.tpu import mesh as mesh_mod

        cap = mesh_mod.chunk_cap(_DEFAULT_CAP, min_pad)
    cap = _pow2_at_least(int(cap), min_pad)
    if floor is None:
        from cometbft_tpu.crypto import batch as cryptobatch

        floor = cryptobatch.ed25519_routing_floor()
    p50 = min(_pow2_at_least(int(floor), min_pad), cap)

    above, size = [], p50 * 2
    while size <= cap:
        above.append(size)
        size *= 2
    measured = calibrate.compile_seconds()
    if measured:
        # warm the cheap buckets first so more of the ladder is covered
        # early; the megabatch cap is the most expensive compile and
        # lands last either way
        above.sort(key=lambda b: (measured.get(b, float(b)), b))
    return [p50] + above


class WarmTarget:
    """One executable the warm boot will pre-compile."""

    __slots__ = ("name", "kernel", "shapes", "donate_from", "sharded",
                 "bucket")

    def __init__(self, name, kernel, shapes, donate_from, sharded, bucket):
        self.name = name
        self.kernel = kernel
        self.shapes = shapes
        self.donate_from = donate_from
        self.sharded = sharded
        self.bucket = bucket


def warmup_plan(
    floor: Optional[int] = None,
    sizes: Optional[Sequence[int]] = None,
    include_single: Optional[bool] = None,
    key_types: Sequence[str] = DEFAULT_KEY_TYPES,
) -> List[WarmTarget]:
    """Every executable the current topology's ROUTED dispatches need,
    in priority order: the canary's bucket of the ``forced`` kernels,
    then for each ladder bucket and each registered kernel with a shape
    template: the sharded variant when >1 device is
    visible (what dispatch_batch actually runs there — warmed first),
    plus the single-device variant (``include_single``, default on so a
    mesh that degrades to one visible device still boots warm). The
    ladder's single-device buckets above a kernel's ``launch`` are left
    out: no flush reaches them, its entry streams at that size. An
    explicit ``sizes`` is taken as given. ``key_types`` are the node's
    ``pub_key_types``: a curve's kernels are warmed only where the
    chain admits its keys."""
    # registering the curve kernels is an import side effect
    from cometbft_tpu.crypto.tpu import ed25519_batch  # noqa: F401
    from cometbft_tpu.crypto.tpu import secp256k1_batch  # noqa: F401
    from cometbft_tpu.crypto.tpu import mesh as mesh_mod

    ndev = mesh_mod.n_devices()
    if include_single is None:
        include_single = True
    targets: List[WarmTarget] = []
    if sizes is not None:
        buckets = list(sizes)
    else:
        buckets = bucket_ladder(floor=floor)
        if _MIN_PAD not in buckets:
            # the canary gates HEALTHY and dispatches 8 lanes, single-
            # device, whatever the floor: its bucket comes first
            targets.extend(
                WarmTarget(
                    reg.name, reg.kernel, reg.bucket_shapes(_MIN_PAD),
                    reg.donate_from, False, _MIN_PAD,
                )
                for reg in registered_kernels(key_types) if reg.forced
            )
    seen_sharded = set()
    regs = registered_kernels(key_types)
    for bucket in buckets:
        for reg in regs:
            if ndev > 1:
                # the one rounding rule of every sharded launch
                # (mesh.shard_chunks): a launch never carries more than
                # the chunk cap in total, so the ladder's buckets,
                # rounded as it rounds them, are every sharded shape
                size = mesh_mod.shard_bucket(bucket, ndev, _MIN_PAD)
                if (reg.name, size) not in seen_sharded:
                    seen_sharded.add((reg.name, size))
                    targets.append(WarmTarget(
                        reg.name, reg.kernel, reg.bucket_shapes(size),
                        reg.donate_from, True, size,
                    ))
            streamed = sizes is None and reg.launch and bucket > reg.launch
            if (ndev == 1 or include_single) and not streamed:
                targets.append(WarmTarget(
                    reg.name, reg.kernel, reg.bucket_shapes(bucket),
                    reg.donate_from, False, bucket,
                ))
    return targets


def run_warm_boot(
    floor: Optional[int] = None,
    sizes: Optional[Sequence[int]] = None,
    include_single: Optional[bool] = None,
    registry: Optional[ExecutableRegistry] = None,
    stop_event: Optional[threading.Event] = None,
    tracer=None,
    key_types: Sequence[str] = DEFAULT_KEY_TYPES,
) -> List[dict]:
    """Compile the whole warm-boot plan into ``registry`` (the process
    default when omitted), eagerly, on the calling thread. → one
    observation per target: {kernel, bucket, sharded, topology,
    compile_s, cached} — the raw material calibrate.merge_compile_times
    folds into the crossover table. Checks ``stop_event`` between
    targets, so a mid-warmup stop() is bounded by ONE compile."""
    reg = registry if registry is not None else default_registry()
    tracer = tracer if tracer is not None else _trace.default_tracer()
    plan = warmup_plan(
        floor=floor, sizes=sizes, include_single=include_single,
        key_types=key_types,
    )
    topo_fp = topology_fingerprint()
    obs: List[dict] = []
    t0 = time.perf_counter()
    reg.metrics.warmup_state.set(1)
    root = tracer.span(
        "aot_warm_boot", topology=topo_fp, targets=len(plan)
    )
    done = 0
    try:
        with _trace.use(root):
            for tgt in plan:
                if stop_event is not None and stop_event.is_set():
                    root.set_tag("stopped", True)
                    break
                secs = reg.warm(
                    tgt.kernel, tgt.shapes,
                    donate_from=tgt.donate_from, sharded=tgt.sharded,
                )
                done += 1
                obs.append({
                    "kernel": tgt.name,
                    "bucket": tgt.bucket,
                    "sharded": tgt.sharded,
                    "topology": topo_fp,
                    "compile_s": round(secs, 3),
                    "cached": secs == 0.0,
                })
    except BaseException:
        reg.metrics.warmup_state.set(3)
        root.end(error="failed", warmed=done)
        raise
    wall = time.perf_counter() - t0
    stopped = stop_event is not None and stop_event.is_set()
    reg.metrics.warmup_state.set(3 if stopped else 2)
    reg.metrics.warmup_seconds.set(round(wall, 3))
    reg.metrics.warmup_executables.set(done)
    root.end(seconds=round(wall, 3), warmed=done)
    return obs


# --------------------------------------------------------------------------
# Warm-boot lifecycle (the node-facing handle).


def warm_boot_mode(config_value: Optional[str] = None) -> str:
    """[crypto] warm_boot resolution: CBFT_WARM_BOOT env > config >
    "background". CBFT_TPU_WARMUP=0 (the legacy kill switch) still
    forces "off"."""
    if os.environ.get("CBFT_TPU_WARMUP", "1") == "0":
        return "off"
    raw = os.environ.get("CBFT_WARM_BOOT")
    mode = raw if raw is not None else (config_value or "background")
    if mode not in ("eager", "background", "off"):
        raise ValueError(
            f"warm_boot={mode!r}: choose from "
            "['eager', 'background', 'off']"
        )
    return mode


class WarmBoot:
    """Handle on one warm-boot run: the supervisor's warmup canary
    joins it before declaring HEALTHY; node stop() stops it with a
    bounded join. ``body(stop_event)`` does the work — the default is
    ``run_warm_boot``; node.py follows it with the calibration sweep."""

    def __init__(
        self,
        body: Optional[Callable[[threading.Event], Any]] = None,
        name: str = "aot-warm-boot",
        **plan_kwargs: Any,
    ):
        if body is None:
            def body(stop_event, _kw=plan_kwargs):
                return run_warm_boot(stop_event=stop_event, **_kw)
        self._body = body
        self._name = name
        self._stop = threading.Event()
        self._done = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.result: Any = None
        self.error: Optional[BaseException] = None

    def run(self) -> Any:
        """Execute the body on the CALLING thread (eager mode)."""
        try:
            self.result = self._body(self._stop)
            return self.result
        except BaseException as exc:
            self.error = exc
            raise
        finally:
            self._done.set()

    def start(self) -> "WarmBoot":
        """Execute the body on a daemon thread (background mode)."""
        def run():
            try:
                self.result = self._body(self._stop)
            except BaseException as exc:  # noqa: BLE001 - surfaced via .error
                self.error = exc
            finally:
                self._done.set()

        self._thread = threading.Thread(
            target=run, daemon=True, name=self._name
        )
        self._thread.start()
        return self

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def join(self, timeout: Optional[float] = None) -> bool:
        """Wait for the warm boot to finish (or be stopped). → True
        when it completed within ``timeout``."""
        return self._done.wait(timeout)

    def stop(self, timeout: Optional[float] = 10.0) -> bool:
        """Request stop and join the worker within ``timeout`` — the
        body checks the stop event between compiles, so the bound is
        one in-flight compile. → True when the worker exited in time
        (trivially True when it never started or already finished)."""
        self._stop.set()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout)
            return not t.is_alive()
        return True


_wb_mtx = threading.Lock()
_current_warm_boot: Optional[WarmBoot] = None


def current_warm_boot() -> Optional[WarmBoot]:
    """The process's live warm-boot handle, if any — what the
    supervisor's warmup canary joins before probing."""
    with _wb_mtx:
        return _current_warm_boot


def set_current_warm_boot(wb: Optional[WarmBoot]) -> Optional[WarmBoot]:
    global _current_warm_boot
    with _wb_mtx:
        prev, _current_warm_boot = _current_warm_boot, wb
    return prev


def start_warm_boot(
    mode: str = "background",
    body: Optional[Callable[[threading.Event], Any]] = None,
    **plan_kwargs: Any,
) -> Optional[WarmBoot]:
    """Create, register, and launch the process warm boot. ``eager``
    runs on the calling thread (node start blocks until warm);
    ``background`` returns immediately; ``off`` is a no-op. A previous
    handle is stopped first (bounded) so two warm boots never race."""
    if mode == "off":
        return None
    wb = WarmBoot(body=body, **plan_kwargs)
    prev = set_current_warm_boot(wb)
    if prev is not None:
        prev.stop(timeout=1.0)
    if mode == "eager":
        try:
            wb.run()
        except Exception:  # noqa: BLE001 - warm boot is best-effort
            pass
        return wb
    return wb.start()


def stop_warm_boot(timeout: Optional[float] = 10.0) -> bool:
    """Stop the process warm boot, if one is running (node stop())."""
    wb = set_current_warm_boot(None)
    if wb is None:
        return True
    return wb.stop(timeout)
