#!/usr/bin/env python3
"""chip_smoke.py — does the verify plane still start on the chip?

Drives the served verify path once on a TPU, from ONE process (a chip
belongs to one process at a time), through the entry points a node
operator uses, at the sizes CometBFT operators run:

  node        `init` a home, [crypto] backend = "tpu", default_new_node,
              start, height >= 3, the supervisor's warmup canary on the
              device (and, with CBFT_WARM_BOOT set, the node's in-process
              AOT warm boot and the calibration sweep behind it)
  megacommit  ValidatorSet.verify_commit on a real 10,000-validator
              commit, twice (resident valset upload, then hit), then the
              same 10,000 lanes with corrupted lanes through
              node.crypto_backend.submit: twice with every key the
              valset's own (the keystore covers the flush: the indexed
              100 B/lane wire), twice with a foreign key among them (the
              keyed compact wire); every mask compared lane by lane with
              CPUBatchVerifier
  blocksync   a window of 64 consecutive 150-validator commits submitted
              block by block the way blocksync's reactor does
              (verify_commit_light's quorum prefix), one forged
              precommit, twice
  service     tools/verifyd.py's Daemon(backend="tpu") on a unix socket,
              16 RemoteVerifier client threads x 20 rounds x 150 lanes,
              released together each round; drain, stop
  multichip   only when more than one device is visible: where sharded
              and per-domain dispatches actually put their buffers

and then reads the program's own counters and fails on any surprise: a
lane that ended on the CPU without being routed there, a fallback, a
retry, a watchdog kill, an open breaker, a compile in a second pass.
Every flush is routed by the program, unpinned; nothing here selects a
route.

[crypto] runs at its defaults (dispatch_timeout_ms, audit_pct,
hedge_pct, min_batch, max_chunk) with two exceptions, `warm_boot` and
`router`: see leg_node. The buckets the legs dispatch compile on demand.

Timings printed are smoke timings — one cold reading each — not a
benchmark. Exit 0 only if every check held. Without a TPU it exits 2
before doing any work.

Data comes from --seed; nothing is read from the network or the repo
beyond the package itself. The node home and socket live in a temporary
directory outside the checkout; only the compile cache
(aot.compile_cache_dir) and chiprun_out/ are written inside it.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)

CHAIN_ID = "chip-smoke"
NO_TPU_EXIT = 2

# The sizes operators run (BASELINE.json configs #5, #4, #3). Fixed: the
# driver runs plain `python chip_smoke.py`; tests/test_chip_smoke.py
# hands the leg functions toy sizes.
MEGA_VALIDATORS = 10_000
WINDOW_BLOCKS = 64
WINDOW_VALIDATORS = 150
SERVICE_CLIENTS = 16
SERVICE_ROUNDS = 20


class SmokeFailure(AssertionError):
    """A check did not hold. Never caught: the first one ends the run."""


def check(cond: bool, what: str, **detail) -> None:
    if not cond:
        raise SmokeFailure(
            what + (": " + json.dumps(detail, default=str) if detail else "")
        )


def say(msg: str) -> None:
    """Progress goes to stderr: stdout carries the summary and the
    result line, and nothing at all when the run does not get that far."""
    print(f"[chip_smoke +{time.monotonic() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.monotonic()


# --------------------------------------------------------------------------
# the platform gate — before anything else


def device_record() -> Dict[str, object]:
    """The device as jax reports it (the contract's last-line shape)."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


# --------------------------------------------------------------------------
# data, all of it from the seed


def _secret(seed: int, *parts) -> bytes:
    return hashlib.sha256(
        ("/".join(str(p) for p in (seed,) + parts)).encode()
    ).digest()


def make_valset(n: int, seed: int, tag: str):
    """n ed25519 validators (equal power) and their signers, in the
    set's canonical order."""
    from cometbft_tpu.crypto import ed25519
    from cometbft_tpu.types.priv_validator import MockPV
    from cometbft_tpu.types.validator import Validator
    from cometbft_tpu.types.validator_set import ValidatorSet

    privs = [
        MockPV(ed25519.gen_priv_key_from_secret(_secret(seed, tag, i)))
        for i in range(n)
    ]
    vals = ValidatorSet([Validator.new(pv.get_pub_key(), 10) for pv in privs])
    by_addr = {pv.get_pub_key().address(): pv for pv in privs}
    return vals, [by_addr[v.address] for v in vals.validators]


def make_commit(vals, privs, height: int, seed: int):
    from cometbft_tpu.proto.gogo import Timestamp
    from cometbft_tpu.types import test_util

    bid = test_util.make_block_id(
        _secret(seed, "block", height), 1, _secret(seed, "parts", height)
    )
    commit = test_util.make_commit(
        bid, height, 0, vals, privs, CHAIN_ID,
        now=Timestamp(1_700_000_000 + height, 0),
    )
    return bid, commit


def commit_items(vals, commit) -> List[tuple]:
    """(pub_key, sign_bytes, signature) for every validator's precommit."""
    return [
        (
            vals.validators[i].pub_key,
            commit.vote_sign_bytes(CHAIN_ID, i),
            cs.signature,
        )
        for i, cs in enumerate(commit.signatures)
    ]


def corrupt(items: List[tuple],
            swap_keys: bool = True) -> Tuple[List[tuple], List[int]]:
    """Spoil a handful of NON-ADJACENT lanes, one of each kind the two
    verifiers must agree on: flipped signature bits, a wrong-length
    signature, s >= L, a pubkey that is no curve point, a changed
    message. (A wrong-length KEY cannot travel this API: PubKeyEd25519
    refuses to be built from one.) ``swap_keys=False`` leaves every
    lane its validator's own key (a flipped bit of S instead), so a
    resident valset still covers the flush. → (items, spoiled lanes)."""
    from cometbft_tpu.crypto import ed25519
    from cometbft_tpu.crypto.tpu.field import L

    n = len(items)
    items = list(items)
    lanes = sorted({(n * k) // 13 + 1 for k in range(12)} | {0, n - 1})
    lanes = [i for j, i in enumerate(lanes) if j == 0 or i - lanes[j - 1] > 1]
    # the first y with no x on the curve (the kernel module's own
    # host-side curve math; both verifiers must fail to decompress it)
    from cometbft_tpu.crypto.tpu import ed25519_batch as eb
    from cometbft_tpu.crypto.tpu.field import D, P

    y = next(
        y for y in range(2, 64)
        if eb._sqrt_ratio_py((y * y - 1) % P, (D * y * y + 1) % P) is None
    )
    off_curve = y.to_bytes(32, "little")
    for j, i in enumerate(lanes):
        pk, msg, sig = items[i]
        kind = j % 5
        if kind == 0:
            sig = sig[:7] + bytes([sig[7] ^ 0x10]) + sig[8:]
        elif kind == 1:
            sig = sig[:63]
        elif kind == 2:
            sig = sig[:32] + (L + 5 + j).to_bytes(32, "little")
        elif kind == 3 and swap_keys:
            pk = ed25519.PubKeyEd25519(off_curve)
        elif kind == 3:
            sig = sig[:40] + bytes([sig[40] ^ 0x02]) + sig[41:]
        else:
            msg = msg + b"!"
        items[i] = (pk, msg, sig)
    return items, lanes


def cpu_oracle(items: Sequence[tuple]) -> List[bool]:
    from cometbft_tpu.crypto.batch import CPUBatchVerifier

    bv = CPUBatchVerifier()
    for pk, m, s in items:
        bv.add(pk, m, s)
    return bv.verify()[1]


def oracle_record() -> Dict[str, bool]:
    """Which CPU oracle runs: the native multi-threaded batch (built
    from native/ed25519_batch.c on first use) or, without cc/libcrypto,
    the OpenSSL-wheel loop or the pure-Python group."""
    from cometbft_tpu import native
    from cometbft_tpu.crypto import ed25519

    return {
        "native_batch": native.load_ed25519() is not None,
        "openssl_wheel": bool(ed25519._HAVE_OPENSSL_WHEEL),
    }


# --------------------------------------------------------------------------
# the program's own counters


class Books:
    """Reads the node's ledgers and metrics; nothing here is counted by
    the smoke itself except what it submitted."""

    DEVICE_ROUTES = ("single", "sharded", "indexed")
    PRIMARY_ROUTES = ("cpu", "single", "sharded")
    # wire-ledger keys of the dispatch loops a scheduler flush can reach
    FLUSH_WIRE_ROUTES = ("single", "auto", "sharded", "indexed")

    def __init__(self, node):
        self.node = node

    def decision_lanes(self) -> Dict[str, int]:
        return self.node.decision_ledger.lanes()

    def wire_lanes(self) -> Dict[str, int]:
        return self.node.wire_ledger.lanes_by_route()

    def flush_wire_lanes(self) -> Dict[str, int]:
        """Device lanes on the wire routes a scheduler flush can reach."""
        wire = self.wire_lanes()
        return {r: wire.get(r, 0) for r in self.FLUSH_WIRE_ROUTES}

    def cpu_pool_lanes(self) -> int:
        return int(
            self.node.telemetry_hub.metrics.device_sigs.with_labels(
                device="cpu"
            ).value()
        )

    def aot_compiles(self) -> int:
        from cometbft_tpu.crypto.tpu import aot

        return aot.default_registry().compile_count

    def supervisor(self) -> Dict[str, float]:
        m = self.node.verify_supervisor.metrics
        out = {
            name: getattr(m, name).value()
            for name in (
                "failures", "cpu_routed", "watchdog_kills",
                "sharded_fallbacks", "indexed_fallbacks",
                "triage_cpu_fallbacks", "triage_runs", "triage_passes",
                "triage_divergence", "audits", "audit_lanes",
                "audit_mismatches", "audit_drops", "hedge_fires",
                "hedge_divergence", "indexed_dispatches",
                "host_lanes", "device_dispatches", "sharded_dispatches",
                "chunk_shrinks", "redistributions",
            )
        }
        out["retries"] = sum(
            m.retries.with_labels(cls=c).value()
            for c in ("transient", "oom")
        )
        out["hedge_wins_cpu"] = m.hedge_wins.with_labels(winner="cpu").value()
        for oc in ("ok", "fail"):
            out[f"probes_{oc}"] = m.probes.with_labels(outcome=oc).value()
        return out

    def recent_since(self, seq: int) -> List[dict]:
        snap = self.node.decision_ledger.snapshot()
        return [d for d in snap["recent"] if d["seq"] > seq]

    def last_seq(self) -> int:
        recent = self.node.decision_ledger.snapshot()["recent"]
        return recent[-1]["seq"] if recent else 0


def wait_for(cond, timeout_s: float, what: str, poll_s: float = 0.05):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        val = cond()
        if val:
            return val
        time.sleep(poll_s)
    raise SmokeFailure(f"timed out after {timeout_s:.0f}s waiting for {what}")


# --------------------------------------------------------------------------
# leg: node


def leg_node(
    home: str,
    expect_platform: str = "tpu",
    min_height: int = 3,
    fault_domains: int = 1,
    min_batch: Optional[int] = None,
    max_chunk: Optional[int] = None,
):
    """Build a home the way `python -m cometbft_tpu init` does, point
    [crypto] at the tpu backend, construct the node through
    default_new_node (memdb, in-process kvstore), start it, and wait for
    height >= min_height and for what the node does for the device plane
    at start, in its own process: the supervisor's warmup canary passing
    on the device and, when a warm boot is on, the AOT warm boot
    (node._warm_tpu_kernels) and the calibration sweep behind it.
    ``min_batch``/``max_chunk`` (the routing floor and the chunk cap,
    which also bound the warm ladder) stay at the config defaults on the
    chip; the toy-size test lowers them. → (node, record)."""
    from cometbft_tpu.cmd.commands import main as cli_main
    from cometbft_tpu.cmd.commands import _load_config
    from cometbft_tpu.crypto.tpu import mesh as tpu_mesh
    from cometbft_tpu.libs.net import free_ports
    from cometbft_tpu.node import default_new_node

    t0 = time.monotonic()
    check(
        cli_main(["--home", home, "init", "--chain-id", CHAIN_ID]) == 0,
        "init failed",
    )
    cfg = _load_config(home)
    rpc_port, p2p_port = free_ports(2)
    cfg.base.proxy_app = "kvstore"
    cfg.base.db_backend = "memdb"
    cfg.rpc.laddr = f"tcp://127.0.0.1:{rpc_port}"
    cfg.p2p.laddr = f"tcp://127.0.0.1:{p2p_port}"
    cfg.crypto.backend = "tpu"
    # Two [crypto] settings leave their defaults; the dispatch watchdog,
    # the audit and the hedge stay where a node has them.
    #
    # warm_boot: the node's own start — 9 ladder executables, then the
    # calibration sweep, which compiles a device-hash program for every
    # size at which the device keeps winning, and on this chip it does —
    # took 833 s cold on the v5e after the canary passed at 86 s (chip
    # run, PR 21: this script with the node's warm boot on), which leaves
    # the legs' own compiles no room inside a 1,200 s run. So the buckets
    # the legs dispatch compile on demand, inside supervised dispatches
    # under the default 60 s watchdog (build time is not dispatch time:
    # aot.BuildClock), and every compile is reported. With CBFT_WARM_BOOT
    # set, the node's env switch wins and the leg waits for that warm
    # boot and its calibration instead.
    cfg.crypto.warm_boot = "off"
    # router: the priced router takes the cheaper of its prices for the
    # host pool and the chip, and on the one-chip v5e machine the two
    # TIE: its 13 host cores verify 2,048 lanes in 25.2 ms and 10,000 in
    # 117.8 ms, the chip in 23.4-26.3 ms and 115-116 ms (chip runs,
    # PR 21). Which side of a tie a flush lands on is noise, and a smoke
    # has to be repeatable, so the flushes here take the threshold ladder
    # (device from min_batch up). Whatever prices the ledger holds are
    # printed with every flush so the tie stays in view (ROADMAP A1).
    cfg.crypto.router = "threshold"
    # 0 = one fault domain per visible chip (without it a multi-chip
    # host has no shard plan); 1 = the single-chip default
    cfg.crypto.fault_domains = fault_domains
    if min_batch is not None:
        cfg.crypto.min_batch = min_batch
    if max_chunk is not None:
        cfg.crypto.max_chunk = max_chunk
    plane_before = tpu_mesh.resolved_plane()
    node = default_new_node(cfg)
    plane = tpu_mesh.device_plane()
    check(
        plane["platform"] == expect_platform,
        "the tpu backend resolved another platform", plane=plane,
    )
    node.start()
    say(f"node started on {plane['platform']} x{plane['n_devices']}")
    try:
        rec = _await_device_plane(node, cfg, min_height, t0)
    except BaseException:
        node.stop()  # nothing is recovered: the failure goes on up
        raise
    rec["plane_resolved_before_node"] = plane_before is not None
    rec["plane"] = plane
    return node, rec


def _await_device_plane(node, cfg, min_height: int, t0: float) -> dict:
    """The waiting half of leg_node. → the leg's record."""
    from cometbft_tpu.crypto.tpu import aot, calibrate

    wait_for(
        lambda: node.block_store.height() >= min_height, 180,
        f"height >= {min_height}",
    )
    height = node.block_store.height()
    say(f"height {height}; waiting for the warmup canary on the device")
    m = node.verify_supervisor.metrics
    n_domains = len(node.verify_topology)

    def canary_done():
        ok = m.probes.with_labels(outcome="ok").value()
        bad = m.probes.with_labels(outcome="fail").value()
        check(bad == 0, "warmup canary failed on the device")
        return ok >= n_domains

    wait_for(canary_done, 600, "the warmup canary", poll_s=0.25)
    t_canary = time.monotonic()
    mode = aot.warm_boot_mode(cfg.crypto.warm_boot)
    boot = aot.current_warm_boot()
    check((boot is None) == (mode == "off"),
          "the node's warm boot does not match [crypto] warm_boot",
          mode=mode)
    warmed = None
    if boot is not None:
        say(f"canary ok; waiting for the node's warm boot ({mode}) "
            "and calibration")
        check(boot.join(timeout=900), "the node's warm boot did not finish")
        check(boot.error is None, "the node's warm boot failed",
              error=repr(boot.error))
        warmed = boot.result
    table = calibrate.load_table() or {}
    if warmed is not None:
        say("warm boot: %d executables, %.0f s compiling; calibration: %s" % (
            len(warmed), sum(o["compile_s"] for o in warmed),
            json.dumps({k: table.get(k) for k in (
                "ed25519", "ed25519_min_batch", "merkle_min_leaves")}),
        ))
    books = Books(node)
    routed = books.decision_lanes()
    check(not any(routed.get(r) for r in Books.DEVICE_ROUTES),
          "a flush took a device route before any leg ran", lanes=routed)
    return {
        "height": height,
        "fault_domains": n_domains,
        "crypto": {
            k: getattr(cfg.crypto, k) for k in (
                "warm_boot", "dispatch_timeout_ms", "audit_pct",
                "min_batch", "max_chunk", "router", "fault_domains",
            )
        },
        "warm_boot": warmed,
        "calibration": {
            k: table.get(k) for k in (
                "ed25519", "ed25519_min_batch", "merkle",
                "merkle_min_leaves", "sharded", "compile",
            )
        },
        # the canary's lanes and the calibration sweep's: what was on the
        # device before any leg ran
        "wire_lanes_before_legs": books.flush_wire_lanes(),
        "builds_before_legs": len(aot.default_registry().stats()["builds"]),
        "smoke_s": {
            "start_to_canary": round(t_canary - t0, 2),
            "canary_to_warm": round(time.monotonic() - t_canary, 2),
        },
    }


# --------------------------------------------------------------------------
# leg: mega-commit


def _submit_and_compare(node, items, subsystem: str, height: int,
                        timeout_s: float) -> List[bool]:
    fut = node.crypto_backend.submit(
        items, subsystem=subsystem, height=height
    )
    _, mask = fut.result(timeout=timeout_s)
    want = cpu_oracle(items)
    diff = [i for i in range(len(items)) if bool(mask[i]) != bool(want[i])]
    check(not diff, "device mask differs from CPUBatchVerifier",
          lanes=diff[:16], n=len(items))
    return [bool(b) for b in mask]


def leg_megacommit(node, n_vals: int, seed: int, timeout_s: float = 900.0):
    """BASELINE.json config #5: one commit signed by n_vals validators.
    verify_commit twice through node.crypto_backend (the resident-valset
    path: upload, then hit), then the same lanes — some spoiled —
    through submit, compared lane by lane with the CPU oracle. The
    valset is resident by then, so a flush all of whose keys it covers
    rides the keystore's indexed wire (signatures plus an index into the
    table on the device, 100 B/lane) where the plane has one device;
    one foreign key in the flush and it ships keyed (128 B/lane). Both
    are driven, twice each: keys intact, then one key swapped."""
    from cometbft_tpu.crypto.tpu import keystore
    from cometbft_tpu.crypto.tpu import mesh as tpu_mesh

    books = Books(node)
    t0 = time.monotonic()
    vals, privs = make_valset(n_vals, seed, "mega")
    height = 1_000
    bid, commit = make_commit(vals, privs, height, seed)
    t_data = time.monotonic()
    ks0 = keystore.default_store().snapshot()["stats"]
    wire0 = books.wire_lanes().get("resident", 0)
    times = []
    compiles = []
    for _ in range(2):
        c0 = books.aot_compiles()
        t = time.monotonic()
        vals.verify_commit(
            CHAIN_ID, bid, height, commit, backend=node.crypto_backend
        )
        times.append(round(time.monotonic() - t, 3))
        compiles.append(books.aot_compiles() - c0)
    ks1 = keystore.default_store().snapshot()["stats"]
    resident_lanes = books.wire_lanes().get("resident", 0) - wire0
    check(ks1["uploads"] - ks0["uploads"] == 1,
          "first verify_commit did not upload the valset", before=ks0,
          after=ks1)
    check(ks1["hits"] - ks0["hits"] >= 1,
          "second verify_commit did not hit the resident valset",
          before=ks0, after=ks1)
    check(resident_lanes == 2 * n_vals,
          "resident-path lanes on the device", got=resident_lanes,
          want=2 * n_vals)
    check(compiles[1] == 0, "second verify_commit compiled",
          compiles=compiles)

    clean = commit_items(vals, commit)
    indexed = tpu_mesh.n_devices() == 1
    submits = []
    flushes = []
    for name, swap_keys in (("keys_intact", False), ("keys_intact", False),
                            ("key_swapped", True), ("key_swapped", True)):
        items, spoiled = corrupt(clean, swap_keys=swap_keys)
        seq0 = books.last_seq()
        c0 = books.aot_compiles()
        lanes0 = books.flush_wire_lanes()
        t = time.monotonic()
        mask = _submit_and_compare(node, items, "consensus", height,
                                   timeout_s)
        wall = time.monotonic() - t
        bad = [i for i, ok in enumerate(mask) if not ok]
        check(bad == spoiled, "rejected lanes are not the spoiled lanes",
              rejected=bad, spoiled=spoiled)
        # a vote of the node's own chain may ride along: >= n_vals lanes
        mine = [d for d in books.recent_since(seq0) if d["n"] >= n_vals]
        check(len(mine) == 1, "expected one flush per submit", flushes=mine)
        (d,) = mine
        _check_device_flush(d)
        # the supervisor re-checks claimed-bad lanes on the device and
        # confirms them on the CPU; a flush routed `indexed` (the priced
        # router's label) is served by the keystore with neither
        triaged = 0 if d["taken"] == "indexed" else len(spoiled)
        lanes1 = books.flush_wire_lanes()
        moved = {r: lanes1[r] - lanes0[r] for r in lanes1
                 if lanes1[r] != lanes0[r]}
        if d["n"] == n_vals:  # no rider with a key of its own
            check(list(moved.values()) == [n_vals + triaged],
                  "the flush's lanes are not all on one wire route",
                  moved=moved, triaged=triaged, submit=name, decision=d)
            covered = indexed and not swap_keys
            check(("indexed" in moved) == covered,
                  "keystore coverage and the wire route disagree",
                  moved=moved, covered=covered, submit=name, decision=d)
        submits.append({
            "keys": name,
            "smoke_s": round(wall, 3),
            "aot_compiles": books.aot_compiles() - c0,
            "device_lanes_by_wire_route": moved,
            "triaged_lanes": triaged,
        })
        flushes.append(d)
    check(submits[1]["aot_compiles"] == 0 and submits[3]["aot_compiles"] == 0,
          "a second submit compiled", submits=submits)
    # wire bytes per real lane in the biggest bucket the indexed route
    # shares with a keyed one (the keyed flush launches 2,048 lanes a
    # chip, the indexed one up to the chunk cap): the same bucket either
    # way, so indexed : keyed = 100 : 128
    profiles = [r for r in node.wire_ledger.snapshot()["profiles"]
                if r["route"] in Books.FLUSH_WIRE_ROUTES]
    keyed_buckets = {r["bucket"] for r in profiles if r["route"] != "indexed"}
    top = max(
        (r["bucket"] for r in profiles
         if r["route"] == "indexed" and r["bucket"] in keyed_buckets),
        default=None,
    )
    wire_bytes = {
        r["route"]: r["bytes_per_lane"] for r in profiles
        if r["bucket"] == top
    }
    if indexed:
        check(any(x["device_lanes_by_wire_route"].get("indexed", 0) >= n_vals
                  for x in submits),
              "no flush rode the keystore's indexed wire", submits=submits)
        keyed = [v for r, v in wire_bytes.items() if r != "indexed"]
        check(keyed and abs(wire_bytes["indexed"] / keyed[0] - 100 / 128)
              < 0.01, "indexed wire is not 100 B/lane against 128 keyed",
              bucket=top, bytes_per_lane=wire_bytes)
    return {
        "validators": n_vals,
        "spoiled_lanes": len(spoiled),
        "verify_commit_smoke_s": times,
        "verify_commit_aot_compiles": compiles,
        "resident_lanes_on_device": resident_lanes,
        "indexed_wire_expected": indexed,
        "wire_bytes_per_real_lane": {"bucket": top, **wire_bytes},
        "submit": submits,
        "flushes": [_brief(d) for d in flushes],
        "supervised_device_lanes": sum(d["n"] for d in flushes),
        "supervised_bad_lanes": sum(x["triaged_lanes"] for x in submits),
        "data_s": round(t_data - t0, 2),
    }


def _brief(d: dict) -> dict:
    """A flush's decision record, plus what the priced router's argmin
    over the same menu would have been (None while a primary rung is
    unpriced) — the router here is the threshold ladder, see leg_node."""
    out = {
        k: d[k] for k in (
            "seq", "n", "reason", "router", "taken", "final", "events",
            "wall_ms", "predicted_ms",
        )
    }
    feasible = d.get("feasible") or {}
    priced = {
        r: ms for r, ms in (d["predicted_ms"] or {}).items()
        if feasible.get(r)
    }
    cold = any(ms is None for r, ms in priced.items()
               if r in Books.PRIMARY_ROUTES)
    out["priced_argmin"] = None if cold or not priced else min(
        (r for r in priced if priced[r] is not None), key=priced.get
    )
    return out


def _check_device_flush(d: dict) -> None:
    """A flush that cleared the floor must have TAKEN a device route,
    unpinned, and ended on it. If the floor or the priced router sent it
    to the host that is a finding (ROADMAP A1) — print the record, fail."""
    check(d["taken"] in Books.DEVICE_ROUTES,
          "a flush above the floor was routed to the host", decision=d)
    check(d["router"] != "pinned", "route was pinned", decision=d)
    check(d["final"] == d["taken"] and not d["events"],
          "flush did not end on the route it took", decision=d)


# --------------------------------------------------------------------------
# leg: blocksync window


def leg_blocksync(node, n_blocks: int, n_vals: int, seed: int,
                  timeout_s: float = 900.0):
    """BASELINE.json config #4 shape: n_blocks consecutive n_vals-
    validator commits, each block its own request carrying
    verify_commit_light's quorum prefix, submitted in one burst exactly
    like blocksync/reactor.py _submit_window_commits; one block carries
    a forged precommit and must be the only one rejected. Twice.

    The second pass does NOT ride the keystore's indexed wire, and that
    is checked: the only uploader of a valset table is the resident
    commit path, which the routing floor closes to a sub-floor valset
    (and a valset above the floor takes the resident path instead of
    this one), so nothing makes a 150-validator set resident. ROADMAP
    B2 carries it; the indexed wire is driven in the mega-commit leg."""
    from cometbft_tpu.crypto import ed25519
    from cometbft_tpu.crypto.tpu import keystore
    from cometbft_tpu.types.validator_set import cs_sig

    books = Books(node)
    floor = node.crypto_spec.min_batch
    indexed0 = books.wire_lanes().get("indexed", 0)
    t0 = time.monotonic()
    vals, privs = make_valset(n_vals, seed, "sync")
    needed = vals.total_voting_power() * 2 // 3
    forged_block = n_blocks // 2
    window = []
    for b in range(n_blocks):
        height = 2_000 + b
        _, commit = make_commit(vals, privs, height, seed)
        items, power = [], 0
        for idx, csig in enumerate(commit.signatures):
            if not csig.for_block():
                continue
            val = vals.validators[idx]
            items.append((
                val.pub_key,
                commit.vote_sign_bytes(CHAIN_ID, idx),
                cs_sig(commit, idx),
            ))
            power += val.voting_power
            if power > needed:
                break
        if b == forged_block:
            # a precommit "from" validator 3 signed by somebody else
            pk, msg, _ = items[3]
            forger = ed25519.gen_priv_key_from_secret(_secret(seed, "forger"))
            items[3] = (pk, msg, forger.sign(msg))
        window.append((height, items))
    lanes_per_pass = sum(len(items) for _, items in window)
    check(lanes_per_pass >= floor,
          "window too small to clear the routing floor",
          lanes=lanes_per_pass, floor=floor)
    want = [all(cpu_oracle(items)) for _, items in window]
    check(want.count(False) == 1 and not want[forged_block],
          "oracle disagrees with the forgery planted")
    t_data = time.monotonic()

    passes = []
    dev_lanes = 0
    for p in range(2):
        seq0 = books.last_seq()
        c0 = books.aot_compiles()
        t = time.monotonic()
        futs = [
            node.crypto_backend.submit(
                items, subsystem="blocksync", height=height
            )
            for height, items in window
        ]
        got = [f.result(timeout=timeout_s)[0] for f in futs]
        wall = time.monotonic() - t
        check(got == want, "block verdicts differ from the CPU oracle",
              rejected=[i for i, ok in enumerate(got) if not ok],
              planted=forged_block)
        # the burst's flushes: everything the window rode, by route.
        # Fragments that closed below the floor went to the host by the
        # floor, which is routing; every flush that cleared it must have
        # taken a device route and ended there.
        flushes = [
            d for d in books.recent_since(seq0)
            if (d["qos"] or {}).get("blocksync")
        ]
        rode = sum(d["n"] for d in flushes)
        check(rode >= lanes_per_pass, "window flushes not all on record",
              rode=rode, lanes=lanes_per_pass)
        on_dev = [d for d in flushes if d["n"] >= floor]
        check(on_dev, "no flush of the window cleared the floor",
              flushes=[_brief(d) for d in flushes])
        for d in on_dev:
            _check_device_flush(d)
        for d in flushes:
            if d["n"] < floor:
                check(d["taken"] == "cpu" and d["router"] == "floor",
                      "sub-floor flush not routed by the floor", decision=d)
        dev_lanes += sum(d["n"] for d in on_dev)
        passes.append({
            "smoke_s": round(wall, 3),
            "aot_compiles": books.aot_compiles() - c0,
            "flushes": [
                {"n": d["n"], "taken": d["taken"], "router": d["router"]}
                for d in flushes
            ],
        })
    check(passes[1]["aot_compiles"] == 0, "second pass compiled",
          passes=passes)
    resident = keystore.covers([pk for pk, _, _ in window[0][1]])
    check(not resident
          and books.wire_lanes().get("indexed", 0) == indexed0,
          "the window's valset became resident: the indexed wire is "
          "reachable from a blocksync window after all (ROADMAP B2)",
          resident=resident, validators=n_vals, floor=floor)
    return {
        "blocks": n_blocks,
        "valset_resident": resident,
        "validators": n_vals,
        "lanes_per_pass": lanes_per_pass,
        "forged_block": forged_block,
        "passes": passes,
        "supervised_device_lanes": dev_lanes,
        # the forged lane is triaged (re-checked on the device, confirmed
        # on the CPU) once per pass IF its block's flush ran on the
        # device; a flush record does not list its requests, so the
        # audit allows 0..2 and pins the number from the wire ledger
        "supervised_bad_lanes_max": 2,
        "data_s": round(t_data - t0, 2),
    }


# --------------------------------------------------------------------------
# leg: service


def leg_service(
    sock_dir: str,
    n_clients: int,
    rounds: int,
    lanes: int,
    seed: int,
    expect_platform: str = "tpu",
    client_timeout_ms: int = 600_000,
    row_verifier=None,
):
    """tools/verifyd.py's Daemon(backend="tpu") in THIS process on a
    unix socket; n_clients RemoteVerifier threads (host-side packing
    only) each send a lanes-wide commit per round, released together so
    the daemon coalesces a round into one flush. The lane budget is one
    round's lanes, so the size trigger — not luck — closes the flush."""
    from cometbft_tpu.crypto import service as servicelib
    from cometbft_tpu.crypto import wire as wirelib
    sys.path.insert(0, os.path.join(_HERE, "tools"))
    from verifyd import Daemon

    t0 = time.monotonic()
    vals, privs = make_valset(lanes, seed, "light")
    requests: List[List[List[tuple]]] = []
    want: List[List[List[bool]]] = []
    for c in range(n_clients):
        per_round = []
        for r in range(rounds):
            _, commit = make_commit(vals, privs, 3_000 + c * rounds + r, seed)
            items = commit_items(vals, commit)
            if (c + r) % 5 == 0:
                i = (7 * c + 3 * r) % lanes
                pk, msg, sig = items[i]
                items[i] = (pk, msg, sig[:9] + bytes([sig[9] ^ 1]) + sig[10:])
            per_round.append(items)
        requests.append(per_round)
        want.append([cpu_oracle(items) for items in per_round])
    t_data = time.monotonic()

    from cometbft_tpu.crypto.tpu import aot

    ledger = wirelib.default_ledger()
    check(ledger is not None, "no wire ledger installed")
    wire0 = ledger.lanes_by_route().get("service", 0)
    compiles0 = aot.default_registry().compile_count
    address = "unix://" + os.path.join(sock_dir, "verifyd.sock")
    daemon = Daemon(
        address,
        backend="tpu",
        flush_us=200_000,
        max_chunk=n_clients * lanes,
        row_verifier=row_verifier,
    )
    daemon.start()
    clients = [
        servicelib.RemoteVerifier(
            address, tenant=f"light-{c}", timeout_ms=client_timeout_ms
        )
        for c in range(n_clients)
    ]
    barrier = threading.Barrier(n_clients)
    results: List[List[Optional[tuple]]] = [
        [None] * rounds for _ in range(n_clients)
    ]
    errors: List[BaseException] = []
    round_wall: List[float] = [0.0] * rounds

    def run(c: int) -> None:
        try:
            for r in range(rounds):
                barrier.wait(timeout=client_timeout_ms / 1e3)
                t = time.monotonic()
                fut = clients[c].submit(
                    requests[c][r], subsystem="light", height=3_000 + r
                )
                _, mask = fut.result(timeout=client_timeout_ms / 1e3)
                results[c][r] = (
                    [bool(b) for b in mask], getattr(fut, "reason", None)
                )
                if c == 0:
                    round_wall[r] = round(time.monotonic() - t, 4)
        except BaseException as exc:  # noqa: BLE001 - surfaced below
            errors.append(exc)
            barrier.abort()

    threads = [
        threading.Thread(target=run, args=(c,), name=f"smoke-client-{c}")
        for c in range(n_clients)
    ]
    t_run = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=client_timeout_ms / 1e3 + 60)
        check(not t.is_alive(), "client thread did not finish")
    check(not errors, "client thread raised", errors=[repr(e) for e in errors])
    wall = time.monotonic() - t_run

    for c in range(n_clients):
        for r in range(rounds):
            mask, reason = results[c][r]
            check(reason is None,
                  "client fell back to its local CPU", client=c, round=r,
                  reason=reason)
            check(mask == [bool(b) for b in want[c][r]],
                  "service verdicts differ from the CPU oracle",
                  client=c, round=r)
    snap = daemon.service.snapshot()
    sched = daemon.scheduler.queue_snapshot()
    total = n_clients * rounds * lanes
    on_dev = ledger.lanes_by_route().get("service", 0) - wire0
    check(on_dev == total, "service lanes on the device", got=on_dev,
          want=total)
    check(sched["routes"]["service"] >= rounds,
          "fewer device flushes than rounds", routes=sched["routes"])
    check(daemon.scheduler.metrics.cpu_fallbacks.value() == 0,
          "daemon fell back to the host verifier")
    check((snap["device_plane"] or {}).get("platform") == expect_platform,
          "verifyd snapshot names another platform",
          plane=snap["device_plane"])
    stats = [cl.snapshot()["stats"] for cl in clients]
    abandoned = daemon.drain()
    check(abandoned == 0, "drain abandoned frames", abandoned=abandoned)
    for cl in clients:
        cl.close()
    daemon.stop()
    return {
        "clients": n_clients,
        "rounds": rounds,
        "lanes_per_request": lanes,
        "lanes_on_device": on_dev,
        "aot_compiles": aot.default_registry().compile_count - compiles0,
        "flushes": sched["routes"]["service"],
        "flush_reasons": sched["flush_reasons"],
        "bytes_per_lane": snap["bytes_per_lane"],
        "client_stats": stats[0],
        "first_round_smoke_s": round_wall[0],
        "later_rounds_smoke_s": round_wall[1:],
        "smoke_s": round(wall, 3),
        "data_s": round(t_data - t0, 2),
    }


# --------------------------------------------------------------------------
# leg: more than one device


def leg_multichip(node, seed: int):
    """Where do buffers actually live? One sharded program over the
    plan's devices, and one single-device dispatch scoped to a non-zero
    fault domain — asserted on the arrays' own device sets."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from cometbft_tpu.crypto.tpu import aot
    from cometbft_tpu.crypto.tpu import ed25519_batch as eb
    from cometbft_tpu.crypto.tpu import mesh as tpu_mesh

    topo = node.verify_topology
    plan = tpu_mesh.shard_plan(topo)
    check(plan is not None, "no shard plan over a multi-device topology",
          topology=topo.snapshot())
    vals, privs = make_valset(256, seed, "multi")
    _, commit = make_commit(vals, privs, 4_000, seed)
    items = commit_items(vals, commit)
    pks = [pk.bytes() for pk, _, _ in items]
    msgs = [m for _, m, _ in items]
    sigs = [s for _, _, s in items]
    wire, valid = eb.prepare_batch_compact(pks, msgs, sigs)
    check(bool(valid.all()), "multichip batch must parse")

    size = tpu_mesh.shard_bucket(len(items), plan.n_shards, 64)
    padded = np.zeros((128, size), np.uint8)
    padded[:, : len(items)] = wire
    sharding = NamedSharding(plan.mesh, PS(None, "batch"))
    placed = jax.device_put(jnp.asarray(padded), sharding)
    out = aot.default_registry().call(
        eb.verify_kernel_compact, [placed], sharded=True, mesh=plan.mesh
    )
    out_devs = {s.device for s in out.addressable_shards}
    check(len(out_devs) == plan.n_shards,
          "sharded output does not sit on one device per shard",
          devices=sorted(str(d) for d in out_devs), shards=plan.n_shards)
    check(bool(np.asarray(out)[: len(items)].all()),
          "sharded program rejected valid signatures")

    handle = topo.device(len(topo) - 1)
    jax_dev = tpu_mesh.placement(handle)
    check(jax_dev is not None and jax_dev != jax.devices()[0],
          "a non-zero fault domain has no chip of its own",
          handle=repr(handle))
    one = np.zeros((128, 256), np.uint8)
    one[:, : len(items)] = wire
    arg = jax.device_put(jnp.asarray(one), jax_dev)
    check(arg.devices() == {jax_dev}, "staging buffer not on the domain")
    res = tpu_mesh.run_single(eb.verify_kernel_compact, [arg], device=jax_dev)
    check(res.devices() == {jax_dev},
          "the domain's program ran somewhere else",
          ran_on=sorted(str(d) for d in res.devices()), want=str(jax_dev))
    check(bool(np.asarray(res)[: len(items)].all()),
          "placed program rejected valid signatures")
    # and the same scoping through the dispatch loop the supervisor uses
    mask = tpu_mesh.dispatch_batch(
        eb.verify_kernel_compact, [wire], len(items), 8192, 64,
        device=handle,
    )
    check(bool(mask.all()), "scoped dispatch_batch rejected valid lanes")
    return {
        "plan_shards": plan.n_shards,
        "sharded_output_devices": sorted(str(d) for d in out_devs),
        "scoped_domain": handle.label,
        "scoped_device": str(jax_dev),
        "direct_device_lanes": len(items),
    }


# --------------------------------------------------------------------------
# the audit: the program's own counters, and no surprises


def audit(node, legs: Dict[str, dict], expect_platform: str = "tpu",
          audit_wait_s: float = 120.0) -> dict:
    from cometbft_tpu.crypto.tpu import aot

    books = Books(node)
    # the corruption audit re-verifies audit_pct % of the supervised
    # device flushes on the CPU, behind the flush: let it finish
    wait_for(lambda: node.verify_supervisor.audits_pending() == 0,
             audit_wait_s, "the corruption audits", poll_s=0.1)

    # the node's own votes keep flushing (to the CPU, by the floor) while
    # the books are read, so the cpu-routed lanes are read twice, around
    sup = books.supervisor()
    dec_lanes = books.decision_lanes()
    wire = books.wire_lanes()
    dec_counts = node.decision_ledger.counts()
    fallbacks = node.decision_ledger.snapshot()["fallbacks"]

    for name in ("failures", "cpu_routed", "watchdog_kills", "retries",
                 "hedge_wins_cpu", "sharded_fallbacks", "indexed_fallbacks",
                 "triage_cpu_fallbacks", "triage_divergence",
                 "audit_mismatches", "audit_drops", "hedge_divergence",
                 "host_lanes", "chunk_shrinks", "probes_fail"):
        check(sup[name] == 0, f"supervisor counter {name} is not zero",
              value=sup[name], all=sup)
    check(node.verify_scheduler.metrics.cpu_fallbacks.value() == 0,
          "verify_scheduler_cpu_fallbacks is not zero")
    check(not fallbacks, "a flush left the route it took",
          fallbacks=fallbacks)
    states = node.verify_supervisor.device_states()
    check(all(s == "healthy" for s in states.values()),
          "a breaker is not HEALTHY", states=states)

    # every lane a device route took reached the device, and no other:
    # since the legs began (the canary's and the calibration sweep's
    # lanes were there before), flush lanes + the triage re-checks of
    # spoiled lanes + what the multichip leg dispatched itself
    before = legs["node"]["wire_lanes_before_legs"]
    dev_flush_lanes = sum(dec_lanes.get(r, 0) for r in Books.DEVICE_ROUTES)
    dev_wire_lanes = sum(
        wire.get(r, 0) - before.get(r, 0) for r in Books.FLUSH_WIRE_ROUTES
    )
    triaged = legs["megacommit"]["supervised_bad_lanes"]
    direct = legs.get("multichip", {}).get("direct_device_lanes", 0)
    lo = dev_flush_lanes + triaged + direct
    hi = lo + legs["blocksync"]["supervised_bad_lanes_max"]
    check(lo <= dev_wire_lanes <= hi,
          "device lanes in the wire ledger do not match the flushes "
          "that took a device route",
          wire=wire, before=before, decisions=dec_lanes, triaged=triaged,
          direct=direct)
    triaged += dev_wire_lanes - lo  # the forged precommit(s), exactly
    check(sup["triage_passes"] == sup["triage_runs"],
          "triage took more than one device pass per run", sup=sup)
    want_dev = (legs["megacommit"]["supervised_device_lanes"]
                + legs["blocksync"]["supervised_device_lanes"])
    check(dev_flush_lanes == want_dev,
          "lanes on device routes differ from what the legs submitted "
          "above the floor", decisions=dec_lanes, want=want_dev)

    # the host pool saw the lanes routed there, the audit's re-verifies
    # and the triage's confirmations — and nothing else
    cpu_pool = books.cpu_pool_lanes()
    time.sleep(0.2)  # a vote flush in flight lands in the ledger
    cpu_routed_now = books.decision_lanes().get("cpu", 0)
    audited = int(sup["audit_lanes"])
    want_lo = dec_lanes.get("cpu", 0) + audited + triaged
    want_hi = cpu_routed_now + audited + triaged
    check(want_lo <= cpu_pool <= want_hi,
          "the cpu pseudo-device saw lanes nothing routed to it",
          cpu_pool=cpu_pool, want=[want_lo, want_hi],
          cpu_routed=dec_lanes.get("cpu", 0), audited=audited,
          triage_confirmed=triaged)

    mem = node.memory_plane.snapshot()
    modes = {k: v.get("mode") for k, v in mem.get("devices", {}).items()}
    if expect_platform == "tpu":
        check(modes and all(m == "device" for m in modes.values()),
              "memory plane is not reading the device", modes=modes)

    reg = aot.default_registry()
    stats = reg.stats()
    return {
        "routes": node.verify_scheduler.queue_snapshot()["routes"],
        "decisions": dec_counts,
        "lanes_by_taken_route": dec_lanes,
        "lanes_on_device_by_wire_route": wire,
        "lanes_on_cpu_pool": cpu_pool,
        "lanes_on_cpu_pool_by_cause": {
            "routed_cpu": [dec_lanes.get("cpu", 0), cpu_routed_now],
            "audited": audited,
            "triage_confirmed": triaged,
        },
        "supervisor": sup,
        "breakers": states,
        "memory": {
            "modes": modes,
            "peak_bytes": {
                k: v.get("bytes_peak") for k, v in
                mem.get("devices", {}).items()
            },
        },
        "aot": {
            "compiles": stats["compiles"],
            "exec_store_hits": reg.metrics.exec_store_hits.value(),
            "exec_store_discards": reg.metrics.exec_store_discards.value(),
            "exec_store_save_failures":
                reg.metrics.exec_store_save_failures.value(),
            "builds": stats["builds"],
        },
    }


# --------------------------------------------------------------------------


def run(seed: int, expect_platform: str = "tpu") -> dict:
    """All legs, in order, at the sizes operators run. → the summary."""
    import jax
    from cometbft_tpu.crypto.tpu import aot
    from cometbft_tpu.crypto.tpu import mesh as tpu_mesh

    cache_events = {"hits": 0, "misses": 0}

    def on_event(name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    legs: Dict[str, dict] = {}
    n_dev = len(jax.devices())
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        node, legs["node"] = leg_node(
            os.path.join(tmp, "home"), expect_platform,
            fault_domains=1 if n_dev == 1 else 0,
        )
        try:
            say("leg megacommit")
            legs["megacommit"] = leg_megacommit(node, MEGA_VALIDATORS, seed)
            say("leg blocksync")
            legs["blocksync"] = leg_blocksync(
                node, WINDOW_BLOCKS, WINDOW_VALIDATORS, seed
            )
            say("leg service")
            legs["service"] = leg_service(
                tmp, SERVICE_CLIENTS, SERVICE_ROUNDS, WINDOW_VALIDATORS,
                seed, expect_platform,
            )
            if n_dev > 1:
                say("leg multichip")
                legs["multichip"] = leg_multichip(node, seed)
            say("audit")
            books = audit(node, legs, expect_platform)
            if n_dev == 1:
                # one chip: the node's warm boot covered every bucket of
                # the kernels it plans that a leg dispatched. (Kernels
                # with no plan — the indexed program, whose table axis
                # follows the valset — compile on first use; so does a
                # fault domain's own placed executable on several chips.)
                planned = {
                    o["kernel"] for o in legs["node"]["warm_boot"] or ()
                }
                late = [
                    b for b in
                    books["aot"]["builds"][legs["node"]["builds_before_legs"]:]
                    if b["source"] == "dispatch" and b["kernel"] in planned
                ]
                check(not late,
                      "a leg compiled a bucket the node's warm boot plans",
                      late=late, warm_boot=legs["node"]["warm_boot"])
        finally:
            node.stop()
    jax.monitoring.unregister_event_listener(on_event)
    plane = tpu_mesh.device_plane()
    import jaxlib

    return {
        "platform": plane["platform"],
        "device_kind": plane["device_kind"],
        "n_devices": plane["n_devices"],
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": plane["libtpu"],
        "seed": seed,
        "oracle": oracle_record(),
        "compile_cache": {
            "dir": aot.compile_cache_dir(),
            "exec_store": aot.exec_store_root(),
            "executables_served_by_store":
                books["aot"]["exec_store_hits"],
            "executables_discarded_on_load":
                books["aot"]["exec_store_discards"],
            "xla_cache_hits": cache_events["hits"],
            "xla_cache_misses": cache_events["misses"],
            "fresh_compiles": books["aot"]["compiles"],
        },
        "compile_seconds": [
            b for b in books["aot"]["builds"] if b["source"] != "store"
        ],
        "load_seconds": [
            b for b in books["aot"]["builds"] if b["source"] == "store"
        ],
        "legs": legs,
        "books": {k: v for k, v in books.items() if k != "aot"},
        "timings_are": "smoke timings (one cold reading each), "
                       "not a benchmark",
        "total_s": round(time.monotonic() - _T0, 1),
        "claim": None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    args = ap.parse_args(argv)
    dev = device_record()
    if dev["platform"] != "tpu":
        print(
            f"chip_smoke: jax found no TPU (platform {dev['platform']!r}, "
            f"{dev['kind']}, {dev['count']} device(s)); nothing was run",
            file=sys.stderr,
        )
        return NO_TPU_EXIT
    say(f"device {dev}")
    summary = run(args.seed)
    out_dir = os.path.join(_HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke.json"), "w") as fh:
        json.dump(summary, fh, indent=1, default=str)
    print(json.dumps(summary, default=str))
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    # No process is ever started; threads of a node that a failed check
    # left half-stopped must not keep the script alive, so the exit is
    # hard either way. Nothing is recovered from here: a failure prints
    # its traceback and the exit code says so.
    try:
        rc = main()
    except SystemExit as exc:
        rc = exc.code if isinstance(exc.code, int) else 1
    except BaseException:  # noqa: BLE001 - report, then exit non-zero
        import traceback

        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
