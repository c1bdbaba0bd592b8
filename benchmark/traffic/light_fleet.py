"""Traffic: a fleet of light clients on one shared ``verifyd``.

One request is one header verification through the entry point a light
client calls: ``light.verifier.verify(trusted, trusted_vals, untrusted,
untrusted_vals, trusting_period, now, max_clock_drift, backend=<that
client's RemoteVerifier>)``, timed from the call to its return: the
client's header checks, sign-bytes and packing are in it, as they are
for a light client. Closed loop: ``clients`` threads, one connection and
one tenant each, one verification in flight each, back to back.

The clients follow ``chains`` chains, spread by Zipf (``zipf_s``; the
traffic file states the split as ``clients_per_chain`` and ``build``
checks the two against each other), numbered in chain order. Client
``i`` with ``i % skipping_every == skipping_every - 1`` runs upstream's
bisection shape (``VerifyNonAdjacent``: trusted header ``h``, untrusted
``h + 2`` or further, the 1/3 trusting prefix against the trusted set
and then the 2/3 prefix: two round trips); the others run the sequence
shape (``VerifyAdjacent``: the 2/3 prefix, one round trip). Each chain
is a pool of ``pool_headers`` pre-signed light blocks verified in
rotation; nothing on the path memoises a verdict (tests/benchmark/
test_light_fleet_cell.py). Each client registers its chain's validator
set once when it connects.

``warm`` starts the daemon inside the plane's process, as
``tools/verifyd.py`` builds it (``Daemon(address, backend="tpu")``, every
other argument at its default), beside the plane's node, which idles and
owns the device gate, the AOT registry and the wire ledger. A
verification any of whose round trips was answered by the client's local
CPU, refused, shed or rejected, or that timed out, or during which the
daemon's host rung moved, is a failed request.

Parameters (the traffic file): ``clients``, ``chains``, ``zipf_s``,
``clients_per_chain``, ``skipping_every``, ``skip_min``, ``skip_max``,
``first_height``, ``request_timeout_s``, ``forged`` (``quorum_lane``: a
lane inside the 2/3 prefix and outside the trusting one; ``trusting_lane``:
inside both).
"""

from __future__ import annotations

import os
import random
import sys
import tempfile
import threading
import time
from typing import Dict, List, Tuple

from benchmark.lib import data, light_reference, loops, reference

# a client's own counters that move when something other than the
# daemon's device path answered (crypto/service.py RemoteVerifier._count)
CLIENT_FAILURES = (
    "timeout", "disconnected", "rejected", "error", "unauthorized",
    "draining", "resync_failed", "failed_over",
)
# before the stale resend existed a refused indexed frame was answered
# by the client's CPU under this reason; with it, "stale" counts frames
# the protocol sent again and the daemon served
STALE, STALE_RESENDS = "stale", "stale_resends"

SECOND_NS = 1_000_000_000


def zipf_split(clients: int, chains: int, s: float) -> List[int]:
    """``clients`` over ``chains`` in proportion to rank ** -s, by
    largest remainders."""
    weights = [(rank + 1) ** -s for rank in range(chains)]
    shares = [w / sum(weights) * clients for w in weights]
    out = [int(x) for x in shares]
    rest = sorted(range(chains), key=lambda c: (int(shares[c]) - shares[c], c))
    for c in rest[: clients - sum(out)]:
        out[c] += 1
    return out


def assignment(params: dict) -> List[Tuple[int, bool]]:
    """[(chain, skipping?)] per client, in client order."""
    per_chain = [int(n) for n in params["clients_per_chain"]]
    every = int(params["skipping_every"])
    chain_of = [c for c, n in enumerate(per_chain) for _ in range(n)]
    return [(c, i % every == every - 1) for i, c in enumerate(chain_of)]


# --------------------------------------------------------------------------
# chains: the program's objects (what light.verify takes) and their plain
# records (what the reference takes)


def make_chain(chain_id: str, valset: tuple, first_height: int, blocks: int,
               seed: int, tag: str) -> list:
    """→ [LightBlock]: ``blocks`` consecutive signed headers of one
    validator set (``valset``: the set and its signers in its order, as
    data.make_valset gives them); a header's hash is its commit's block
    id, its validators hash and next-validators hash the set's."""
    from cometbft_tpu.proto.version import ConsensusVersion
    from cometbft_tpu.types import test_util
    from cometbft_tpu.types.block import BlockID, Header
    from cometbft_tpu.types.light_block import LightBlock, SignedHeader
    from cometbft_tpu.types.part_set import PartSetHeader
    from cometbft_tpu.version import BLOCK_PROTOCOL

    vals, privs = valset
    vhash = vals.hash()
    out, last = [], BlockID()
    for k in range(blocks):
        height = first_height + k
        header = Header(
            version=ConsensusVersion(BLOCK_PROTOCOL, 0),
            chain_id=chain_id,
            height=height,
            time=data.timestamp(height),
            last_block_id=last,
            validators_hash=vhash,
            next_validators_hash=vhash,
            consensus_hash=data.secret(seed, tag, "consensus"),
            app_hash=data.secret(seed, tag, "app", height),
            proposer_address=vals.validators[k % vals.size()].address,
        )
        bid = BlockID(
            header.hash(),
            PartSetHeader(1, data.secret(seed, tag, "parts", height)),
        )
        commit = test_util.make_commit(
            bid, height, 0, vals, privs, chain_id, now=header.time
        )
        out.append(LightBlock(SignedHeader(header, commit), vals))
        last = bid
    return out


def plain_vals(vals) -> dict:
    return {
        "hash": vals.hash(),
        "rows": [(v.address, int(v.voting_power), v.pub_key.bytes())
                 for v in vals.validators],
    }


def plain_block(block, chain_id: str) -> dict:
    """One light block with nothing of the program's types left in it."""
    sh = block.signed_header
    header, commit = sh.header, sh.commit
    rows = []
    for i, cs in enumerate(commit.signatures):
        msg = b"" if cs.is_absent() else commit.vote_sign_bytes(chain_id, i)
        rows.append((int(cs.block_id_flag), bytes(cs.validator_address),
                     bytes(cs.signature), msg))
    return {
        "chain_id": header.chain_id,
        "height": int(header.height),
        "time_ns": int(header.time.to_unix_ns()),
        "hash": header.hash(),
        "validators_hash": bytes(header.validators_hash),
        "next_validators_hash": bytes(header.next_validators_hash),
        "commit": {
            "height": int(commit.height),
            "block_hash": bytes(commit.block_id.hash),
            "rows": rows,
        },
    }


def copy_commit(block):
    """The same light block with a commit of its own (fresh rows, no
    cached hash), so that a row can be changed; header and validator set
    are shared."""
    import dataclasses

    from cometbft_tpu.types.block import Commit
    from cometbft_tpu.types.light_block import LightBlock, SignedHeader

    sh = block.signed_header
    commit = Commit(
        height=sh.commit.height, round=sh.commit.round,
        block_id=sh.commit.block_id,
        signatures=[dataclasses.replace(cs) for cs in sh.commit.signatures],
    )
    return LightBlock(SignedHeader(sh.header, commit), block.validator_set)


def forge_block(block, lane: int, chain_id: str, seed: int):
    """The same light block with precommit ``lane`` signed by somebody
    else: a vote "from" that validator which its key did not sign."""
    from cometbft_tpu.crypto import ed25519

    forged = copy_commit(block)
    commit = forged.signed_header.commit
    forger = ed25519.gen_priv_key_from_secret(data.secret(seed, "forger"))
    commit.signatures[lane].signature = forger.sign(
        commit.vote_sign_bytes(chain_id, lane)
    )
    return forged


class _Memo:
    """``reference.verify_many`` with each distinct lane verified once
    over the build: a header's 2/3 prefix is the same lanes whichever
    trusted header it is verified from."""

    def __init__(self):
        self.seen: Dict[tuple, bool] = {}

    def __call__(self, lanes):
        todo = [lane for lane in dict.fromkeys(lanes) if lane not in self.seen]
        for lane, ok in zip(todo, reference.verify_many(todo)):
            self.seen[lane] = ok
        return [self.seen[lane] for lane in lanes]


def program_verdict(plan: dict, backend, trusted, untrusted) -> str:
    """``light.verifier.verify`` → the reference's class names."""
    from cometbft_tpu.light import errors, verifier

    try:
        verifier.verify(
            trusted.signed_header, trusted.validator_set,
            untrusted.signed_header, untrusted.validator_set,
            plan["trusting_period_ns"], plan["now"],
            plan["max_clock_drift_ns"], backend=backend,
        )
    except errors.ErrOldHeaderExpired:
        return light_reference.EXPIRED
    except errors.ErrInvalidHeader:
        return light_reference.INVALID_HEADER
    except errors.ErrNewValSetCantBeTrusted:
        return light_reference.CANT_BE_TRUSTED
    except ValueError:
        return light_reference.TRUSTING_COMMIT
    return light_reference.ACCEPT


def build(config: dict, params: dict, seed: int) -> dict:
    from cometbft_tpu.proto.gogo import Timestamp

    n_clients, n_chains = int(params["clients"]), int(params["chains"])
    per_chain = [int(n) for n in params["clients_per_chain"]]
    if per_chain != zipf_split(n_clients, n_chains, float(params["zipf_s"])):
        raise ValueError(
            f"clients_per_chain {per_chain} is not {n_clients} clients over "
            f"{n_chains} chains by Zipf s={params['zipf_s']}"
        )
    pool = int(config["pool_headers"])
    first = int(params["first_height"])
    skip_min, skip_max = int(params["skip_min"]), int(params["skip_max"])
    trusting_ns = int(config["trusting_period_s"]) * SECOND_NS
    drift_ns = int(config["max_clock_drift_s"]) * SECOND_NS
    newest = data.timestamp(first + pool - 1)
    now = Timestamp(newest.seconds + int(config["now_after_newest_s"]), 0)
    now_ns = now.to_unix_ns()
    rng = random.Random(seed)
    memo = _Memo()
    forged = params["forged"]

    def want(tv, t, uv, u) -> str:
        return light_reference.verify(
            t, tv, u, uv, trusting_ns, now_ns, drift_ns, verify_many=memo
        )[0]

    chains = []
    for c in range(n_chains):
        chain_id = f"{config['chain_id']}-{c}"
        tag = f"light{c}"
        valset = data.make_valset(int(config["validators"]), seed, tag)
        vals = valset[0]
        blocks = make_chain(chain_id, valset, first, pool, seed, tag)
        pv = plain_vals(vals)
        plain = [plain_block(b, chain_id) for b in blocks]
        adjacent = [(k, k + 1) for k in range(pool - 1)]
        skipping = [
            (k, k + rng.randint(skip_min, skip_max))
            for k in range(pool - skip_max)
        ]
        # warm-up's forged headers: the first pair of each shape with one
        # precommit of the untrusted header forged
        bad = {}
        for name, (k, m), lane in (
            ("adjacent_quorum", adjacent[0], forged["quorum_lane"]),
            ("skipping_quorum", skipping[0], forged["quorum_lane"]),
            ("skipping_trusting", skipping[0], forged["trusting_lane"]),
        ):
            block = forge_block(blocks[m], int(lane), chain_id, seed)
            bad[name] = {
                "trusted": blocks[k], "untrusted": block,
                "want": want(pv, plain[k], pv, plain_block(block, chain_id)),
            }
        chains.append({
            "chain_id": chain_id,
            "valset": vals,
            "pub_keys": [v.pub_key.bytes() for v in vals.validators],
            "blocks": blocks,
            "adjacent": adjacent,
            "skipping": skipping,
            "want": {
                pair: want(pv, plain[pair[0]], pv, plain[pair[1]])
                for pair in adjacent + skipping
            },
            "forged": bad,
        })
    lanes = shape_lanes(chains[0])
    clients = [
        {"chain": c, "skipping": skips, "offset": rng.randrange(pool)}
        for c, skips in assignment(params)
    ]
    return {
        "chains": chains,
        "clients": clients,
        "now": now,
        "trusting_period_ns": trusting_ns,
        "max_clock_drift_ns": drift_ns,
        "lanes": lanes,
        # the most lanes one flush can hold: every client's larger frame
        "max_flush_lanes": sum(
            lanes["skipping"][1] if cl["skipping"] else lanes["adjacent"][0]
            for cl in clients
        ),
        "timeout_s": float(params["request_timeout_s"]),
        "daemon": config.get("daemon", {}),
    }


class Recording:
    """A backend that verifies nothing remotely: it keeps what
    ``light.verifier.verify`` hands it, lane for lane, and answers with
    the host verifier's verdicts."""

    def __init__(self):
        from cometbft_tpu.crypto.batch import BackendSpec

        self.spec = BackendSpec(name="cpu")
        self.calls: List[List[tuple]] = []

    def submit(self, items, subsystem=None, height=None):
        from cometbft_tpu.crypto.batch import CPUBatchVerifier
        from cometbft_tpu.crypto.scheduler import VerifyFuture

        self.calls.append(list(items))
        bv = CPUBatchVerifier()
        for pk, msg, sig in items:
            bv.add(pk, msg, sig)
        fut = VerifyFuture()
        fut._set(bv.verify())
        return fut


def shape_lanes(chain: dict) -> Dict[str, List[int]]:
    """{"adjacent": [lanes of its round trip], "skipping": [lanes of
    each of its two]}, as the program's verifier produces them."""
    from cometbft_tpu.light import verifier
    from cometbft_tpu.proto.gogo import Timestamp

    out = {}
    blocks = chain["blocks"]
    far = Timestamp(blocks[-1].signed_header.header.time.seconds + 1, 0)
    for name in ("adjacent", "skipping"):
        k, m = chain[name][0]
        rec = Recording()
        verifier.verify(
            blocks[k].signed_header, blocks[k].validator_set,
            blocks[m].signed_header, blocks[m].validator_set,
            10**18, far, 10**10, backend=rec,
        )
        out[name] = [len(call) for call in rec.calls]
    return out


# --------------------------------------------------------------------------
# the daemon and its clients


class Fleet:
    """tools/verifyd.py's Daemon in this process, and one RemoteVerifier
    a client."""

    def __init__(self, plan: dict):
        from cometbft_tpu.crypto import service as servicelib

        tools = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))), "tools",
        )
        if tools not in sys.path:
            sys.path.insert(0, tools)
        from verifyd import Daemon

        self.dir = tempfile.mkdtemp(prefix="bench_verifyd_")
        address = "unix://" + os.path.join(self.dir, "verifyd.sock")
        self.daemon = Daemon(address, **plan["daemon"])
        self.daemon.start()
        self.address = address
        self.clients = [
            servicelib.RemoteVerifier(
                address, tenant=f"light-{i}",
                timeout_ms=int(plan["timeout_s"] * 1e3),
            )
            for i in range(len(plan["clients"]))
        ]

    def warm_client(self, timeout_s: float):
        """One more connection, for warm-up's flushes alone: they may
        build an executable each, so its requests wait longer."""
        from cometbft_tpu.crypto import service as servicelib

        return servicelib.RemoteVerifier(
            self.address, tenant="light-warm", timeout_ms=int(timeout_s * 1e3)
        )

    def stop(self) -> None:
        for cl in self.clients:
            cl.close()
        if self.daemon.service.is_running():
            self.daemon.stop()
        try:
            os.rmdir(self.dir)
        except OSError:
            pass

    def registered_sets(self) -> int:
        """Validator sets the daemon's key store holds."""
        store = self.daemon.scheduler.queue_snapshot().get("keystore") or {}
        return len(store.get("entries", []))

    def host_rung(self) -> float:
        return self.daemon.scheduler.metrics.cpu_fallbacks.value()

    def books(self) -> Dict[str, float]:
        """The counters the cell's readers take differences of. A program
        without one of them (the parent of the PR that added it) leaves
        it out, and its reader finds nothing."""
        snap = self.daemon.service.snapshot()
        queue = self.daemon.scheduler.queue_snapshot()
        out: Dict[str, float] = {
            "sched_requests": self.daemon.scheduler.metrics.requests.value(),
            "sched_dispatches": queue["dispatches"],
            "host_rung": self.host_rung(),
            "req_frames": snap["frames"].get("req", 0),
            "register_frames": snap["frames"].get("register", 0),
            "lanes_indexed": snap["lanes"].get("indexed", 0),
            "lanes_compact": snap["lanes"].get("compact", 0),
            "stale_drops": snap["stale_drops"],
            "errors": sum(snap["errors"].values()),
        }
        for name, lane in queue.get("qos", {}).get("classes", {}).items():
            out["qos_refused"] = out.get("qos_refused", 0) + sum(
                lane.get(k, 0) for k in ("sheds", "drops")
            )
            out["qos_admits_" + name] = lane.get("admits", 0)
        for reason, count in queue["flush_reasons"].items():
            out["flush_" + reason] = count
        if "served" in snap:
            out["served"] = snap["served"]
            out["served_s"] = snap["served_s"]
        store = (queue.get("keystore") or {}).get("stats", {})
        for key, name in (("uploads", "keystore_uploads"),
                          ("evictions", "keystore_evictions"),
                          ("keystore_thrash", "keystore_thrash")):
            if key in store:
                out[name] = store[key]
        stats = [cl.snapshot() for cl in self.clients]
        for key in ("rtts", STALE, STALE_RESENDS) + CLIENT_FAILURES:
            out["client_" + key] = sum(
                s["stats"].get(key, 0) for s in stats
            )
        if all("rtt_s" in s for s in stats):
            out["client_rtt_s"] = sum(s["rtt_s"] for s in stats)
        return out


def client_failures(stats: Dict[str, int]) -> Dict[str, int]:
    """Of one client's counters, those that say a round trip was not
    served by the daemon. ``stale`` without as many resends is the older
    protocol's local fallback."""
    out = {k: stats.get(k, 0) for k in CLIENT_FAILURES}
    out[STALE] = stats.get(STALE, 0) - stats.get(STALE_RESENDS, 0)
    return out


def verify_once(plane, plan: dict, fleet: Fleet, i: int, trusted, untrusted,
                want: str) -> Tuple[str, str]:
    """One request of client ``i`` → (status, the class it got)."""
    client = fleet.clients[i]
    marks = client_failures(client.stats())
    rung = fleet.host_rung()
    with plane.span("bench:light.verify"):
        got = program_verdict(plan, client, trusted, untrusted)
    moved = [k for k, v in client_failures(client.stats()).items()
             if v != marks[k]]
    if moved:
        return moved[0], got
    if got != want:
        return "mismatch", got
    if fleet.host_rung() != rung:
        return "fallback", got
    return "ok", got


def warm_sizes(plan: dict) -> List[int]:
    """Lanes of the single-request flushes that between them reach every
    ``verify_compact`` shape a flush of the fleet can reach, whichever
    way the program cuts a flush: one for each power of two from the
    smallest frame's bucket to the largest flush's, at most the largest
    flush (a flush launches as one padded bucket, or as a stream whose
    shapes are buckets a smaller flush reaches alone)."""
    smallest = min(plan["lanes"]["skipping"] + plan["lanes"]["adjacent"])
    out, size = [], 1 << (smallest - 1).bit_length()
    while size < plan["max_flush_lanes"]:
        out.append(max(smallest, size))
        size *= 2
    return out + [plan["max_flush_lanes"]]


def warm(plane, plan: dict) -> dict:
    """The daemon, the clients and their registrations (all of which the
    daemon must hold); every shape; the forged headers, which alone must
    be refused, each with the reference's class; then one request a
    client, all at once. Raises on any verdict the reference does not
    give."""
    fleet = plan["fleet"] = Fleet(plan)
    stop_node = plane.stop

    def stop() -> None:
        fleet.stop()
        stop_node()

    plane.stop = stop
    long_s = plan["timeout_s"] * 20
    for i, cl in enumerate(plan["clients"]):
        fleet.clients[i].register_valset(
            plan["chains"][cl["chain"]]["pub_keys"]
        )
    held = fleet.registered_sets()
    if held < len(plan["chains"]):
        # every later request of some chain would be refused as stale and
        # answered by its client's CPU: no service to measure, and said
        # before any executable is built
        raise AssertionError(
            f"the daemon's key store holds {held} of the fleet's "
            f"{len(plan['chains'])} validator sets after every client "
            "registered: this program cannot serve the deployment"
        )
    chain = plan["chains"][plan["clients"][0]["chain"]]
    lanes = [
        lane for block in chain["blocks"]
        for lane in data.quorum_prefix_items(
            chain["valset"], block.signed_header.commit, chain["chain_id"]
        )
    ]
    sizes = warm_sizes(plan)
    warmer = fleet.warm_client(long_s)
    try:
        for n in sizes:
            while len(lanes) < n:
                lanes = lanes + lanes
            fut = warmer.submit(lanes[:n])
            ok, _ = fut.result(timeout=long_s + 10)
            if not ok or getattr(fut, "reason", None):
                raise AssertionError(
                    f"warm-up flush of {n} lanes: ok={ok}, "
                    f"reason={getattr(fut, 'reason', None)}"
                )
    finally:
        warmer.close()
    refused = 0
    first_of: Dict[Tuple[int, bool], int] = {}
    for i, cl in enumerate(plan["clients"]):
        first_of.setdefault((cl["chain"], cl["skipping"]), i)
    for (c, skips), i in sorted(first_of.items()):
        names = (("skipping_quorum", "skipping_trusting") if skips
                 else ("adjacent_quorum",))
        for name in names:
            case = plan["chains"][c]["forged"][name]
            status, got = verify_once(plane, plan, fleet, i, case["trusted"],
                                      case["untrusted"], case["want"])
            if case["want"] == light_reference.ACCEPT or got != case["want"]:
                raise AssertionError(
                    f"forged header {name} of chain {c}: got {got}, the "
                    f"reference says {case['want']} ({status})"
                )
            refused += 1
    first = _round(plane, plan, fleet, rounds=2)
    bad = [r for r in first if r[2] != "ok"]
    if any(status == "mismatch" for _, _, status in bad):
        raise AssertionError(f"a warm-up verification is wrong: {bad[:4]}")
    return {"flush_sizes": sizes, "forged_refused": refused,
            "warm_not_ok": len(bad), "books": fleet.books()}


def _pairs(plan: dict, i: int):
    cl = plan["clients"][i]
    chain = plan["chains"][cl["chain"]]
    pairs = chain["skipping" if cl["skipping"] else "adjacent"]
    sigs = sum(plan["lanes"]["skipping" if cl["skipping"] else "adjacent"])
    return chain, pairs, cl["offset"], sigs


def _round(plane, plan: dict, fleet: Fleet, rounds: int) -> list:
    """``rounds`` requests a client, every client at once."""
    out: List[tuple] = []

    def run(i: int) -> None:
        chain, pairs, offset, sigs = _pairs(plan, i)
        for r in range(rounds):
            pair = pairs[(offset + r) % len(pairs)]
            t = time.monotonic()
            status, _ = verify_once(
                plane, plan, fleet, i, chain["blocks"][pair[0]],
                chain["blocks"][pair[1]], chain["want"][pair],
            )
            out.append((time.monotonic() - t, sigs, status))

    _run_clients(run, len(plan["clients"]), plan["timeout_s"] * 20 * rounds)
    return out


def _run_clients(run, n: int, join_s: float) -> None:
    errors: List[BaseException] = []

    def guarded(i: int) -> None:
        try:
            run(i)
        except BaseException as exc:  # noqa: BLE001 - re-raised below
            errors.append(exc)

    threads = [
        threading.Thread(target=guarded, args=(i,), name=f"bench-light-{i}",
                         daemon=True)
        for i in range(n)
    ]
    for t in threads:
        t.start()
    deadline = time.monotonic() + join_s
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.monotonic()))
    if errors:
        raise errors[0]
    if any(t.is_alive() for t in threads):
        raise TimeoutError(f"a client thread did not end within {join_s:.0f}s")


def drive(plane, plan: dict, seconds: float) -> dict:
    fleet: Fleet = plan["fleet"]
    n = len(plan["clients"])
    per_client: List[List[tuple]] = [[] for _ in range(n)]
    served = [0] * n
    before = fleet.books()
    t0 = time.monotonic()
    stop_at = t0 + seconds
    done = threading.Event()

    def run(i: int) -> None:
        chain, pairs, offset, sigs = _pairs(plan, i)
        k = 0
        while time.monotonic() < stop_at:
            pair = pairs[(offset + k) % len(pairs)]
            k += 1
            t = time.monotonic()
            try:
                status, got = verify_once(
                    plane, plan, fleet, i, chain["blocks"][pair[0]],
                    chain["blocks"][pair[1]], chain["want"][pair],
                )
            except Exception as exc:  # noqa: BLE001 - a failed request
                status, got = "error", repr(exc)
            if status == "ok":
                served[i] += sigs
            elif len(per_client[i]) < 3 or status == "mismatch":
                plane.note(f"client {i} pair {pair}: {status} ({got})")
            per_client[i].append((time.monotonic() - t, sigs, status))

    # one unit of host cost a whole second: 32 requests overlap, so a
    # request has no CPU seconds of its own
    cpu_units: List[Tuple[float, int]] = []

    def sample() -> None:
        cpu, sigs = loops.cpu_seconds(), 0
        while not done.wait(1.0):
            cpu_now, sigs_now = loops.cpu_seconds(), sum(served)
            cpu_units.append((cpu_now - cpu, sigs_now - sigs))
            cpu, sigs = cpu_now, sigs_now

    sampler = threading.Thread(target=sample, name="bench-light-cpu",
                               daemon=True)
    sampler.start()
    try:
        # a request in flight when the window closes ends within its own
        # timeout (the client's deadline answers it locally)
        _run_clients(run, n, seconds + plan["timeout_s"] * 2 + 10)
    finally:
        done.set()
        sampler.join(timeout=5.0)
    window_s = time.monotonic() - t0
    after = fleet.books()
    requests = [r for rows in per_client for r in rows]
    moved = {key: after[key] - before.get(key, 0) for key in after}
    plane.note(f"fleet books over the window: {moved}")
    return {
        "loop": "closed",
        "window_s": window_s,
        "attempted": len(requests),
        "requests": requests,
        "cpu_units": cpu_units,
        "extra_sigs": 0,
        "spans_s": {"fleet": moved},
        "late_s": [],
    }


