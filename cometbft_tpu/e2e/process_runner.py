"""Process-isolated multi-node testnet with real perturbations.

Reference: test/e2e/runner/perturb.go:44-74 — the reference's runner
kills node CONTAINERS with SIGKILL, pauses them (docker pause =
SIGSTOP), and disconnects them from the network. The in-process
`runner.Testnet` cannot exercise any of those: its "kill" is a
cooperative `node.stop()` which cleanly flushes the WAL. Here every
node is a real `python -m cometbft_tpu start` subprocess on its own
home directory, so:

- kill(i)        = SIGKILL — fsync ordering and WAL-torn-tail handling
                   get exercised by the restart's catchup replay
- pause(i)       = SIGSTOP / SIGCONT (docker pause semantics)
- disconnect(i)  = every p2p byte flows through per-pair TCP relays
                   owned by the harness (the moral equivalent of
                   `docker network disconnect`); a partitioned node's
                   relays drop live pipes and refuse new ones
- heal(i)        = relays resume; persistent-peer redial reconnects

The relay layer exists because the image has no iptables/netns: the
nodes themselves run unmodified — only the wire between them is cut,
which is exactly what a network partition is.
"""

from __future__ import annotations

import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional, Tuple

from cometbft_tpu.cmd.commands import _load_config, main as cli_main
from cometbft_tpu.config import write_config_file
from cometbft_tpu.libs.net import free_ports as _free_ports
from cometbft_tpu.rpc.client import HTTPClient


class _Relay:
    """One direction of one peer link: accept on `listen_port`, pipe to
    `target_port`. `enabled=False` closes live pipes and refuses new
    connections (refused, not black-holed: the dialer sees ECONNRESET
    immediately, like a downed interface with an RST-emitting router)."""

    def __init__(self, listen_port: int, target_port: int):
        self.listen_port = listen_port
        self.target_port = target_port
        self.enabled = True
        self._socks: List[socket.socket] = []
        self._mtx = threading.Lock()
        self._server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._server.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._server.bind(("127.0.0.1", listen_port))
        self._server.listen(16)
        self._stopped = False
        self._thread = threading.Thread(target=self._accept_loop, daemon=True)
        self._thread.start()

    def _accept_loop(self) -> None:
        while not self._stopped:
            try:
                cli, _ = self._server.accept()
            except OSError:
                return
            if self._stopped:
                cli.close()
                return
            if not self.enabled:
                cli.close()
                continue
            try:
                srv = socket.create_connection(
                    ("127.0.0.1", self.target_port), timeout=5
                )
            except OSError:
                cli.close()
                continue
            with self._mtx:
                self._socks += [cli, srv]
            for a, b in ((cli, srv), (srv, cli)):
                threading.Thread(
                    target=self._pipe, args=(a, b), daemon=True
                ).start()

    def _pipe(self, src: socket.socket, dst: socket.socket) -> None:
        try:
            while True:
                data = src.recv(65536)
                if not data:
                    break
                dst.sendall(data)
        except OSError:
            pass
        finally:
            for s in (src, dst):
                try:
                    s.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
                try:
                    s.close()
                except OSError:
                    pass

    def set_enabled(self, enabled: bool) -> None:
        self.enabled = enabled
        if not enabled:
            with self._mtx:
                socks, self._socks = self._socks, []
            for s in socks:
                # shutdown BEFORE close: a bare close() leaves the pipe
                # threads blocked in recv() holding the kernel socket
                # alive, so the peers never see FIN and the "cut" link
                # stays silently connected
                try:
                    s.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
                try:
                    s.close()
                except OSError:
                    pass

    def stop(self) -> None:
        self._stopped = True
        try:
            self._server.close()
        except OSError:
            pass
        self.set_enabled(False)


from cometbft_tpu.e2e.observe import NetObserver


class ProcessTestnet(NetObserver):
    """N validator subprocesses wired through harness-owned relays."""

    _client_timeout = 5  # a SIGSTOPped node must not stall polling long

    __test__ = False

    def __init__(
        self,
        n_validators: int = 4,
        proxy_app: str = "kvstore",
        chain_id: str = "e2e-proc-chain",
        timeout_commit_ns: int = 300_000_000,
        base_dir: Optional[str] = None,
    ):
        self.n = n_validators
        self.proxy_app = proxy_app
        self.chain_id = chain_id
        self.timeout_commit_ns = timeout_commit_ns
        self.base_dir = base_dir or tempfile.mkdtemp(prefix="e2e-proc-")
        self._own_dir = base_dir is None
        self.procs: Dict[int, Optional[subprocess.Popen]] = {}
        self._clients: Dict[int, HTTPClient] = {}
        self.rpc_ports: List[int] = []
        self.p2p_ports: List[int] = []
        # relay for the link node i dials toward node j
        self.relays: Dict[Tuple[int, int], _Relay] = {}
        # per-node inbound relay, self-reported as external_address: an
        # inbound persistent peer that dies is redialed at its
        # SELF-REPORTED listen address (switch.go:367 reconnect rule), so
        # that address must also be a wire the harness controls
        self.inbound_relays: Dict[int, _Relay] = {}
        self._log_files: Dict[int, object] = {}

    def _home(self, i: int) -> str:
        return os.path.join(self.base_dir, f"node{i}")

    def setup(self) -> None:
        n = self.n
        ports = _free_ports(3 * n + n * (n - 1))
        self.p2p_ports = ports[:n]
        self.rpc_ports = ports[n : 2 * n]
        inbound_ports = ports[2 * n : 3 * n]
        relay_ports = ports[3 * n :]
        cli_main(
            [
                "testnet",
                "--v", str(n),
                "--output-dir", self.base_dir,
                "--chain-id", self.chain_id,
                "--proxy_app", self.proxy_app,
            ]
        )
        from cometbft_tpu.p2p.key import NodeKey

        ids = []
        for i in range(n):
            cfg = _load_config(self._home(i))
            ids.append(
                NodeKey.load_or_gen(
                    os.path.join(self._home(i), cfg.base.node_key_file)
                ).id()
            )
        self.node_ids = ids
        # one relay per ordered pair (i dials j through relays[(i, j)])
        k = 0
        for i in range(n):
            for j in range(n):
                if i == j:
                    continue
                self.relays[(i, j)] = _Relay(
                    relay_ports[k], self.p2p_ports[j]
                )
                k += 1
        for i in range(n):
            self.inbound_relays[i] = _Relay(
                inbound_ports[i], self.p2p_ports[i]
            )
        for i in range(n):
            cfg = _load_config(self._home(i))
            cfg.base.proxy_app = self.proxy_app
            cfg.p2p.laddr = f"tcp://127.0.0.1:{self.p2p_ports[i]}"
            cfg.p2p.external_address = (
                f"tcp://127.0.0.1:{self.inbound_relays[i].listen_port}"
            )
            cfg.rpc.laddr = f"tcp://127.0.0.1:{self.rpc_ports[i]}"
            cfg.p2p.persistent_peers = ",".join(
                f"{ids[j]}@127.0.0.1:{self.relays[(i, j)].listen_port}"
                for j in range(n)
                if j != i
            )
            cfg.p2p.addr_book_strict = False
            # PEX would gossip the nodes' REAL self-reported addresses and
            # let peers re-dial around the relays, silently un-cutting a
            # partition; this net speaks persistent-peers-over-relay only
            cfg.p2p.pex = False
            cfg.consensus.timeout_commit_ns = self.timeout_commit_ns
            cfg.consensus.create_empty_blocks = True
            write_config_file(
                os.path.join(self._home(i), "config", "config.toml"), cfg
            )

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        for i in range(self.n):
            self.start_node(i)

    def start_node(self, i: int) -> None:
        env = dict(os.environ)
        # a chip belongs to one process: e2e node children are never
        # its owner, so they stay on the CPU platform
        env["JAX_PLATFORMS"] = "cpu"
        env["CMT_CRYPTO_BACKEND"] = "cpu"
        old_log = self._log_files.get(i)
        if old_log is not None:
            try:
                old_log.close()  # kill/restart cycles must not leak fds
            except OSError:
                pass
        log = open(os.path.join(self.base_dir, f"node{i}.log"), "ab")
        self._log_files[i] = log
        self.procs[i] = subprocess.Popen(
            [sys.executable, "-m", "cometbft_tpu",
             "--home", self._home(i), "start"],
            stdout=log,
            stderr=subprocess.STDOUT,
            env=env,
            cwd=os.path.dirname(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            ),
        )

    def kill_node(self, i: int) -> None:
        """perturb.go:53 "kill": SIGKILL, no chance to flush anything."""
        p = self.procs.get(i)
        if p is not None:
            p.kill()
            p.wait(10)
            self.procs[i] = None

    def pause_node(self, i: int) -> None:
        """perturb.go:59 "pause" (docker pause = cgroup freeze ≈ SIGSTOP)."""
        p = self.procs.get(i)
        if p is not None:
            os.kill(p.pid, signal.SIGSTOP)

    def resume_node(self, i: int) -> None:
        p = self.procs.get(i)
        if p is not None:
            os.kill(p.pid, signal.SIGCONT)

    def disconnect_node(self, i: int) -> None:
        """perturb.go:66 "disconnect": cut every link touching node i.

        The victim's outbound redials of formerly-INBOUND peers target
        those peers' self-reported external addresses (switch.go:367
        rule), which the per-pair relays can't attribute to a source —
        so the partition window disables EVERY inbound relay. The
        majority stays connected regardless: their live links aren't
        touched and their config/outbound redials use the per-pair
        relays, which remain up between non-victims. One partition at a
        time (like the reference runner's sequential perturbations)."""
        for (a, b), r in self.relays.items():
            if a == i or b == i:
                r.set_enabled(False)
        for r in self.inbound_relays.values():
            r.set_enabled(False)

    def connect_node(self, i: int, reconnect_timeout: float = 45.0) -> None:
        for (a, b), r in self.relays.items():
            if a == i or b == i:
                r.set_enabled(True)
        for r in self.inbound_relays.values():
            r.set_enabled(True)
        # nudge re-dials until the healed node actually HAS peers: the
        # switch's own reconnect (quick attempts + exponential backoff)
        # heals organically, but on a starved CI host its sleeps stretch
        # and a single dial_peers burst can race a busy RPC — mirror the
        # operator's repeated `dial_peers` move as belt-and-braces
        deadline = time.monotonic() + reconnect_timeout
        while time.monotonic() < deadline:
            for a in range(self.n):
                if a == i:
                    continue
                for src, dst in ((a, i), (i, a)):
                    addr = (
                        f"{self.node_ids[dst]}"
                        f"@127.0.0.1:{self.relays[(src, dst)].listen_port}"
                    )
                    try:
                        self.client(src).call(
                            "dial_peers",
                            {"peers": [addr], "persistent": True},
                        )
                    except Exception:  # noqa: BLE001 - best-effort nudge
                        pass
            try:
                ni = self.client(i).call("net_info", {})
                if int(ni.get("n_peers") or 0) > 0:
                    return
            except Exception:  # noqa: BLE001 - node busy; retry
                pass
            time.sleep(1.0)

    def terminate_node(self, i: int) -> None:
        """Graceful SIGTERM stop (not a perturbation — teardown)."""
        p = self.procs.get(i)
        if p is not None:
            p.terminate()
            try:
                p.wait(15)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(10)
            self.procs[i] = None

    def stop(self) -> None:
        for i in list(self.procs):
            try:
                self.terminate_node(i)
            except Exception:
                pass
        for r in self.relays.values():
            r.stop()
        for r in self.inbound_relays.values():
            r.stop()
        for f in self._log_files.values():
            try:
                f.close()
            except Exception:
                pass
        if self._own_dir:
            shutil.rmtree(self.base_dir, ignore_errors=True)

    # -- observation: NetObserver (shared with the in-process runner) --------

    def live_indexes(self) -> List[int]:
        return [
            i
            for i, p in self.procs.items()
            if p is not None and p.poll() is None
        ]
