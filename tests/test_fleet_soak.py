"""Host-fleet soak: N node PROCESSES gossiping over real TCP through
lossy proxies (awset_test.go:16-17's exchange model made real at
fleet scale).

The parent runs one lossy TCP proxy per worker: a seeded 20% of proxied
connections are CUT after forwarding a random prefix (torn frames /
connection-closed mid-exchange — the socket-level face of a dropped
gossip round).  Workers additionally duplicate ~15% of exchanges and
reshuffle peer order per sweep (duplication + reordering).  Phase 2
sweeps every pair directly once the fleet is quiescent, after which
every replica must hold the identical global union — digest equality,
not just liveness.
"""

import json
import socket
import subprocess
import sys
import threading
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
N_WORKERS = 8
NUM_ELEMENTS = 64


class LossyProxy:
    """Forwards TCP connections to ``target_port``; a seeded fraction
    are cut after a random forwarded prefix (both directions pumped;
    the cut closes both ends abruptly)."""

    def __init__(self, target_port: int, seed: int, drop_rate: float = 0.2):
        self.target_port = target_port
        self.rng_lock = threading.Lock()
        self.rng = __import__("random").Random(seed)
        self.drop_rate = drop_rate
        self.total = 0
        self.dropped = 0
        self._closing = False
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(64)
        self.port = self.sock.getsockname()[1]
        threading.Thread(target=self._accept_loop, daemon=True).start()

    def _accept_loop(self) -> None:
        while not self._closing:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            with self.rng_lock:
                self.total += 1
                cut = self.rng.random() < self.drop_rate
                cut_after = self.rng.randint(0, 40) if cut else None
                if cut:
                    self.dropped += 1
            threading.Thread(target=self._pump_pair, daemon=True,
                             args=(conn, cut_after)).start()

    def _pump_pair(self, conn: socket.socket, cut_after) -> None:
        try:
            upstream = socket.create_connection(
                ("127.0.0.1", self.target_port), timeout=5.0)
        except OSError:
            conn.close()
            return

        def pump(src, dst, budget):
            forwarded = 0
            try:
                while True:
                    take = 4096 if budget is None else min(
                        4096, budget - forwarded)
                    if take <= 0:
                        break
                    data = src.recv(take)
                    if not data:
                        break
                    dst.sendall(data)
                    forwarded += len(data)
            except OSError:
                pass
            finally:
                # abrupt close of BOTH ends: the peer sees a torn frame
                for s in (src, dst):
                    try:
                        s.close()
                    except OSError:
                        pass

        threading.Thread(target=pump, daemon=True,
                         args=(conn, upstream, cut_after)).start()
        pump(upstream, conn, cut_after)

    def close(self) -> None:
        self._closing = True
        try:
            self.sock.close()
        except OSError:
            pass


def _read_until(proc, prefix: str) -> str:
    while True:
        line = proc.stdout.readline()
        if not line:
            raise AssertionError(
                f"worker exited early: {proc.stderr.read()[-2000:]}")
        if line.startswith(prefix):
            return line.strip()


def test_fleet_converges_under_injected_loss():
    sys.path.insert(0, str(REPO))
    from __graft_entry__ import _scrubbed_cpu_env

    env = _scrubbed_cpu_env(1)
    workers = []
    proxies = []
    try:
        for i in range(N_WORKERS):
            workers.append(subprocess.Popen(
                [sys.executable, str(REPO / "tests" / "fleet_worker.py"),
                 str(i), str(N_WORKERS), str(NUM_ELEMENTS)],
                env=env, cwd=str(REPO), text=True,
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE))
        direct = [int(_read_until(w, "PORT").split()[1]) for w in workers]
        proxies = [LossyProxy(p, seed=7000 + j)
                   for j, p in enumerate(direct)]
        addrs = " ".join(str(p.port) for p in proxies) + " " + " ".join(
            str(p) for p in direct)
        for w in workers:
            w.stdin.write(f"ADDRS {addrs}\n")
            w.stdin.flush()
        for w in workers:
            _read_until(w, "PHASE1")
        # the loss injection must have actually fired: ~20% of ~4
        # sweeps x 7 peers x ~1.15 dials x 8 workers ~ 50 connections
        assert sum(p.dropped for p in proxies) >= 10
        assert sum(p.total for p in proxies) >= 100
        for w in workers:
            w.stdin.write("PHASE2\n")
            w.stdin.flush()
        for w in workers:
            _read_until(w, "PHASE2DONE")
        for w in workers:
            w.stdin.write("REPORT\n")
            w.stdin.flush()
        reports = [json.loads(_read_until(w, "{")) for w in workers]
        for w in workers:
            assert w.wait(timeout=30) == 0
    finally:
        for p in proxies:
            p.close()
        for w in workers:
            if w.poll() is None:
                w.kill()

    expected = sorted(e for i in range(N_WORKERS)
                      for e in range(i * 4, i * 4 + 4))
    lost = sum(r["lost"] for r in reports)
    assert lost >= 10, "proxy cuts must surface as lost exchanges"
    for i, r in enumerate(reports):
        assert r["members"] == expected, f"worker {i} diverged"
        assert r["vv"] == reports[0]["vv"], f"worker {i} VV diverged"
