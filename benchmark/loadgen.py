"""The load generator: a child process that drives a served store over
loopback TCP through the client protocol, as users reach it.

It never starts a JAX backend (the harness runs it with
``JAX_PLATFORMS=cpu``, so a stray import cannot touch the chip), keeps
the server's GIL free of client work, and talks to its parent over
stdin/stdout:

  parent -> child   one JSON line: the job (address, traffic, seed,
                    seconds, universe, output path)
  child -> parent   ``READY`` once the connections are up and the
                    warm-up ops are acked
  parent -> child   ``GO <t0>``: the window starts at monotonic time t0
  child -> parent   ``DONE`` once every op sent has resolved (or a
                    minute past the window's close has gone by), after
                    writing per-op times and outcomes to the output path

Per op it records when it was due, when it was sent, when its reply
came and what the reply was, and its own CPU time over the window;
the harness turns those into the cell's numbers and checks the acked
ops against the reference.
"""

from __future__ import annotations

import json
import os
import resource
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import gen  # noqa: E402

# outcome codes, per op
NOT_SENT, ACKED, REJECTED, FAILED, NO_REPLY = -1, 0, 1, 2, 3
_WAIT_PAST_CLOSE_S = 60.0


class _Conn:
    """One client connection and the ops it owns."""

    def __init__(self, client_cls, addr, idx: np.ndarray, in_flight: int):
        self.idx = idx
        self.slots = threading.Semaphore(in_flight)
        self.client = client_cls(tuple(addr), timeout=120.0,
                                 on_result=lambda op: self.slots.release())
        self.pending = {}           # op index -> PendingOp

    def submit(self, i: int, kind: int, key: int):
        self.pending[i] = self.client.submit_async(kind, [key])


def _closed(conn: _Conn, kinds, keys, sent, t_end: float) -> None:
    for i in conn.idx:
        if time.monotonic() >= t_end:
            return
        conn.slots.acquire()
        if time.monotonic() >= t_end:
            return
        sent[i] = time.monotonic()
        conn.submit(int(i), int(kinds[i]), int(keys[i]))


def _open(conn: _Conn, kinds, keys, due, sent, t0: float) -> None:
    for i in conn.idx:
        wait = t0 + due[i] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        sent[i] = time.monotonic()
        conn.submit(int(i), int(kinds[i]), int(keys[i]))


def _resolve(conns, n: int, deadline: float):
    """Wait for every sent op's reply until ``deadline``; per-op reply
    time and outcome."""
    acked_at = np.full(n, np.nan)
    outcome = np.full(n, NOT_SENT, np.int8)
    for conn in conns:
        for i, op in conn.pending.items():
            try:
                latency = op.wait(max(0.0, deadline - time.monotonic()))
            except TimeoutError:
                outcome[i] = NO_REPLY
            except ConnectionError:
                outcome[i] = FAILED
            except Exception:  # noqa: BLE001 — a typed reject: shed
                outcome[i] = REJECTED
            else:
                outcome[i] = ACKED
                acked_at[i] = op.t_sent + latency
    return acked_at, outcome


def _cpu_s(use) -> float:
    return use.ru_utime + use.ru_stime


def _backend_started() -> bool:
    """Did anything in this process start a JAX backend?"""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return bool(xla_bridge._backends)


def run(job: dict, stdin, stdout) -> None:
    from go_crdt_playground_tpu.serve.client import ServeClient

    p, seed, seconds = job["traffic"], int(job["seed"]), float(job["seconds"])
    kinds, keys = gen.op_stream(p, seed, int(job["num_keys"]),
                                gen.stream_length(p, seconds))
    n = kinds.size
    conn_of = gen.split(keys, int(p["connections"]))
    warm = int(p.get("warmup_ops", 0))
    in_flight = int(p.get("in_flight", 8)) if p["loop"] == "closed" \
        else n
    conns = [_Conn(ServeClient, job["addr"],
                   np.nonzero(conn_of[warm:] == c)[0] + warm, in_flight)
             for c in range(int(p["connections"]))]
    sent = np.full(n, np.nan)
    # warm-up: the first ops of the stream, a few in flight per
    # connection, all acked before the window
    for i in range(warm):
        c = conns[conn_of[i]]
        c.slots.acquire()
        c.submit(i, int(kinds[i]), int(keys[i]))
        sent[i] = time.monotonic()
    _, warm_out = _resolve(conns, n, time.monotonic() + 120.0)
    if np.any(warm_out[:warm] != ACKED):
        raise SystemExit(f"warm-up ops not acked: "
                         f"{np.bincount(warm_out[:warm] + 1).tolist()}")
    for c in conns:
        c.pending.clear()
    print("READY", flush=True, file=stdout)
    line = stdin.readline().split()
    if not line or line[0] != "GO":
        raise SystemExit(f"expected GO, got {line!r}")
    t0 = float(line[1])
    t_end = t0 + seconds
    use0 = resource.getrusage(resource.RUSAGE_SELF)
    due = np.full(n, np.nan)
    if p["loop"] == "open":
        due[warm:] = gen.arrivals(p, seed, seconds)
        args = [(c, kinds, keys, due, sent, t0) for c in conns]
        target = _open
    else:
        args = [(c, kinds, keys, sent, t_end) for c in conns]
        target = _closed
    threads = [threading.Thread(target=target, args=a, daemon=True)
               for a in args]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    use1 = resource.getrusage(resource.RUSAGE_SELF)
    acked_at, outcome = _resolve(conns, n, t_end + _WAIT_PAST_CLOSE_S)
    outcome[:warm] = ACKED
    for c in conns:
        c.client.close()
    np.savez(job["out"], sent=sent, due=due, acked_at=acked_at,
             outcome=outcome, t0=t0, t_end=t_end, warm=warm,
             backend_started=_backend_started(),
             cpu_s=_cpu_s(use1) - _cpu_s(use0))
    print("DONE", flush=True, file=stdout)


def main() -> int:
    job = json.loads(sys.stdin.readline())
    run(job, sys.stdin, sys.stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
