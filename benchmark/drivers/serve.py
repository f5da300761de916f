"""Driver for served cells: one ``serve --ingest`` frontend on the chip,
built from CLI flags the way the CLI builds it, driven by the load
generator child over loopback TCP.

Set-up (``setup_s``): the store the traffic starts from is computed
from the seed in host numpy, written as the node's durable checkpoint
and restored by the frontend as a restart would; the frontend warms
its own programs; the child connects and sends the traffic's warm-up
ops.  Then the window.  After it: every op's reply, the replica's
members and clock through QUERY, the peak memory, a crash-shaped stop
(no final checkpoint) and a restart from the durable directory, whose
members and clock must match too.  The reference replays the acked
ops from the seed (``reference/served.py``).

Every run, traced or not, also puts the window as the host saw it into
the line's ``info`` (``_host_info``), which no metric or check reads:
so the runs of a set can be told apart by their phases.
"""

from __future__ import annotations

import json
import math
import os
import resource
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

from benchmark import gen, harness, readers
from benchmark.loadgen import ACKED, FAILED, NO_REPLY
from benchmark.reference import served as ref

FAMILY = "served"


def _write_store(path: str, E: int, A: int, present, dot, deleted,
                 del_dot, clock: int) -> None:
    """The preloaded store as the node's durable checkpoint, written by
    the node's own checkpoint path."""
    from go_crdt_playground_tpu.models.awset_delta import AWSetDeltaState
    from go_crdt_playground_tpu.net.peer import Node
    from go_crdt_playground_tpu.utils.checkpoint import CheckpointStore

    vv = np.zeros((1, A), np.uint32)
    vv[0, 0] = clock
    zero = np.zeros((1, E), np.uint32)
    node = Node(0, E, A)
    node._state = AWSetDeltaState(
        vv=vv, present=present[None], dot_actor=zero, dot_counter=dot[None],
        actor=np.zeros(1, np.uint32), deleted=deleted[None],
        del_dot_actor=zero, del_dot_counter=del_dot[None], processed=vv)
    node.save_durable(CheckpointStore(path))


def _frontend(cfg: dict, store: str, control: bool = False):
    """The frontend as ``serve --ingest`` builds it.  ``control``: the
    CLI's non-durable regime (no ``--durable-dir``: acks are not backed
    by the WAL), with the preloaded store installed by hand - it breaks
    the restart guarantee, and the restart check must catch it."""
    from go_crdt_playground_tpu.__main__ import _build_frontend, build_parser
    from go_crdt_playground_tpu.net.peer import Node

    durable = [] if control else ["--durable-dir", store]
    args = build_parser().parse_args(
        ["serve", "--ingest", "--elements", str(cfg["elements"]),
         "--actors", str(cfg["actors"]), *durable, *cfg["serve_flags"]])
    fe = _build_frontend(args)
    if control and os.path.isdir(store):
        loaded = Node.restore_durable(store)
        fe.node._state = loaded._state
        loaded.close()
        loaded.wal.close()
    addr = fe.serve(port=0)
    return fe, addr


def _crash_stop(fe) -> None:
    """Stop the frontend as a crash would leave its disk: no drain and
    no final checkpoint (``close()`` would write one from memory and
    hide a missing WAL record)."""
    fe.host.stop_accepting()
    fe.batcher.stop()
    if fe.supervisor is not None:
        fe.supervisor.stop()
    fe.node.close()
    with fe.node._lock:
        wal = fe.node.wal
    if wal is not None:
        wal.close()
    fe.host.close_sessions(flush_timeout_s=1.0)


def _read_back(addr):
    from go_crdt_playground_tpu.serve.client import ServeClient

    with ServeClient(tuple(addr), timeout=120.0) as c:
        ids, vv = c.members()
    return np.asarray(ids, np.int64), np.asarray(vv, np.int64)


def _child(job: dict):
    """Start the load generator on CPU-only JAX and hand it its job."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.Popen(
        [sys.executable, os.path.join(harness.BENCH, "loadgen.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, text=True,
        cwd=harness.ROOT)
    proc.stdin.write(json.dumps(job) + "\n")
    proc.stdin.flush()
    return proc


def _expect(proc, word: str) -> None:
    line = proc.stdout.readline().strip()
    if line != word:
        proc.kill()
        proc.wait()
        raise RuntimeError(f"load generator said {line!r}, not {word}")


def run(ctx: dict) -> None:
    import jax

    cfg, p = ctx["config"], ctx["traffic"]
    seed, seconds = ctx["seed"], ctx["seconds"]
    E, A = int(cfg["elements"]), int(cfg["actors"])
    tmp = tempfile.mkdtemp(prefix="bench-serve-")
    store = os.path.join(tmp, "store")
    fe = proc = None
    phases = {"jax_ready": time.monotonic() - ctx["t_start"]}
    try:
        start = gen.preload(p, seed, E)
        if p.get("store", {}).get("preload", "empty") != "empty":
            _write_store(store, E, A, *start)
        phases["store_written"] = time.monotonic() - ctx["t_start"]
        fe, addr = _frontend(cfg, store, ctx.get("control", False))
        phases["frontend_serving"] = time.monotonic() - ctx["t_start"]
        out = os.path.join(tmp, "ops.npz")
        proc = _child({"addr": list(addr), "traffic": p, "seed": seed,
                       "seconds": seconds, "num_keys": E, "out": out})
        _expect(proc, "READY")
        rec = fe.recorder
        trace_dir = os.path.join(tmp, "trace")
        if ctx["trace"]:
            harness.start_trace(trace_dir)
        ctx["compiles"].start()
        rec0 = rec.snapshot()
        use0 = resource.getrusage(resource.RUSAGE_SELF)
        t0 = time.monotonic()
        ctx["setup_s"] = t0 - ctx["t_start"]
        proc.stdin.write(f"GO {t0!r}\n")
        proc.stdin.flush()
        with jax.profiler.TraceAnnotation("bench.window"):
            time.sleep(max(0.0, t0 + seconds - time.monotonic()))
        rec1 = rec.snapshot()
        use1 = resource.getrusage(resource.RUSAGE_SELF)
        t1 = time.monotonic()
        compiles = ctx["compiles"].stop()
        if ctx["trace"]:
            jax.profiler.stop_trace()
        _expect(proc, "DONE")
        proc.wait(timeout=30)
        t_done = time.monotonic()
        ops = dict(np.load(out))
        members, vv = _read_back(addr)
        ctx["memory_peak_bytes"] = harness.memory_peak(
            ctx["device"]["count"])
        regime = getattr(fe.node, "_fused_regime", None)
        _crash_stop(fe)
        fe = None
        from go_crdt_playground_tpu.net.peer import Node

        again = Node.restore_durable(
            store, fallback_init=lambda: Node(0, E, A))
        r_members, r_vv = again.members_vv()
        again.close()
        del again
        # the reference: replay every acked op from the seed
        n = ops["outcome"].size
        kinds, keys = gen.op_stream(p, seed, E, n)
        acked = ops["outcome"] == ACKED
        present, clock = ref.replay(start[0], start[4], kinds, keys, acked)
        live = ref.compare(present, clock, A, members, vv)
        restart = ref.compare(present, clock, A, r_members, r_vv[0]
                              if np.ndim(r_vv) == 2 else r_vv)
        warm = int(ops["warm"])
        win = slice(warm, n)
        sent = ~np.isnan(ops["sent"][win])
        lost = int(np.sum(np.isin(ops["outcome"][win], (FAILED, NO_REPLY))))
        ctx["checks"] = [
            ("members_vs_reference", live["members"], 0),
            ("vv_vs_reference", live["vv"], 0),
            ("members_after_restart", restart["members"], 0),
            ("vv_after_restart", restart["vv"], 0),
            ("ops_without_reply", lost, 0),
            ("loadgen_backend_started", int(ops["backend_started"]), 0),
        ]
        ctx["attempted"] = int(np.sum(sent))
        ctx["failed"] = int(np.sum(sent & (ops["outcome"][win] != ACKED)))
        ctx["ops"] = ops
        ctx["t0"], ctx["t1"] = t0, t1
        ctx["rec0"], ctx["rec1"] = rec0, rec1
        ctx["shapes"] = {"E": E, "A": A,
                         "B": int(_flag(cfg["serve_flags"], "--max-batch",
                                        32)),
                         "k": regime[1] if regime else 0}
        ctx["info"] = {"compiles_in_window": compiles,
                       "setup_phases_s": phases,
                       "check_s": time.monotonic() - t_done,
                       "regime": None if regime is None else
                       f"{getattr(regime[0], '__name__', '?')}:k={regime[1]}",
                       "members": int(members.size),
                       **_host_info(ctx, p, keys, use0, use1)}
        if ctx["trace"]:
            from benchmark import trace_reduce

            ctx["trace_red"] = trace_reduce.reduce(
                trace_reduce.find_xplane(trace_dir))
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait()
        if fe is not None:
            _crash_stop(fe)
        shutil.rmtree(tmp, ignore_errors=True)


def _host_info(ctx, p, keys, use0, use1) -> dict:
    """The window as the host saw it: each span's time per batch and
    the window's counts (``readers.phase_split``), how late the load
    generator sent (open loop: after the op was due; closed: after the
    reply that freed its slot), acks in each second of the window, and
    the CPU time of the server and of the load generator."""
    ops = ctx["ops"]
    if p["loop"] == "closed":
        late = readers.slot_late_ms(ctx, gen.split(keys, int(
            p["connections"])), int(p.get("in_flight", 8)))
    else:
        late = readers.due_late_ms(ctx)
    late = late.tolist()
    at = ops["acked_at"][~np.isnan(ops["acked_at"])] - ctx["t0"]
    acks = np.histogram(at, bins=np.arange(math.ceil(ctx["seconds"]) + 1))

    def cpu_s(use):
        return use.ru_utime + use.ru_stime

    return {"phases": readers.phase_split(ctx),
            "gen_late_ms": {"p50": harness.quantile(late, 0.5),
                            "p99": harness.quantile(late, 0.99)},
            "acked_by_second": acks[0].tolist(),
            "host": {"cpus": len(os.sched_getaffinity(0)),
                     "server_cpu_s": cpu_s(use1) - cpu_s(use0),
                     "loadgen_cpu_s": float(ops["cpu_s"])}}


def _flag(flags, name: str, default):
    return flags[flags.index(name) + 1] if name in flags else default
