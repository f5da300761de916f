"""Host spans and the compaction-overflow counter (DESIGN.md §16 "Host
spans"): ``Recorder.time`` observes a phase and writes it to the
profiler's timeline, ``watch_gc`` times collections without ever
blocking on the Recorder's lock, and every phase of one served batch
lands exactly once."""

import gc
import glob
import os
import threading

import numpy as np

from go_crdt_playground_tpu.obs import Recorder, timed, watch_gc

E, A = 64, 2


def _host_events(log_dir):
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return [ev.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def test_recorder_time_observes_and_writes_a_host_event(tmp_path):
    import jax

    rec = Recorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.time("test.phase_s"):
            jax.numpy.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    o = rec.snapshot()["observations"]["test.phase_s"]
    assert o["n"] == 1 and o["sum"] > 0
    assert "test.phase_s" in _host_events(str(tmp_path))


def test_span_end_hands_over_once():
    rec = Recorder()
    with rec.time("test.first_s") as span:
        span.end()
        with rec.time("test.second_s"):
            pass
        span.end()
    obs = rec.snapshot()["observations"]
    assert obs["test.first_s"]["n"] == 1 and obs["test.second_s"]["n"] == 1
    with timed(None, "test.none_s") as span:  # no recorder: nothing
        span.end()
    assert "test.none_s" not in rec.snapshot()["observations"]


def test_watch_gc_records_a_collection_and_unhooks():
    before = list(gc.callbacks)
    rec = Recorder()
    watch = watch_gc(rec)
    gc.collect()
    watch.remove()
    watch.remove()  # idempotent
    assert gc.callbacks == before
    o = rec.snapshot()["observations"]["process.gc_s"]
    assert o["n"] >= 1 and o["sum"] > 0


def test_gc_inside_a_locked_recorder_section_does_not_block():
    """A collection can start while this very thread holds the
    Recorder's lock; its observation waits for the next locked call
    instead of deadlocking."""
    rec = Recorder()
    watch = watch_gc(rec)
    done = threading.Event()

    def collect_under_the_lock():
        with rec._lock:
            gc.collect()
        done.set()

    try:
        t = threading.Thread(target=collect_under_the_lock, daemon=True)
        t.start()
        t.join(timeout=30)
        assert done.is_set(), "gc callback blocked on the Recorder lock"
    finally:
        watch.remove()
    assert rec.snapshot()["observations"]["process.gc_s"]["n"] >= 1


def _fused_node(tmp_path, rec, k, monkeypatch):
    from go_crdt_playground_tpu.net.peer import Node
    from go_crdt_playground_tpu.ops import ingest as ingest_ops
    from go_crdt_playground_tpu.utils.wal import DeltaWal

    # the on-device fixed-K compaction regime, through the XLA twin
    monkeypatch.setattr(ingest_ops, "ingest_delta_regime",
                        lambda E: (ingest_ops.ingest_rows_delta, k))
    return Node(0, E, A, recorder=rec,
                wal=DeltaWal(str(tmp_path / "wal"), recorder=rec))


def test_compact_overflow_counts_batches_past_k(tmp_path, monkeypatch):
    from go_crdt_playground_tpu.net.peer import Node

    rec = Recorder()
    node = _fused_node(tmp_path, rec, 4, monkeypatch)
    B = 4
    add, dl = np.zeros((B, E), bool), np.zeros((B, E), bool)
    add[0, [1, 2]] = True
    node.ingest_batch(add, dl)  # 2 changed lanes: fits k = 4
    c = rec.snapshot()["counters"]
    assert c["ingest.compact_overflow"] == 0
    assert c["wal.compact_records"] == 1
    add[:] = False
    add[0, 10:20] = True
    dl[1, [1]] = True
    node.ingest_batch(add, dl)  # 10 changed lanes + 1 deletion
    c = rec.snapshot()["counters"]
    assert c["ingest.compact_overflow"] == 1
    obs = rec.snapshot()["observations"]
    assert obs["wal.delta.pull_s"]["n"] == 2
    assert obs["wal.delta.dense_s"]["n"] == 1
    members, vv = node.members_vv()
    with node._lock:
        node.wal.close()
    back = Node.restore_durable(str(tmp_path),
                                fallback_init=lambda: Node(0, E, A))
    r_members, r_vv = back.members_vv()
    assert r_members.tolist() == members.tolist() == [2] + list(range(10, 20))
    assert r_vv.tolist() == vv.tolist()
    back.close()
    back.wal.close()


def test_one_batch_lands_once_in_every_write_path_span(tmp_path,
                                                        monkeypatch):
    from go_crdt_playground_tpu.serve import protocol
    from go_crdt_playground_tpu.serve.admission import (AdmissionQueue,
                                                        OpRequest)
    from go_crdt_playground_tpu.serve.batcher import MicroBatcher

    class _Session:
        def send(self, kind, body):
            return True

    names = ("serve.batch.pack_s", "node.ingest.dispatch_s",
             "wal.fsync_s")
    for k in (0, 4):  # host compaction (the CPU regime), then fixed-K
        rec = Recorder()
        node = _fused_node(tmp_path / f"k{k}", rec, k, monkeypatch)
        b = MicroBatcher(node, AdmissionQueue(8), max_batch=4,
                         recorder=rec)
        b._apply([OpRequest(1, protocol.OP_ADD, [3, 5], None, _Session(),
                            0.0)])
        obs = rec.snapshot()["observations"]
        assert {n: obs[n]["n"] for n in names} == dict.fromkeys(names, 1)
        pulls = sum(obs.get(n, {"n": 0})["n"]
                    for n in ("wal.delta.pull_s", "wal.delta.dense_s"))
        assert pulls == 1, obs.keys()
        assert ("wal.delta.pull_s" in obs) == (k > 0)
        assert obs["serve.batch.apply_s"]["n"] == 1
        assert rec.snapshot()["counters"]["ingest.compact_overflow"] == 0
        with node._lock:
            node.wal.close()


def test_serving_loop_leaves_gc_callbacks_as_found(tmp_path):
    """The GC watch lives as long as the batcher loop: a graceful
    close and a crash-shaped stop both unhook it."""
    from go_crdt_playground_tpu.serve.frontend import ServeFrontend

    before = list(gc.callbacks)
    fe = ServeFrontend(E, A, durable_dir=str(tmp_path / "a"))
    fe.serve()
    assert len(gc.callbacks) == len(before) + 1
    fe.close()
    assert gc.callbacks == before
    fe = ServeFrontend(E, A, durable_dir=str(tmp_path / "b"))
    fe.serve()
    fe.host.stop_accepting()  # crash-shaped: no drain, no checkpoint
    fe.batcher.stop()
    assert gc.callbacks == before
    fe.supervisor.stop()
    fe.node.close()
    with fe.node._lock:
        fe.node.wal.close()
    fe.host.close_sessions(flush_timeout_s=1.0)
