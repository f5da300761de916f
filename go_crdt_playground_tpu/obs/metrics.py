"""North-star metrics plumbing (SURVEY §5.5, BASELINE.json).

The reference has zero metrics machinery; its operational counters are
implicit in stdout traces.  This module gives the framework the three
counters the measurement ladder tracks — merges/sec, rounds-to-
convergence, δ-payload bytes — behind one small thread-safe ``Recorder``
(net.Node takes one and counts every sync exchange on it) plus
payload-size helpers for δ payloads.

Spans: ``Recorder.time(name)`` is the program's one span API.  A span
is an observation in seconds on the Recorder AND, while a
``jax.profiler`` session runs, a host event of the same name on the
profiler's timeline, beside the device's operations.  Spans on one
thread never enclose one another (DESIGN.md §16 "Host spans"), so the
host event overlapping a device-idle gap names one phase.  JAX is never
imported here: a process that has not loaded it has no profiler
session to write to.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import math
import sys
import threading
import time
from typing import Dict, List, Optional, Sequence

# Bounded log-spaced histogram backing observe()/percentile().  Bucket i
# covers (BASE·G^(i-1), BASE·G^i]; index 0 is the underflow bucket
# (values <= BASE, incl. zero/negatives) and the last bucket absorbs
# overflow.  With BASE=1µs and G=√2, 64 buckets span ~1e-6..4.3e3 —
# microsecond kernel dispatches through hour-long soaks — at a worst-case
# relative quantile error of √2, and the whole histogram is one fixed
# 64-int list per stream (bounded memory however long the stream runs).
_HIST_BASE = 1e-6
_HIST_GROWTH = math.sqrt(2.0)
_HIST_BUCKETS = 64
_LOG_GROWTH = math.log(_HIST_GROWTH)


def _bucket_index(value: float) -> int:
    if value <= _HIST_BASE:
        return 0
    i = 1 + int(math.floor(math.log(value / _HIST_BASE) / _LOG_GROWTH))
    return min(i, _HIST_BUCKETS - 1)


def _bucket_upper(index: int) -> float:
    return _HIST_BASE * (_HIST_GROWTH ** index)


_NO_EVENT = contextlib.nullcontext()


def _host_event(name: str):
    """``jax.profiler.TraceAnnotation(name)`` if this process has
    loaded JAX (with no session active it costs one flag check), else a
    no-op."""
    prof = sys.modules.get("jax.profiler")
    annotation = getattr(prof, "TraceAnnotation", None)
    return _NO_EVENT if annotation is None else annotation(name)


class Span:
    """One timed phase: ``with recorder.time(name) as span``.  Observes
    the elapsed seconds under ``name`` and brackets the same interval
    with a profiler host event.  ``end()`` closes it early (idempotent;
    the ``with`` exit then does nothing) — how a phase hands over to
    the next one inside a block it does not own."""

    __slots__ = ("_recorder", "_name", "_event", "_t0")

    def __init__(self, recorder: Optional["Recorder"], name: str):
        self._recorder = recorder
        self._name = name
        self._event = None
        self._t0: Optional[float] = None

    def __enter__(self) -> "Span":
        if self._recorder is not None:
            self._event = _host_event(self._name)
            self._event.__enter__()
            self._t0 = time.perf_counter()
        return self

    def end(self) -> None:
        if self._t0 is None:
            return
        elapsed = time.perf_counter() - self._t0
        self._t0 = None
        self._event.__exit__(None, None, None)
        self._recorder.observe(self._name, elapsed)

    def __exit__(self, *exc) -> None:
        self.end()


def timed(recorder: Optional["Recorder"], name: str) -> Span:
    """``recorder.time(name)`` for a component whose recorder is
    optional: with none, the span records nothing."""
    return Span(recorder, name)


class Recorder:
    """Thread-safe counters, value observations, and wall-clock timers.

    count():      monotonically increasing totals (merges, rounds, bytes).
    observe():    value streams summarized as n/sum/min/max PLUS a bounded
                  log-spaced histogram (fixed buckets, so memory never
                  grows with the stream).
    percentile(): quantile estimate from the histogram (worst-case √2
                  relative error, clamped to the exact observed min/max);
                  snapshot() reports p50/p95/p99 per stream — the serve
                  frontend's SLO numbers (DESIGN.md §16) ride these.
    time():       a ``Span``: observe() fed with elapsed seconds, and a
                  profiler host event of the same name (module doc).
    observe_deferred(): observe() for code that may run while this
                  thread already holds the lock (a ``gc.callbacks``
                  hook — ``watch_gc``): queued without locking, folded
                  in by the next locked call.
    set_gauge():  last-write-wins point-in-time values (e.g. the per-peer
                  circuit-breaker state the sync supervisor exports:
                  0=closed, 1=open, 2=half_open — net/antientropy.py).

    Durability-layer names (the crash-recovery contract, DESIGN.md §14
    "Durability ladder"): counters ``wal.appends`` / ``wal.appended_bytes``
    / ``wal.truncations`` (write path), ``wal.records`` /
    ``wal.bad_records`` / ``wal.future_records`` (replay; the last is a
    record refused by the causal replay guard), ``wal.torn_tail`` (tear
    found and repaired), ``restore.fallbacks`` (a checkpoint generation
    failed verification and the previous one was used),
    ``restore.unknown_type`` (restore degraded to a plain array dict),
    ``restore.full_resync`` / ``sync.full_resync_complete`` (the
    regressed-restore forced-FULL healing epoch armed / retired); gauge
    ``restore.generation`` (the generation recovery actually loaded).
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[str, int] = {}
        self._observations: Dict[str, Dict[str, float]] = {}
        self._histograms: Dict[str, List[int]] = {}  # guarded-by: _lock
        self._gauges: Dict[str, float] = {}
        # race-ok: deque append/popleft are atomic; appended without
        # the lock by observe_deferred, drained under it
        self._deferred: collections.deque = collections.deque()

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def count_many(self, counts: Dict[str, int]) -> None:
        """Atomically bump several counters — a snapshot() concurrent with
        one count_many sees either none or all of its increments."""
        with self._lock:
            for name, n in counts.items():
                self._counters[name] = self._counters.get(name, 0) + n

    def observe(self, name: str, value: float) -> None:
        with self._lock:
            self._fold_deferred_locked()
            self._observe_locked(name, value)

    def observe_deferred(self, name: str, value: float) -> None:
        self._deferred.append((name, float(value)))

    # requires-lock: _lock
    def _fold_deferred_locked(self) -> None:
        while self._deferred:
            self._observe_locked(*self._deferred.popleft())

    # requires-lock: _lock
    def _observe_locked(self, name: str, value: float) -> None:
        o = self._observations.get(name)
        if o is None:
            self._observations[name] = {
                "n": 1, "sum": float(value),
                "min": float(value), "max": float(value),
            }
            self._histograms[name] = [0] * _HIST_BUCKETS
        else:
            o["n"] += 1
            o["sum"] += float(value)
            o["min"] = min(o["min"], float(value))
            o["max"] = max(o["max"], float(value))
        self._histograms[name][_bucket_index(float(value))] += 1

    # requires-lock: _lock
    def _percentile_locked(self, name: str, q: float) -> float:
        """Caller holds the lock.  Smallest bucket upper bound covering
        the q-quantile rank, clamped to the exact observed [min, max] —
        a stream of identical values reports that value exactly, and no
        estimate can leave the observed range."""
        o = self._observations[name]
        hist = self._histograms[name]
        rank = max(1, math.ceil(q * o["n"]))
        cum = 0
        for i, c in enumerate(hist):
            cum += c
            if cum >= rank:
                if i == _HIST_BUCKETS - 1:
                    return o["max"]  # overflow bucket: nominal upper lies
                return min(max(_bucket_upper(i), o["min"]), o["max"])
        return o["max"]  # unreachable: buckets always sum to n

    def percentile(self, name: str, q: float) -> float:
        """Estimate the q-quantile (q in [0, 1]) of an observed stream
        from its bounded histogram.  Raises KeyError for a stream never
        observed — "no data" must not read as "zero latency"."""
        if not 0.0 <= q <= 1.0:
            raise ValueError(f"quantile must be in [0, 1], got {q}")
        with self._lock:
            self._fold_deferred_locked()
            if name not in self._observations:
                raise KeyError(f"no observations for {name!r}")
            return self._percentile_locked(name, q)

    def time(self, name: str) -> Span:
        return Span(self, name)

    def set_gauge(self, name: str, value: float) -> None:
        """Set a last-write-wins instantaneous value (unlike count(),
        snapshot() reports the CURRENT value, not an accumulation)."""
        with self._lock:
            self._gauges[name] = value

    def gauge(self, name: str, default: float = 0.0) -> float:
        """Read one gauge without paying a full snapshot()."""
        with self._lock:
            return self._gauges.get(name, default)

    def counter(self, name: str, default: int = 0) -> int:
        """Read one counter without paying a full snapshot()."""
        with self._lock:
            return self._counters.get(name, default)

    def histogram(self, name: str) -> Optional[List[int]]:
        """Copy of a stream's bucket counts (CUMULATIVE since process
        start), or None if never observed.  Pollers that need a RECENT
        quantile — e.g. the compaction scheduler's headroom check,
        serve/compaction.py — diff two copies and feed the window to
        ``percentile_of_counts``; the cumulative histogram alone would
        let an hour of idle history mask a current latency spike."""
        with self._lock:
            self._fold_deferred_locked()
            h = self._histograms.get(name)
            return None if h is None else list(h)

    def snapshot(self) -> Dict[str, object]:
        """Point-in-time copy: {"counters": {...}, "observations": {...},
        "gauges": {...}} with per-stream mean and histogram-derived
        p50/p95/p99 added, plus the raw cumulative ``buckets`` vector —
        a REMOTE poller (the fleet autopilot reading STATS over the
        wire, control/signals.py) windows a quantile exactly like the
        in-process compaction scheduler does: diff two snapshots'
        buckets and feed ``percentile_of_counts``.  64 ints per stream,
        bounded like the histogram itself."""
        with self._lock:
            self._fold_deferred_locked()
            obs = {
                name: {**o, "mean": o["sum"] / o["n"],
                       "p50": self._percentile_locked(name, 0.50),
                       "p95": self._percentile_locked(name, 0.95),
                       "p99": self._percentile_locked(name, 0.99),
                       "buckets": list(self._histograms[name])}
                for name, o in self._observations.items()
            }
            return {"counters": dict(self._counters), "observations": obs,
                    "gauges": dict(self._gauges)}


class GcWatch:
    """A ``gc.callbacks`` hook that times each cyclic collection as the
    span ``process.gc_s``.  A collection can start inside any code,
    the Recorder's own locked sections included, so the observation
    goes through ``observe_deferred``.  ``remove()`` unhooks it."""

    def __init__(self, recorder: Recorder):
        self._recorder = recorder
        self._event = None
        self._t0: Optional[float] = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        # collections never overlap: one start/stop pair at a time
        if phase == "start":
            self._event = _host_event("process.gc_s")
            self._event.__enter__()
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            elapsed = time.perf_counter() - self._t0
            self._t0 = None
            self._event.__exit__(None, None, None)
            self._recorder.observe_deferred("process.gc_s", elapsed)

    def remove(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)


def watch_gc(recorder: Recorder) -> GcWatch:
    """Time every cyclic GC collection of this process on ``recorder``
    until the returned handle's ``remove()``."""
    return GcWatch(recorder)


def percentile_of_counts(hist: Sequence[int], q: float) -> Optional[float]:
    """Quantile estimate over a raw bucket-count vector (the same
    log-spaced buckets ``Recorder.observe`` fills) — for WINDOWED
    quantiles built by diffing two ``Recorder.histogram`` copies.
    Returns the covering bucket's nominal upper bound (no exact min/max
    is known for a window), or None for an empty window ("no recent
    data" must stay distinguishable from "zero latency")."""
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile must be in [0, 1], got {q}")
    n = sum(hist)
    if n <= 0:
        return None
    rank = max(1, math.ceil(q * n))
    cum = 0
    for i, c in enumerate(hist):
        cum += c
        if cum >= rank:
            return _bucket_upper(i)
    return _bucket_upper(_HIST_BUCKETS - 1)  # unreachable


def payload_metrics(payload, wire: bool = True) -> Dict[str, int]:
    """Size/occupancy metrics for one δ payload (ops/delta.DeltaPayload,
    single-replica slices): changed/deleted lane counts, dense on-device
    bytes, and (optionally — it costs an encode) actual wire bytes."""
    import numpy as np

    out = {
        "changed_lanes": int(np.asarray(payload.changed).sum()),
        "deleted_lanes": int(np.asarray(payload.deleted).sum()),
        "dense_bytes": int(payload.nbytes_dense()),
    }
    if wire:
        from go_crdt_playground_tpu.utils.wire import payload_nbytes_wire

        out["wire_bytes"] = int(payload_nbytes_wire(payload))
    return out
