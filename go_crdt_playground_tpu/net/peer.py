"""A networked δ-AWSet replica node.

One ``Node`` is the process-level analogue of one reference replica struct
(awset_test.go:159-168): it owns a single-replica packed
``AWSetDeltaState`` (R=1), mutates it with the models/awset_delta ops, and
anti-entropies with peers over TCP instead of the reference's direct
method call.

One ``sync_with`` call is a push-pull exchange:

    client                                server
      HELLO(actor, E, vv)  ------------->
                           <-------------  HELLO(actor, E, vv)
      PAYLOAD(δ vs server vv)  --------->  apply
                           <-------------  PAYLOAD(δ vs client vv)
      apply

Each side compresses against the other's advertised VV — exactly the
sender-side ``MakeDeltaMergeData`` contract (awset-delta_test.go:79-105) —
and ships FULL state on first contact (the receiver-side dispatch
condition ``Counter(src.Actor) <= 0``, awset-delta_test.go:53, evaluated
from the advertised VV).  Apply uses the same kernels as the on-chip
gossip path (ops/delta.py), so in-process, on-mesh, and cross-socket
synchronization share one semantics implementation.

Deadline model (both sides of the exchange):

* The SERVER runs two budgets — a short whole-frame ``hello_timeout_s``
  for the initial HELLO (a real client sends it immediately on connect,
  so idle half-open dials release their connection slot in seconds) and
  the longer ``conn_timeout_s`` for the PAYLOAD frame (which may carry a
  full state image).
* The CLIENT honors the same asymmetry: the TCP dial is bounded by
  ``connect_timeout_s`` (default: the overall ``timeout``), the server's
  HELLO reply — sent before any kernel work — by ``hello_timeout_s``
  (default: this node's own ``hello_timeout_s``, clamped to ``timeout``),
  and the PAYLOAD reply — which sits behind the server's apply+extract —
  by the full ``timeout``.  Every frame deadline is ABSOLUTE for the
  whole frame (framing.recv_frame's deadline semantics), so a trickling
  peer cannot stretch an exchange past its budget.

Failure typing: ``sync_with`` never leaks a raw ``OSError`` /
``ProtocolError``.  Dial failures raise ``ConnectFailed``, any deadline
raises ``PeerTimeout`` (with ``.phase`` naming the exchange step),
transport failures mid-exchange raise ``PeerReset``, and malformed or
out-of-order frames raise ``PeerProtocolError``.  Each keeps the legacy
exception as a base (``OSError`` family / ``framing.ProtocolError``), so
pre-hierarchy callers catching those still work; a server-reported
``framing.RemoteError`` propagates unchanged (it is already typed and
carries the remote message).  net/antientropy.py maps this hierarchy to
failure classes for retry, circuit-breaker, and metric treatment.
"""

from __future__ import annotations

import os
import socket
import threading
from typing import NamedTuple, Optional, Tuple

import numpy as np

from go_crdt_playground_tpu.net import framing
from go_crdt_playground_tpu.net.framing import (MODE_DELTA, MODE_FULL,
                                                MODE_SLICE, MSG_HELLO,
                                                MSG_PAYLOAD, ProtocolError)
from go_crdt_playground_tpu.obs import timed


class SyncError(Exception):
    """Base of every client-side sync failure.  A mixin base: concrete
    subclasses ALSO inherit the legacy exception their call sites used
    to leak (``OSError`` family / ``framing.ProtocolError``), so code
    written against the old raw exceptions keeps catching these."""


class ConnectFailed(SyncError, ConnectionError):
    """The TCP dial itself failed (refused, unreachable, DNS)."""


class PeerTimeout(SyncError, socket.timeout):
    """A deadline expired.  ``phase`` names the exchange step that blew
    its budget: "connect" | "hello" | "payload" — the supervisor treats
    a connect timeout (peer likely down) differently from a frame
    deadline (peer up but slow/wedged)."""

    def __init__(self, message: str, phase: str):
        super().__init__(message)
        self.phase = phase


class PeerReset(SyncError, ConnectionError):
    """The transport failed mid-exchange (reset / broken pipe) after the
    dial succeeded — distinct from ConnectFailed because the peer WAS
    reachable, so breakers treat it as flakiness, not absence."""


class PeerProtocolError(SyncError, ProtocolError):
    """The peer spoke the protocol wrong (bad magic, unexpected frame
    type, malformed body, torn frame)."""


class SyncStats(NamedTuple):
    """One push-pull exchange, measured (δ-payload-bytes is a north-star
    metric, BASELINE.json)."""

    bytes_sent: int
    bytes_received: int
    mode_sent: int      # MODE_DELTA | MODE_FULL
    mode_received: int


class Node:
    """A single networked replica.  Thread-safe; one lock serializes local
    mutations, payload extraction, and payload application."""

    # Server-side concurrency bounds (the MergerServer pattern,
    # bridge/service.py): connection threads are capped so a misbehaving
    # fleet can't grow one thread per dial, and half-open clients can't
    # pin a thread forever.  At capacity new dials are shed, not queued —
    # anti-entropy self-heals a dropped exchange (SURVEY §5.3), so
    # shedding is semantically a lost gossip round, never lost data.
    # The initial HELLO gets a much shorter deadline than the payload
    # exchange: a legitimate client sends HELLO immediately on connect,
    # so an idle half-open dial must release its slot in seconds — at
    # MAX_CONNS=64, 64 silent dials holding slots for the full payload
    # timeout would shed every legitimate gossip dial for 30s.
    CONN_TIMEOUT_S = 30.0
    HELLO_TIMEOUT_S = 2.0
    MAX_CONNS = 64

    def __init__(self, actor: int, num_elements: int, num_actors: int,
                 delta_semantics: str = "v2",
                 strict_reference_semantics: bool = True,
                 recorder=None, conn_timeout_s: Optional[float] = None,
                 hello_timeout_s: Optional[float] = None,
                 max_conns: Optional[int] = None, wal=None,
                 ingest_fused: bool = True,
                 wal_compact_records: bool = True):
        """recorder: optional obs.Recorder; when given, every exchange
        counts sync.exchanges / sync.bytes_sent / sync.bytes_received /
        sync.full_payloads on it (served and initiated alike).

        wal: optional utils.wal.DeltaWal.  When attached (here or by
        plain assignment later), every applied PAYLOAD body and every
        local mutation's δ is durably logged BEFORE the state mutation
        is acknowledged, so a kill between checkpoints loses at most the
        in-flight record (the documented WAL-tail window) — see
        ``replay_wal`` / ``restore_durable`` for the recovery half.

        ingest_fused: ``ingest_batch`` uses the one-dispatch fused
        ingest+δ kernel (ops/ingest.ingest_rows_delta; the Pallas twin
        on TPU backends).  False restores the seed two-dispatch path
        (apply, then a separate delta_extract for the WAL record) —
        kept for the serve soak's fused-vs-seed comparison.

        wal_compact_records: sparse δs are WAL-logged in the compact
        index-lane record form (utils/wire.encode_compact_wal_body —
        O(changed) fsync bytes instead of O(E)); dense records remain
        the overflow fallback and both forms replay (``replay_wal``)."""
        from go_crdt_playground_tpu.models import awset_delta

        if not 0 <= actor < num_actors:
            raise ValueError(f"actor {actor} outside actor axis {num_actors}")
        self.recorder = recorder
        self.wal = wal  # guarded-by: _lock
        # race-ok: read-only configuration after __init__
        self.ingest_fused = ingest_fused
        # (fused_fn, k) resolved on first fused batch — backend and E
        # are fixed for the node's lifetime
        self._fused_regime = None  # guarded-by: _lock
        # the open node.ingest.dispatch_s span of the batch being
        # applied; _append_delta_record ends it where the record begins
        self._dispatch_span = None  # guarded-by: _lock
        # digest-sync kernel dispatch (net/digestsync.py), resolved on
        # first digest exchange — backend and E are lifetime-fixed
        # race-ok: idempotent lazy init (every racer computes the same
        # backend dispatch; last write wins harmlessly)
        self._digest_regime = None
        # race-ok: read-only configuration after __init__
        self.wal_compact_records = wal_compact_records
        # freshest causal-stability vector each peer actor advertised
        # in an applied payload — the provable deletion-GC frontier's
        # peer half (deletion_frontier)
        self._peer_processed: dict = {}  # guarded-by: _lock
        # last durably-restored/saved store generation
        self.generation = 0  # guarded-by: _lock
        # regressed-restore healing epoch (see restore_durable): while
        # pending, the first exchange with each peer advertises a ZERO
        # vv so the peer ships FULL state — a replayed WAL record whose
        # src_vv outran a regressed base may have fast-forwarded our vv
        # past lanes we never received, and delta compression would hide
        # that hole forever
        self.full_resync_pending = False  # guarded-by: _lock
        self._full_resync_done: set = set()  # guarded-by: _lock
        self._resync_flag_path: Optional[str] = None  # guarded-by: _lock
        self.actor = actor
        self.num_elements = num_elements
        self.num_actors = num_actors
        self.delta_semantics = delta_semantics
        self.strict_reference_semantics = strict_reference_semantics
        self._lock = threading.Lock()
        self._state = awset_delta.init(  # guarded-by: _lock
            1, num_elements, num_actors,
            actors=np.asarray([actor], np.uint32))
        # race-ok: serve()/close() owner thread; _accept_loop snapshots
        self._server_sock: Optional[socket.socket] = None
        # race-ok: serve()/close() owner thread only
        self._server_thread: Optional[threading.Thread] = None
        self._closing = False  # race-ok: benign monotonic stop flag
        self.conn_timeout_s = (self.CONN_TIMEOUT_S if conn_timeout_s is None
                               else conn_timeout_s)
        # tunable for slow-but-legitimate WAN dialers; still clamped by
        # conn_timeout_s so the HELLO deadline can never exceed the
        # payload deadline it exists to undercut
        self.hello_timeout_s = min(
            self.HELLO_TIMEOUT_S if hello_timeout_s is None
            else hello_timeout_s,
            self.conn_timeout_s)
        # explicit per-frame body cap for every peer-dialect read (W004
        # frame-cap discipline): sized to the dense FULL payload, so a
        # hostile length header can never balloon a reader to the codec
        # ceiling.
        # race-ok: read-only after __init__
        self._frame_cap = framing.peer_frame_cap(num_elements,
                                                 num_actors)
        self._conn_slots = threading.BoundedSemaphore(
            self.MAX_CONNS if max_conns is None else max_conns)

    # -- local ops (reference Add/Del, awset.go:89-101 δ-variant) ----------

    def add(self, *element_ids: int) -> None:
        """Add elements; each ticks the clock once (awset.go:89-94).
        One fused add_elements dispatch for the whole call (the
        del_elements selector pattern applied to the add path)."""
        import jax.numpy as jnp

        from go_crdt_playground_tpu.models import awset_delta

        for e in element_ids:
            if not 0 <= e < self.num_elements:
                raise ValueError(f"element id {e} outside universe "
                                 f"{self.num_elements}")
        if not element_ids:
            return
        # bucket the call shape to the next power of two so varying
        # arities reuse one compiled program per bucket, not one per K
        k = len(element_ids)
        bucket = 1 << (k - 1).bit_length()
        padded = np.zeros(bucket, np.uint32)
        padded[:k] = element_ids
        with self._lock:
            pre_vv = (np.asarray(self._state.vv[0]).copy()
                      if self.wal is not None else None)
            self._state = awset_delta.add_elements(
                self._state, jnp.uint32(0), jnp.asarray(padded),
                jnp.uint32(k))
            if pre_vv is not None:
                self._log_local_delta(pre_vv)

    def delete(self, *element_ids: int) -> None:
        """δ-Del: one clock tick per call, one shared deletion dot for all
        hit keys (awset-delta_test.go:14-33)."""
        import jax.numpy as jnp

        from go_crdt_playground_tpu.models import awset_delta

        selector = np.zeros(self.num_elements, bool)
        for e in element_ids:
            if not 0 <= e < self.num_elements:
                raise ValueError(f"element id {e} outside universe "
                                 f"{self.num_elements}")
            selector[e] = True
        with self._lock:
            pre_vv = (np.asarray(self._state.vv[0]).copy()
                      if self.wal is not None else None)
            self._state = awset_delta.del_elements(
                self._state, jnp.uint32(0), jnp.asarray(selector))
            if pre_vv is not None:
                self._log_local_delta(pre_vv)

    def ingest_batch(self, add_rows: np.ndarray, del_rows: np.ndarray,
                     live: Optional[np.ndarray] = None,
                     stripe_hint: Optional[np.ndarray] = None) -> None:
        """Apply one packed ``(B, E)`` micro-batch of client op-rows in a
        single compiled dispatch (row b's add selector is one Add(k...)
        call, its del selector one Del(k...) call, ``live`` masks
        padding rows), WAL-logging the batch's resulting δ BEFORE
        returning — the group-commit durability point the serve
        frontend acks against: one fsync covers the whole batch
        (DESIGN.md §16).

        The fused path (``ingest_fused``, the default) gets state AND
        the WAL record's δ — routed through the fixed-K compact lanes —
        from ONE dispatch of ``ops/ingest.ingest_rows_delta`` (the
        Pallas twin on TPU backends), so the host pulls O(changed)
        lanes for the record instead of re-extracting a dense O(E)
        payload in a second dispatch.  ``ingest.dispatches`` counts the
        compiled applies per batch (fused: 1; seed path: 2 when a WAL
        is attached).

        Spans (``Recorder.time``): ``node.ingest.dispatch_s`` from
        before the node lock (its wait, the pre-vv pull, the row
        slice, the H2D of the rows, the kernel enqueue and the
        write-back) to where the δ record begins; the record's own
        spans follow (``_append_delta_record``), so none encloses
        another.

        ``stripe_hint`` is the conflict-aware admission scheduler's
        per-row stripe assignment (serve/scheduler.py; int per batch
        row, negatives = unhinted).  Only a target with replicated
        ingest stripes (``parallel/meshtarget2d.Mesh2DApplyTarget``)
        acts on it — a plain node applies rows in order regardless, so
        the hint is validated for shape and otherwise advisory."""
        add_rows = np.asarray(add_rows, bool)
        del_rows = np.asarray(del_rows, bool)
        if add_rows.shape != del_rows.shape or add_rows.ndim != 2 \
                or add_rows.shape[1] != self.num_elements:
            raise ValueError(
                f"op-batch shape {add_rows.shape}/{del_rows.shape} does "
                f"not match (B, {self.num_elements})")
        if live is None:
            live = np.ones(add_rows.shape[0], bool)
        live = np.asarray(live, bool)
        if live.shape != (add_rows.shape[0],):
            raise ValueError(f"live mask shape {live.shape} does not "
                             f"match batch axis {add_rows.shape[0]}")
        if stripe_hint is not None:
            stripe_hint = np.asarray(stripe_hint, np.int32)
            if stripe_hint.shape != (add_rows.shape[0],):
                raise ValueError(
                    f"stripe hint shape {stripe_hint.shape} does not "
                    f"match batch axis {add_rows.shape[0]}")
        with timed(self.recorder, "node.ingest.dispatch_s") as dispatch:
            with self._lock:
                self._dispatch_span = dispatch
                try:
                    pre_vv = (np.asarray(self._state.vv[0]).copy()
                              if self.wal is not None else None)
                    self._apply_batch_locked(add_rows, del_rows, live,
                                             pre_vv,
                                             stripe_hint=stripe_hint)
                finally:
                    self._dispatch_span = None

    # requires-lock: _lock
    def _apply_batch_locked(self, add_rows: np.ndarray,
                            del_rows: np.ndarray, live: np.ndarray,
                            pre_vv: Optional[np.ndarray],
                            stripe_hint: Optional[np.ndarray] = None
                            ) -> None:
        """The apply+log half of ``ingest_batch`` (validation done):
        the replica-flavor seam — ``parallel/meshtarget.MeshApplyTarget``
        overrides this with the mesh-sharded one-dispatch path while
        the ack-after-durable contract stays in the caller.  Caller
        holds the lock; ``pre_vv`` is None iff no WAL is attached;
        ``stripe_hint`` rides to the 2-D mesh override
        (parallel/meshtarget2d.py) — the sequential path ignores it
        (row order already IS the durable order here)."""
        import jax
        import jax.numpy as jnp

        from go_crdt_playground_tpu.ops import ingest as ingest_ops

        row = jax.tree.map(lambda x: x[0], self._state)
        if self.ingest_fused and pre_vv is not None:
            # (without a WAL there is no record to build — the δ
            # half of the fused dispatch would be computed and
            # discarded, so the plain apply below is the fast path)
            if self._fused_regime is None:
                self._fused_regime = ingest_ops.ingest_delta_regime(
                    self.num_elements)
                # at 0 from here on: a reader tells "never overflowed"
                # from a node that does not count
                self._count("ingest.compact_overflow", 0)
            fused_fn, k = self._fused_regime
            merged, payload, compact = fused_fn(
                row, jnp.asarray(add_rows), jnp.asarray(del_rows),
                jnp.asarray(live), k_changed=k, k_deleted=k)
            self._state = jax.tree.map(
                lambda full, r: full.at[0].set(r), self._state,
                merged)
            self._count("ingest.dispatches")
            self._append_delta_record(pre_vv, payload, compact)
        else:
            merged = ingest_ops.ingest_rows(
                row, jnp.asarray(add_rows), jnp.asarray(del_rows),
                jnp.asarray(live))
            self._state = jax.tree.map(
                lambda full, r: full.at[0].set(r), self._state,
                merged)
            self._count("ingest.dispatches")
            if pre_vv is not None:
                self._count("ingest.dispatches")  # delta_extract
                self._log_local_delta(pre_vv)

    def members(self) -> np.ndarray:
        """Sorted live element ids (SortedValues, awset.go:61-70, on ids)."""
        with self._lock:
            return np.nonzero(np.asarray(self._state.present[0]))[0]

    def members_vv(self) -> Tuple[np.ndarray, np.ndarray]:
        """Membership + vv under ONE lock hold — the serve QUERY read.
        Pulls ONLY the ``present`` bitmask and the vv leaves, not the
        full 9-field state pytree: against a mesh-sharded replica
        (parallel/meshtarget.py) that is one E-byte mask gather plus a
        replicated A-word vector instead of every dot/deletion lane in
        HBM crossing to the host per query."""
        with self._lock:
            present = np.asarray(self._state.present[0])
            vv = np.asarray(self._state.vv[0]).copy()
        return np.nonzero(present)[0], vv

    def vv(self) -> np.ndarray:
        with self._lock:
            return np.asarray(self._state.vv[0]).copy()

    def state_slice(self):
        """Snapshot of the single-replica state (for tests/checkpointing)."""
        import jax

        with self._lock:
            return jax.tree.map(lambda x: x[0], self._state)

    # -- payload plumbing ---------------------------------------------------

    # requires-lock: _lock
    def _extract_payload(self, peer_vv: np.ndarray):
        """The FULL/DELTA ladder's payload for a peer that advertised
        peer_vv, pre-encode: ``(mode, processed, payload)``.  Caller
        holds the lock.  Split from ``_extract_msg`` so the digest
        tier's δ-fallback rung can census the shipped lanes before
        encoding (net/digestsync.py — ``digest.lanes_sent`` must count
        EVERY state lane, whichever rung ships it)."""
        import jax
        import jax.numpy as jnp

        from go_crdt_playground_tpu.ops import delta as delta_ops

        me = jax.tree.map(lambda x: x[0], self._state)
        first_contact = int(peer_vv[self.actor]) == 0
        if first_contact:
            # FULL: ship the complete entry set + deletion log — the wire
            # image of the reference's full-merge branch source state.
            payload = delta_ops.DeltaPayload(
                src_vv=me.vv,
                changed=me.present,
                ch_da=me.dot_actor, ch_dc=me.dot_counter,
                deleted=me.deleted,
                del_da=me.del_dot_actor, del_dc=me.del_dot_counter,
                src_actor=jnp.uint32(self.actor),
                src_processed=me.processed,
            )
            mode = MODE_FULL
        else:
            payload = delta_ops.delta_extract(me, jnp.asarray(peer_vv))
            mode = MODE_DELTA
        return mode, np.asarray(me.processed), payload

    # requires-lock: _lock
    def _extract_msg(self, peer_vv: np.ndarray) -> Tuple[int, bytes]:
        """Build the PAYLOAD frame body for a peer that advertised peer_vv.
        Caller holds the lock."""
        mode, processed, payload = self._extract_payload(peer_vv)
        body = framing.encode_payload_msg(
            mode, self.actor, processed, payload)
        return mode, body

    # requires-lock: _lock
    def _apply_msg(self, body: bytes) -> int:
        """Decode + apply a PAYLOAD frame body.  Caller holds the lock."""
        mode, payload = framing.decode_payload_msg(
            body, self.num_elements, self.num_actors)
        # write-AHEAD: the decoded-valid body hits the log before the
        # state mutates, so a crash can only lose the in-flight record,
        # never log an effect it then fails to persist.  Replay is an
        # idempotent merge, so an extra logged-but-unapplied record is
        # harmless.  The record is prefixed with a replay GUARD — our
        # pre-apply vv, the causal context the delta's compression
        # assumed — so recovery can refuse records that outrun a
        # regressed base (see replay_wal).  Applied peer bodies are
        # logged as-received (dense): re-compacting a payload that
        # already crossed the wire would cost a host decode for bytes
        # the batch path never pays.
        if self.wal is not None:
            self.wal.append(self._guard_bytes() + body)
            self._count("wal.dense_records")
        self._apply_payload(mode, payload)
        return mode

    # requires-lock: _lock
    def _apply_payload(self, mode: int, payload) -> None:
        """Apply one decoded payload (no WAL side effects — the two
        producers log in their own record form first).  Caller holds
        the lock."""
        import jax

        from go_crdt_playground_tpu.models.awset_delta import AWSetDeltaState
        from go_crdt_playground_tpu.ops import delta as delta_ops

        me = jax.tree.map(lambda x: x[0], self._state)
        if mode == MODE_FULL:
            src = AWSetDeltaState(
                vv=payload.src_vv,
                present=payload.changed,
                dot_actor=payload.ch_da, dot_counter=payload.ch_dc,
                actor=payload.src_actor,
                deleted=payload.deleted,
                del_dot_actor=payload.del_da,
                del_dot_counter=payload.del_dc,
                processed=payload.src_processed,
            )
            merged = delta_ops.full_merge_delta(me, src, self.delta_semantics)
        elif mode == MODE_SLICE:
            # keyspace handoff: the fenced donor slice is authoritative
            # for its lanes — overwrite, never vv-arbitrate (see
            # extract_slice / ops/delta.slice_apply)
            merged = delta_ops.slice_apply(me, payload)
        else:
            # MODE_DELTA and MODE_DIGEST both apply by δ arbitration:
            # a digest-sync lane payload differs only in its wire form
            # (index lanes, net/digestsync.py) — its merge semantics
            # are exactly a δ's, which is what lets both directions of
            # a digest push-pull round compose CRDT-monotonically
            merged = delta_ops.delta_apply(
                me, payload, self.delta_semantics,
                self.strict_reference_semantics)
        self._state = jax.tree.map(
            lambda full, row: full.at[0].set(row), self._state, merged)
        # deletion-GC bookkeeping (serve/compaction.py): remember the
        # freshest causal-stability vector this origin actor advertised
        # — the peer half of the provable frontier (deletion_frontier).
        # Monotone join, so stale/replayed payloads only under-claim.
        src_actor = int(payload.src_actor)
        if src_actor != self.actor:
            proc = np.asarray(payload.src_processed, np.uint32)
            prev = self._peer_processed.get(src_actor)
            self._peer_processed[src_actor] = (
                proc.copy() if prev is None else np.maximum(prev, proc))

    # requires-lock: _lock
    def _guard_bytes(self, vv: Optional[np.ndarray] = None) -> bytes:
        """Encode the replay guard: the vv this record's δ-compression
        was computed against (default: our current vv).  Caller holds
        the lock."""
        from go_crdt_playground_tpu.utils import wire

        if vv is None:
            vv = np.asarray(self._state.vv[0])
        return wire._encode_vv_py(np.asarray(vv, np.uint32))

    # requires-lock: _lock
    def _log_local_delta(self, pre_vv: np.ndarray) -> None:
        """WAL a local mutation as the δ it produced vs the pre-op VV.
        Sparse δs are written in the compact index-lane record form
        (``wal_compact_records``; O(changed) bytes), δs past the
        compact break-even in the dense PAYLOAD-body form merged deltas
        are logged in — both replay through ``replay_wal``.  The guard
        is the pre-op vv (the δ contains exactly the changes since
        it).  Caller holds the lock."""
        import jax
        import jax.numpy as jnp

        from go_crdt_playground_tpu.ops import delta as delta_ops

        me = jax.tree.map(lambda x: x[0], self._state)
        payload = delta_ops.delta_extract(me, jnp.asarray(pre_vv))
        self._append_delta_record(pre_vv, payload)

    # requires-lock: _lock
    def _append_delta_record(self, pre_vv: np.ndarray, payload,
                             compact=None) -> None:
        """Append one δ WAL record in whatever form the shared policy
        picks (``framing.encode_delta_wal_record`` — the single
        implementation the bench measures too).  ``compact`` is the
        fused batch path's on-device fixed-K form (TPU regime: the
        host pulls O(K) index lanes, fsyncs O(changed) bytes);
        ``compact=None`` (CPU regime) or overflow compacts host-side
        from the dense payload under the break-even rule, and an
        oversized δ falls back to the dense record — O(E) bytes for
        that batch, nothing is ever dropped.  Caller holds the
        lock.

        Spans: the batch's dispatch span ends here;
        ``wal.delta.pull_s`` is the blocking pull of the compact form,
        where the device's kernel and compaction time surfaces on the
        host; ``wal.delta.dense_s`` builds the record from the dense δ
        (its pull, host compaction, deletion filter) when the compact
        form is absent or overflowed — counted per batch as
        ``ingest.compact_overflow``; the WAL times its own append
        (``wal.fsync_s``)."""
        if self._dispatch_span is not None:
            self._dispatch_span.end()
        if not self.wal_compact_records:
            compact = None
        if compact is not None:
            import jax

            with timed(self.recorder, "wal.delta.pull_s"):
                # one pull for the whole fixed-K pytree — device_get
                # starts every leaf's transfer before blocking, vs a
                # sequential device round-trip per field under the lock
                # transfer-ok: the one sanctioned bounded pull of the
                # WAL encode path (caller holds the node lock)
                compact = jax.device_get(compact)
            if bool(compact.overflow):
                self._count("ingest.compact_overflow")
                compact = None
        if compact is None:
            with timed(self.recorder, "wal.delta.dense_s"):
                body, is_compact = framing.encode_delta_wal_record(
                    pre_vv, self.actor, payload,
                    compact_records=self.wal_compact_records)
        else:
            body, is_compact = framing.encode_delta_wal_record(
                pre_vv, self.actor, payload, compact)
        self.wal.append(body)
        self._count("wal.compact_records" if is_compact
                    else "wal.dense_records")

    # -- keyspace handoff (live resharding, DESIGN.md §18) ------------------

    def extract_slice(self, element_mask: np.ndarray) -> bytes:
        """Build the keyspace-handoff transfer payload: this replica's
        COMPLETE state for the masked elements (live entries with their
        dots, un-resurrected deletion records with theirs, plus our full
        vv/processed vectors), encoded as a ``MODE_SLICE`` anti-entropy
        PAYLOAD frame body.

        MODE_SLICE applies by OVERWRITE of the payload's lanes
        (ops/delta.slice_apply), never by vv arbitration: slice pushes
        join donor vvs into the recipient, so its vv comes to cover
        donor dots it never received (vvs are per-lane, slices are
        per-element), and an arbitrated apply would drop exactly those
        dots when a LATER handoff moves them here — a silently lost
        acked op.  Overwrite is sound because the router fences the
        slice for the whole transfer: the donor is the unique
        authority for these elements (ownership lineage always moves
        state forward whole, so a lane this donor has no state for was
        never acked anywhere), lanes outside the payload are
        untouched, and a retried push is idempotent."""
        import jax
        import jax.numpy as jnp

        from go_crdt_playground_tpu.ops import delta as delta_ops

        mask = np.asarray(element_mask, bool)
        if mask.shape != (self.num_elements,):
            raise ValueError(f"slice mask shape {mask.shape} does not "
                             f"match universe ({self.num_elements},)")
        m = jnp.asarray(mask)
        with self._lock:
            me = jax.tree.map(lambda x: x[0], self._state)
            p = delta_ops.delta_extract(
                me, jnp.zeros(self.num_actors, jnp.uint32))
            p = p._replace(
                changed=p.changed & m,
                ch_da=jnp.where(m, p.ch_da, 0),
                ch_dc=jnp.where(m, p.ch_dc, 0),
                deleted=p.deleted & m,
                del_da=jnp.where(m, p.del_da, 0),
                del_dc=jnp.where(m, p.del_dc, 0))
            return framing.encode_payload_msg(
                MODE_SLICE, self.actor, np.asarray(me.processed), p)

    def apply_payload_body(self, body: bytes) -> None:
        """Apply one anti-entropy PAYLOAD frame body (the recipient
        half of a keyspace handoff push — and any other out-of-band
        payload delivery).  Rides ``_apply_msg`` unchanged, so the body
        is WAL-logged with its replay guard BEFORE the state mutates:
        once the caller acks, the slice survives a SIGKILL exactly like
        any client op (restore_durable replays it)."""
        with self._lock:
            self._apply_msg(body)

    # -- shard replication (WAL shipping, shard/replica.py, §23) ------------

    def apply_wal_record(self, body: bytes) -> str:
        """Apply ONE shipped WAL record body — the standby half of a
        shard replication group: decode it exactly like ``replay_wal``
        (compact-tag dispatch, replay-GUARD check), write-ahead the
        ORIGINAL bytes to our own WAL, then apply through the normal
        payload path.  Logging the record VERBATIM keeps the standby's
        log replayable under the same guard discipline (the guard is
        the primary's pre-record vv, which a caught-up standby
        mirrors) and its state bitwise-convergent with the primary's
        restart path — both sides run the identical payload sequence
        through the identical apply.

        Returns ``"applied"``, or ``"future"`` when the guard outruns
        our vv — a GAP in the stream (never possible on an in-order
        tail; possible after a missed catch-up): the caller must
        digest-catch-up, never skip, because applying past a gap would
        fast-forward the vv over lanes we never received (the
        replay_wal hole).  Raises ``ProtocolError``/``ValueError`` for
        an undecodable record (the stream is corrupt: catch up and
        resume)."""
        from go_crdt_playground_tpu.net.framing import MODE_DELTA as _D
        from go_crdt_playground_tpu.utils import wire

        if body[:1] == bytes((wire.WAL_COMPACT_TAG,)):
            guard, payload = wire.decode_compact_wal_body(
                body, self.num_elements, self.num_actors)
            with self._lock:
                if np.any(np.asarray(guard, np.uint32)
                          > np.asarray(self._state.vv[0])):
                    return "future"
                if self.wal is not None:
                    self.wal.append(body)
                self._apply_payload(_D, payload)
        else:
            guard, pos = wire._decode_vv_py(body, 0, self.num_actors)
            mode, payload = framing.decode_payload_msg(
                body[pos:], self.num_elements, self.num_actors)
            with self._lock:
                if np.any(np.asarray(guard, np.uint32)
                          > np.asarray(self._state.vv[0])):
                    return "future"
                if self.wal is not None:
                    self.wal.append(body)
                self._apply_payload(mode, payload)
        return "applied"

    # -- digest-driven anti-entropy (net/digestsync.py, DESIGN.md §19) ------

    def _digest_fn(self, state_slice, group_size):
        """The digest-kernel backend dispatch, resolved once per node
        lifetime (ops/digest.digest_regime: Pallas twin on TPU, fused
        XLA pass elsewhere)."""
        if self._digest_regime is None:
            from go_crdt_playground_tpu.ops.digest import digest_regime

            self._digest_regime = digest_regime(self.num_elements)
        return self._digest_regime(state_slice, group_size)

    def digest_summary_arrays(self, group_size: int):
        """The digest-summary read's ``(vv, processed, digests)``
        triple — the array half of ``net/digestsync.node_summary``
        (the codec half stays there).  Split out as a replica-flavor
        hook: this base form snapshots the state reference under the
        lock and runs the digest kernel outside it; the mesh targets
        override it with a one-dispatch collective read that never
        materializes the per-field ``x[0]`` slices
        (parallel/meshtarget.py ``build_mesh_summary`` — the
        MESH_CURVE digest-fall-off fix)."""
        import jax

        with self._lock:
            me = jax.tree.map(lambda x: x[0], self._state)
        digests = np.asarray(self._digest_fn(me, group_size))
        return np.asarray(me.vv), np.asarray(me.processed), digests

    def note_peer_processed(self, src_actor: int, processed) -> None:
        """Record a peer's advertised causal-stability vector — the
        ``_apply_payload`` GC bookkeeping, callable WITHOUT a payload:
        a quiescent digest exchange ships no state yet still proves
        what the peer has processed, and without this the deletion-GC
        frontier (deletion_frontier) would freeze in a converged
        digest fleet.  Monotone join, like the payload path."""
        src_actor = int(src_actor)
        if src_actor == self.actor:
            return
        proc = np.asarray(processed, np.uint32)
        with self._lock:
            prev = self._peer_processed.get(src_actor)
            self._peer_processed[src_actor] = (
                proc.copy() if prev is None else np.maximum(prev, proc))

    # -- deletion-record GC (serve-path compaction, DESIGN.md §16) ----------

    def deletion_frontier(self, participants=None) -> np.ndarray:
        """The causal-stability frontier this node can PROVE: the
        elementwise min of its own ``processed`` vector and the
        freshest ``processed`` vector each PARTICIPATING replica actor
        has advertised in an applied payload (``_apply_payload``
        bookkeeping).  A deletion record ``(k, (a, c))`` is stable —
        droppable — iff ``c <= frontier[a]``.

        ``participants`` is the deployment's declared replica-actor
        set (self excluded implicitly).  It must cover every replica
        that could hold our elements live — gossip is TRANSITIVE, so a
        replica we never synced directly can still have learned an add
        via a relay, advertise a nonzero vv for us on its eventual
        first direct exchange (skipping the FULL-merge branch that
        would heal it), and keep a deleted element forever if its
        deletion record was dropped early.  A participant we have no
        advertised vector for therefore contributes ZEROS (no GC for
        its lanes), never "nothing".

        Membership is DECLARED, never inferred: ``participants=None``
        (undeclared) always yields the all-zeros frontier — GC
        disabled — because any runtime heuristic ("have I heard a
        peer?") is forgotten across a restart while the fleet is not;
        an EMPTY participant set is the explicit isolated declaration
        (this replica is the whole deployment) and yields our own
        vector.  Wrong declarations are operator error of the same
        class as a wrong peer list."""
        if participants is None:
            # before the lock: an undeclared-membership scheduler polls
            # this every wake and must not contend with the batcher
            return np.zeros(self.num_actors, np.uint32)
        with self._lock:
            own = np.asarray(self._state.processed[0], np.uint32).copy()
            heard = dict(self._peer_processed)
        out = own
        zeros = np.zeros_like(own)
        for a in participants:
            a = int(a)
            if a == self.actor:
                continue
            out = np.minimum(out, heard.get(a, zeros))
        return out

    def gc_deletions(self, frontier: Optional[np.ndarray] = None,
                     participants=None) -> dict:
        """Drop causally-stable deletion records
        (``ops/delta.gc_frontier``/``gc_apply`` wired to a live node —
        the schedulable half the kernels always had).  v2 semantics
        only: the reference mode never absorbs records, so there is
        nothing provably stable to drop.  GC is pure compaction — no
        WAL record: a crash-replay may resurrect dropped records from
        pre-GC log entries and the next cycle re-drops them.  The
        frontier defaults to ``deletion_frontier(participants)`` —
        see its membership contract."""
        import jax.numpy as jnp

        from go_crdt_playground_tpu.ops import delta as delta_ops

        if self.delta_semantics != "v2":
            raise ValueError("deletion GC requires v2 (record-absorbing) "
                             "delta semantics")
        if frontier is None:
            frontier = self.deletion_frontier(participants)
        f = jnp.asarray(np.asarray(frontier, np.uint32))
        with self._lock:
            before = int(np.asarray(self._state.deleted[0]).sum())
            self._state = delta_ops.gc_apply(self._state, f)
            after = int(np.asarray(self._state.deleted[0]).sum())
        return {"dropped": before - after, "remaining": after}

    def replay_wal(self, wal) -> dict:
        """Apply every intact, CAUSALLY-SAFE WAL record (oldest-first)
        through the normal payload-apply path — the recovery half of
        the WAL contract: state = checkpoint ⊔ replay(tail).

        Three stop conditions, one prefix rule (trust nothing after the
        first bad record):

        * the scan itself stops at the first CRC/framing tear;
        * an undecodable-but-CRC-clean body (``wal.bad_records``);
        * a record whose replay GUARD (the vv its δ-compression was
          computed against) is not covered by the current state
          (``wal.future_records``) — on a REGRESSED base (checkpoint
          generation fallback) such a record would fast-forward our vv
          past lanes delivered only in already-truncated records,
          punching a hole that δ-compression hides forever and that
          full-merge reads as an observed REMOVE.  Refusing it keeps
          the state causally consistent; anti-entropy re-ships the gap.

        Idempotent: records whose effects the checkpoint already
        contains merge to no-ops.  Counts ``wal.records`` (replayed,
        with a ``wal.replayed_compact`` / ``wal.replayed_dense`` mode
        breakdown) on the recorder.  Both record forms — legacy dense
        (guard-vv || PAYLOAD body) and compact index-lane
        (utils/wire.py, tag byte 0x00) — replay in segment order under
        the same guard check; a mixed segment is the normal case for a
        store that upgraded mid-history.  Detaches ``self.wal`` for the
        duration so replay never re-logs its own records."""
        from go_crdt_playground_tpu.net.framing import MODE_DELTA as _DELTA
        from go_crdt_playground_tpu.utils import wire

        replayed = bad = future = 0
        compact_n = dense_n = 0
        with self._lock:
            saved, self.wal = self.wal, None
        try:
            for body in wal.records():
                try:
                    if body[:1] == bytes((wire.WAL_COMPACT_TAG,)):
                        guard, payload = wire.decode_compact_wal_body(
                            body, self.num_elements, self.num_actors)
                        with self._lock:
                            if np.any(np.asarray(guard, np.uint32)
                                      > np.asarray(self._state.vv[0])):
                                future += 1
                                break
                            self._apply_payload(_DELTA, payload)
                        compact_n += 1
                    else:
                        guard, pos = wire._decode_vv_py(body, 0,
                                                        self.num_actors)
                        with self._lock:
                            if np.any(np.asarray(guard, np.uint32)
                                      > np.asarray(self._state.vv[0])):
                                future += 1
                                break
                            self._apply_msg(body[pos:])
                        dense_n += 1
                except (ProtocolError, ValueError):
                    # CRC-clean but semantically unreadable (e.g. a
                    # dimension change since the log was written): same
                    # prefix rule as a torn record — trust nothing after
                    bad += 1
                    break
                replayed += 1
        finally:
            with self._lock:
                self.wal = saved
        if self.recorder is not None:
            if replayed:
                self.recorder.count("wal.records", replayed)
            if compact_n:
                self.recorder.count("wal.replayed_compact", compact_n)
            if dense_n:
                self.recorder.count("wal.replayed_dense", dense_n)
            if bad:
                self.recorder.count("wal.bad_records", bad)
            if future:
                self.recorder.count("wal.future_records", future)
        return {"replayed": replayed, "bad": bad, "future": future,
                "compact": compact_n, "dense": dense_n}

    # -- server -------------------------------------------------------------

    def serve(self, host: str = "127.0.0.1",
              port: int = 0) -> Tuple[str, int]:
        """Start answering sync requests; returns the bound (host, port)."""
        if self._server_sock is not None:
            raise RuntimeError("already serving")
        sock = socket.create_server((host, port))
        self._server_sock = sock
        self._closing = False
        self._server_thread = threading.Thread(
            target=self._accept_loop, name=f"crdt-node-{self.actor}",
            daemon=True)
        self._server_thread.start()
        return sock.getsockname()[:2]

    def _accept_loop(self) -> None:
        sock = self._server_sock  # snapshot: close() may null the field
        assert sock is not None
        while not self._closing:
            try:
                conn, _ = sock.accept()
            except OSError:
                return  # socket closed
            if not self._conn_slots.acquire(blocking=False):
                conn.close()  # at capacity: shed load instead of queueing
                continue
            # daemonic and unretained: connection threads die with their
            # socket, so a long-lived node doesn't accumulate objects.
            # The slot handoff is finally-shaped: ANY failure to start
            # the handler (thread exhaustion, interpreter shutdown —
            # not just RuntimeError) must shed the dial AND return the
            # slot, else capacity decays one leak at a time.
            handed_off = False
            try:
                threading.Thread(target=self._handle, args=(conn,),
                                 daemon=True).start()
                handed_off = True
            except RuntimeError:
                pass  # OS thread exhaustion: shed the dial, keep serving
            finally:
                if not handed_off:
                    conn.close()
                    self._conn_slots.release()

    def _handle(self, conn: socket.socket) -> None:
        try:
            self._serve_conn(conn)
        finally:
            self._conn_slots.release()

    def _serve_conn(self, conn: socket.socket) -> None:
        try:
            with conn:
                # base per-op timeout covers the SENDS (a client that
                # stops reading fills the TCP window and blocks sendall);
                # each recv_frame below overrides it with a whole-frame
                # deadline and restores it afterwards
                conn.settimeout(self.conn_timeout_s)
                # short ABSOLUTE deadline for the whole HELLO frame: idle
                # half-open dials — and dialers trickling a byte per
                # timeout window — must release their slot quickly (a
                # real client sends HELLO immediately on connect)
                msg_type, body = framing.recv_frame(
                    conn, timeout=self.hello_timeout_s,
                    max_body=self._frame_cap)
                if msg_type == framing.MSG_DIGEST:
                    # digest-driven anti-entropy (DESIGN.md §19): the
                    # whole exchange is the tier's job — summary for
                    # summary, then lane payloads.  Dispatched here so
                    # one listener speaks both ladders; a pre-digest
                    # peer never sends this frame.
                    from go_crdt_playground_tpu.net import digestsync

                    digestsync.serve_digest_exchange(self, conn, body)
                    return
                if msg_type != MSG_HELLO:
                    framing.send_frame(conn, framing.MSG_ERROR,
                                       f"expected HELLO, got {msg_type}"
                                       .encode())
                    return
                recv = framing.frame_size(len(body))
                try:
                    peer_actor, peer_vv = framing.decode_hello(
                        body, self.num_elements, self.num_actors)
                except ProtocolError as e:
                    framing.send_frame(conn, framing.MSG_ERROR,
                                       str(e).encode())
                    return
                sent = framing.send_frame(
                    conn, MSG_HELLO, framing.encode_hello(
                        self.actor, self.num_elements, self.vv()))
                # the payload read gets the SAME whole-frame deadline
                # treatment (longer budget): per-recv timeouts reset on
                # every byte, so a post-HELLO trickler would otherwise
                # hold the slot indefinitely
                msg_type, body = framing.recv_frame(
                    conn, timeout=self.conn_timeout_s,
                    max_body=self._frame_cap)
                if msg_type != MSG_PAYLOAD:
                    framing.send_frame(conn, framing.MSG_ERROR,
                                       f"expected PAYLOAD, got {msg_type}"
                                       .encode())
                    return
                try:
                    with self._lock:
                        self._apply_msg(body)
                        # extract after absorbing the client's payload so
                        # transitively-learned entries ride along;
                        # compression vs the client's advertised VV
                        # filters what it has.
                        reply_mode, reply = self._extract_msg(peer_vv)
                except (ProtocolError, ValueError) as e:
                    # ValueError: apply hit a closed/refusing WAL (a
                    # teardown race) — the peer gets a clean error frame
                    # and retries next round, not a torn connection from
                    # a dead handler thread
                    framing.send_frame(conn, framing.MSG_ERROR,
                                       str(e).encode())
                    return
                sent += framing.send_frame(conn, MSG_PAYLOAD, reply)
                recv += framing.frame_size(len(body))
                self._record(reply_mode, bytes_sent=sent,
                             bytes_received=recv)
        except (ProtocolError, framing.RemoteError, OSError):
            pass  # connection-scoped failure; anti-entropy self-heals

    # -- crash / recovery ---------------------------------------------------

    def save(self, path: str, metadata: Optional[dict] = None) -> str:
        """Checkpoint this node's replica state (single-file atomic dump,
        utils/checkpoint).  State-based CRDTs make recovery trivial: a
        restored node re-joins with a possibly-stale state and anti-
        entropy self-heals the gap (SURVEY §5.3-5.4 — the merge IS the
        fault-tolerance story)."""
        from go_crdt_playground_tpu.utils.checkpoint import save_checkpoint

        with self._lock:
            state = self._state
        meta = dict(metadata or {})
        meta.update(
            actor=self.actor,
            delta_semantics=self.delta_semantics,
            strict_reference_semantics=self.strict_reference_semantics,
        )
        return save_checkpoint(path, state, metadata=meta)

    @classmethod
    def restore(cls, path: str, recorder=None) -> "Node":
        """Recover a node from a checkpoint written by ``save`` — state,
        actor identity, and semantics switches included.  The restored
        node is not serving; call ``serve()`` to rejoin."""
        from go_crdt_playground_tpu.utils.checkpoint import (
            restore_checkpoint)

        ck = restore_checkpoint(path)
        meta = ck.metadata
        missing = [k for k in
                   ("actor", "delta_semantics", "strict_reference_semantics")
                   if k not in meta]
        if missing:
            raise ValueError(
                f"checkpoint at {path!r} lacks node metadata {missing}: "
                "Node.restore requires a checkpoint written by Node.save "
                "(a bare utils.checkpoint.save_checkpoint file has state "
                "only — restore it with restore_checkpoint instead)")
        node = cls(
            actor=int(meta["actor"]),
            num_elements=int(ck.state.present.shape[-1]),
            num_actors=int(ck.state.vv.shape[-1]),
            delta_semantics=meta["delta_semantics"],
            strict_reference_semantics=meta["strict_reference_semantics"],
            recorder=recorder,
        )
        with node._lock:
            node._state = ck.state
        return node

    def full_resync_is_pending(self) -> bool:
        """Locked read of the healing-epoch flag (the supervisor polls
        this once per round; a stale read would only delay retirement by
        a round, but the lockset detector rightly refuses to bless
        "mostly harmless" bare reads of a mutated field)."""
        with self._lock:
            return self.full_resync_pending

    def full_resync_done_for(self, addr: Tuple[str, int]) -> bool:
        with self._lock:
            return (addr[0], int(addr[1])) in self._full_resync_done

    def clear_full_resync(self) -> None:
        """End the regressed-restore healing epoch: every peer has served
        a FULL exchange (the supervisor calls this once its whole peer
        set is covered), so the durable flag can go."""
        with self._lock:
            self.full_resync_pending = False
            self._full_resync_done.clear()
            flag_path = self._resync_flag_path
        if flag_path is not None:
            try:
                os.unlink(flag_path)
            except OSError:
                pass

    def _node_metadata(self, metadata: Optional[dict]) -> dict:
        meta = dict(metadata or {})
        meta.update(
            actor=self.actor,
            delta_semantics=self.delta_semantics,
            strict_reference_semantics=self.strict_reference_semantics,
        )
        return meta

    def save_durable(self, store, metadata: Optional[dict] = None) -> int:
        """Checkpoint into a generational ``utils.checkpoint.
        CheckpointStore`` and retire the WAL records the dump contains.

        Two-phase so the expensive state dump never stalls concurrent
        exchanges: under the node lock (cheap) the state reference is
        snapshotted and the WAL is SEALED (rotated — records appended
        afterwards land in a fresh segment); the dump itself runs
        outside the lock; the sealed segments are dropped only once the
        checkpoint is durable.  The dropped records are thus exactly
        the ones whose effects the snapshot contains.  A crash anywhere
        in between merely leaves pre-checkpoint segments behind —
        replay re-merges them idempotently.  Single writer per store
        (the same assumption the store's generation numbering makes).
        Returns the new generation number."""
        meta = self._node_metadata(metadata)
        with self._lock:
            state = self._state  # states are immutable pytrees: a
            wal = self.wal       # reference IS a snapshot
            sealed = wal.seal() if wal is not None else None
        gen = store.save(state, metadata=meta)
        if sealed is not None and wal is not None:
            wal.drop_segments(sealed)
        with self._lock:
            self.generation = gen
        return gen

    @classmethod
    def restore_durable(cls, dirpath: str, *, recorder=None,
                        min_generation: int = 0, keep: int = 3,
                        fallback_init=None,
                        node_kwargs: Optional[dict] = None) -> "Node":
        """Full crash-recovery path: newest VALID checkpoint generation
        (fallback past corrupt ones, fenced by ``min_generation``) plus
        a replay of the WAL tail, with the WAL left attached so the
        recovered node keeps logging.  ``fallback_init`` (a zero-arg
        Node factory) covers the died-before-first-checkpoint case —
        the store is empty but the WAL may still hold the entire
        history.  ``node_kwargs`` are extra constructor kwargs for
        ``cls`` (subclass plumbing — e.g. ``MeshApplyTarget``'s
        ``mesh_devices`` — which checkpoint metadata deliberately does
        not carry: placement is deployment config, not state).  The
        restored node is not serving; call ``serve()`` to rejoin."""
        import os as _os

        from go_crdt_playground_tpu.utils.checkpoint import (
            CheckpointCorrupt, CheckpointStore)
        from go_crdt_playground_tpu.utils.wal import DeltaWal

        store = CheckpointStore(dirpath, keep=keep, recorder=recorder)
        latest_on_disk = store.latest_generation()
        fell_back = False
        try:
            gen, ck = store.restore(min_generation=min_generation)
        except (FileNotFoundError, CheckpointCorrupt):
            # empty store, or EVERY generation failed verification: with
            # a fallback factory, recovery proceeds from a fresh state +
            # WAL replay + anti-entropy FULL resync instead of aborting
            # (each skipped generation already counted restore.fallbacks)
            if fallback_init is None:
                raise
            node = fallback_init()
            if node.recorder is None:
                # the factory usually omits it; without this the replay
                # counters (wal.records / wal.future_records) vanish
                node.recorder = recorder
            gen = 0
            fell_back = latest_on_disk > 0
        else:
            meta = ck.metadata
            missing = [k for k in ("actor", "delta_semantics",
                                   "strict_reference_semantics")
                       if k not in meta]
            if missing:
                raise ValueError(
                    f"checkpoint store at {dirpath!r} lacks node metadata "
                    f"{missing}: restore_durable needs checkpoints written "
                    "by Node.save_durable")
            node = cls(
                actor=int(meta["actor"]),
                num_elements=int(ck.state.present.shape[-1]),
                num_actors=int(ck.state.vv.shape[-1]),
                delta_semantics=meta["delta_semantics"],
                strict_reference_semantics=meta[
                    "strict_reference_semantics"],
                recorder=recorder,
                **(node_kwargs or {}),
            )
            with node._lock:
                node._state = ck.state
        with node._lock:
            node.generation = gen
        wal = DeltaWal(_os.path.join(dirpath, "wal"), recorder=recorder)
        stats = node.replay_wal(wal)
        if stats["bad"] or stats["future"]:
            # the refused suffix can never replay (the base it needs is
            # gone for good) and new acked records must NOT land behind
            # it — a second kill would replay, stop at the same refused
            # record, and silently discard them.  Reset to a clean log;
            # the armed resync epoch / anti-entropy covers the gap.
            wal.truncate()
        with node._lock:
            node.wal = wal
        # regressed restore (an older generation than the newest on
        # disk): WAL records logged against the newer lineage may have
        # fast-forwarded our vv past lanes delivered only in truncated
        # records — a hole delta compression can never re-fill.  Persist
        # a resync-pending flag (it must survive a re-kill before the
        # heal completes) and enter the forced-FULL healing epoch; the
        # supervisor clears it once every peer served a FULL exchange.
        regressed = (fell_back or (0 < gen < latest_on_disk)
                     or stats["future"] > 0)
        flag_path = _os.path.join(dirpath, "resync-pending")
        with node._lock:
            node._resync_flag_path = flag_path
        if regressed:
            with open(flag_path, "w") as f:
                f.write("regressed restore: full resync pending\n")
                f.flush()
                _os.fsync(f.fileno())
            if recorder is not None:
                recorder.count("restore.full_resync")
        pending = regressed or _os.path.exists(flag_path)
        with node._lock:
            node.full_resync_pending = pending
        return node

    def close(self) -> None:
        self._closing = True
        if self._server_sock is not None:
            try:
                self._server_sock.close()
            finally:
                self._server_sock = None
        if self._server_thread is not None:
            self._server_thread.join(timeout=5.0)
            self._server_thread = None

    def __enter__(self) -> "Node":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- client -------------------------------------------------------------

    def sync_with(self, addr: Tuple[str, int], timeout: float = 30.0, *,
                  connect_timeout_s: Optional[float] = None,
                  hello_timeout_s: Optional[float] = None) -> SyncStats:
        """One push-pull anti-entropy exchange with the peer at addr.

        ``timeout`` bounds the PAYLOAD reply (the expensive step: the
        server extracts it after applying ours).  The dial is bounded by
        ``connect_timeout_s`` (default: ``timeout``) and the HELLO reply
        — which the server sends before any kernel work — by
        ``hello_timeout_s`` (default: this node's own ``hello_timeout_s``,
        clamped to ``timeout``): the client-side mirror of the server's
        HELLO/payload budget asymmetry.  See the module docstring for the
        full deadline model.  Raises only the typed ``SyncError``
        hierarchy (plus ``framing.RemoteError`` for server-reported
        failures).
        """
        connect_t = timeout if connect_timeout_s is None else \
            connect_timeout_s
        hello_t = min(self.hello_timeout_s if hello_timeout_s is None
                      else hello_timeout_s, timeout)
        try:
            sock = socket.create_connection(addr, timeout=connect_t)
        except socket.timeout as e:
            raise PeerTimeout(f"connect to {addr}: {e}",
                              phase="connect") from e
        except OSError as e:
            raise ConnectFailed(f"connect to {addr}: {e}") from e
        # create_connection left connect_t as the socket's persistent
        # timeout; sends must ride the payload budget (recv_frame manages
        # its own deadline), else a short dead-peer-detection connect_t
        # would bound a large FULL-state send.
        sock.settimeout(timeout)
        # regressed-restore healing: advertise a zero vv on the first
        # exchange with each peer so it ships FULL state (the normal
        # first-contact branch) — delta compression against our real vv
        # would skip any lane a regressed replay fast-forwarded us past
        addr_key = (addr[0], int(addr[1]))
        with self._lock:
            forcing_full = (self.full_resync_pending
                            and addr_key not in self._full_resync_done)
            adv_vv = (np.zeros(self.num_actors, np.uint32) if forcing_full
                      else np.asarray(self._state.vv[0]).copy())
        with sock:
            phase = "hello"
            try:
                sent = framing.send_frame(
                    sock, MSG_HELLO, framing.encode_hello(
                        self.actor, self.num_elements, adv_vv))
                msg_type, body = framing.recv_frame(
                    sock, timeout=hello_t, max_body=self._frame_cap)
                if msg_type != MSG_HELLO:
                    raise ProtocolError(f"expected HELLO, got {msg_type}")
                _, peer_vv = framing.decode_hello(
                    body, self.num_elements, self.num_actors)
                recv = framing.frame_size(len(body))
                with self._lock:
                    mode_sent, out = self._extract_msg(peer_vv)
                phase = "payload"
                sent += framing.send_frame(sock, MSG_PAYLOAD, out)
                msg_type, body = framing.recv_frame(
                    sock, timeout=timeout, max_body=self._frame_cap)
                if msg_type != MSG_PAYLOAD:
                    raise ProtocolError(f"expected PAYLOAD, got {msg_type}")
                recv += framing.frame_size(len(body))
                with self._lock:
                    mode_recv = self._apply_msg(body)
            except SyncError:
                raise
            except framing.RemoteError:
                raise  # already typed; carries the server's message
            except socket.timeout as e:
                raise PeerTimeout(f"{phase} exchange with {addr}: {e}",
                                  phase=phase) from e
            except framing.TruncatedFrame as e:
                # a torn frame is transport loss, not peer malice —
                # surface it as the (retryable) reset class
                raise PeerReset(
                    f"{phase} exchange with {addr}: {e}") from e
            except ProtocolError as e:
                raise PeerProtocolError(str(e)) from e
            except OSError as e:
                raise PeerReset(
                    f"{phase} exchange with {addr}: {e}") from e
        if forcing_full:
            with self._lock:
                self._full_resync_done.add(addr_key)
        self._record(mode_sent, bytes_sent=sent, bytes_received=recv)
        return SyncStats(bytes_sent=sent, bytes_received=recv,
                         mode_sent=mode_sent, mode_received=mode_recv)

    def _count(self, name: str, n: int = 1) -> None:
        if self.recorder is not None:
            self.recorder.count(name, n)

    def _record(self, mode_sent: int, bytes_sent: int,
                bytes_received: int) -> None:
        if self.recorder is None:
            return
        counts = {
            "sync.exchanges": 1,
            "sync.bytes_sent": bytes_sent,
            "sync.bytes_received": bytes_received,
        }
        if mode_sent == MODE_FULL:
            counts["sync.full_payloads"] = 1
        self.recorder.count_many(counts)
