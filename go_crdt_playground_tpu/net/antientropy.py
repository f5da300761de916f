"""Resilient anti-entropy runtime: retries, backoff, circuit breakers.

The paper's robustness claim (SURVEY §5.3) is that state-based merge is
idempotent and commutative, so a lost exchange is only DELAYED
convergence, never lost data.  ``net/peer.py`` realizes the exchange but
is one-shot: a failed ``sync_with`` raises and nothing retries,
classifies, or degrades.  This module is the runtime that turns the
semantic claim into operational behavior:

* ``classify_failure`` maps the typed ``SyncError`` hierarchy (plus the
  legacy raw exceptions) onto a small set of failure CLASSES —
  connect-refused, connect-timeout, frame-deadline, reset, protocol,
  remote — because the right response differs per class: a refused
  connect means the peer is down (retry later, open the breaker), a
  frame deadline means it is up but slow (retry now), a protocol or
  remote-reported error is deterministic (retrying the same bytes
  cannot help).
* ``CircuitBreaker`` is the per-peer damage limiter: CLOSED until
  ``failure_threshold`` consecutive peer failures, then OPEN (all syncs
  to that peer are skipped — no connect attempts, no timeout budget
  burned) until ``cooldown_s`` elapses, then HALF_OPEN grants exactly
  one probe: success closes the breaker, failure re-opens it for a
  fresh cool-down.  The clock is injectable so the transition table is
  unit-testable without sleeping.
* ``SyncSupervisor`` drives one ``Node`` against a peer set on a
  (jittered) gossip cadence with a bounded per-round retry budget drawn
  from a shared ``utils.backoff.BackoffPolicy``, per-peer breakers, and
  optional periodic ``Node.save`` checkpoints — the crash-recovery half
  of the fault story: a killed supervisor restarts from its checkpoint
  (``SyncSupervisor.restore``) and the rejoined replica catches up via
  the first-contact FULL-state branch, because anti-entropy IS the
  recovery protocol.

Every breaker transition, retry, and failure class flows through the
``obs.metrics.Recorder`` (the metric names are the contract — see
DESIGN.md "Fault model & degradation ladder"), so a chaos run's
degradation behavior is assertable from ``Recorder.snapshot()`` alone.
Determinism: all randomness (backoff jitter, cadence jitter, peer-order
shuffle) derives from the supervisor seed, so a seeded chaos scenario
replays the same schedule.
"""

from __future__ import annotations

import random
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from go_crdt_playground_tpu.net import framing
from go_crdt_playground_tpu.net.peer import (ConnectFailed, Node,
                                             PeerProtocolError, PeerReset,
                                             PeerTimeout)
from go_crdt_playground_tpu.obs import timed
from go_crdt_playground_tpu.utils.backoff import Backoff, BackoffPolicy

Addr = Tuple[str, int]

# -- failure classification -------------------------------------------------

CLASS_CONNECT_REFUSED = "connect_refused"
CLASS_CONNECT_TIMEOUT = "connect_timeout"
CLASS_FRAME_DEADLINE = "frame_deadline"
CLASS_RESET = "reset"
CLASS_PROTOCOL = "protocol"
CLASS_REMOTE = "remote"
CLASS_UNKNOWN = "unknown"

FAILURE_CLASSES = (
    CLASS_CONNECT_REFUSED, CLASS_CONNECT_TIMEOUT, CLASS_FRAME_DEADLINE,
    CLASS_RESET, CLASS_PROTOCOL, CLASS_REMOTE, CLASS_UNKNOWN,
)

# Classes where an immediate in-round retry is pointless: the failure is
# a deterministic function of the bytes exchanged (dimension mismatch,
# malformed frame), not of network weather.
NON_RETRYABLE_CLASSES = frozenset({CLASS_PROTOCOL, CLASS_REMOTE})

# Classes that trip a breaker straight to OPEN: the peer positively
# REPORTED an incompatibility (MSG_ERROR frame) — hammering it with the
# same universe/actor axis can only ever fail the same way.
BREAKER_FATAL_CLASSES = frozenset({CLASS_REMOTE})


def classify_failure(exc: BaseException) -> str:
    """Map one sync failure onto its class.  Accepts both the typed
    hierarchy (net.peer) and the legacy raw exceptions, so callers that
    drive ``sync_with`` through older wrappers still classify."""
    if isinstance(exc, PeerTimeout):
        return (CLASS_CONNECT_TIMEOUT if exc.phase == "connect"
                else CLASS_FRAME_DEADLINE)
    if isinstance(exc, ConnectFailed):
        return CLASS_CONNECT_REFUSED
    if isinstance(exc, framing.RemoteError):
        return CLASS_REMOTE
    if isinstance(exc, framing.TruncatedFrame):
        return CLASS_RESET  # torn frame = transport loss, retryable
    if isinstance(exc, (PeerProtocolError, framing.ProtocolError)):
        return CLASS_PROTOCOL
    if isinstance(exc, (PeerReset, ConnectionError)):
        return CLASS_RESET
    if isinstance(exc, TimeoutError):   # raw socket.timeout from a
        return CLASS_FRAME_DEADLINE     # pre-hierarchy call path
    if isinstance(exc, OSError):
        return CLASS_CONNECT_REFUSED
    return CLASS_UNKNOWN


# -- circuit breaker --------------------------------------------------------

CLOSED = "closed"
OPEN = "open"
HALF_OPEN = "half_open"

_STATE_GAUGE = {CLOSED: 0, OPEN: 1, HALF_OPEN: 2}


class CircuitBreaker:
    """Per-peer consecutive-failure breaker.

    Transition table (pinned by tests/test_antientropy.py):

        CLOSED    --failure x threshold-->  OPEN
        OPEN      --cooldown elapsed----->  HALF_OPEN (allow() grants
                                            exactly ONE probe per
                                            cool-down window)
        HALF_OPEN --probe success------->   CLOSED
        HALF_OPEN --probe failure------->   OPEN (fresh cooldown)
        any       --trip()-------------->   OPEN

    ``allow()`` is the gate the supervisor consults before dialing; it
    performs the OPEN→HALF_OPEN transition itself when the cool-down has
    elapsed.  A probe whose owner dies without recording an outcome does
    NOT blacklist the peer forever: after a further ``cooldown_s`` in
    HALF_OPEN, ``allow()`` grants a fresh probe.  ``clock`` is
    injectable (monotonic seconds) so the state machine unit-tests
    without wall time.  Thread-safe.
    """

    def __init__(self, failure_threshold: int = 3, cooldown_s: float = 2.0,
                 clock: Callable[[], float] = time.monotonic,
                 on_transition: Optional[Callable[[str, str], None]] = None):
        if failure_threshold < 1:
            raise ValueError("failure_threshold must be >= 1")
        if cooldown_s < 0:
            raise ValueError("cooldown_s must be >= 0")
        self.failure_threshold = failure_threshold
        self.cooldown_s = cooldown_s
        self._clock = clock
        self._on_transition = on_transition
        self._lock = threading.Lock()
        self._state = CLOSED  # guarded-by: _lock
        self._consecutive = 0  # guarded-by: _lock
        self._opened_at = 0.0  # guarded-by: _lock
        self._probe_granted_at = 0.0  # guarded-by: _lock

    @property
    def state(self) -> str:
        with self._lock:
            return self._state

    @property
    def consecutive_failures(self) -> int:
        with self._lock:
            return self._consecutive

    # requires-lock: _lock
    def _set_state(self, new: str) -> None:
        """Caller holds the lock.  Fires the transition hook OUTSIDE any
        state mutation ordering concern (hook runs under the lock; keep
        hooks cheap — the supervisor's just bumps a counter)."""
        old, self._state = self._state, new
        if old != new and self._on_transition is not None:
            self._on_transition(old, new)

    def allow(self) -> bool:
        with self._lock:
            if self._state == CLOSED:
                return True
            if self._state == OPEN:
                if self._clock() - self._opened_at >= self.cooldown_s:
                    self._set_state(HALF_OPEN)
                    self._probe_granted_at = self._clock()
                    return True
                return False
            # HALF_OPEN: the granted probe is still in flight.  If its
            # owner died without ever recording an outcome, a further
            # cool-down re-grants — a wedged probe must not blacklist
            # the peer forever.
            if self._clock() - self._probe_granted_at >= self.cooldown_s:
                self._probe_granted_at = self._clock()
                return True
            return False

    def record_success(self) -> None:
        with self._lock:
            self._consecutive = 0
            if self._state != CLOSED:
                self._set_state(CLOSED)

    def record_failure(self) -> None:
        with self._lock:
            self._consecutive += 1
            if self._state == HALF_OPEN or (
                    self._state == CLOSED
                    and self._consecutive >= self.failure_threshold):
                self._opened_at = self._clock()
                self._set_state(OPEN)
            elif self._state == OPEN:
                # a failure recorded while OPEN (e.g. a racing probe from
                # another thread) refreshes the cooldown
                self._opened_at = self._clock()

    def trip(self) -> None:
        """Force OPEN now (deterministic-incompatibility fast path)."""
        with self._lock:
            self._opened_at = self._clock()
            if self._state != OPEN:
                self._set_state(OPEN)


# -- supervisor -------------------------------------------------------------


class SyncSupervisor:
    """Drives one ``Node`` against a peer set with bounded retries,
    per-peer circuit breakers, and periodic checkpoints.

    One ``sync_round()`` visits every registered peer once (seeded
    shuffle order): peers behind an OPEN breaker are skipped outright;
    the rest get one ``sync_with`` plus up to ``policy.max_retries``
    in-round retries with jittered exponential backoff — except for
    non-retryable failure classes (protocol/remote), where retrying the
    same bytes is pointless.  The breaker records ONE outcome per peer
    per round (the round's net result), so its consecutive-failure count
    means "rounds of sustained failure", not "attempts".

    Metric names (full table in DESIGN.md "Fault model & degradation
    ladder"): ``sync.supervisor.rounds``, ``sync.successes``,
    ``sync.peer_failures``, ``sync.skipped_open``,
    ``sync.failures.<class>``, ``sync.retries.<class>``,
    ``breaker.to_open`` / ``breaker.to_half_open`` / ``breaker.to_closed``,
    ``sync.checkpoints``; plus a ``breaker.state.<host>:<port>`` gauge
    (0=closed, 1=open, 2=half_open).

    ``sleep`` and ``clock`` are injectable for wall-time-free tests; all
    randomness derives from ``seed``.

    Checkpoint regimes (mutually exclusive): ``checkpoint_path`` is the
    legacy single-file ``Node.save`` dump; ``durable_dir`` is the full
    durability ladder (DESIGN.md §14) — a generational verified
    ``CheckpointStore`` plus a ``DeltaWal`` attached to the node (if it
    has none), so every merged/local δ is durable between checkpoints
    and each ``checkpoint()`` truncates the log it just superseded.
    ``SyncSupervisor.restore_durable`` is the matching restart path.
    """

    def __init__(self, node: Node, peers: Sequence[Addr], *,
                 policy: Optional[BackoffPolicy] = None,
                 sync_timeout_s: float = 5.0,
                 connect_timeout_s: Optional[float] = None,
                 hello_timeout_s: Optional[float] = None,
                 breaker_threshold: int = 3,
                 breaker_cooldown_s: float = 1.0,
                 fanout: Optional[int] = None,
                 interval_s: float = 0.05,
                 interval_jitter: float = 0.2,
                 checkpoint_path: Optional[str] = None,
                 checkpoint_every: int = 0,
                 durable_dir: Optional[str] = None,
                 keep_generations: int = 3,
                 wal_fsync: bool = True,
                 sync_mode: str = "delta",
                 recorder=None, seed: int = 0,
                 sleep: Callable[[float], None] = time.sleep,
                 clock: Callable[[], float] = time.monotonic):
        """``sync_mode``: the anti-entropy regime (DESIGN.md §19).
        ``"delta"`` is the FULL/DELTA ladder; ``"digest"`` opens every
        exchange with a digest summary (net/digestsync.py) and ships
        only mismatched lanes — O(diff) rounds — NEGOTIATED per peer:
        a peer answering "expected HELLO" is pinned legacy and synced
        over the ladder for its lifetime, so mixed fleets roll forward
        safely.  Digest exchanges require v2 delta semantics (the
        reference mode never absorbs deletion records, so its logs
        never converge bitwise and every digest would mismatch
        forever).  A node healing a regressed restore
        (full_resync_pending) rides the ladder until the epoch
        retires — the forced-FULL zero-vv advertisement is the
        ladder's mechanism."""
        if durable_dir is not None and checkpoint_path is not None:
            raise ValueError(
                "durable_dir and checkpoint_path are alternative "
                "checkpoint regimes; pass one")
        if sync_mode not in ("delta", "digest"):
            raise ValueError(f"unknown sync_mode {sync_mode!r} "
                             "(expected 'delta' or 'digest')")
        if sync_mode == "digest" and node.delta_semantics != "v2":
            raise ValueError(
                "digest sync requires v2 (record-absorbing) delta "
                "semantics: reference-mode deletion logs never "
                "converge bitwise, so their digests mismatch forever")
        self.sync_mode = sync_mode
        self._negotiator = None
        self._group_adapter = None
        if sync_mode == "digest":
            from go_crdt_playground_tpu.net.digestsync import (
                AdaptiveGroupSize, DigestNegotiator)

            self._negotiator = DigestNegotiator()
            # per-peer online group-size tuning (digest rung b): the
            # tuner is thread-safe; its streak evidence comes from the
            # stats each exchange returns below
            self._group_adapter = AdaptiveGroupSize(node.num_elements)
        self.node = node
        self.policy = policy if policy is not None else BackoffPolicy()
        self.sync_timeout_s = sync_timeout_s
        self.connect_timeout_s = connect_timeout_s
        self.hello_timeout_s = hello_timeout_s
        self.breaker_threshold = breaker_threshold
        self.breaker_cooldown_s = breaker_cooldown_s
        if fanout is not None and fanout < 1:
            raise ValueError("fanout must be >= 1 (or None for all peers)")
        self.fanout = fanout
        self.interval_s = interval_s
        self.interval_jitter = interval_jitter
        self.checkpoint_path = checkpoint_path
        self.checkpoint_every = checkpoint_every
        self.durable_dir = durable_dir
        self.recorder = recorder if recorder is not None else node.recorder
        self._store = None
        if durable_dir is not None:
            from go_crdt_playground_tpu.utils.checkpoint import \
                CheckpointStore
            from go_crdt_playground_tpu.utils.wal import DeltaWal
            import os as _os

            self._store = CheckpointStore(
                durable_dir, keep=keep_generations, recorder=self.recorder)
            with node._lock:
                if node.wal is None:
                    # attach the log so every delta the supervisor's
                    # rounds merge (and every local mutation) is durable
                    # between the periodic checkpoints
                    node.wal = DeltaWal(
                        _os.path.join(durable_dir, "wal"),
                        fsync=wal_fsync, recorder=self.recorder)
        self.seed = seed
        self._sleep = sleep
        self._clock = clock
        # race-ok: single-driver contract — rounds run from one thread
        # at a time (run()/sync_round() caller XOR the start() loop)
        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        # serializes checkpoint() callers: the supervisor loop and the
        # serve compaction scheduler (serve/compaction.py) may both
        # rotate checkpoints, and CheckpointStore assumes one writer
        self._ckpt_lock = threading.Lock()
        self._peers: List[Addr] = []  # guarded-by: _lock
        self._breakers: Dict[Addr, CircuitBreaker] = {}  # guarded-by: _lock
        self._rounds_done = 0  # guarded-by: _lock
        self._stop = threading.Event()
        # race-ok: start()/stop() owner thread only
        self._thread: Optional[threading.Thread] = None
        # race-ok: post-mortem breadcrumb (loop thread writes, a
        # post-stop reader inspects); no control flow depends on it
        self.last_error: Optional[BaseException] = None
        for p in peers:
            self.add_peer(p)

    # -- peer set ----------------------------------------------------------

    def add_peer(self, addr: Addr) -> None:
        addr = (addr[0], int(addr[1]))
        with self._lock:
            if addr in self._breakers:
                return
            self._peers.append(addr)
            self._breakers[addr] = CircuitBreaker(
                failure_threshold=self.breaker_threshold,
                cooldown_s=self.breaker_cooldown_s,
                clock=self._clock,
                on_transition=lambda old, new, a=addr:
                    self._on_breaker_transition(a, old, new))

    def remove_peer(self, addr: Addr) -> None:
        addr = (addr[0], int(addr[1]))
        with self._lock:
            self._peers = [p for p in self._peers if p != addr]
            self._breakers.pop(addr, None)

    @property
    def peers(self) -> List[Addr]:
        with self._lock:
            return list(self._peers)

    def breaker(self, addr: Addr) -> CircuitBreaker:
        with self._lock:
            return self._breakers[(addr[0], int(addr[1]))]

    # -- metrics -----------------------------------------------------------

    def _count(self, name: str, n: int = 1) -> None:
        if self.recorder is not None:
            self.recorder.count(name, n)

    def _on_breaker_transition(self, addr: Addr, old: str, new: str) -> None:
        self._count(f"breaker.to_{new}")
        if self.recorder is not None and hasattr(self.recorder, "set_gauge"):
            self.recorder.set_gauge(
                f"breaker.state.{addr[0]}:{addr[1]}", _STATE_GAUGE[new])

    # -- rounds ------------------------------------------------------------

    def sync_round(self) -> Dict[str, int]:
        """One pass over the peer set (seeded shuffle).  With ``fanout``
        set, only that many (seeded-sampled) peers are visited — classic
        gossip fanout, what gives rounds-to-convergence its meaning in
        the chaos soak curve.  Returns the round summary {"succeeded",
        "failed", "skipped"}."""
        peers = self.peers
        self._rng.shuffle(peers)
        if self.fanout is not None:
            peers = peers[:self.fanout]
        summary = {"succeeded": 0, "failed": 0, "skipped": 0}
        for addr in peers:
            try:
                breaker = self.breaker(addr)
            except KeyError:
                continue  # removed concurrently
            if not breaker.allow():
                self._count("sync.skipped_open")
                summary["skipped"] += 1
                continue
            ok = self._sync_peer(addr, breaker)
            summary["succeeded" if ok else "failed"] += 1
        if self.node.full_resync_is_pending():
            # regressed-restore healing epoch: once every registered
            # peer has served a forced-FULL exchange, the durable
            # resync-pending flag can be retired
            all_peers = self.peers
            if all_peers and all(self.node.full_resync_done_for(p)
                                 for p in all_peers):
                self.node.clear_full_resync()
                self._count("sync.full_resync_complete")
        self._count("sync.supervisor.rounds")
        with self._lock:
            self._rounds_done += 1
            rounds = self._rounds_done
        if ((self.checkpoint_path or self._store is not None)
                and self.checkpoint_every > 0
                and rounds % self.checkpoint_every == 0):
            self.checkpoint()
        return summary

    def _sync_peer(self, addr: Addr, breaker: CircuitBreaker) -> bool:
        """One peer's exchange with the in-round retry budget.  The
        caller (sync_round) has already passed the breaker's allow()
        gate — consulting it here again would double-spend the single
        HALF_OPEN probe grant."""
        # a fresh per-(round, peer) seed keeps retry jitter deterministic
        # yet uncorrelated across peers and rounds
        bo = Backoff(self.policy, seed=self._rng.getrandbits(32))
        while True:
            try:
                self._exchange(addr)
            except Exception as e:  # noqa: BLE001 — classified below
                cls = classify_failure(e)
                if cls == CLASS_UNKNOWN and not isinstance(
                        e, (OSError, RuntimeError)):
                    # a programming error, not network weather — record
                    # the round's outcome FIRST (so a HALF_OPEN probe
                    # grant is returned and the breaker can never wedge
                    # on a dead probe owner), then surface it
                    breaker.record_failure()
                    self._count(f"sync.failures.{cls}")
                    self._count("sync.peer_failures")
                    raise
                self._count(f"sync.failures.{cls}")
                if cls in BREAKER_FATAL_CLASSES:
                    breaker.trip()
                    self._count("sync.peer_failures")
                    return False
                delay = (None if cls in NON_RETRYABLE_CLASSES
                         else bo.next_delay())
                if delay is None:
                    breaker.record_failure()
                    self._count("sync.peer_failures")
                    return False
                self._count(f"sync.retries.{cls}")
                self._sleep(delay)
            else:
                breaker.record_success()
                self._count("sync.successes")
                return True

    def _exchange(self, addr: Addr) -> None:
        """One exchange on the negotiated regime: digest-first when the
        digest regime is on, the peer is not pinned legacy, and no
        forced-FULL healing epoch is pending; a peer that answers
        "expected HELLO" is pinned legacy (``sync.digest.unsupported``)
        and the SAME attempt completes over the ladder — negotiation
        costs one extra dial once per legacy peer, never a failed
        round."""
        if (self._negotiator is not None
                and self._negotiator.use_digest(addr)
                and not self.node.full_resync_is_pending()):
            from go_crdt_playground_tpu.net import digestsync

            gs = self._group_adapter.size(addr)
            try:
                try:
                    stats = digestsync.sync_digest(
                        self.node, addr, timeout=self.sync_timeout_s,
                        connect_timeout_s=self.connect_timeout_s,
                        group_size=gs)
                except (PeerProtocolError, framing.RemoteError) as e:
                    # a pre-adaptive server rejects any non-default
                    # size with its group-size-mismatch error (served
                    # as MSG_ERROR → RemoteError): pin the default for
                    # this peer's lifetime and complete the SAME
                    # attempt at it — negotiation costs one extra dial
                    # once, like the legacy-ladder fallback
                    if (gs == digestsync.DIGEST_GROUP_LANES
                            or "group-size mismatch" not in str(e)):
                        raise
                    self._group_adapter.pin(
                        addr, digestsync.DIGEST_GROUP_LANES)
                    self._count("digest.group_pinned")
                    stats = digestsync.sync_digest(
                        self.node, addr, timeout=self.sync_timeout_s,
                        connect_timeout_s=self.connect_timeout_s,
                        group_size=digestsync.DIGEST_GROUP_LANES)
                move = self._group_adapter.observe(addr, stats)
                if move != "hold":
                    self._count(f"digest.group_{move}")
                if self.recorder is not None and hasattr(
                        self.recorder, "set_gauge"):
                    self.recorder.set_gauge(
                        "digest.group_size",
                        self._group_adapter.size(addr))
                return
            except digestsync.DigestUnsupported:
                self._negotiator.mark_legacy(addr)
                self._count("sync.digest.unsupported")
        self.node.sync_with(
            addr, timeout=self.sync_timeout_s,
            connect_timeout_s=self.connect_timeout_s,
            hello_timeout_s=self.hello_timeout_s)

    def run(self, max_rounds: Optional[int] = None,
            until: Optional[Callable[[], bool]] = None) -> int:
        """Run rounds on the jittered cadence until ``until()`` is true
        or ``max_rounds`` elapse; returns rounds run."""
        if max_rounds is None and until is None:
            raise ValueError("run() needs max_rounds and/or until — an "
                             "unbounded foreground loop is start()'s job")
        # a stale stop() from a prior start()/stop() cycle must not veto
        # this run — clear it like start() does
        self._stop.clear()
        rounds = 0
        while not self._stop.is_set():
            self.sync_round()
            rounds += 1
            if until is not None and until():
                break
            if max_rounds is not None and rounds >= max_rounds:
                break
            self._pace()
        return rounds

    def _pace(self) -> None:
        if self.interval_s > 0:
            j = 1.0 + self.interval_jitter * self._rng.uniform(-1.0, 1.0)
            self._sleep(self.interval_s * j)

    # -- background operation ---------------------------------------------

    def start(self) -> None:
        """Run rounds on a daemon thread until ``stop()``.  The loop
        NEVER dies on an exception — a resilience runtime whose own
        thread can be killed by one bad peer payload is no runtime at
        all.  Escaped errors are counted (``sync.supervisor.errors``)
        and kept on ``last_error`` for post-mortems."""
        if self._thread is not None and self._thread.is_alive():
            raise RuntimeError("supervisor already running")
        self._stop.clear()

        def loop() -> None:
            while not self._stop.is_set():
                try:
                    self.sync_round()
                except Exception as e:  # noqa: BLE001 — see docstring
                    self.last_error = e
                    self._count("sync.supervisor.errors")
                self._pace()

        self._thread = threading.Thread(
            target=loop, name=f"sync-supervisor-{self.node.actor}",
            daemon=True)
        self._thread.start()

    def stop(self, timeout: float = 10.0) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=timeout)
            if not t.is_alive():
                self._thread = None
            # else: keep the handle — a wedged round is still running,
            # and dropping it would let start() spawn a SECOND loop over
            # the same breakers/checkpoints.  start() re-checks
            # is_alive(), so a late exit is not a permanent lockout.

    def __enter__(self) -> "SyncSupervisor":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- crash / recovery --------------------------------------------------

    def checkpoint(self) -> Optional[str]:
        """Periodic crash-recovery dump.  With ``durable_dir`` this is
        the full durability contract — ``Node.save_durable`` writes the
        next verified generation AND truncates the WAL under one node
        lock hold (the truncated records are exactly the ones the dump
        contains); without it, the legacy single-file ``Node.save``.
        Returns the written path.  The dump is the span
        ``node.checkpoint_s``."""
        with self._lock:
            meta = {"supervisor_rounds": self._rounds_done}
        with self._ckpt_lock:
            if self._store is not None:
                with timed(self.recorder, "node.checkpoint_s"):
                    gen = self.node.save_durable(self._store,
                                                 metadata=meta)
                self._count("sync.checkpoints")
                return self._store.path_for(gen)
            if not self.checkpoint_path:
                return None
            path = self.node.save(self.checkpoint_path, metadata=meta)
            self._count("sync.checkpoints")
            return path

    @classmethod
    def restore(cls, checkpoint_path: str, peers: Sequence[Addr],
                recorder=None, **kwargs) -> "SyncSupervisor":
        """Restart path: restore the Node from its supervisor checkpoint
        and wrap it in a fresh supervisor over ``peers``.  The restored
        replica's first exchange with any peer that never saw it rides
        the FULL-state first-contact branch — anti-entropy heals the gap
        between the checkpoint and the fleet (SURVEY §5.3-5.4)."""
        node = Node.restore(checkpoint_path, recorder=recorder)
        # default, not override: the caller may checkpoint somewhere else
        # (or pass checkpoint_every) without a duplicate-kwarg TypeError
        kwargs.setdefault("checkpoint_path", checkpoint_path)
        return cls(node, peers, recorder=recorder, **kwargs)

    @classmethod
    def restore_durable(cls, durable_dir: str, peers: Sequence[Addr],
                        recorder=None, *, min_generation: int = 0,
                        keep_generations: int = 3, fallback_init=None,
                        **kwargs) -> "SyncSupervisor":
        """Crash-recovery restart: newest VALID checkpoint generation
        (falling back past corrupt ones, fenced by ``min_generation``)
        plus a replay of the WAL tail (``Node.restore_durable``), wrapped
        in a fresh supervisor that keeps checkpointing into the same
        directory.  Anti-entropy then heals whatever the WAL-tail window
        lost — at most the record in flight at the kill."""
        node = Node.restore_durable(
            durable_dir, recorder=recorder, min_generation=min_generation,
            keep=keep_generations, fallback_init=fallback_init)
        return cls(node, peers, recorder=recorder, durable_dir=durable_dir,
                   keep_generations=keep_generations, **kwargs)
