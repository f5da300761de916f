"""Message framing for the peer sync protocol.

Frame:  MAGIC(2) | type(1) | varint body_len | body

Bodies reuse the δ wire primitives (utils/wire.py) so every section is
byte-identical whether it crosses a socket, lands in a checkpoint, or is
produced by the C++ codec:

  HELLO    varint actor | varint E | vv-section(vv)
  PAYLOAD  mode(1) | varint src_actor | vv-section(processed) | payload
  ERROR    utf-8 message

where ``payload`` is utils.wire.encode_payload's three-section form and
``mode`` is FULL on first contact (receiver's clock has never seen the
sender, the dispatch condition of awset-delta_test.go:53) else DELTA.
"""

from __future__ import annotations

import socket
import time
from typing import Optional, Tuple

import numpy as np

from go_crdt_playground_tpu.utils import wire

MAGIC = b"\xc7\xd1"

MSG_HELLO = 1
MSG_PAYLOAD = 2
# protocol-ignore: internal — recv_frame raises it as RemoteError
# before any dispatcher sees a frame type
MSG_ERROR = 3
# digest-driven anti-entropy (DESIGN.md §19): the opening frame of a
# digest exchange carries a compact summary — vv + processed + packed
# per-lane-group digests (net/digestsync.py owns the body codec) —
# instead of HELLO.  A pre-digest peer answers it with MSG_ERROR
# ("expected HELLO"), which the client reads as version-mismatch and
# falls back to the FULL/DELTA ladder for that peer.
MSG_DIGEST = 4

MODE_DELTA = 0
MODE_FULL = 1
# keyspace-handoff slice transfer (DESIGN.md §18): the payload is the
# donor's complete FENCED state for the lanes it names, applied by
# OVERWRITE (ops/delta.slice_apply), never by vv arbitration — the
# recipient's vv may legitimately cover dots it never received (prior
# slice pushes join donor vvs), and arbitration would drop exactly
# those lanes
MODE_SLICE = 2
# digest-sync lane payload (DESIGN.md §19): the sender's COMPLETE lane
# state for digest-mismatched groups, index-encoded (utils/wire.py
# encode_payload_lanes — O(diff) bytes, no E/8 section bitmasks),
# applied by normal v2 δ arbitration (ops/delta.delta_apply): lanes in
# digest-MATCHED groups are withheld because they are provably (to the
# ops/digest.py collision bound) identical, which is what makes the
# full-vv join safe — contrast MODE_SLICE's fenced overwrite.
MODE_DIGEST = 3

_MAX_BODY = 1 << 30


def peer_frame_cap(num_elements: int, num_actors: int) -> int:
    """The explicit ``max_body`` for peer-dialect frames (W004 frame-cap
    discipline, DESIGN.md §15): the largest legal body is a dense FULL
    payload — two E/8-byte section bitmasks plus at most ~10 varint
    bytes per set lane per section, plus vv sections — so
    ``32·E + 8·A + 64KB`` bounds every legal HELLO / DIGEST summary /
    PAYLOAD body with slack while keeping a hostile length header from
    committing a reader to the 1GB codec ceiling."""
    return 32 * int(num_elements) + 8 * int(num_actors) + (1 << 16)


class ProtocolError(RuntimeError):
    pass


class TruncatedFrame(ProtocolError):
    """The connection closed mid-frame (torn frame).  Still a
    ProtocolError for compatibility, but distinguishable: a torn frame
    is TRANSPORT loss (transient — the resilient runtime retries it),
    while other ProtocolErrors mean the peer spoke the protocol wrong
    (deterministic — retrying the same bytes cannot help)."""


class RemoteError(RuntimeError):
    """The peer reported a protocol-level failure (MSG_ERROR frame)."""


def _recv_exact(sock: socket.socket, n: int,
                deadline: Optional[float] = None) -> bytes:
    """Read exactly n bytes.  With a ``deadline`` (time.monotonic()-based),
    the WHOLE read must finish by then: the per-recv socket timeout is
    re-derived from the remaining budget each iteration, so a peer
    trickling one byte per timeout window cannot hold the read open
    indefinitely the way a bare settimeout allows.  The socket's own
    timeout configuration is restored on exit (success or raise), so
    the deadline never leaks onto the socket for later callers."""
    if deadline is None:
        return _recv_exact_inner(sock, n, None)
    saved = sock.gettimeout()
    try:
        return _recv_exact_inner(sock, n, deadline)
    finally:
        sock.settimeout(saved)


def _recv_exact_inner(sock: socket.socket, n: int,
                      deadline: Optional[float]) -> bytes:
    chunks = []
    while n:
        if deadline is not None:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise socket.timeout("frame deadline exceeded")
            sock.settimeout(remaining)
        b = sock.recv(min(n, 1 << 20))
        if not b:
            raise TruncatedFrame("connection closed mid-frame")
        chunks.append(b)
        n -= len(b)
    return b"".join(chunks)


def _recv_varint(sock: socket.socket,
                 deadline: Optional[float] = None) -> int:
    out = 0
    shift = 0
    while True:
        b = _recv_exact(sock, 1, deadline)[0]
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out
        shift += 7
        if shift > 63:
            raise ProtocolError("malformed varint")


def frame_size(body_len: int) -> int:
    """Total on-wire bytes of a frame with a body_len-byte body."""
    n, varint_len = body_len, 1
    while n >= 0x80:
        n >>= 7
        varint_len += 1
    return 2 + 1 + varint_len + body_len


def send_frame(sock: socket.socket, msg_type: int, body: bytes) -> int:
    head = bytearray(MAGIC)
    head.append(msg_type)
    wire._put_varint(head, len(body))
    data = bytes(head) + body
    sock.sendall(data)
    return len(data)


def recv_frame(sock: socket.socket, timeout: Optional[float] = None,
               max_body=_MAX_BODY) -> Tuple[int, bytes]:
    """Receive one frame.  ``timeout`` bounds the WHOLE frame (absolute
    deadline semantics), not each recv, and the socket's own timeout
    configuration is restored afterwards; on None it applies per recv
    as usual.  ``max_body`` caps the declared body size BEFORE any body
    byte is buffered — the default fits peer FULL-state payloads;
    dialects facing untrusted clients (serve/) pass a far smaller cap
    so a hostile length header cannot balloon per-connection memory.
    It may be a callable ``msg_type -> int`` for dialects whose legal
    frame sizes differ by verb (the serve frontend's keyspace-handoff
    SLICE_PUSH scales with the universe; its op frames stay tiny)."""
    if timeout is None:
        return _recv_frame(sock, None, max_body)
    saved = sock.gettimeout()
    try:
        return _recv_frame(sock, time.monotonic() + timeout, max_body)
    finally:
        sock.settimeout(saved)


def _recv_frame(sock: socket.socket, deadline: Optional[float],
                max_body=_MAX_BODY) -> Tuple[int, bytes]:
    magic = _recv_exact(sock, 2, deadline)
    if magic != MAGIC:
        raise ProtocolError(f"bad magic {magic!r}")
    msg_type = _recv_exact(sock, 1, deadline)[0]
    n = _recv_varint(sock, deadline)
    limit = max_body(msg_type) if callable(max_body) else max_body
    if n > min(limit, _MAX_BODY):
        raise ProtocolError(f"oversized frame ({n} bytes)")
    body = _recv_exact(sock, n, deadline)
    if msg_type == MSG_ERROR:
        raise RemoteError(body.decode("utf-8", "replace"))
    return msg_type, body


# ---------------------------------------------------------------------------
# Bodies
# ---------------------------------------------------------------------------


def encode_hello(actor: int, num_elements: int, vv: np.ndarray) -> bytes:
    out = bytearray()
    wire._put_varint(out, actor)
    wire._put_varint(out, num_elements)
    return bytes(out) + wire._encode_vv_py(np.asarray(vv, np.uint32))


def decode_hello(body: bytes, num_elements: int,
                 num_actors: int) -> Tuple[int, np.ndarray]:
    """Returns (actor, vv); raises ProtocolError on any dimension
    disagreement — peers must share one dictionary-encoded universe and
    actor axis."""
    try:
        actor, pos = wire._get_varint(body, 0)
        e, pos = wire._get_varint(body, pos)
        if e != num_elements:
            raise ProtocolError(f"element-universe mismatch: peer E={e}, "
                                f"ours E={num_elements}")
        vv, pos = wire._decode_vv_py(body, pos, num_actors)
    except ValueError as err:  # wire-layer section mismatch / malformed
        raise ProtocolError(str(err)) from err
    if pos != len(body):
        raise ProtocolError("trailing bytes after HELLO")
    if actor >= num_actors:
        raise ProtocolError(f"peer actor {actor} outside actor axis "
                            f"{num_actors}")
    return actor, vv


def encode_payload_msg(mode: int, src_actor: int, processed: np.ndarray,
                       payload) -> bytes:
    out = bytearray()
    out.append(mode)
    wire._put_varint(out, src_actor)
    head = (bytes(out)
            + wire._encode_vv_py(np.asarray(processed, np.uint32)))
    if mode == MODE_DIGEST:
        # digest-sync lane payloads are sparse by construction (only
        # mismatched groups' lanes): index-encode them — the dense
        # section bitmasks would reintroduce the O(E) floor the digest
        # exchange exists to beat
        return head + wire.encode_payload_lanes(
            payload, int(payload.changed.shape[-1]))
    return head + wire.encode_payload(payload)


def encode_delta_wal_record(pre_vv: np.ndarray, src_actor: int, payload,
                            compact=None, *, compact_records: bool = True
                            ) -> Tuple[bytes, bool]:
    """THE WAL record-form policy for one δ (serve-path throughput
    ladder): choose and encode the record body, returning
    ``(body, is_compact)``.  One implementation serves every producer
    — ``net/peer.Node``'s batch and local-op loggers and the
    ``bench.py --ingest`` ladder — so the committed bench artifact can
    never measure a policy the server no longer runs.

    Selection ladder (DESIGN.md §16): the fixed-K on-device form when
    ``compact`` (an ``ops/compact.CompactDeltaPayload``, already pulled
    to the host: ``Node._append_delta_record`` times that pull) is
    given and did not overflow → host-side compaction of the dense ``payload``
    while under the break-even (~3 bytes of index varints per claimed
    lane vs the dense record's two E/8-byte section bitmasks) → the
    legacy dense record (guard-vv || PAYLOAD body).  Nothing is ever
    dropped; ``compact_records=False`` forces the dense form (the
    seed-comparison mode).

    DELETION-LOG FILTERING (every form, DESIGN.md §16): the δ's
    deleted section carries the WHOLE un-resurrected deletion log
    (``delta_extract`` ships records regardless of the receiver's
    clock — reference wire semantics), so without filtering every
    record costs O(changed + deletion log).  For a WAL record the
    replay GUARD gives the exact filter: a deletion dot ``(a, c)``
    with ``c <= pre_vv[a]`` predates this record's ops, so the record
    that INTRODUCED it — the local delete whose dot outran its own
    pre-vv, or the applied peer payload logged dense as-received —
    sits earlier in checkpoint ⊔ log and replays first (the prefix
    rule preserves the order; a guard-refused suffix resets the log
    whole).  Only deletions the record's own window produced survive
    the filter, making records O(changed) outright.  Replay-compat
    pinned in tests/test_durability.py.

    Recovery-model note: after a guard-refused replay RESETS the log
    (restore_durable), the applied prefix lives only in state until
    the next checkpoint — changed lanes have ALWAYS ridden that
    window (later records compress them away against pre-vv; the
    persisted resync epoch + anti-entropy is the documented heal),
    and filtered deletion records now ride the same one instead of
    being accidentally re-carried by every later record."""
    pre_vv = np.asarray(pre_vv, np.uint32)
    num_elements = int(payload.changed.shape[-1])

    def fresh_mask(da: np.ndarray, dc: np.ndarray) -> np.ndarray:
        # NOT covered by the guard: introduced by this record's window
        return dc > np.take(pre_vv, da.astype(np.int64), mode="clip")

    if compact_records:
        if compact is not None and not bool(compact.overflow):
            chv = compact.ch_valid
            dlv = compact.del_valid & np.asarray(
                fresh_mask(compact.del_da, compact.del_dc))
            return wire.encode_compact_wal_body(
                pre_vv, src_actor, compact.src_processed,
                compact.src_vv,
                compact.ch_idx[chv],
                compact.ch_da[chv],
                compact.ch_dc[chv],
                compact.del_idx[dlv],
                compact.del_da[dlv],
                compact.del_dc[dlv], num_elements), True
        changed = np.asarray(payload.changed)
        del_da = np.asarray(payload.del_da)
        del_dc = np.asarray(payload.del_dc)
        deleted = np.asarray(payload.deleted) & fresh_mask(del_da,
                                                           del_dc)
        # break-even on the FILTERED lane count: an old deletion log
        # must not push a small record into the dense form
        lanes = int(changed.sum()) + int(deleted.sum())
        if lanes * 3 <= max(16, num_elements // 4):
            ch = np.nonzero(changed)[0]
            dl = np.nonzero(deleted)[0]
            return wire.encode_compact_wal_body(
                pre_vv, src_actor, np.asarray(payload.src_processed),
                np.asarray(payload.src_vv),
                ch, np.asarray(payload.ch_da)[ch],
                np.asarray(payload.ch_dc)[ch],
                dl, del_da[dl], del_dc[dl], num_elements), True
    # dense fallback: the deletion filter applies here too (the form
    # is an encoding, the record contract is the same)
    del_da = np.asarray(payload.del_da)
    del_dc = np.asarray(payload.del_dc)
    deleted = np.asarray(payload.deleted) & fresh_mask(del_da, del_dc)
    # host numpy throughout: the encoder np.asarray's every field, so
    # bouncing the filtered arrays through the device buys nothing
    filtered = payload._replace(
        deleted=deleted,
        del_da=np.where(deleted, del_da, np.uint32(0)),
        del_dc=np.where(deleted, del_dc, np.uint32(0)))
    body = encode_payload_msg(
        MODE_DELTA, src_actor, np.asarray(payload.src_processed),
        filtered)
    return wire._encode_vv_py(pre_vv) + body, False


def decode_payload_msg(body: bytes, num_elements: int, num_actors: int):
    """Returns (mode, DeltaPayload) with src_actor and src_processed
    rehydrated from the out-of-band fields."""
    if not body:
        raise ProtocolError("empty PAYLOAD body")
    mode = body[0]
    if mode not in (MODE_DELTA, MODE_FULL, MODE_SLICE, MODE_DIGEST):
        raise ProtocolError(f"unknown payload mode {mode}")
    try:
        src_actor, pos = wire._get_varint(body, 1)
        if src_actor >= num_actors:
            raise ProtocolError(f"payload src_actor {src_actor} outside "
                                f"actor axis {num_actors}")
        processed, pos = wire._decode_vv_py(body, pos, num_actors)
        decode = (wire.decode_payload_lanes if mode == MODE_DIGEST
                  else wire.decode_payload)
        payload = decode(body[pos:], num_elements, num_actors,
                         src_actor=src_actor)
    except ValueError as err:  # wire-layer section mismatch / malformed
        raise ProtocolError(str(err)) from err
    import jax.numpy as jnp

    return mode, payload._replace(src_processed=jnp.asarray(processed))
