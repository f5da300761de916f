"""δ-payload wire format: dense masked tensors <-> compact bytes.

On device a δ payload is dense masked tensors (ops/delta.DeltaPayload —
the TPU-friendly form of ``MakeDeltaMergeData``'s compacted maps,
awset-delta_test.go:79-105).  Off device — DCN shipping between hosts,
persistence, or feeding a non-TPU peer — the payload serializes to a
compact row format:

  changed-section || deleted-section || vv-section

where each masked section is ``varint E, varint n_set, bitmask,
(varint dot_actor, varint dot_counter) per set lane`` and the vv
section is ``varint A, varint counter * A``.  Sparse payloads shrink
toward ~E/8 bytes + a few bytes per actually-changed lane — the wire
realization of the reference's "ship only what the receiver hasn't
seen" compression.

Implementations: the C++ codec (native/codec.cpp, via ctypes) when a
toolchain is available, else the pure-Python/numpy twin below.  Both
produce byte-identical output (tests/test_native_codec.py).
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from go_crdt_playground_tpu import native
from go_crdt_playground_tpu.ops.delta import DeltaPayload

# ---------------------------------------------------------------------------
# Pure-Python primitives (byte-identical to native/codec.cpp)
# ---------------------------------------------------------------------------


def _put_varint(out: bytearray, v: int) -> None:
    while True:
        if v < 0x80:
            out.append(v)
            return
        out.append((v & 0x7F) | 0x80)
        v >>= 7


def _get_varint(buf: bytes, pos: int) -> Tuple[int, int]:
    out = 0
    shift = 0
    while True:
        if pos >= len(buf) or shift > 63:
            raise ValueError("malformed varint")
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, pos
        shift += 7


def _encode_masked_py(mask: np.ndarray, da: np.ndarray,
                      dc: np.ndarray) -> bytes:
    e = mask.shape[0]
    out = bytearray()
    _put_varint(out, e)
    _put_varint(out, int(mask.sum()))
    out.extend(np.packbits(mask, bitorder="little").tobytes())
    for i in np.nonzero(mask)[0]:
        _put_varint(out, int(da[i]))
        _put_varint(out, int(dc[i]))
    return bytes(out)


def _decode_masked_py(buf: bytes, pos: int, e: int):
    enc_e, pos = _get_varint(buf, pos)
    if enc_e != e:
        raise ValueError(f"universe mismatch: encoded {enc_e}, expected {e}")
    n_set, pos = _get_varint(buf, pos)
    nbytes = (e + 7) // 8
    bits = np.frombuffer(buf[pos:pos + nbytes], np.uint8)
    if bits.size != nbytes:
        raise ValueError("truncated bitmask")
    pos += nbytes
    mask = np.unpackbits(bits, count=e, bitorder="little").astype(bool)
    if int(mask.sum()) != n_set:
        raise ValueError("bitmask popcount mismatch")
    da = np.zeros(e, np.uint32)
    dc = np.zeros(e, np.uint32)
    for i in np.nonzero(mask)[0]:
        a, pos = _get_varint(buf, pos)
        c, pos = _get_varint(buf, pos)
        if a > 0xFFFFFFFF or c > 0xFFFFFFFF:
            raise ValueError("dot component out of uint32 range")
        da[i], dc[i] = a, c
    return mask, da, dc, pos


def _encode_vv_py(vv: np.ndarray) -> bytes:
    out = bytearray()
    _put_varint(out, vv.shape[0])
    for c in vv:
        _put_varint(out, int(c))
    return bytes(out)


def _decode_vv_py(buf: bytes, pos: int, a: int):
    enc_a, pos = _get_varint(buf, pos)
    if enc_a != a:
        raise ValueError(f"actor-axis mismatch: encoded {enc_a}, expected {a}")
    vv = np.zeros(a, np.uint32)
    for i in range(a):
        v, pos = _get_varint(buf, pos)
        if v > 0xFFFFFFFF:
            raise ValueError("counter out of uint32 range")
        vv[i] = v
    return vv, pos


# ---------------------------------------------------------------------------
# Native-backed primitives
# ---------------------------------------------------------------------------


def _encode_masked_native(lib, mask, da, dc) -> bytes:
    import ctypes

    e = mask.shape[0]
    cap = int(lib.delta_encode_bound(e))
    out = (ctypes.c_uint8 * cap)()
    m = np.ascontiguousarray(mask, np.uint8)
    a = np.ascontiguousarray(da, np.uint32)
    c = np.ascontiguousarray(dc, np.uint32)
    n = lib.delta_encode(
        m.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        c.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        e, out, cap)
    if n < 0:
        raise ValueError("native delta_encode failed")
    return bytes(out[:n])


def _decode_masked_native(lib, buf: bytes, pos: int, e: int):
    import ctypes

    mask = np.zeros(e, np.uint8)
    da = np.zeros(e, np.uint32)
    dc = np.zeros(e, np.uint32)
    raw = np.frombuffer(buf, np.uint8)[pos:]
    raw = np.ascontiguousarray(raw)
    n = lib.delta_decode(
        raw.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), raw.size, e,
        mask.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        da.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        dc.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)))
    if n < 0:
        raise ValueError("malformed delta section")
    return mask.astype(bool), da, dc, pos + int(n)


def _encode_vv_native(lib, vv) -> bytes:
    import ctypes

    a = vv.shape[0]
    cap = int(lib.vv_encode_bound(a))
    out = (ctypes.c_uint8 * cap)()
    v = np.ascontiguousarray(vv, np.uint32)
    n = lib.vv_encode(
        v.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)), a, out, cap)
    if n < 0:
        raise ValueError("native vv_encode failed")
    return bytes(out[:n])


def _decode_vv_native(lib, buf: bytes, pos: int, a: int):
    import ctypes

    vv = np.zeros(a, np.uint32)
    raw = np.ascontiguousarray(np.frombuffer(buf, np.uint8)[pos:])
    n = lib.vv_decode(
        raw.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), raw.size, a,
        vv.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)))
    if n < 0:
        raise ValueError("malformed vv section")
    return vv, pos + int(n)


# ---------------------------------------------------------------------------
# Payload-level API
# ---------------------------------------------------------------------------


def encode_payload(p: DeltaPayload, prefer_native: bool = True) -> bytes:
    """Serialize one replica's δ payload (single-replica slices, shapes
    [E]/[A]) to the compact wire form."""
    changed = np.asarray(p.changed, bool)
    deleted = np.asarray(p.deleted, bool)
    ch_da, ch_dc = np.asarray(p.ch_da), np.asarray(p.ch_dc)
    del_da, del_dc = np.asarray(p.del_da), np.asarray(p.del_dc)
    vv = np.asarray(p.src_vv)
    lib = native.load() if prefer_native else None
    if lib is not None:
        return (_encode_masked_native(lib, changed, ch_da, ch_dc)
                + _encode_masked_native(lib, deleted, del_da, del_dc)
                + _encode_vv_native(lib, vv))
    return (_encode_masked_py(changed, ch_da, ch_dc)
            + _encode_masked_py(deleted, del_da, del_dc)
            + _encode_vv_py(vv))


def decode_payload(buf: bytes, num_elements: int, num_actors: int,
                   src_actor: int = 0,
                   prefer_native: bool = True) -> DeltaPayload:
    """Inverse of encode_payload.  ``src_processed`` is not shipped (it
    is v2 *local* bookkeeping, not part of the reference's payload) and
    comes back zeroed; ``src_actor`` likewise rides out-of-band."""
    lib = native.load() if prefer_native else None
    if lib is not None:
        changed, ch_da, ch_dc, pos = _decode_masked_native(
            lib, buf, 0, num_elements)
        deleted, del_da, del_dc, pos = _decode_masked_native(
            lib, buf, pos, num_elements)
        vv, pos = _decode_vv_native(lib, buf, pos, num_actors)
    else:
        changed, ch_da, ch_dc, pos = _decode_masked_py(buf, 0, num_elements)
        deleted, del_da, del_dc, pos = _decode_masked_py(
            buf, pos, num_elements)
        vv, pos = _decode_vv_py(buf, pos, num_actors)
    if pos != len(buf):
        raise ValueError(f"{len(buf) - pos} trailing bytes after payload")
    import jax.numpy as jnp

    return DeltaPayload(
        src_vv=jnp.asarray(vv),
        changed=jnp.asarray(changed),
        ch_da=jnp.asarray(ch_da),
        ch_dc=jnp.asarray(ch_dc),
        deleted=jnp.asarray(deleted),
        del_da=jnp.asarray(del_da),
        del_dc=jnp.asarray(del_dc),
        src_actor=jnp.uint32(src_actor),
        src_processed=jnp.zeros(num_actors, jnp.uint32),
    )


def payload_nbytes_wire(p: DeltaPayload) -> int:
    """Wire size of a payload — the honest δ-payload-bytes metric
    (BASELINE.json north-star metrics) as shipped, vs nbytes_dense for the
    on-device dense form."""
    return len(encode_payload(p))


# ---------------------------------------------------------------------------
# Compact WAL record bodies (serve-path throughput ladder, DESIGN.md §16)
# ---------------------------------------------------------------------------
#
# The dense WAL record (net/peer.Node: guard-vv || PAYLOAD frame body)
# costs O(E) bytes per fsync — two E/8-byte section bitmasks — even when
# a micro-batch touched a handful of lanes.  The compact record is the
# same δ in index form: only the claimed lanes cross the fsync, so
# bytes-per-batch is O(changed), the reference's map-shaped
# ``MakeDeltaMergeData`` bandwidth restored on disk (the ops/compact.py
# treatment applied to the WAL).
#
# Version tagging: a legacy dense record body begins with the guard
# vv's ``varint A`` and every real store has A >= 1, so a leading 0x00
# byte can never open a valid dense record.  Compact records exploit
# that: body = 0x00 | version | varint src_actor | guard-vv |
# processed-vv | src-vv | varint E | changed-lanes | deleted-lanes,
# each lane section ``varint n, n x (varint element, varint dot_actor,
# varint dot_counter)``.  E is embedded and checked like the dense
# form's masked sections: a store reopened at a different universe
# must FAIL decode (replay's bad-record prefix rule), never merge
# in-range lane ids onto the wrong lanes.  Old stores (all-dense) replay through the new
# reader unchanged; a mixed segment replays in order with the causal
# guard intact (tests/test_durability.py).  Overflowing deltas fall
# back to the dense record — never dropped.

WAL_COMPACT_TAG = 0x00
WAL_COMPACT_V1 = 1


def _put_lane_section(out: bytearray, idx, da, dc) -> None:
    _put_varint(out, len(idx))
    for i, a, c in zip(idx, da, dc):
        _put_varint(out, int(i))
        _put_varint(out, int(a))
        _put_varint(out, int(c))


def _get_lane_section(buf: bytes, pos: int, e: int):
    n, pos = _get_varint(buf, pos)
    if n > e:
        raise ValueError(f"lane section claims {n} lanes in universe {e}")
    mask = np.zeros(e, bool)
    da = np.zeros(e, np.uint32)
    dc = np.zeros(e, np.uint32)
    for _ in range(n):
        i, pos = _get_varint(buf, pos)
        a, pos = _get_varint(buf, pos)
        c, pos = _get_varint(buf, pos)
        if i >= e:
            raise ValueError(f"lane id {i} outside universe {e}")
        if a > 0xFFFFFFFF or c > 0xFFFFFFFF:
            raise ValueError("dot component out of uint32 range")
        mask[i], da[i], dc[i] = True, a, c
    return mask, da, dc, pos


def encode_compact_wal_body(guard_vv: np.ndarray, src_actor: int,
                            processed: np.ndarray, src_vv: np.ndarray,
                            ch_idx, ch_da, ch_dc, del_idx, del_da,
                            del_dc, num_elements: int) -> bytes:
    """One compact WAL record body.  ``*_idx``/``*_da``/``*_dc`` are
    1-D sequences of the claimed lanes only (already filtered to valid
    slots — the fixed-K ``compact_payload`` form's valid lanes, or a
    host-side ``np.nonzero`` of the dense masks); ``num_elements`` is
    the writer's universe, embedded for the decode-time dimension
    check."""
    out = bytearray((WAL_COMPACT_TAG, WAL_COMPACT_V1))
    _put_varint(out, int(src_actor))
    body = bytes(out)
    body += _encode_vv_py(np.asarray(guard_vv, np.uint32))
    body += _encode_vv_py(np.asarray(processed, np.uint32))
    body += _encode_vv_py(np.asarray(src_vv, np.uint32))
    tail = bytearray()
    _put_varint(tail, int(num_elements))
    _put_lane_section(tail, ch_idx, ch_da, ch_dc)
    _put_lane_section(tail, del_idx, del_da, del_dc)
    return body + tail


def decode_compact_wal_body(body: bytes, num_elements: int,
                            num_actors: int):
    """Inverse of ``encode_compact_wal_body``: returns ``(guard_vv,
    DeltaPayload)`` with the lane sections scattered back to the dense
    device form (exactly the payload the producing dispatch extracted,
    when it fit the record's lanes — which is the only case written).
    Raises ``ValueError`` on any structural problem, which replay
    treats like any other undecodable record (prefix rule)."""
    if len(body) < 2 or body[0] != WAL_COMPACT_TAG:
        raise ValueError("not a compact WAL record")
    if body[1] != WAL_COMPACT_V1:
        raise ValueError(f"unknown compact WAL record version {body[1]}")
    src_actor, pos = _get_varint(body, 2)
    if src_actor >= num_actors:
        raise ValueError(f"src_actor {src_actor} outside actor axis "
                         f"{num_actors}")
    guard, pos = _decode_vv_py(body, pos, num_actors)
    processed, pos = _decode_vv_py(body, pos, num_actors)
    src_vv, pos = _decode_vv_py(body, pos, num_actors)
    enc_e, pos = _get_varint(body, pos)
    if enc_e != num_elements:
        raise ValueError(f"universe mismatch: encoded {enc_e}, "
                         f"expected {num_elements}")
    changed, ch_da, ch_dc, pos = _get_lane_section(body, pos,
                                                   num_elements)
    deleted, del_da, del_dc, pos = _get_lane_section(body, pos,
                                                     num_elements)
    if pos != len(body):
        raise ValueError(f"{len(body) - pos} trailing bytes after "
                         "compact WAL record")
    import jax.numpy as jnp

    return guard, DeltaPayload(
        src_vv=jnp.asarray(src_vv),
        changed=jnp.asarray(changed),
        ch_da=jnp.asarray(ch_da),
        ch_dc=jnp.asarray(ch_dc),
        deleted=jnp.asarray(deleted),
        del_da=jnp.asarray(del_da),
        del_dc=jnp.asarray(del_dc),
        src_actor=jnp.uint32(src_actor),
        src_processed=jnp.asarray(processed),
    )


# ---------------------------------------------------------------------------
# Index-lane payload bodies (digest-driven anti-entropy, DESIGN.md §19)
# ---------------------------------------------------------------------------
#
# A digest-sync round ships only the lanes of digest-MISMATCHED groups
# (net/digestsync.py).  The dense payload encoding above always pays two
# E/8-byte section bitmasks — exactly the O(E) floor the digest exchange
# exists to beat — so MODE_DIGEST payload bodies use the index-lane form
# the compact WAL records pioneered: O(claimed lanes) bytes, with the
# writer's universe embedded and checked so a mis-dimensioned peer fails
# decode instead of scattering in-range lane ids onto wrong lanes.


def encode_payload_lanes(p: DeltaPayload, num_elements: int) -> bytes:
    """Index-lane wire form of a sparse payload: ``varint E |
    vv-section(src_vv) | changed lane-section | deleted lane-section``
    (lane sections as in the compact WAL body: ``varint n, n x (varint
    element, varint dot_actor, varint dot_counter)``).  ``src_processed``
    and ``src_actor`` ride out-of-band like encode_payload's."""
    changed = np.asarray(p.changed, bool)
    deleted = np.asarray(p.deleted, bool)
    out = bytearray()
    _put_varint(out, num_elements)
    body = bytes(out) + _encode_vv_py(np.asarray(p.src_vv, np.uint32))
    tail = bytearray()
    ch = np.nonzero(changed)[0]
    _put_lane_section(tail, ch, np.asarray(p.ch_da)[ch],
                      np.asarray(p.ch_dc)[ch])
    dl = np.nonzero(deleted)[0]
    _put_lane_section(tail, dl, np.asarray(p.del_da)[dl],
                      np.asarray(p.del_dc)[dl])
    return body + bytes(tail)


def decode_payload_lanes(buf: bytes, num_elements: int, num_actors: int,
                         src_actor: int = 0) -> DeltaPayload:
    """Inverse of encode_payload_lanes: lane sections scattered back to
    the dense device form.  Raises ``ValueError`` on any structural
    problem (dimension change, trailing bytes) — callers map it to their
    dialect's protocol error like decode_payload's."""
    enc_e, pos = _get_varint(buf, 0)
    if enc_e != num_elements:
        raise ValueError(f"universe mismatch: encoded {enc_e}, "
                         f"expected {num_elements}")
    src_vv, pos = _decode_vv_py(buf, pos, num_actors)
    changed, ch_da, ch_dc, pos = _get_lane_section(buf, pos,
                                                   num_elements)
    deleted, del_da, del_dc, pos = _get_lane_section(buf, pos,
                                                     num_elements)
    if pos != len(buf):
        raise ValueError(f"{len(buf) - pos} trailing bytes after lane "
                         "payload")
    import jax.numpy as jnp

    return DeltaPayload(
        src_vv=jnp.asarray(src_vv),
        changed=jnp.asarray(changed),
        ch_da=jnp.asarray(ch_da),
        ch_dc=jnp.asarray(ch_dc),
        deleted=jnp.asarray(deleted),
        del_da=jnp.asarray(del_da),
        del_dc=jnp.asarray(del_dc),
        src_actor=jnp.uint32(src_actor),
        src_processed=jnp.zeros(num_actors, jnp.uint32),
    )
