"""Fixed-K compact δ payloads: bandwidth-shaped gossip for sparse deltas.

The reference's ``MakeDeltaMergeData`` ships two *maps* whose size is the
number of changed/deleted keys, not the universe (awset-delta_test.go:
79-105).  The dense tensor payload (ops/delta.DeltaPayload) loses that:
its wire cost is O(E) regardless of sparsity.  This module restores the
reference's bandwidth shape under XLA's static-shape rules: a payload is
compacted to fixed-capacity index/value lanes (``K`` slots), which is
what actually crosses ICI in the compact ring round
(parallel/gossip.compact_ring_round_shardmap) — O(K) bytes instead of
O(E).

Overflow policy: when more than K lanes changed, the surplus lanes are
left out of this round's payload and ``overflow`` is set.  Dropping
lanes is SAFE — an anti-entropy exchange is idempotent and monotone, so
a truncated payload is just a smaller exchange; the missing lanes ship
on a later round once the receiver's VV (which did NOT advance past
them — truncation also drops their dots from nothing, and VV join uses
the sender's full VV...) — see the correctness note below.

CORRECTNESS NOTE (why truncation must also mask the VV join): applying
the sender's full VV while withholding changed lanes would let the
receiver's clock cover adds it never saw — phase-1 ``HasDot`` would then
treat the missing adds as already-deleted on a later exchange
(awset.go:133-135), dropping them permanently.  So on overflow the
compact payload carries the sender VV only for CLAIMED lanes to stay
below: ``src_vv`` is replaced by the receiver-safe join input
``where(overflow, receiver_vv_advancing_nothing, src_vv)`` — i.e. the
whole exchange degrades to "partial data, no clock advance", which is
exactly a lossy network round (SURVEY §5.3) and converges by retry.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from go_crdt_playground_tpu.ops.delta import DeltaPayload


class CompactDeltaPayload(NamedTuple):
    """One replica pair's δ payload in fixed-K index form (vmap-batched).

    ``*_idx`` are element ids for the claimed lanes, valid where
    ``*_valid``; capacity (K_changed, K_deleted) is static.  ``src_vv``
    here is already the RECEIVER-SAFE join input (see module docstring):
    equal to the sender's VV on complete payloads, and neutralized to
    zeros on overflow so a truncated exchange cannot advance the
    receiver's clock past unshipped adds.
    """

    src_vv: jnp.ndarray         # uint32[A]
    ch_idx: jnp.ndarray         # uint32[Kc]
    ch_valid: jnp.ndarray       # bool[Kc]
    ch_da: jnp.ndarray          # uint32[Kc]
    ch_dc: jnp.ndarray          # uint32[Kc]
    del_idx: jnp.ndarray        # uint32[Kd]
    del_valid: jnp.ndarray      # bool[Kd]
    del_da: jnp.ndarray         # uint32[Kd]
    del_dc: jnp.ndarray         # uint32[Kd]
    overflow: jnp.ndarray       # bool[]  (either section truncated)
    src_actor: jnp.ndarray      # uint32[]
    src_processed: jnp.ndarray  # uint32[A]

    def nbytes_wire(self) -> int:
        """Dense device bytes of the compact form — the ICI payload cost
        of one exchange (compare DeltaPayload.nbytes_dense: O(E))."""
        return sum(x.size * x.dtype.itemsize for x in self)


def _compact_section(mask: jnp.ndarray, idx_dtype, k: int, *values):
    """Pack the lanes where ``mask`` into the first ``count`` of k slots
    (stable, ascending element id).  Returns (idx, valid, packed_values,
    overflowed).

    Slot j holds the (j+1)-th set lane: the first lane whose inclusive
    running count of ``mask`` reaches j + 1, found by a binary search
    (about log2(E) steps of k gathers).  The work is one E-long running
    count plus O(k log E) gathers, so it reads the E lanes once rather
    than once per output lane as a scatter into k slots would."""
    E = mask.shape[-1]
    csum = jnp.cumsum(mask, dtype=jnp.int32)        # inclusive
    count = csum[-1]
    src = jnp.searchsorted(csum, jnp.arange(1, k + 1, dtype=jnp.int32),
                           side="left")             # E where j >= count
    valid = jnp.arange(k) < count
    idx = jnp.where(valid, src, 0).astype(idx_dtype)
    take = jnp.minimum(src, E - 1)
    packed = tuple(jnp.where(valid, v[take], jnp.zeros((), v.dtype))
                   for v in values)
    overflowed = count > k
    return idx, valid, packed, overflowed


def compact_payload(p: DeltaPayload, k_changed: int,
                    k_deleted: int) -> CompactDeltaPayload:
    """Dense payload (single replica slice, [E] fields) -> fixed-K form."""
    ch_idx, ch_valid, (ch_da, ch_dc), ch_over = _compact_section(
        p.changed, jnp.uint32, k_changed, p.ch_da, p.ch_dc)
    del_idx, del_valid, (del_da, del_dc), del_over = _compact_section(
        p.deleted, jnp.uint32, k_deleted, p.del_da, p.del_dc)
    overflow = ch_over | del_over
    # Receiver-safe VV (module docstring): neutralize the clock advance
    # whenever any lane was truncated.
    safe_vv = jnp.where(overflow, jnp.zeros_like(p.src_vv), p.src_vv)
    return CompactDeltaPayload(
        src_vv=safe_vv,
        ch_idx=ch_idx, ch_valid=ch_valid, ch_da=ch_da, ch_dc=ch_dc,
        del_idx=del_idx, del_valid=del_valid, del_da=del_da,
        del_dc=del_dc, overflow=overflow,
        src_actor=p.src_actor,
        src_processed=jnp.where(overflow,
                                jnp.zeros_like(p.src_processed),
                                p.src_processed),
    )


def expand_payload(c: CompactDeltaPayload,
                   num_elements: int) -> DeltaPayload:
    """Fixed-K form -> dense payload (inverse of compact_payload on
    payloads that fit; the truncated-lane subset otherwise)."""
    E = num_elements

    def scatter(idx, valid, vals, dtype):
        dest = jnp.where(valid, idx, E).astype(jnp.int32)
        return jnp.zeros((E,), dtype).at[dest].set(vals, mode="drop")

    changed = scatter(c.ch_idx, c.ch_valid, c.ch_valid, bool)
    deleted = scatter(c.del_idx, c.del_valid, c.del_valid, bool)
    return DeltaPayload(
        src_vv=c.src_vv,
        changed=changed,
        ch_da=scatter(c.ch_idx, c.ch_valid, c.ch_da, jnp.uint32),
        ch_dc=scatter(c.ch_idx, c.ch_valid, c.ch_dc, jnp.uint32),
        deleted=deleted,
        del_da=scatter(c.del_idx, c.del_valid, c.del_da, jnp.uint32),
        del_dc=scatter(c.del_idx, c.del_valid, c.del_dc, jnp.uint32),
        src_actor=c.src_actor,
        src_processed=c.src_processed,
    )


compact_payload_batch = jax.vmap(compact_payload,
                                 in_axes=(0, None, None))
expand_payload_batch = jax.vmap(expand_payload, in_axes=(0, None))
