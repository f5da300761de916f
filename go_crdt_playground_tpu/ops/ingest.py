"""Packed micro-batch op-apply: B client op-rows in one compiled dispatch.

The serve frontend (serve/batcher.py) coalesces pending client requests
into one packed ``(batch, elems)`` tensor pair — ``add_rows[b]`` is the
key set of request b's ``Add(k...)`` call, ``del_rows[b]`` of its
``Del(k...)`` call — and applies the whole micro-batch to a single
replica slice with ONE dispatch of ``ingest_rows``.  Per row the algebra
is exactly the fused branch-free lane algebra of the host-driven ops
(models/awset_delta.add_elements / del_elements); ``lax.scan`` threads
the rows because ops against one replica serialize on its clock — the
batch saves dispatches and (through ``Node.ingest_batch``) WAL fsyncs,
never reorders semantics.

Semantics pinned to the reference (awset.go:89-101, awset-delta_test.go:
14-33), with the batching-specific deltas called out:

* an Add row ticks the clock once per touched key; dots are assigned in
  ASCENDING ELEMENT ORDER (the selector form has no call-site argument
  order — callers that care about intra-request dot order must sort,
  which the wire protocol's set-of-keys framing already implies);
* a Del row ticks the clock ONCE iff the row selects at least one key
  (reference δ-Del ticks even when nothing selected is present; an
  all-empty row here is a padding lane and must not tick) and stamps
  every actually-present selected key with that one shared deletion dot;
* ``live[b] = False`` masks row b entirely (bucketing padding), so one
  compiled program serves every batch occupancy.

The resulting state is bitwise-identical to applying the same requests
through ``add_elements``/``del_elements`` one dispatch each (pinned by
tests/test_serve.py); dissemination of the batch's δ rides the existing
kernel path (``ops/delta.delta_extract`` via ``Node._log_local_delta``
and the anti-entropy exchange).

Fused ingest+δ (the serve-path throughput ladder, DESIGN.md §16):
``ingest_rows_delta`` returns the merged state AND the batch's δ vs the
PRE-batch vv — the exact payload ``Node.ingest_batch`` used to compute
with a second ``delta_extract`` dispatch for its WAL record — in ONE
compiled program, plus the δ's fixed-K compact form (ops/compact.py) so
the host pulls O(changed) lanes for the WAL record instead of the dense
O(E) masks.  ``ops/pallas_ingest.py`` is the Pallas twin of the same
contract (bitwise-pinned by tests/test_ingest_fused.py).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from go_crdt_playground_tpu.models.awset_delta import AWSetDeltaState


# fixed-K capacity of the fused path's on-device δ compaction: batches
# whose δ claims more lanes fall back to the dense WAL record — never
# dropped (net/peer.Node and bench.py both select through
# ingest_delta_regime, so there is exactly one policy)
WAL_COMPACT_K = 128


def ingest_delta_regime(num_elements: int):
    """THE backend regime for the fused ingest+δ path: returns
    ``(fused_fn, k)`` — the Pallas twin with the fixed-K on-device
    compaction on TPU backends (the compaction shrinks the
    device→host pull), the XLA fused path with ``k=0`` (host-side
    compaction from the dense payload) everywhere else.  One selection
    serves ``Node.ingest_batch`` and ``bench.py --ingest``: the bench
    cannot drift into measuring a path the server no longer runs."""
    import jax

    if jax.default_backend() == "tpu":
        from go_crdt_playground_tpu.ops.pallas_ingest import \
            pallas_ingest_rows_delta

        return pallas_ingest_rows_delta, min(WAL_COMPACT_K, num_elements)
    return ingest_rows_delta, 0


def _apply_add_row(st: AWSetDeltaState, row: jnp.ndarray) -> AWSetDeltaState:
    """One Add(k...) op-row on a single-replica slice.  row: bool[E]."""
    a = st.actor.astype(jnp.int32)
    base = st.vv[a]
    # 1-based dot position per touched lane, ascending element order
    pos1 = jnp.cumsum(row.astype(jnp.uint32)) * row
    k = jnp.max(pos1)
    new_vv = base + k
    return st._replace(
        vv=st.vv.at[a].set(new_vv),
        present=st.present | row,
        dot_actor=jnp.where(row, st.actor, st.dot_actor),
        dot_counter=jnp.where(row, base + pos1, st.dot_counter),
        processed=st.processed.at[a].set(new_vv),
    )


def _apply_del_row(st: AWSetDeltaState, row: jnp.ndarray) -> AWSetDeltaState:
    """One Del(k...) op-row on a single-replica slice.  row: bool[E]."""
    a = st.actor.astype(jnp.int32)
    tick = jnp.any(row).astype(jnp.uint32)
    new_counter = st.vv[a] + tick
    hit = row & st.present
    return st._replace(
        vv=st.vv.at[a].set(new_counter),
        present=st.present & ~hit,
        dot_actor=jnp.where(hit, 0, st.dot_actor),
        dot_counter=jnp.where(hit, 0, st.dot_counter),
        deleted=st.deleted | hit,
        del_dot_actor=jnp.where(hit, st.actor, st.del_dot_actor),
        del_dot_counter=jnp.where(hit, new_counter, st.del_dot_counter),
        processed=st.processed.at[a].set(new_counter),
    )


@jax.jit
def ingest_rows(state: AWSetDeltaState, add_rows: jnp.ndarray,
                del_rows: jnp.ndarray,
                live: jnp.ndarray) -> AWSetDeltaState:
    """Apply B op-rows to ONE replica slice in a single compiled program.

    state: single-replica AWSetDeltaState slice (vv[A], present[E], ...).
    add_rows / del_rows: bool[B, E]; live: bool[B] (padding mask).  Rows
    apply in order b=0..B-1 (adds before dels within a row); the batcher
    keeps B static so every occupancy reuses one compiled program.
    """

    def step(st, x):
        add_row, del_row, is_live = x
        st = _apply_add_row(st, add_row & is_live)
        st = _apply_del_row(st, del_row & is_live)
        return st, None

    out, _ = jax.lax.scan(step, state, (add_rows, del_rows, live))
    return out


@functools.partial(jax.jit, static_argnames=("k_changed", "k_deleted"))
def ingest_rows_delta(state: AWSetDeltaState, add_rows: jnp.ndarray,
                      del_rows: jnp.ndarray, live: jnp.ndarray,
                      k_changed: int, k_deleted: int) -> Tuple:
    """Fused ingest+δ: one dispatch returning ``(merged, payload,
    compact)`` — the merged single-replica slice, the batch δ vs the
    PRE-batch vv (``delta_extract(merged, pre_vv)``, bitwise what the
    two-pass path computed in its second dispatch), and the δ routed
    through ``ops/compact.py``'s fixed-K lanes (``compact.overflow``
    set when the δ doesn't fit — callers fall back to the dense
    payload, never drop).

    The δ is extracted against the pre-batch vv, so it contains the
    batch's own effects PLUS any pre-existing lanes whose dots the
    pre-batch vv did not cover (the compact-overflow gossip path can
    leave those behind); that is exactly what
    ``Node._log_local_delta`` always logged, preserved here bitwise.

    ``k_changed == 0`` (or ``k_deleted == 0``) skips the on-device
    compaction and returns ``compact=None``: the fixed-K form exists to
    shrink the device→host pull, which costs nothing on a CPU backend
    — there the caller compacts host-side from the dense payload
    (``Node._append_delta_record``), and the on-device compaction (an
    E-long running count and k binary searches per section) would only
    add work to the batch.
    """
    from go_crdt_playground_tpu.ops import compact as compact_ops
    from go_crdt_playground_tpu.ops import delta as delta_ops

    pre_vv = state.vv
    merged = ingest_rows(state, add_rows, del_rows, live)
    payload = delta_ops.delta_extract(merged, pre_vv)
    if k_changed == 0 or k_deleted == 0:
        return merged, payload, None
    compact = compact_ops.compact_payload(payload, k_changed, k_deleted)
    return merged, payload, compact
