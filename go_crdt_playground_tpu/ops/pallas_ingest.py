"""Fused Pallas TPU kernel for the serve ingest path: batch apply + δ.

``ops/ingest.ingest_rows`` applies one packed ``(B, E)`` micro-batch
with a ``lax.scan`` over rows; ``Node.ingest_batch`` then used to pay a
SECOND dispatch (``ops/delta.delta_extract``) to build the WAL record's
δ.  On the XLA path the scan materializes the full E-lane state B times
per batch; here the whole batch folds over each element block IN VMEM —
state streams HBM→VMEM once, all B rows apply to the resident block,
and the δ-vs-pre-batch-vv extraction reads the final lanes while they
are still on chip (the ``ops/pallas_delta.py`` treatment applied to the
ingest hot path).

The row algebra is sequential by semantics (ops/ingest.py docstring:
rows serialize on the replica clock), but its cross-row data
dependencies are only SCALAR: each row's dot counters depend on the
popcounts/ticks of earlier rows, never on their lane effects, except
through the present bit itself.  So the kernel receives the per-row
counter bases precomputed by cheap XLA prefix sums ([B]-shaped) plus
the per-lane add-dot counters ([B, E], ``add_base[b] + row prefix``),
and the in-kernel fold is a pure per-lane state machine:

    for b in 0..B:  present |= add_row; dots := add dots
                    hit = del_row & present; clear hits; log deletion

The A-shaped outputs (vv, processed) are closed-form (the batch ticks
one actor's counter) and computed in XLA around the kernel — the whole
thing is ONE jitted dispatch, like the fused XLA path.

``pallas_ingest_rows_delta`` is bitwise-pinned to
``ops/ingest.ingest_rows_delta`` (tests/test_ingest_fused.py) across
occupancies, padding rows, and the empty batch; off-TPU it runs in
interpret mode, and shapes the kernel cannot take (an empty batch
axis) fall back to the XLA fused path — the same
interpret-mode/XLA-fallback ladder as the merge and δ kernels.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from go_crdt_playground_tpu.models.awset_delta import AWSetDeltaState
from go_crdt_playground_tpu.ops.pallas_merge import (_LANE, _round_up,
                                                     gather_rows)

_SUB = 8  # sublanes of one vreg tile


def _ingest_kernel(actor_ref, vv_ref, p_ref, da_ref, dc_ref, d_ref,
                   dda_ref, ddc_ref, arow_ref, drow_ref, adddc_ref,
                   delctr_ref, po_ref, dao_ref, dco_ref, do_ref,
                   ddao_ref, ddco_ref, cho_ref, chdao_ref, chdco_ref,
                   dmo_ref, dlda_ref, dldc_ref):
    """One element block: fold all B rows over the resident lanes, then
    extract the block's δ sections vs the PRE-batch vv.  Masks ride as
    uint8 (select between i1 vectors doesn't lower on Mosaic)."""
    actor = actor_ref[0]              # uint32 scalar (SMEM)
    num_rows = arow_ref.shape[0]

    def body(b, carry):
        p, da, dc, d, dda, ddc = carry
        on = arow_ref[b] != 0                        # uint32 row -> mask
        adc = adddc_ref[b]
        p = jnp.where(on, jnp.uint8(1), p)
        da = jnp.where(on, actor, da)
        dc = jnp.where(on, adc, dc)
        hit = (drow_ref[b] != 0) & (p != 0)
        p = jnp.where(hit, jnp.uint8(0), p)
        da = jnp.where(hit, jnp.uint32(0), da)
        dc = jnp.where(hit, jnp.uint32(0), dc)
        d = jnp.where(hit, jnp.uint8(1), d)
        dda = jnp.where(hit, actor, dda)
        ddc = jnp.where(hit, delctr_ref[b], ddc)
        return p, da, dc, d, dda, ddc

    p, da, dc, d, dda, ddc = jax.lax.fori_loop(
        0, num_rows, body,
        (p_ref[...], da_ref[...], dc_ref[...], d_ref[...], dda_ref[...],
         ddc_ref[...]))
    po_ref[...] = p
    dao_ref[...] = da
    dco_ref[...] = dc
    do_ref[...] = d
    ddao_ref[...] = dda
    ddco_ref[...] = ddc

    # fused δ extraction vs the PRE-batch vv (ops/delta.delta_extract
    # on the merged lanes, while they are still in VMEM)
    covered = dc <= gather_rows(vv_ref[...], da)
    changed = (p != 0) & ~covered
    cho_ref[...] = changed.astype(jnp.uint8)
    chdao_ref[...] = jnp.where(changed, da, 0)
    chdco_ref[...] = jnp.where(changed, dc, 0)
    resurrected = (p != 0) & ((da != dda) | (dc > ddc))
    deleted_p = (d != 0) & ~resurrected
    dmo_ref[...] = deleted_p.astype(jnp.uint8)
    dlda_ref[...] = jnp.where(deleted_p, dda, 0)
    dldc_ref[...] = jnp.where(deleted_p, ddc, 0)


@functools.partial(jax.jit, static_argnames=("k_changed", "k_deleted",
                                             "block_e", "interpret"))
def _fused_ingest(state: AWSetDeltaState, add_rows, del_rows, live,
                  k_changed: int, k_deleted: int, block_e: int,
                  interpret: bool):
    from go_crdt_playground_tpu.ops import compact as compact_ops
    from go_crdt_playground_tpu.ops.delta import DeltaPayload

    num_b, num_e = add_rows.shape
    num_a = state.vv.shape[0]
    # The E lanes ride as _SUB sublanes x cols: Mosaic's lane gather
    # (gather_rows) only lowers on full (8, 128) tiles, and a 1-row
    # (1, E) layout is refused.  Lane e sits at (e // cols, e % cols),
    # so a plain reshape maps it back.
    e_pad = _round_up(num_e, _SUB * _LANE)
    cols = e_pad // _SUB
    a_pad = _round_up(num_a, _LANE)
    blk = min(_round_up(max(block_e // _SUB, 1), _LANE), cols)
    while cols % blk:
        blk -= _LANE

    a = state.actor.astype(jnp.int32)
    pre_vv = state.vv
    arow = (add_rows & live[:, None]).astype(jnp.uint32)
    drow = (del_rows & live[:, None]).astype(jnp.uint32)
    k = jnp.sum(arow, axis=1, dtype=jnp.uint32)        # adds per row
    t = jnp.max(drow, axis=1).astype(jnp.uint32)       # del tick per row
    steps = k + t
    c0 = pre_vv[a]
    add_base = c0 + jnp.cumsum(steps) - steps          # exclusive prefix
    del_ctr = add_base + steps                         # post-row counter
    add_dc = add_base[:, None] + jnp.cumsum(arow, axis=1, dtype=jnp.uint32)
    final = c0 + jnp.sum(steps, dtype=jnp.uint32)
    new_vv = pre_vv.at[a].set(final)
    new_processed = state.processed.at[a].set(final)

    def tile_rows(x):
        x = jnp.pad(x, ((0, 0), (0, e_pad - num_e)))
        return x.reshape(num_b, _SUB, cols)

    def tile_lane(x):
        x = x.astype(jnp.uint8) if x.dtype == jnp.bool_ else x
        return jnp.pad(x, (0, e_pad - num_e)).reshape(_SUB, cols)

    ins = [
        state.actor.astype(jnp.uint32).reshape(1),
        jnp.broadcast_to(jnp.pad(pre_vv, (0, a_pad - num_a)),
                         (_SUB, a_pad)),
        tile_lane(state.present),
        tile_lane(state.dot_actor),
        tile_lane(state.dot_counter),
        tile_lane(state.deleted),
        tile_lane(state.del_dot_actor),
        tile_lane(state.del_dot_counter),
        tile_rows(arow),
        tile_rows(drow),
        tile_rows(add_dc),
        del_ctr,
    ]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    a_blk = pl.BlockSpec((_SUB, a_pad), lambda j: (0, 0))
    e_blk = pl.BlockSpec((_SUB, blk), lambda j: (0, j))
    r_blk = pl.BlockSpec((num_b, _SUB, blk), lambda j: (0, 0, j))
    in_specs = [smem, a_blk, e_blk, e_blk, e_blk, e_blk, e_blk, e_blk,
                r_blk, r_blk, r_blk, smem]
    u8, u32 = jnp.uint8, jnp.uint32
    out_dts = [u8, u32, u32, u8, u32, u32, u8, u32, u32, u8, u32, u32]
    outs = pl.pallas_call(
        _ingest_kernel,
        grid=(cols // blk,),
        in_specs=in_specs,
        out_specs=[e_blk] * 12,
        out_shape=[jax.ShapeDtypeStruct((_SUB, cols), d) for d in out_dts],
        interpret=interpret,
    )(*ins)
    (p, da, dc, d, dda, ddc,
     ch, chda, chdc, dm, dlda, dldc) = (o.reshape(e_pad)[:num_e]
                                        for o in outs)

    merged = AWSetDeltaState(
        vv=new_vv, present=p != 0, dot_actor=da, dot_counter=dc,
        actor=state.actor, deleted=d != 0, del_dot_actor=dda,
        del_dot_counter=ddc, processed=new_processed)
    payload = DeltaPayload(
        src_vv=new_vv, changed=ch != 0, ch_da=chda, ch_dc=chdc,
        deleted=dm != 0, del_da=dlda, del_dc=dldc,
        src_actor=state.actor, src_processed=new_processed)
    if k_changed == 0 or k_deleted == 0:
        return merged, payload, None
    compact = compact_ops.compact_payload(payload, k_changed, k_deleted)
    return merged, payload, compact


def pallas_ingest_rows_delta(state: AWSetDeltaState, add_rows, del_rows,
                             live, *, k_changed: int, k_deleted: int,
                             block_e: int = 512,
                             interpret: bool | None = None) -> Tuple:
    """Drop-in bitwise twin of ``ops/ingest.ingest_rows_delta`` (the
    fused batch apply + δ + fixed-K compaction) with the batch fold and
    the δ extraction in one Pallas kernel.  Off-TPU it runs in
    interpret mode; an empty batch axis falls back to the XLA fused
    path (the scan handles length 0, the kernel block shapes cannot)."""
    from go_crdt_playground_tpu.ops import ingest as ingest_ops

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    add_rows = jnp.asarray(add_rows, bool)
    del_rows = jnp.asarray(del_rows, bool)
    live = jnp.asarray(live, bool)
    if add_rows.shape[0] == 0:
        return ingest_ops.ingest_rows_delta(
            state, add_rows, del_rows, live,
            k_changed=k_changed, k_deleted=k_deleted)
    return _fused_ingest(state, add_rows, del_rows, live,
                         k_changed=k_changed, k_deleted=k_deleted,
                         block_e=block_e, interpret=interpret)
