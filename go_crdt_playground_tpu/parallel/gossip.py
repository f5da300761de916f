"""Anti-entropy gossip: pairing schedules, merge rounds, fault injection,
convergence loops.

Reference analogue: a "message exchange" is ``dst.Merge(src)`` between two
in-process structs (awset_test.go:16-17).  Here one gossip round is a
single batched tensor op: every replica r absorbs replica ``perm[r]``
(``state[perm]`` is a gather that XLA lowers to collective-permute /
all-to-all over ICI when the replica axis is sharded), then the vmapped
merge kernel runs with zero cross-replica data dependence.

Schedules:
  * ring (offset 1)        — classic neighbor gossip; O(R) rounds.
  * dissemination (doubling offsets 1,2,4,...) — converges in ceil(log2 R)
    rounds; the butterfly realization of "all-pairs" (SURVEY §5.7c): valid
    because membership-convergence is associative across merge chains
    [verified, SURVEY §3.2].
  * butterfly (XOR pairs)  — symmetric exchanges, R power of two.
  * random pairing         — uniform gossip for fault-injection studies.

Fault injection (SURVEY §5.3): a dropped exchange is a masked no-op lane —
replica keeps its old state for the round.  State-based merge is idempotent
and commutative-on-membership, so drops only delay convergence; the
rounds-to-convergence-under-drop-rate curve is a north-star metric.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from go_crdt_playground_tpu.models.awset import AWSetState
from go_crdt_playground_tpu.models.awset_delta import AWSetDeltaState
from go_crdt_playground_tpu.ops.merge import merge_pairwise
from go_crdt_playground_tpu.ops.delta import (
    delta_apply, delta_extract, delta_merge_pairwise)
from go_crdt_playground_tpu.parallel import collectives
from go_crdt_playground_tpu.parallel import mesh as mesh_mod
from go_crdt_playground_tpu.parallel.mesh import (
    ELEMENT_AXIS, REPLICA_AXIS, partition_specs)

# One fused program for the per-round convergence predicate — the
# measurement loop calls it up to max_rounds times.
converged_jit = jax.jit(collectives.converged)


def _shard_map(f, *, mesh, in_specs, out_specs, check_vma=True):
    """jax moved shard_map from jax.experimental to the top level and
    renamed check_rep -> check_vma along the way; accept every
    generation so one source serves them all (same dance as the
    pltpu.CompilerParams shim in ops/pallas_merge.py)."""
    sm = getattr(jax, "shard_map", None)
    if sm is None:
        from jax.experimental.shard_map import shard_map as sm
    try:
        return sm(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                  check_vma=check_vma)
    except TypeError:
        return sm(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                  check_rep=check_vma)

# ---------------------------------------------------------------------------
# Pairing schedules (permutations of the replica axis)
# ---------------------------------------------------------------------------


def ring_perm(num_replicas: int, offset: int = 1) -> jnp.ndarray:
    """Partner of r is (r + offset) mod R."""
    return (jnp.arange(num_replicas, dtype=jnp.uint32) + offset) % num_replicas


def butterfly_perm(num_replicas: int, stage: int) -> jnp.ndarray:
    """Partner of r is r XOR 2^stage (symmetric pairs; R power of two)."""
    if num_replicas & (num_replicas - 1):
        raise ValueError("butterfly needs a power-of-two replica count")
    if not 0 <= stage or (1 << stage) >= num_replicas:
        raise ValueError(
            f"butterfly stage {stage} out of range for R={num_replicas} "
            f"(need 1 << stage < R; JAX would silently clamp the partners)")
    return jnp.arange(num_replicas, dtype=jnp.uint32) ^ jnp.uint32(1 << stage)


def random_perm(key: jax.Array, num_replicas: int) -> jnp.ndarray:
    return jax.random.permutation(key, num_replicas).astype(jnp.uint32)


# ---------------------------------------------------------------------------
# Gossip rounds
# ---------------------------------------------------------------------------


def _auto_kernel(state, delta_semantics: Optional[str] = None,
                 single_device: bool = True) -> str:
    """The fused-kernel auto-dispatch rule, in ONE place: Pallas on TPU
    backends (single-device processes unless the caller runs per shard
    inside shard_map) when the actor axis fits the fused row kernels.
    Both δ semantics fuse — the strict-reference empty-δ quirk is a
    scratch-accumulated cross-E reduction inside the kernel
    (ops/pallas_delta._strict_vv_epilogue).  All choices are
    bitwise-identical; on TPU the XLA HasDot gather lowers
    pathologically inside compiled loops (~40x slower, see
    ops/pallas_merge.py regime notes)."""
    from go_crdt_playground_tpu.ops.pallas_merge import MAX_FUSED_ACTORS

    fusible = (state.vv.shape[-1] <= MAX_FUSED_ACTORS
               and delta_semantics in (None, "v2", "reference"))
    ok = (jax.default_backend() == "tpu"
          and (not single_device or jax.device_count() == 1)
          and fusible)
    if (not ok and fusible and single_device
            and jax.default_backend() == "tpu"
            and jax.device_count() > 1):
        # the ONLY reason this fleet fell off the fused path is the
        # multi-device process: a bare pallas_call has no GSPMD
        # partitioning rule under an arbitrary perm, and the XLA HasDot
        # gather lowers pathologically on TPU (~40x, see
        # ops/pallas_merge.py regime notes).  Don't let users pay that
        # silently — the mesh-native rounds keep the fused kernel.
        import warnings

        warnings.warn(
            "multi-device TPU process: this gossip round is running the "
            "XLA gather path (~40x slower than the fused kernel on TPU). "
            "Use ring_round_shardmap / delta-ring or "
            "butterfly_round_shardmap for mesh schedules, or pass "
            "kernel='xla' to acknowledge the slow path.",
            stacklevel=3)
    return "pallas" if ok else "xla"


def _select_rows(mask_r: jnp.ndarray, new, old):
    """Per-replica select between two state pytrees (mask True -> new)."""
    return jax.tree.map(
        lambda n, o: jnp.where(mask_r.reshape((-1,) + (1,) * (n.ndim - 1)),
                               n, o),
        new, old,
    )


def gossip_round(
    state: AWSetState,
    perm: jnp.ndarray,
    drop_mask: Optional[jnp.ndarray] = None,
    kernel: str = "auto",
) -> AWSetState:
    """One full-state anti-entropy round: r <- perm[r] for all r.

    drop_mask: bool[R], True = this replica's exchange is lost this round
    (it keeps its old state) — fault injection as a masked lane.

    kernel: "auto" (fused Pallas kernel on single-device TPU processes,
    XLA elsewhere), "xla", or "pallas".  All choices are bitwise-
    identical; on TPU the XLA HasDot gather lowers pathologically
    inside compiled loops (~40x slower, see ops/pallas_merge.py regime
    notes), so auto picks the multi-row fused kernel there.  auto stays
    on XLA when more than one device is visible — a bare pallas_call
    has no GSPMD partitioning rule under an arbitrary perm; mesh
    programs get the fused path through ring_round_shardmap (its auto
    dispatch invokes the kernel per shard inside shard_map, so TPU
    meshes never pay the XLA HasDot penalty on the ring schedule).
    """
    if kernel == "auto":
        kernel = _auto_kernel(state)
    if kernel == "pallas":
        from go_crdt_playground_tpu.ops.pallas_merge import (
            pallas_gossip_round_rows)

        merged = pallas_gossip_round_rows(state, perm)
    else:
        src = jax.tree.map(lambda x: x[perm], state)
        merged, _ = merge_pairwise(state, src)
    if drop_mask is not None:
        merged = _select_rows(~drop_mask, merged, state)
    return merged


gossip_round_jit = jax.jit(gossip_round, static_argnames=("kernel",))


def ring_gossip_round(
    state: AWSetState,
    offset,
    drop_mask: Optional[jnp.ndarray] = None,
    kernel: str = "auto",
) -> AWSetState:
    """One full-state ring round: r <- (r + offset) mod R, the pairing
    every production schedule here uses (dissemination offsets, ICI
    rings).  Bitwise-equal to ``gossip_round(state, ring_perm(R,
    offset))`` but on TPU it dispatches the ring-FUSED kernel: partner
    rows are read in place via block index maps, so no ``state[perm]``
    copy is materialized — peak HBM drops from ~3x to ~2x state and a
    full state read of HBM traffic disappears (ops/pallas_merge.py).
    ``offset`` may be a traced scalar: one compiled program serves a
    whole dissemination schedule."""
    if kernel == "auto":
        kernel = _auto_kernel(state)
    if kernel == "pallas":
        from go_crdt_playground_tpu.ops.pallas_merge import (
            pallas_ring_round_rows)

        merged = pallas_ring_round_rows(state, offset)
    else:
        merged = gossip_round(state, ring_perm(state.vv.shape[0], offset),
                              kernel=kernel)
    if drop_mask is not None:
        merged = _select_rows(~drop_mask, merged, state)
    return merged


ring_gossip_round_jit = jax.jit(ring_gossip_round,
                                static_argnames=("kernel",))


def delta_gossip_round(
    state: AWSetDeltaState,
    perm: jnp.ndarray,
    drop_mask: Optional[jnp.ndarray] = None,
    delta_semantics: str = "v2",
    strict_reference_semantics: bool = True,
    kernel: str = "auto",
) -> AWSetDeltaState:
    """One δ anti-entropy round (payload-compressed exchanges).

    kernel: "auto" picks the fused Pallas δ kernel on single-device TPU
    processes (bitwise-identical, ~44x faster at fleet scale — the XLA
    HasDot gathers lower pathologically there, ops/pallas_merge.py
    regime notes); both δ semantics fuse, incl. the strict empty-δ
    quirk (scratch-accumulated cross-E reduction in the kernel).  Mesh
    programs keep XLA (same GSPMD caveat as gossip_round — use
    shard_map + kernel="pallas" per shard instead).
    """
    if kernel == "auto":
        kernel = _auto_kernel(state, delta_semantics)
    if kernel == "pallas":
        from go_crdt_playground_tpu.ops.pallas_delta import (
            pallas_delta_gossip_round)

        merged = pallas_delta_gossip_round(
            state, perm, delta_semantics=delta_semantics,
            strict_reference_semantics=strict_reference_semantics)
    else:
        src = jax.tree.map(lambda x: x[perm], state)
        merged = delta_merge_pairwise(state, src, delta_semantics,
                                      strict_reference_semantics)
    if drop_mask is not None:
        merged = _select_rows(~drop_mask, merged, state)
    return merged


delta_gossip_round_jit = jax.jit(
    delta_gossip_round,
    static_argnames=("delta_semantics", "strict_reference_semantics",
                     "kernel"),
)


def delta_ring_gossip_round(
    state: AWSetDeltaState,
    offset,
    drop_mask: Optional[jnp.ndarray] = None,
    delta_semantics: str = "v2",
    strict_reference_semantics: bool = True,
    kernel: str = "auto",
) -> AWSetDeltaState:
    """One δ ring round: r absorbs (r + offset) mod R.  On TPU this
    dispatches the ring-fused δ kernel (BOTH semantics — reference mode
    fuses the empty-δ VV-skip as an in-kernel emptiness reduction),
    which reads partner rows in place — no materialized ``state[perm]``
    copy.  That is what lets the 1M-replica north star fit on one 16GB
    chip: the gather path peaks at ~3x the 6.5GB state and OOMs.
    Bitwise-equal to ``delta_gossip_round(state, ring_perm(R, offset),
    ...)``."""
    if kernel == "auto":
        kernel = _auto_kernel(state, delta_semantics)
    if kernel == "pallas":
        from go_crdt_playground_tpu.ops.pallas_delta import (
            pallas_delta_ring_round)

        merged = pallas_delta_ring_round(
            state, offset, delta_semantics=delta_semantics,
            strict_reference_semantics=strict_reference_semantics)
    else:
        merged = delta_gossip_round(
            state, ring_perm(state.vv.shape[0], offset),
            delta_semantics=delta_semantics,
            strict_reference_semantics=strict_reference_semantics,
            kernel=kernel)
    if drop_mask is not None:
        merged = _select_rows(~drop_mask, merged, state)
    return merged


delta_ring_gossip_round_jit = jax.jit(
    delta_ring_gossip_round,
    static_argnames=("delta_semantics", "strict_reference_semantics",
                     "kernel"),
)


def ormap_gossip_round(state, perm: jnp.ndarray, kernel: str = "auto"):
    """One OR-Map anti-entropy round: the key membership is exactly the
    AWSet round (fused Pallas kernel on single-device TPU, same dispatch
    as gossip_round), the value cells join with the elementwise LWW rule.
    Bitwise-equivalent to ``lattices.gossip_round(lattices.ormap_join,
    state, perm)`` — that XLA path pays the pathological HasDot-gather
    lowering at fleet scale, this one doesn't."""
    from go_crdt_playground_tpu.ops.lattices import ORMapState, _lww_newer

    base = AWSetState(vv=state.vv, present=state.present,
                      dot_actor=state.dot_actor,
                      dot_counter=state.dot_counter, actor=state.actor)
    merged = gossip_round(base, perm, kernel=kernel)
    src_ts = state.ts[perm]
    src_wa = state.wr_actor[perm]
    take = _lww_newer(src_ts, src_wa, state.ts, state.wr_actor)
    return ORMapState(
        vv=merged.vv, present=merged.present, dot_actor=merged.dot_actor,
        dot_counter=merged.dot_counter, actor=state.actor,
        ts=jnp.where(take, src_ts, state.ts),
        wr_actor=jnp.where(take, src_wa, state.wr_actor),
        val=jnp.where(take, state.val[perm], state.val),
    )


def ormap_ring_gossip_round(state, offset, kernel: str = "auto"):
    """OR-Map ring round: the key membership runs the ring-FUSED AWSet
    kernel (in-place partner reads), the LWW value cells join against
    partner rows obtained by a row roll (a contiguous-slice shift, not
    the pathological elementwise gather).  Bitwise-equivalent to
    ``ormap_gossip_round(state, ring_perm(R, offset))``."""
    from go_crdt_playground_tpu.ops.lattices import ORMapState, _lww_newer

    base = AWSetState(vv=state.vv, present=state.present,
                      dot_actor=state.dot_actor,
                      dot_counter=state.dot_counter, actor=state.actor)
    merged = ring_gossip_round(base, offset, kernel=kernel)
    # row gather, not jnp.roll: with a traced offset roll lowers to
    # concatenate((x, x)) + dynamic_slice — a transient 2x copy per
    # value plane — while a [R]-index row gather materializes exactly
    # one partner copy at HBM bandwidth
    src_rows = ring_perm(state.ts.shape[0], offset)
    roll = lambda x: jnp.take(x, src_rows, axis=0)  # noqa: E731
    src_ts, src_wa = roll(state.ts), roll(state.wr_actor)
    take = _lww_newer(src_ts, src_wa, state.ts, state.wr_actor)
    return ORMapState(
        vv=merged.vv, present=merged.present, dot_actor=merged.dot_actor,
        dot_counter=merged.dot_counter, actor=state.actor,
        ts=jnp.where(take, src_ts, state.ts),
        wr_actor=jnp.where(take, src_wa, state.wr_actor),
        val=jnp.where(take, roll(state.val), state.val),
    )


def _extract_round(state: AWSetDeltaState, perm: jnp.ndarray):
    """Batched sender-side δ-extraction for one round's pairing: replica r
    will absorb perm[r], so extract perm[r]'s payload against r's VV."""
    src = jax.tree.map(lambda x: x[perm], state)
    return jax.vmap(delta_extract)(src, state.vv)


@jax.jit
def pipelined_delta_gossip(state: AWSetDeltaState,
                           perms: jnp.ndarray) -> AWSetDeltaState:
    """PP-analogue δ gossip (SURVEY §2.3 PP row): the δ-extract →
    δ-apply → VV-join pipeline is staged ACROSS rounds with a
    double-buffered payload.

    Round i's apply consumes the payload extracted during round i-1, and
    round i+1's payload is extracted from the PRE-apply state — so inside
    the compiled ``lax.scan`` body the extraction (and, on a sharded
    replica axis, its collective-permute traffic) has no data dependence
    on the in-flight apply and XLA overlaps the two stages.  The price is
    one round of staleness: payloads are compressed against a receiver VV
    that is one round old.  A stale receiver VV only ever ENLARGES the
    payload (the receiver's clock is monotone), and δ-apply is idempotent
    and mask-guarded, so the schedule stays convergent — it just ships
    data learned in round i starting at round i+2 instead of i+1
    (pipeline depth 2, exactly the double buffer).

    v2 δ semantics (payload-only exchanges subsume the first-contact full
    merge: extraction against a never-seen receiver VV ships every present
    lane and live deletion record).  perms: uint32[n_rounds, R].
    """
    apply_round = jax.vmap(
        lambda d, p: delta_apply(d, p, delta_semantics="v2"))
    payload = _extract_round(state, perms[0])
    n = perms.shape[0]

    def body(carry, i):
        s, p = carry
        return (apply_round(s, p), _extract_round(s, perms[i + 1])), None

    if n > 1:  # scan the first n-1 rounds; the last apply needs no staging
        (state, payload), _ = jax.lax.scan(
            body, (state, payload), jnp.arange(n - 1))
    return apply_round(state, payload)


@functools.partial(jax.jit, static_argnames=("k_changed", "k_deleted"))
def compact_delta_gossip_round(
    state: AWSetDeltaState,
    perm: jnp.ndarray,
    k_changed: int = 64,
    k_deleted: int = 64,
) -> AWSetDeltaState:
    """One δ round through the fixed-K compact payload form
    (ops/compact.py): extract -> compact to K index/value lanes ->
    expand -> apply (v2 semantics).

    This is the steady-state gossip path — the analogue of the
    reference's δ branch after first contact (awset-delta_test.go:57-62).
    When a pair's payload exceeds K, that exchange degrades to a safe
    partial one (entries up to capacity, NO clock advance — see
    ops/compact.py's correctness note), exactly like a lossy network
    round; schedules should bootstrap bulk divergence with dense rounds
    (delta_gossip_round / gossip_round, the full-merge analogue of
    awset-delta_test.go:53-56) and use compact rounds once payloads fit.
    """
    from go_crdt_playground_tpu.ops import compact as compact_ops

    E = state.present.shape[-1]
    src = jax.tree.map(lambda x: x[perm], state)
    payload = jax.vmap(delta_extract)(src, state.vv)
    comp = compact_ops.compact_payload_batch(payload, k_changed, k_deleted)
    dense = compact_ops.expand_payload_batch(comp, E)
    return jax.vmap(
        lambda d, p: delta_apply(d, p, delta_semantics="v2"))(state, dense)


@functools.lru_cache(maxsize=None)
def _compact_ring_step_compiled(mesh: Mesh, k_changed: int, k_deleted: int):
    """Cached jitted compact-payload ring: the only arrays that cross
    devices are the receiver VV advertisement (backward) and the fixed-K
    payload (forward) — O(K) ICI bytes per replica instead of O(E)."""
    from go_crdt_playground_tpu.ops import compact as compact_ops

    n = mesh.shape[REPLICA_AXIS]
    fwd = [(i, (i + 1) % n) for i in range(n)]       # sender -> receiver
    bwd = [(i, (i - 1) % n) for i in range(n)]       # receiver VV -> sender
    # The element mesh dim is pinned to 1 (caller-checked), so the EP
    # spec — actor axes formally sharded over it — is the same layout
    # while letting shard_map's replication inference accept vv/processed
    # outputs that mix element-tagged values (the payload path) in.
    specs = partition_specs(AWSetDeltaState, shard_actors=True)

    def step(local):
        E = local.present.shape[-1]
        # 1. receiver advertises its VV to its ring sender
        #    (the wire protocol of awset-delta_test.go:59: δ-extraction
        #    is compressed against the receiver's clock)
        recv_vv = jax.lax.ppermute(local.vv, REPLICA_AXIS, bwd)
        # 2. sender-side extract + compact against the advertised VV
        payload = jax.vmap(delta_extract)(local, recv_vv)
        comp = compact_ops.compact_payload_batch(
            payload, k_changed, k_deleted)
        # 3. only the compact payload crosses the ring
        shipped = jax.tree.map(
            lambda x: jax.lax.ppermute(x, REPLICA_AXIS, fwd), comp)
        # 4. receiver-side expand + apply
        dense = compact_ops.expand_payload_batch(shipped, E)
        return jax.vmap(
            lambda d, p: delta_apply(d, p, delta_semantics="v2"))(
                local, dense)

    return jax.jit(
        _shard_map(step, mesh=mesh, in_specs=(specs,), out_specs=specs)
    )


def compact_ring_round_shardmap(
    state: AWSetDeltaState,
    mesh: Mesh,
    k_changed: int = 64,
    k_deleted: int = 64,
) -> AWSetDeltaState:
    """One compact-payload ring round with the communication pinned to
    ICI neighbors: device i's replica block syncs into device i+1's,
    shipping only the fixed-K payload lanes (plus the receiver's VV
    advertisement going the other way).  Equivalent to
    ``compact_delta_gossip_round`` with the block-shift permutation;
    requires the element axis unsharded (compaction scans E locally).
    """
    if mesh.shape[ELEMENT_AXIS] != 1:
        raise ValueError(
            "compact ring needs the element axis unsharded "
            f"(mesh element dim {mesh.shape[ELEMENT_AXIS]}): lane "
            "compaction is a scan over the full element axis")
    return _compact_ring_step_compiled(mesh, k_changed, k_deleted)(state)


def dissemination_offsets(num_replicas: int):
    """Doubling offsets 1, 2, 4, ... — ceil(log2 R) rounds to full
    convergence on any replica count."""
    offs, o = [], 1
    while o < num_replicas:
        offs.append(o)
        o *= 2
    return offs


def disjoint_update_join(local, base, axis_name: str, num_shards: int):
    """Converge per-device copies of a REPLICATED state whose devices
    applied KEY-DISJOINT updates, via dissemination-doubling ring
    rounds over ``axis_name`` — the 2-D serve mesh's dp-axis
    convergence (parallel/meshtarget2d.py): each dp replica applies
    its own stripe of a super-batch, then ceil(log2 dp) ring rounds
    (offsets 1, 2, 4, ... — the ``dissemination_offsets`` schedule,
    realized as ``ppermute`` neighbor exchanges under shard_map) leave
    every replica holding the exact join.

    The join rule leans on the striping invariant instead of the
    general merge kernel: every lane was updated by AT MOST ONE
    replica (the batcher's key-disjoint stripes), so "partner's lane
    differs from the shared pre-update ``base``" identifies the unique
    writer and a plain select reconstructs the sequential result
    BITWISE — dots included, which the general full-merge rule cannot
    promise (its both-present overwrite is order-sensitive).  Clocks
    join elementwise (vv/processed are monotone counters, max IS their
    join).  Overlapping dissemination windows are safe: two rounds
    that both carry a lane carry the identical value (unique writer),
    so the select is idempotent.

    Must run inside ``shard_map`` with ``axis_name`` bound; ``local``
    and ``base`` are single-replica slices (fields [E_loc]/[A]).
    """
    from go_crdt_playground_tpu.models.layout import (ACTOR_AXIS_FIELDS,
                                                      REPLICA_ONLY_FIELDS)

    if num_shards == 1:
        return local
    clock_fields = set(ACTOR_AXIS_FIELDS) | set(REPLICA_ONLY_FIELDS)
    lane_fields = [f for f in type(local)._fields
                   if f not in clock_fields]

    def lane_diff(candidate):
        d = None
        for f in lane_fields:
            neq = getattr(candidate, f) != getattr(base, f)
            d = neq if d is None else (d | neq)
        return d

    for off in dissemination_offsets(num_shards):
        pairs = [((d + off) % num_shards, d) for d in range(num_shards)]
        partner = jax.tree.map(
            lambda x: jax.lax.ppermute(x, axis_name, pairs), local)
        take = lane_diff(partner)
        updates = {f: jnp.where(take, getattr(partner, f),
                                getattr(local, f))
                   for f in lane_fields}
        for f in ACTOR_AXIS_FIELDS:
            if f in type(local)._fields:
                updates[f] = jnp.maximum(getattr(local, f),
                                         getattr(partner, f))
        local = local._replace(**updates)
    return local


@functools.partial(jax.jit, static_argnames=("delta", "delta_semantics"))
def all_pairs_converge(state, delta: bool = False,
                       delta_semantics: str = "v2"):
    """The all-pairs exchange realized as ceil(log2 R) doubling-offset
    rounds instead of O(R^2) work (SURVEY §5.7c)."""
    R = state.vv.shape[0]
    for off in dissemination_offsets(R):
        if delta:
            state = delta_ring_gossip_round(
                state, off, delta_semantics=delta_semantics)
        else:
            state = ring_gossip_round(state, off)
    return state


@functools.lru_cache(maxsize=None)
def _advance_program(delta: bool, schedule: str, delta_semantics: str,
                     has_drop: bool):
    """Cached jitted multi-round advance for rounds_to_convergence: a
    whole chunk of rounds is ONE dispatch — the round index drives
    offset selection and the drop/perm randomness INSIDE a lax.scan
    (fold_in on the traced index reproduces the exact stream the old
    eager loop drew), so a measurement pays rounds/check_every host
    syncs instead of 2-3 per round.  key and
    drop_rate are traced operands, so the six-rate droprate sweep
    shares one compiled program per chunk width; distinct static n
    values are the chunk size plus O(log check_every) bisection
    widths.  has_drop is static so no-drop runs keep the drop=None fast
    path (no mask draw, no per-round full-state select)."""
    round_fn = delta_gossip_round if delta else gossip_round
    ring_fn = delta_ring_gossip_round if delta else ring_gossip_round
    kw = {"delta_semantics": delta_semantics} if delta else {}

    @functools.partial(jax.jit, static_argnames=("n",))
    def advance_jit(s, key, offsets_arr, drop_rate, start, n: int):
        R = s.vv.shape[0]

        def body(c, i):
            rnd = start + i
            drop = None
            if has_drop:
                drop = jax.random.bernoulli(
                    jax.random.fold_in(key, 2 * rnd + 1), drop_rate, (R,))
            if schedule == "random":
                perm = random_perm(jax.random.fold_in(key, 2 * rnd), R)
                return round_fn(c, perm, drop, **kw), None
            if schedule == "butterfly":
                # stages cycle 0..log2(R)-1; the m distinct XOR stages
                # are hypercube dissemination — all-pairs in exactly m
                # rounds (R power-of-two, validated by the caller)
                stage = rnd % jnp.uint32(R.bit_length() - 1)
                perm = (jnp.arange(R, dtype=jnp.uint32)
                        ^ (jnp.uint32(1) << stage))
                return round_fn(c, perm, drop, **kw), None
            off = (jnp.uint32(1) if schedule == "ring"
                   else offsets_arr[rnd % offsets_arr.shape[0]])
            return ring_fn(c, off, drop, **kw), None

        s, _ = jax.lax.scan(body, s, jnp.arange(n, dtype=jnp.uint32))
        # the convergence digest rides in the same program: a chunk costs
        # ONE device->host sync (the bool), not a second digest dispatch
        return s, collectives.converged(s.present, s.vv)

    return advance_jit


def rounds_to_convergence(
    state,
    key: Optional[jax.Array] = None,
    drop_rate: float = 0.0,
    max_rounds: int = 10_000,
    delta: bool = False,
    delta_semantics: str = "v2",
    schedule: str = "dissemination",
    check_every: int = 8,
) -> Tuple[int, object]:
    """Host-driven convergence loop: gossip until every replica agrees on
    (membership, VV); returns (rounds, final state).  The north-star
    metric's measurement harness (BASELINE.json).

    With drop_rate > 0 each replica's exchange is lost independently per
    round (requires ``key``).

    check_every: how many rounds run between host-synced convergence
    checks.  Every check is a device->host round trip, so per-round
    checking dominates measurement at fleet scale; with a chunk size k the loop pays rounds/k + O(log k)
    syncs instead of rounds.  The returned round count is EXACT for any
    chunk size: when a chunk lands converged, the minimal prefix is
    found by bisection, replaying rounds from the chunk-start state —
    valid because round randomness derives from the round INDEX
    (fold_in), so replay reproduces the same drops/pairings, and a
    converged fleet stays converged under further gossip (merge is
    idempotent), making convergence monotone within the chunk.

    Memory note: chunking keeps the chunk-start state live for replay —
    ONE extra fleet copy on device.  When a fleet barely fits (e.g. the
    1M-replica δ north star at ~6.5GB state), pass check_every=1 to
    trade the sync savings back for the old single-copy footprint.
    """
    R = state.vv.shape[0]
    offsets = dissemination_offsets(R) or [1]
    if schedule not in ("dissemination", "ring", "random", "butterfly"):
        raise ValueError(f"unknown schedule {schedule!r}")
    if schedule == "random" and key is None:
        raise ValueError("random schedule requires a key")
    if schedule == "butterfly" and R & (R - 1):
        raise ValueError(
            f"butterfly schedule needs a power-of-two replica count "
            f"(R={R})")
    if drop_rate > 0.0 and key is None:
        raise ValueError("drop_rate requires a key")
    offsets_arr = jnp.asarray(offsets, jnp.uint32)
    advance_prog = _advance_program(bool(delta), schedule, delta_semantics,
                                    drop_rate > 0.0)
    # key/drop_rate ride as DATA so one compiled program serves every
    # (positive rate, seed) run of a measurement sweep; no-drop runs
    # share a second, mask-free program (a dummy key placates the
    # signature — its stream is never drawn there)
    key_arr = key if key is not None else jax.random.key(0)
    rate_arr = jnp.float32(drop_rate)

    def advance(s, start: int, n: int):
        """n rounds + the fused digest: (state, converged) for ONE
        device->host sync (the bool fetch)."""
        s, c = advance_prog(s, key_arr, offsets_arr, rate_arr,
                            jnp.uint32(start), n)
        return s, bool(c)

    if bool(converged_jit(state.present, state.vv)):
        return 0, state
    rnd = 0
    while rnd < max_rounds:
        k = min(max(1, check_every), max_rounds - rnd)
        chunk_start = state
        state, chunk_conv = advance(state, rnd, k)
        if chunk_conv:
            # invariants: NOT converged after lo rounds, converged after
            # hi; each probe resumes from the last non-converged prefix
            # (lo_state), so the whole bisection replays O(k) rounds
            # total, not O(k log k)
            lo, hi = 0, k
            lo_state, hi_state = chunk_start, state
            while lo + 1 < hi:
                mid = (lo + hi) // 2
                s_mid, mid_conv = advance(lo_state, rnd + lo, mid - lo)
                if mid_conv:
                    hi, hi_state = mid, s_mid
                else:
                    lo, lo_state = mid, s_mid
            return rnd + hi, hi_state
        rnd += k
    raise RuntimeError(
        f"no convergence within {max_rounds} rounds "
        f"(schedule={schedule!r}, drop_rate={drop_rate}) — refusing to "
        "report an exhausted budget as a measured rounds-to-convergence")


# ---------------------------------------------------------------------------
# Explicit shard_map ring (collectives pinned to ICI neighbors)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _ring_step_compiled(mesh: Mesh, state_cls, kernel: str):
    """Cached jitted shard_map ring step per (mesh, state type, kernel) —
    a fresh jit per call would recompile the program every round.

    kernel="pallas" runs the fused multi-row merge kernel PER SHARD: the
    partner block arrives by ppermute, so each device invokes
    pallas_merge_pairwise_rows on its local rows — this is how mesh
    programs get the fused path (a bare pallas_call has no GSPMD
    partitioning rule, but inside shard_map the kernel only ever sees
    the local block)."""
    n = mesh.shape[REPLICA_AXIS]
    pairs = [(i, (i + 1) % n) for i in range(n)]
    specs = partition_specs(state_cls)

    def step(local):
        recv = jax.tree.map(
            lambda x: jax.lax.ppermute(x, REPLICA_AXIS, pairs), local)
        if kernel == "pallas":
            from go_crdt_playground_tpu.ops.pallas_merge import (
                pallas_merge_pairwise_rows)

            return pallas_merge_pairwise_rows(local, recv)
        merged, _ = merge_pairwise(local, recv)
        return merged

    # pallas_call's out_shape carries no varying-manual-axes annotation,
    # so the vma consistency check can't see through it — disable it for
    # the fused path (the bitwise-equality test vs the checked XLA path
    # is the stronger guarantee anyway).
    return jax.jit(
        _shard_map(step, mesh=mesh, in_specs=(specs,), out_specs=specs,
                      check_vma=(kernel != "pallas"))
    )


@functools.lru_cache(maxsize=None)
def _ep_ring_step_compiled(mesh: Mesh, state_cls):
    """Cached jitted EP ring step: vv's actor axis lives sharded over the
    mesh element dim (SURVEY §2.3 EP row — per-actor ownership of VV
    slots, awset.go:91)."""
    n_r = mesh.shape[REPLICA_AXIS]
    n_e = mesh.shape[ELEMENT_AXIS]
    pairs = [(i, (i + 1) % n_r) for i in range(n_r)]
    specs = partition_specs(state_cls, shard_actors=True)

    def step(local):
        # HasDot reads arbitrary actor slots, so the EP gather is one
        # all_gather of the vv shards per round (the expert-parallel
        # pattern: gather the sharded table, compute, re-slice).
        vv_full = jax.lax.all_gather(
            local.vv, ELEMENT_AXIS, axis=1, tiled=True)
        full = local._replace(vv=vv_full)
        recv = jax.tree.map(
            lambda x: jax.lax.ppermute(x, REPLICA_AXIS, pairs), full)
        merged, _ = merge_pairwise(full, recv)
        a_shard = merged.vv.shape[1] // n_e
        idx = jax.lax.axis_index(ELEMENT_AXIS)
        vv_local = jax.lax.dynamic_slice_in_dim(
            merged.vv, idx * a_shard, a_shard, axis=1)
        return merged._replace(vv=vv_local)

    return jax.jit(
        _shard_map(step, mesh=mesh, in_specs=(specs,), out_specs=specs)
    )


def ep_ring_round_shardmap(state: AWSetState, mesh: Mesh) -> AWSetState:
    """One ring round under the EP layout (mesh.partition_specs with
    shard_actors=True): version-vector slots are owned per actor shard,
    all-gathered for the round's HasDot gathers, and the joined vv is
    sliced back to this shard's slots.  Bitwise-identical results to
    ring_round_shardmap — EP is a layout choice, never a semantics choice.

    Wants A large relative to the element-dim shard count; the win is VV
    memory (A can be as big as R in an every-replica-writes world, making
    vv[R, A] the dominant array) spread over the mesh instead of
    replicated per element shard.
    """
    mesh_mod.validate_ep_layout(state, mesh)
    return _ep_ring_step_compiled(mesh, type(state))(state)


def ring_round_shardmap(state: AWSetState, mesh: Mesh,
                        kernel: str = "auto") -> AWSetState:
    """One ring round with the communication pinned explicitly: each
    replica-shard ppermutes its whole block to the next device over the
    ring (ICI neighbor), then every replica merges with the received
    peer — the ring-anti-entropy schedule of SURVEY §5.7b, the set-merge
    analogue of ring attention's neighbor exchange.

    kernel: "auto" runs the fused Pallas merge per shard on TPU meshes
    (the v5e-4 fast path — no 40x XLA HasDot penalty on mesh programs),
    XLA elsewhere; "pallas"/"xla" force a path.  All bitwise-identical
    (pinned by tests/test_gossip.py on the CPU mesh in interpret mode).

    Full-state AWSet only: the merge kernel has no cross-element
    reductions, so an element-sharded block is self-contained.  (The δ
    kernel's strict mode reduces over E — route δ gossip through
    delta_gossip_round under jit instead, where XLA inserts the psum.)
    """
    if kernel == "auto":
        kernel = _auto_kernel(state, single_device=False)
    return _ring_step_compiled(mesh, type(state), kernel)(state)


@functools.lru_cache(maxsize=None)
def _butterfly_step_compiled(mesh: Mesh, state_cls, stage: int,
                             kernel: str):
    """Cached jitted shard_map butterfly stage per (mesh, state type,
    stage, kernel).

    The XOR pairing decomposes cleanly over a power-of-two block layout
    (global row r = d*blk + i):

      * 2^stage <  blk — block-LOCAL: i ^ 2^stage stays inside the
        block, so the stage is a per-shard permuted merge with zero
        communication (the fused multi-row kernel per shard on TPU);
      * 2^stage >= blk — device-pair swap: partner row is the SAME
        intra index on device d ^ (2^stage/blk), so the stage is one
        symmetric ppermute of whole blocks + the pairwise-rows merge.
    """
    n = mesh.shape[REPLICA_AXIS]
    s = 1 << stage
    specs = partition_specs(state_cls)

    def step(local):
        blk = local.vv.shape[0]
        if s < blk:
            local_perm = (jnp.arange(blk, dtype=jnp.uint32)
                          ^ jnp.uint32(s))
            if kernel == "pallas":
                from go_crdt_playground_tpu.ops.pallas_merge import (
                    pallas_gossip_round_rows)

                return pallas_gossip_round_rows(local, local_perm)
            src = jax.tree.map(lambda x: x[local_perm], local)
            merged, _ = merge_pairwise(local, src)
            return merged
        pairs = [(d, d ^ (s // blk)) for d in range(n)]
        recv = jax.tree.map(
            lambda x: jax.lax.ppermute(x, REPLICA_AXIS, pairs), local)
        if kernel == "pallas":
            from go_crdt_playground_tpu.ops.pallas_merge import (
                pallas_merge_pairwise_rows)

            return pallas_merge_pairwise_rows(local, recv)
        merged, _ = merge_pairwise(local, recv)
        return merged

    return jax.jit(
        _shard_map(step, mesh=mesh, in_specs=(specs,), out_specs=specs,
                      check_vma=(kernel != "pallas"))
    )


def butterfly_round_shardmap(state: AWSetState, mesh: Mesh, stage: int,
                             kernel: str = "auto") -> AWSetState:
    """One butterfly stage (partner = r XOR 2^stage, SURVEY §5.7c) with
    the replica axis explicitly sharded — the mesh-native realization of
    butterfly_perm, bitwise-identical to ``gossip_round(state,
    butterfly_perm(R, stage))``.

    Stages below the per-device block size are block-local (zero ICI);
    stages at or above it are one whole-block ppermute between XOR
    device pairs.  Either way the merge runs the fused kernel per shard
    on TPU meshes, so butterfly schedules never pay the multi-device
    XLA HasDot penalty that _auto_kernel warns about.

    Full-state AWSet family only (same restriction as
    ring_round_shardmap: the merge kernel has no cross-element
    reductions, so element-sharded blocks are self-contained).
    """
    R = state.vv.shape[0]
    n = mesh.shape[REPLICA_AXIS]
    if R & (R - 1):
        raise ValueError(f"butterfly needs a power-of-two replica count "
                         f"(R={R})")
    if R % n:
        raise ValueError(f"R={R} not divisible by replica mesh dim {n}")
    blk = R // n
    if blk & (blk - 1):
        raise ValueError(
            f"per-device block {blk} must be a power of two for the XOR "
            "pairing to decompose into block-local and block-swap stages")
    if not 0 <= stage or (1 << stage) >= R:
        raise ValueError(
            f"butterfly stage {stage} out of range for R={R} "
            "(need 1 << stage < R)")
    if kernel == "auto":
        kernel = _auto_kernel(state, single_device=False)
    return _butterfly_step_compiled(mesh, type(state), stage, kernel)(state)


# ---------------------------------------------------------------------------
# Bitpacked δ gossip with an explicitly sharded replica axis
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _packed_block_ring_compiled(mesh: Mesh, shift: int, kernel_offset: int,
                                state_cls=None):
    from jax.sharding import PartitionSpec as P

    from go_crdt_playground_tpu.models.packed import (
        DotPackedAWSetDeltaState, DotPackedAWSetState,
        PackedAWSetDeltaState, PackedAWSetState)
    from go_crdt_playground_tpu.ops.pallas_delta import (
        pallas_delta_ring_round_dotpacked, pallas_delta_ring_round_packed)
    from go_crdt_playground_tpu.ops.pallas_merge import (
        pallas_ring_round_rows_dotpacked, pallas_ring_round_rows_packed)

    if state_cls is None:
        state_cls = PackedAWSetDeltaState
    round_fn = {
        PackedAWSetDeltaState: pallas_delta_ring_round_packed,
        DotPackedAWSetDeltaState: pallas_delta_ring_round_dotpacked,
        PackedAWSetState: pallas_ring_round_rows_packed,
        DotPackedAWSetState: pallas_ring_round_rows_dotpacked,
    }[state_cls]
    n = mesh.shape[REPLICA_AXIS]
    # device d receives the block of device (d + shift) mod n
    pairs = [((i + shift) % n, i) for i in range(n)]
    row = P(REPLICA_AXIS, None)
    # every array is row-sharded 2-D except the 1-D actor column
    specs = state_cls(**{f: (P(REPLICA_AXIS) if f == "actor" else row)
                         for f in state_cls._fields})

    def step(local):
        if shift:
            recv = jax.tree.map(
                lambda x: jax.lax.ppermute(x, REPLICA_AXIS, pairs), local)
        else:
            recv = local
        stacked = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b], axis=0), local, recv)
        out = round_fn(stacked, kernel_offset)
        return jax.tree.map(lambda x: x[: x.shape[0] // 2], out)

    # check_vma off for the same reason as _ring_step_compiled's pallas
    # path: pallas_call's out_shape carries no varying-manual-axes
    # annotation (the bitwise pin vs the global-jit packed round in
    # tests/test_gossip.py is the stronger guarantee).
    return jax.jit(
        _shard_map(step, mesh=mesh, in_specs=(specs,), out_specs=specs,
                      check_vma=False)
    )


def packed_block_ring_round_shardmap(state, mesh: Mesh, offset):
    """One packed-layout gossip round with the replica axis explicitly
    sharded.  Accepts any of the four packed layouts (models/packed.py:
    bitpacked or dot-word, full-state or δ) and dispatches the matching
    single-device ring kernel per shard; membership crosses ICI as
    uint32[blk, E/32] words — 8x less wire traffic for the membership
    sections than the bool layouts — and the dot-word forms halve the
    dot-section traffic on top.

    Pairing, with ``blk = R / n_devices`` rows per device:

    * ``offset % blk == 0`` — block-aligned ring: row r absorbs
      r + offset globally, i.e. device d's rows absorb device
      (d + offset/blk)'s rows pairwise.  Bitwise-identical to
      ``pallas_delta_ring_round_packed(state, offset)`` on one device.
    * ``offset < blk`` — intra-device ring: row i absorbs row
      (i + offset) mod blk WITHIN its device block, no communication.
      This wraps per block rather than globally, so it is a different
      (equally convergent, v2-semantics) anti-entropy pairing than the
      global ring at that offset — dissemination schedules compose
      intra rounds (offsets < blk) with block-aligned rounds (offset
      multiples of blk) to reach all-pairs in ceil(log2 R) rounds.

    Both forms run the packed ring kernel on the stacked [local; recv]
    (or [local; local]) 2*blk block at an in-kernel offset that lands
    every kept row on its partner; rows >= blk are partner-absorbing
    scratch and are discarded (2x compute for zero gather/copy of the
    partner block — the shard-side analogue of the in-place ring reads).
    Requires the element mesh dim unsharded and blk a multiple of 64
    (ring_supported on the stacked block).
    """
    if mesh.shape[ELEMENT_AXIS] != 1:
        raise ValueError(
            "packed block ring needs the element axis unsharded (mesh "
            f"element dim {mesh.shape[ELEMENT_AXIS]}): packed words are "
            "not element-shardable")
    n = mesh.shape[REPLICA_AXIS]
    R = state.vv.shape[0]
    if R % n:
        raise ValueError(f"R={R} not divisible by replica mesh dim {n}")
    blk = R // n
    from go_crdt_playground_tpu.ops.pallas_merge import ring_supported
    if not ring_supported(2 * blk):
        # the kernel runs on the stacked [local; recv] 2*blk block, so
        # the per-device block itself must satisfy the ring kernel's
        # whole-aligned-blocks layout; failing here beats a
        # kernel-internal layout assert (or a silently odd tiling)
        raise ValueError(
            f"per-device block {blk} (R={R} / {n} devices) stacks to a "
            f"{2 * blk}-row kernel block, which the packed ring kernel "
            "cannot tile (needs a multiple of 64 rows, at least 128)")
    offset = int(offset) % R
    if offset == 0:
        raise ValueError("offset 0 is a no-op round")
    if offset % blk == 0:
        shift, kernel_offset = offset // blk, blk
    elif offset < blk:
        shift, kernel_offset = 0, blk + offset
    else:
        raise ValueError(
            f"offset {offset} is neither intra-block (< {blk}) nor "
            f"block-aligned (multiple of {blk})")
    return _packed_block_ring_compiled(mesh, shift, kernel_offset,
                                       type(state))(state)
