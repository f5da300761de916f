"""Fixed-K compact δ payloads (ops/compact.py): roundtrip fidelity,
overflow safety, and the compact gossip rounds (including the ICI ring
that ships only O(K) bytes per replica)."""

import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from go_crdt_playground_tpu.models import awset_delta
from go_crdt_playground_tpu.ops import compact as compact_ops
from go_crdt_playground_tpu.ops import delta as delta_ops
from go_crdt_playground_tpu.parallel import collectives, gossip
from go_crdt_playground_tpu.parallel import mesh as mesh_mod


def _random_delta_state(rng, R=8, E=32, A=8):
    st = awset_delta.init(R, E, A)
    for _ in range(6 * R):
        r = rng.randrange(R)
        e = rng.randrange(E)
        if rng.random() < 0.75:
            st = awset_delta.add_element(st, np.uint32(r), np.uint32(e))
        else:
            sel = np.zeros(E, bool)
            sel[e] = True
            st = awset_delta.del_elements(st, np.uint32(r), np.asarray(sel))
    return st


def _payload_fields_equal(a, b):
    for name in a._fields:
        assert np.array_equal(np.asarray(getattr(a, name)),
                              np.asarray(getattr(b, name))), name


def _scatter_compact_section(mask, idx_dtype, k, *values):
    """The earlier form of ``_compact_section``: one E-long scatter into
    k slots per output lane.  Kept as a second reference."""
    E = mask.shape[-1]
    pos = jnp.cumsum(mask) - 1
    claim = mask & (pos < k)
    dest = jnp.where(claim, pos, k).astype(jnp.int32)
    eids = jnp.arange(E, dtype=idx_dtype)
    idx = jnp.zeros((k,), idx_dtype).at[dest].set(eids, mode="drop")
    valid = jnp.zeros((k,), bool).at[dest].set(claim, mode="drop")
    packed = tuple(
        jnp.zeros((k,), v.dtype).at[dest].set(
            jnp.where(claim, v, 0), mode="drop")
        for v in values)
    return idx, valid, packed, jnp.sum(mask) > k


def _numpy_compact_section(mask, idx_dtype, k, *values):
    """First k set lanes in ascending element id, zeros elsewhere."""
    lanes = np.flatnonzero(mask)[:k]
    n = lanes.size
    idx = np.zeros(k, idx_dtype)
    idx[:n] = lanes
    valid = np.arange(k) < n
    packed = []
    for v in values:
        out = np.zeros(k, v.dtype)
        out[:n] = v[lanes]
        packed.append(out)
    return idx, valid, tuple(packed), np.bool_(mask.sum() > k)


def _mask_case(E, k, kind, rng):
    if kind == "empty":
        return np.zeros(E, bool)
    if kind == "all":
        return np.ones(E, bool)
    if kind in ("exactly_k", "k_plus_1"):
        n = min(E, k + (kind == "k_plus_1"))
        m = np.zeros(E, bool)
        m[rng.choice(E, n, replace=False)] = True
        return m
    return rng.random(E) < float(kind)      # a density


def _leaves_identical(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for i, (g, w) in enumerate(zip(got, want)):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape, (i, g.dtype,
                                                             w.dtype)
        assert np.array_equal(g, w), i


@pytest.mark.parametrize("E,k,kind", [
    (1024, 128, "empty"),
    (1024, 128, "exactly_k"),
    (1024, 128, "k_plus_1"),
    (1024, 128, "all"),
    (1024, 1024, "0.5"),          # K = E
    (1024, 1024, "all"),
    (1024, 1, "0.01"),            # K = 1
    (1024, 1, "empty"),
    (1000, 128, "0.1"),           # E not a multiple of 128
    (1000, 128, "k_plus_1"),
    (1 << 16, 128, "0.0005"),     # random densities at E = 2^16
    (1 << 16, 128, "0.002"),
    (1 << 16, 128, "0.01"),
    (1 << 16, 128, "0.5"),
    (1 << 16, 4096, "0.05"),
])
def test_compact_section_matches_references(E, k, kind):
    rng = np.random.default_rng(E * 1009 + k)
    mask = _mask_case(E, k, kind, rng)
    da = rng.integers(0, 2 ** 32, E, dtype=np.uint32)
    dc = rng.integers(0, 2 ** 32, E, dtype=np.uint32)
    got = jax.jit(compact_ops._compact_section, static_argnums=(1, 2))(
        jnp.asarray(mask), jnp.uint32, k, da, dc)
    _leaves_identical(got, _numpy_compact_section(mask, np.uint32, k,
                                                  da, dc))
    old = jax.jit(_scatter_compact_section, static_argnums=(1, 2))(
        jnp.asarray(mask), jnp.uint32, k, da, dc)
    _leaves_identical(got, old)


def _dense_payload(rng, E, A, ch_density, del_density):
    def u32():
        return rng.integers(0, 2 ** 32, E, dtype=np.uint32)

    return delta_ops.DeltaPayload(
        src_vv=rng.integers(1, 2 ** 16, A, dtype=np.uint32),
        changed=rng.random(E) < ch_density, ch_da=u32(), ch_dc=u32(),
        deleted=rng.random(E) < del_density, del_da=u32(), del_dc=u32(),
        src_actor=np.uint32(3),
        src_processed=rng.integers(1, 2 ** 16, A, dtype=np.uint32))


def test_compact_payload_batch_matches_per_row_references():
    """Eight vmapped rows, some overflowing one section, some neither:
    every field equals the per-row NumPy and scatter references, and an
    overflowed row zeroes its clock lanes."""
    rng = np.random.default_rng(83)
    E, A, kc, kd = 4096, 16, 64, 32
    rows = [_dense_payload(rng, E, A, d, d / 2)
            for d in (0.0, 0.001, 0.005, 0.01, 0.02, 0.05, 0.3, 1.0)]
    batch = jax.tree.map(lambda *xs: np.stack(xs), *rows)
    got = compact_ops.compact_payload_batch(batch, kc, kd)
    over = np.asarray(got.overflow)
    assert over.any() and not over.all()
    for r, p in enumerate(rows):
        row = jax.tree.map(lambda x: np.asarray(x)[r], got)
        for ref in (_numpy_compact_section, _scatter_compact_section):
            ch = ref(p.changed, np.uint32, kc, p.ch_da, p.ch_dc)
            dl = ref(p.deleted, np.uint32, kd, p.del_da, p.del_dc)
            _leaves_identical(
                (row.ch_idx, row.ch_valid, (row.ch_da, row.ch_dc)),
                ch[:3])
            _leaves_identical(
                (row.del_idx, row.del_valid, (row.del_da, row.del_dc)),
                dl[:3])
            assert bool(row.overflow) == bool(ch[3] | dl[3])
        keep = not bool(row.overflow)
        for name in ("src_vv", "src_processed"):
            want = getattr(p, name) if keep else np.zeros(A, np.uint32)
            assert np.array_equal(getattr(row, name), want), (r, name)


def test_compact_payload_lowers_without_scatter_at_served_size():
    """At the served store's size (E = 2^20, K = 128) the compaction is
    gathers over a running count: an E-long scatter per output lane
    would read all 2^20 lanes to fill 128 slots."""
    E, A = 1 << 20, 16
    sds = jax.ShapeDtypeStruct
    p = delta_ops.DeltaPayload(
        src_vv=sds((A,), jnp.uint32), changed=sds((E,), jnp.bool_),
        ch_da=sds((E,), jnp.uint32), ch_dc=sds((E,), jnp.uint32),
        deleted=sds((E,), jnp.bool_), del_da=sds((E,), jnp.uint32),
        del_dc=sds((E,), jnp.uint32), src_actor=sds((), jnp.uint32),
        src_processed=sds((A,), jnp.uint32))
    hlo = jax.jit(compact_ops.compact_payload,
                  static_argnums=(1, 2)).lower(p, 128, 128).as_text()
    assert "gather" in hlo
    assert "scatter" not in hlo


def test_compact_expand_roundtrip_when_fits():
    rng = random.Random(43)
    st = _random_delta_state(rng)
    E = st.present.shape[-1]
    src = jax.tree.map(lambda x: x[gossip.ring_perm(8, 1)], st)
    payload = jax.vmap(delta_ops.delta_extract)(src, st.vv)
    comp = compact_ops.compact_payload_batch(payload, E, E)  # K = E: fits
    assert not bool(comp.overflow.any())
    back = compact_ops.expand_payload_batch(comp, E)
    _payload_fields_equal(payload, back)


def test_compact_wire_bytes_are_o_k_not_o_e():
    rng = random.Random(47)
    st = _random_delta_state(rng, E=512)
    src = jax.tree.map(lambda x: x[gossip.ring_perm(8, 1)], st)
    payload = jax.vmap(delta_ops.delta_extract)(src, st.vv)
    comp = compact_ops.compact_payload_batch(payload, 16, 16)
    one_dense = jax.tree.map(lambda x: x[0], payload)
    one_comp = jax.tree.map(lambda x: x[0], comp)
    assert one_comp.nbytes_wire() < one_dense.nbytes_dense() / 4


def test_overflow_truncates_and_masks_clock():
    rng = random.Random(53)
    st = _random_delta_state(rng)
    E = st.present.shape[-1]
    src = jax.tree.map(lambda x: x[gossip.ring_perm(8, 1)], st)
    payload = jax.vmap(delta_ops.delta_extract)(src, st.vv)
    counts = np.asarray(payload.changed.sum(axis=-1))
    k = int(counts.max()) - 1
    assert k >= 1
    comp = compact_ops.compact_payload_batch(payload, k, E)
    over = np.asarray(comp.overflow)
    # mixed coverage: the max-count row(s) overflow at k = max-1, rows
    # with smaller payloads don't (the seeded fixture guarantees spread)
    assert over.any() and not over.all(), counts
    # truncated rows must not advance the receiver clock (vv zeroed)
    assert (np.asarray(comp.src_vv)[over] == 0).all()
    back = compact_ops.expand_payload_batch(comp, E)
    # claimed lanes are a subset of the dense payload with equal dots
    ch = np.asarray(back.changed)
    assert (ch <= np.asarray(payload.changed)).all()
    assert (ch.sum(axis=-1) <= k).all()
    where = ch.nonzero()
    assert np.array_equal(np.asarray(back.ch_da)[where],
                          np.asarray(payload.ch_da)[where])


def test_compact_round_matches_dense_delta_round_steady_state():
    """After a dense bootstrap round (the full-merge analogue of
    awset-delta_test.go:53-56), compact rounds with adequate K are
    bitwise the dense v2 δ rounds."""
    rng = random.Random(59)
    st = _random_delta_state(rng)
    R, E = st.present.shape
    st = gossip.delta_gossip_round(st, gossip.ring_perm(R, 1),
                                   delta_semantics="v2")
    for off in (2, 1, 4):
        perm = gossip.ring_perm(R, off)
        dense = gossip.delta_gossip_round(st, perm, delta_semantics="v2")
        comp = gossip.compact_delta_gossip_round(st, perm, E, E)
        for name in dense._fields:
            assert np.array_equal(np.asarray(getattr(dense, name)),
                                  np.asarray(getattr(comp, name))), \
                (off, name)
        st = dense


def test_tiny_k_rounds_are_safe_and_dense_rounds_complete():
    """Overflowed compact rounds are lossy-but-safe: membership keeps
    its invariants and a dense schedule afterwards still converges to
    the same fixed point as a pure-dense run."""
    rng = random.Random(61)
    st = _random_delta_state(rng)
    R = st.present.shape[0]
    lossy = st
    for off in (1, 2, 4, 1):
        lossy = gossip.compact_delta_gossip_round(
            lossy, gossip.ring_perm(R, off), 2, 2)
    # dense completion from the lossy state
    done = gossip.all_pairs_converge(lossy, delta=True,
                                     delta_semantics="v2")
    ref = gossip.all_pairs_converge(st, delta=True, delta_semantics="v2")
    assert bool(collectives.converged(done.present, done.vv))
    assert np.array_equal(np.asarray(done.present), np.asarray(ref.present))
    assert np.array_equal(np.asarray(done.vv), np.asarray(ref.vv))


def test_compact_ring_shardmap_matches_jit_round():
    rng = random.Random(67)
    st = _random_delta_state(rng, R=16, E=32, A=16)
    m = mesh_mod.make_mesh((8, 1))
    sharded = mesh_mod.shard_state(st, m)
    ring = gossip.compact_ring_round_shardmap(sharded, m, 32, 32)
    # ring: device i's block -> i+1, i.e. replica r absorbs r - shard_size
    perm = (jnp.arange(16, dtype=jnp.uint32) - 2) % 16
    expected = gossip.compact_delta_gossip_round(st, perm, 32, 32)
    for name in ring._fields:
        assert np.array_equal(np.asarray(getattr(ring, name)),
                              np.asarray(getattr(expected, name))), name


def test_compact_ring_rejects_sharded_element_axis():
    rng = random.Random(71)
    st = _random_delta_state(rng, R=8, E=32, A=8)
    m = mesh_mod.make_mesh((4, 2))
    with pytest.raises(ValueError):
        gossip.compact_ring_round_shardmap(st, m)
