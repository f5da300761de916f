"""Parallel-layer tests on a virtual 8-device CPU mesh: sharding
transparency (sharded == unsharded bitwise), convergence of every schedule,
fault injection, the explicit shard_map ring, and the collective reductions.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from go_crdt_playground_tpu.models import awset, awset_delta
from go_crdt_playground_tpu.ops import delta as delta_ops
from go_crdt_playground_tpu.parallel import collectives, gossip, mesh as mesh_mod


def _random_state(rng, R=16, E=32, A=16, delta=False):
    """Independent replica histories via the jitted local ops."""
    st = (awset_delta if delta else awset).init(R, E, A)
    for _ in range(4 * R):
        r = rng.randrange(R)
        e = rng.randrange(E)
        if rng.random() < 0.75:
            st = (awset_delta if delta else awset).add_element(
                st, np.uint32(r), np.uint32(e))
        elif delta:
            sel = np.zeros(E, bool)
            sel[e] = True
            st = awset_delta.del_elements(st, np.uint32(r), np.asarray(sel))
        else:
            st = awset.del_element(st, np.uint32(r), np.uint32(e))
    return st


def _assert_states_equal(a, b, context=""):
    for name in a._fields:
        assert np.array_equal(np.asarray(getattr(a, name)),
                              np.asarray(getattr(b, name))), (context, name)


def test_eight_virtual_devices_present():
    assert len(jax.devices()) == 8


def test_sharded_gossip_bitwise_equals_unsharded():
    """The same gossip round must produce identical bytes whether the
    replica/element axes are sharded over the mesh or on one device —
    sharding is a layout choice, never a semantics choice."""
    import random
    rng = random.Random(5)
    state = _random_state(rng)
    R = state.vv.shape[0]
    perm = gossip.ring_perm(R, 3)
    plain = gossip.gossip_round_jit(state, perm)
    m = mesh_mod.make_mesh((4, 2))
    sharded_in = mesh_mod.shard_state(state, m)
    sharded = gossip.gossip_round_jit(sharded_in, perm)
    _assert_states_equal(plain, sharded, "ring offset 3")
    # butterfly stage too
    perm2 = gossip.butterfly_perm(R, 2)
    _assert_states_equal(
        gossip.gossip_round_jit(state, perm2),
        gossip.gossip_round_jit(sharded_in, perm2),
        "butterfly stage 2",
    )


def test_all_pairs_converges_to_union_log2_rounds():
    import random
    rng = random.Random(7)
    state = _random_state(rng, R=16, E=32, A=16)
    out = gossip.all_pairs_converge(state)
    present = np.asarray(out.present)
    vv = np.asarray(out.vv)
    assert bool(collectives.converged(out.present, out.vv))
    # all replicas agree
    assert (present == present[0]).all()
    assert (vv == vv[0]).all()
    # VV is the global join
    assert np.array_equal(vv[0], np.asarray(
        collectives.global_vv_join(state.vv)))


def test_rounds_to_convergence_dissemination_bound():
    import random
    rng = random.Random(9)
    state = _random_state(rng, R=16)
    rounds, out = gossip.rounds_to_convergence(state)
    assert bool(collectives.converged(out.present, out.vv))
    assert rounds <= 4 + 1, rounds  # ceil(log2 16) = 4 (+1 slack)


@pytest.mark.parametrize("drop_rate", [0.3, 0.6])
def test_convergence_under_message_drops(drop_rate):
    """Masked merges (lost exchanges) must still converge — the
    self-healing property the reference documents (awset.go:28-35) turned
    into a fault-injection test (SURVEY §5.3)."""
    import random
    rng = random.Random(11)
    state = _random_state(rng, R=16)
    rounds, out = gossip.rounds_to_convergence(
        state, key=jax.random.PRNGKey(0), drop_rate=drop_rate,
        schedule="random", max_rounds=500)
    assert bool(collectives.converged(out.present, out.vv)), drop_rate
    assert rounds < 500


def test_delta_gossip_converges_and_gc_empties_log():
    import random
    rng = random.Random(13)
    state = _random_state(rng, R=8, E=16, A=8, delta=True)
    R = 8
    for off in gossip.dissemination_offsets(R) * 2:
        state = gossip.delta_gossip_round_jit(
            state, gossip.ring_perm(R, off))
    assert bool(collectives.converged(state.present, state.vv))
    frontier = delta_ops.gc_frontier(state.processed)
    cleaned = delta_ops.gc_apply(state, frontier)
    assert not np.asarray(cleaned.deleted).any()


def test_delta_gossip_sharded_equals_unsharded():
    import random
    rng = random.Random(17)
    state = _random_state(rng, R=8, E=16, A=8, delta=True)
    perm = gossip.ring_perm(8, 1)
    plain = gossip.delta_gossip_round_jit(state, perm)
    m = mesh_mod.make_mesh((8, 1))
    sharded = gossip.delta_gossip_round_jit(
        mesh_mod.shard_state(state, m), perm)
    _assert_states_equal(plain, sharded)


def test_pipelined_delta_gossip_converges_to_same_fixed_point():
    """The double-buffered PP schedule (one round of payload staleness)
    must reach the same (membership, VV) fixed point as the unpipelined
    δ gossip — staleness only delays shipment, never changes the join."""
    import random
    rng = random.Random(37)
    R = 16
    state = _random_state(rng, R=R, E=32, A=16, delta=True)
    offsets = gossip.dissemination_offsets(R)
    # pipeline depth 2 => cycle the dissemination schedule enough times
    # to cover the lag (2x + slack)
    perms = jnp.stack([gossip.ring_perm(R, o) for o in offsets] * 3)
    piped = gossip.pipelined_delta_gossip(state, perms)
    assert bool(collectives.converged(piped.present, piped.vv))
    ref = gossip.all_pairs_converge(state, delta=True,
                                    delta_semantics="v2")
    assert bool(collectives.converged(ref.present, ref.vv))
    assert np.array_equal(np.asarray(piped.present), np.asarray(ref.present))
    assert np.array_equal(np.asarray(piped.vv), np.asarray(ref.vv))


def test_pipelined_round_lag_is_exactly_one():
    """Data added before round 0 reaches the ring neighbor at round 1
    (payload for round 0 is extracted fresh), but data present only in
    the staged buffer propagates with the documented one-round lag."""
    R, E, A = 4, 8, 4
    state = awset_delta.init(R, E, A)
    state = awset_delta.add_element(state, np.uint32(0), np.uint32(3))
    perms = jnp.stack([gossip.ring_perm(R, 1)])  # replica r absorbs r+1
    one = gossip.pipelined_delta_gossip(state, perms)
    # replica 3 absorbs replica 0's fresh payload in round 0
    assert bool(one.present[3, 3])
    assert not bool(one.present[2, 3])


def test_ring_shardmap_matches_equivalent_gather_round():
    """The explicit ppermute ring (device i's block -> device i+1) is the
    gather round with offset -shard_size; both paths must agree bitwise."""
    import random
    rng = random.Random(19)
    R = 16
    state = _random_state(rng, R=R)
    m = mesh_mod.make_mesh((8, 1))
    sharded = mesh_mod.shard_state(state, m)
    ring = gossip.ring_round_shardmap(sharded, m)
    shard_size = R // 8
    perm = (jnp.arange(R, dtype=jnp.uint32) - shard_size) % R
    expected = gossip.gossip_round_jit(state, perm)
    _assert_states_equal(ring, expected)


def test_ring_shardmap_pallas_matches_xla():
    """The per-shard fused Pallas ring (the TPU-mesh fast path) must agree bitwise with the XLA shard_map ring AND
    the unsharded gather round — on the CPU test mesh the kernel runs
    in interpret mode, on real TPU it is the Mosaic program."""
    import random
    rng = random.Random(23)
    R = 16
    for shape in ((8, 1), (4, 2)):
        state = _random_state(rng, R=R, E=32)
        m = mesh_mod.make_mesh(shape)
        sharded = mesh_mod.shard_state(state, m)
        fused = gossip.ring_round_shardmap(sharded, m, kernel="pallas")
        plain = gossip.ring_round_shardmap(sharded, m, kernel="xla")
        _assert_states_equal(fused, plain, f"mesh {shape}")
        shard_size = R // shape[0]
        perm = (jnp.arange(R, dtype=jnp.uint32) - shard_size) % R
        _assert_states_equal(fused, gossip.gossip_round_jit(state, perm),
                             f"mesh {shape} vs gather")


def test_ep_ring_matches_replicated_actor_ring():
    """EP layout (vv's actor axis sharded over the mesh element dim,
    SURVEY §2.3 EP row) must be invisible in the results: the EP ring
    round agrees bitwise with the replicated-actor ring round on the
    same mesh, and with the equivalent gather round."""
    import random
    rng = random.Random(29)
    R, A = 16, 16
    state = _random_state(rng, R=R, E=32, A=A)
    for shape in ((4, 2), (2, 4)):
        m = mesh_mod.make_mesh(shape)
        ep = gossip.ep_ring_round_shardmap(
            mesh_mod.shard_state(state, m, shard_actors=True), m)
        plain = gossip.ring_round_shardmap(
            mesh_mod.shard_state(state, m), m)
        _assert_states_equal(ep, plain, f"mesh {shape}")
        shard_size = R // shape[0]
        perm = (jnp.arange(R, dtype=jnp.uint32) - shard_size) % R
        _assert_states_equal(ep, gossip.gossip_round_jit(state, perm),
                             f"mesh {shape} vs gather")


def test_ep_ring_rejects_indivisible_actor_axis():
    state = awset.init(16, 32, 12, actors=np.arange(16) % 12)
    m = mesh_mod.make_mesh((1, 8))   # A=12 not divisible by 8
    with pytest.raises(ValueError):
        gossip.ep_ring_round_shardmap(state, m)
    with pytest.raises(ValueError):
        mesh_mod.shard_state(state, m, shard_actors=True)


def test_ormap_gossip_round_matches_lattice_join():
    """The fast OR-Map round (AWSet kernel for membership + elementwise
    LWW for cells) is bitwise the generic lattice-join round."""
    import random
    from go_crdt_playground_tpu.ops import lattices as L

    rng = random.Random(73)
    R, E = 8, 16
    st = L.ormap_init(R, E, R)
    ts = 0
    for _ in range(60):
        r, e = rng.randrange(R), rng.randrange(E)
        if rng.random() < 0.7:
            ts += 1
            st = L.ormap_put(st, np.uint32(r), np.uint32(e),
                             np.uint32(rng.randrange(1, 99)), np.uint32(ts))
        else:
            st = L.ormap_delete(st, np.uint32(r), np.uint32(e))
    for off in (1, 3):
        perm = gossip.ring_perm(R, off)
        want = L.gossip_round(L.ormap_join, st, perm)
        for kernel in ("xla", "pallas"):
            got = gossip.ormap_gossip_round(st, perm, kernel=kernel)
            _assert_states_equal(want, got, f"off {off} kernel {kernel}")
        st = want


def test_config_factories():
    from go_crdt_playground_tpu.config import REFERENCE_CONFIG, Config

    st = REFERENCE_CONFIG.init_awset()
    assert st.present.shape == (3, 16) and st.vv.shape == (3, 3)
    d = REFERENCE_CONFIG.element_dict()
    assert d.capacity == 16
    cfg = Config(num_replicas=8, num_elements=32, num_actors=8,
                 mesh_shape=(4, 2))
    ds = cfg.init_awset_delta()
    assert ds.deleted.shape == (8, 32)
    m = cfg.make_mesh()
    assert dict(m.shape) == {"replica": 4, "element": 2}


def test_gossip_determinism():
    import random
    rng = random.Random(23)
    state = _random_state(rng)
    perm = gossip.ring_perm(16, 5)
    a = gossip.gossip_round_jit(state, perm)
    b = gossip.gossip_round_jit(state, perm)
    _assert_states_equal(a, b)


def test_butterfly_stage_guard():
    with pytest.raises(ValueError):
        gossip.butterfly_perm(8, 3)   # 1<<3 == 8: JAX would clamp silently
    with pytest.raises(ValueError):
        gossip.butterfly_perm(12, 1)  # not a power of two


def test_rounds_to_convergence_raises_on_budget_exhaustion():
    import random
    rng = random.Random(3)
    state = _random_state(rng, R=16)
    with pytest.raises(RuntimeError):
        gossip.rounds_to_convergence(
            state, key=jax.random.PRNGKey(0), drop_rate=0.99,
            schedule="random", max_rounds=3)


def test_membership_hash_properties():
    present = jnp.zeros((3, 16), bool)
    h0 = np.asarray(collectives.membership_hash(present))
    assert (h0 == 0).all()
    p1 = present.at[0, 3].set(True).at[0, 7].set(True)
    p2 = present.at[1, 7].set(True).at[1, 3].set(True)  # order-free
    h = np.asarray(collectives.membership_hash(p1 | p2))
    assert h[0] == h[1] != 0
    # digest includes the VV
    vv = jnp.zeros((3, 4), jnp.uint32)
    d1 = np.asarray(collectives.state_digest(p1 | p2, vv))
    d2 = np.asarray(collectives.state_digest(p1 | p2, vv.at[0, 0].set(1)))
    assert d1[0] != d2[0]


@pytest.mark.parametrize("check_every", [1, 4, 32])
def test_rounds_to_convergence_chunked_exact(check_every):
    """The chunked convergence loop returns the SAME minimal round count
    for any chunk size (bisect replays from the chunk start with
    index-derived randomness), including under drops."""
    import random
    rng = random.Random(21)
    state = _random_state(rng, R=16)
    want_rounds, want_out = gossip.rounds_to_convergence(
        state, key=jax.random.PRNGKey(5), drop_rate=0.4,
        schedule="random", max_rounds=300, check_every=1)
    got_rounds, got_out = gossip.rounds_to_convergence(
        state, key=jax.random.PRNGKey(5), drop_rate=0.4,
        schedule="random", max_rounds=300, check_every=check_every)
    assert got_rounds == want_rounds
    for a, b in zip(jax.tree.leaves(want_out), jax.tree.leaves(got_out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ormap_ring_round_matches_perm_round():
    """Offset-form OR-Map ring round == perm-form round, bitwise, on
    both kernel paths (pallas runs in interpret mode on CPU) and with
    traced offsets through a scanned schedule."""
    import random
    from go_crdt_playground_tpu.ops import lattices as L

    rng = random.Random(31)
    from go_crdt_playground_tpu.ops import pallas_merge

    R_, E_ = 2 * pallas_merge._BLOCK_R, 8  # ring-kernel-eligible R
    st = L.ormap_init(R_, E_, R_)
    ts = 0
    for _ in range(60):
        r, e = rng.randrange(R_), rng.randrange(E_)
        if rng.random() < 0.6:
            ts += 1
            st = L.ormap_put(st, np.uint32(r), np.uint32(e),
                             np.uint32(rng.randrange(1, 99)),
                             np.uint32(ts))
        else:
            st = L.ormap_delete(st, np.uint32(r), np.uint32(e))
    st0 = st
    for off in (1, 5, 15):
        want = gossip.ormap_gossip_round(st, gossip.ring_perm(R_, off),
                                         kernel="xla")
        for kernel in ("xla", "pallas"):
            got = gossip.ormap_ring_gossip_round(st, off, kernel=kernel)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                              err_msg=f"{off}/{kernel}")
        st = want

    # traced offsets through a scanned schedule reuse one program
    offsets = jnp.asarray([1, 5, 15], jnp.uint32)

    @jax.jit
    def run(s):
        def body(c, off):
            return gossip.ormap_ring_gossip_round(c, off), None
        return jax.lax.scan(body, s, offsets)[0]

    got = run(st0)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_packed_block_ring_shardmap_bitwise_and_converges():
    """The sharded bitpacked δ ring (gossip.packed_block_ring_round_shardmap):

    * block-aligned offsets must equal the single-device packed ring
      round bitwise (same global pairing, explicit ppermute + stacked
      kernel is pure layout);
    * intra offsets must equal the per-block packed round bitwise
      (documented per-block wraparound pairing);
    * the composed dissemination schedule (intra doublings then block
      doublings) must converge the fleet.
    """
    import random

    from go_crdt_playground_tpu.models import packed as packed_mod
    from go_crdt_playground_tpu.ops import pallas_delta
    from tests.test_pallas_delta import _scenario_state

    n = 8
    blk = 64
    R, E, A = n * blk, 96, 8
    rng = random.Random(11)
    state = _scenario_state(rng, R, E, A)
    packed = packed_mod.pack_awset_delta(state)
    m = mesh_mod.make_mesh((n, 1))
    sharded = mesh_mod.shard_state(packed, m)

    # block-aligned: bitwise vs the global packed ring round
    got = gossip.packed_block_ring_round_shardmap(sharded, m, blk)
    want = pallas_delta.pallas_delta_ring_round_packed(packed, blk)
    for name in want._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)),
            np.asarray(getattr(want, name)), err_msg=f"aligned/{name}")

    # intra: bitwise vs the packed round applied per block
    off = 3
    got = gossip.packed_block_ring_round_shardmap(sharded, m, off)
    for b in range(n):
        sl = slice(b * blk, (b + 1) * blk)
        block = jax.tree.map(lambda x: x[sl], packed)
        # per-block reference via the stacked form on one device (blk=64
        # alone is below ring_supported, which is exactly why the
        # shard_map path stacks)
        stacked = jax.tree.map(
            lambda x: jnp.concatenate([x, x], axis=0), block)
        want_b = jax.tree.map(
            lambda x: x[:blk],
            pallas_delta.pallas_delta_ring_round_packed(stacked, blk + off))
        for name in want_b._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(got, name))[sl],
                np.asarray(getattr(want_b, name)),
                err_msg=f"intra/block{b}/{name}")

    # composed dissemination: intra doublings, then block doublings
    st = sharded
    o = 1
    while o < blk:
        st = gossip.packed_block_ring_round_shardmap(st, m, o)
        o *= 2
    while o < R:
        st = gossip.packed_block_ring_round_shardmap(st, m, o)
        o *= 2
    assert bool(collectives.converged_packed(st.present_bits, st.vv))
    # and it must agree with the bool-layout convergence digest
    unpacked = packed_mod.unpack_awset_delta(
        jax.tree.map(np.asarray, st), E)
    assert bool(collectives.converged(unpacked.present, unpacked.vv))


def test_packed_block_ring_shardmap_rejects_untileable_block():
    """An R/mesh combo whose per-device block stacks below the packed
    ring kernel's tiling must fail at the API boundary with a clear
    error, not inside kernel layout asserts (ADVICE r4)."""
    from go_crdt_playground_tpu.models import packed as packed_mod

    n = 8
    R, E, A = n * 8, 96, 64  # blk=8 -> stacked block 16 rows: untileable
    state = awset_delta.init(R, E, A)
    packed = packed_mod.pack_awset_delta(state)
    m = mesh_mod.make_mesh((n, 1))
    sharded = mesh_mod.shard_state(packed, m)
    with pytest.raises(ValueError, match="stacks to a 16-row"):
        gossip.packed_block_ring_round_shardmap(sharded, m, 8)


def test_butterfly_shardmap_bitwise_and_converges():
    """The mesh-native butterfly stage (gossip.butterfly_round_shardmap):
    every stage — block-local and device-swap,
    XLA and per-shard fused kernels — must equal the unsharded butterfly
    round bitwise, and the full hypercube schedule must converge."""
    import random
    rng = random.Random(41)
    R = 16
    state = _random_state(rng, R=R, E=32, A=16)
    for shape in ((8, 1), (4, 2)):
        m = mesh_mod.make_mesh(shape)
        sharded = mesh_mod.shard_state(state, m)
        for stage in range(4):  # blk=2: stage 0 local; 1..3 device swaps
            want = gossip.gossip_round_jit(
                state, gossip.butterfly_perm(R, stage))
            for kernel in ("xla", "pallas"):
                got = gossip.butterfly_round_shardmap(
                    sharded, m, stage, kernel=kernel)
                _assert_states_equal(
                    got, want, f"mesh {shape} stage {stage} {kernel}")
    # full hypercube schedule = all-pairs convergence
    m = mesh_mod.make_mesh((4, 2))
    st = mesh_mod.shard_state(state, m)
    for stage in range(4):
        st = gossip.butterfly_round_shardmap(st, m, stage)
    assert bool(collectives.converged(st.present, st.vv))


def test_butterfly_shardmap_validation():
    import random
    rng = random.Random(43)
    m = mesh_mod.make_mesh((8, 1))
    with pytest.raises(ValueError, match="power-of-two replica"):
        gossip.butterfly_round_shardmap(
            mesh_mod.shard_state(_random_state(rng, R=24, A=24), m), m, 1)
    st = mesh_mod.shard_state(_random_state(rng, R=16), m)
    with pytest.raises(ValueError, match="out of range"):
        gossip.butterfly_round_shardmap(st, m, 4)


def test_multi_device_tpu_slow_path_warns(monkeypatch):
    """A general-perm gossip round on a multi-device TPU process drops
    to the ~40x XLA HasDot path; that must be LOUD, while kernel='xla' acknowledges it silently."""
    import warnings as warnings_mod

    import random
    rng = random.Random(47)
    state = _random_state(rng, R=8, E=16, A=8)
    perm = gossip.butterfly_perm(8, 1)
    monkeypatch.setattr(gossip.jax, "default_backend", lambda: "tpu")
    with pytest.warns(UserWarning, match="40x"):
        gossip.gossip_round(state, perm)
    # explicit kernel choice is an acknowledgement — no warning
    with warnings_mod.catch_warnings():
        warnings_mod.simplefilter("error")
        gossip.gossip_round(state, perm, kernel="xla")


def test_butterfly_schedule_converges_in_exactly_log2_rounds():
    """The butterfly schedule's m distinct XOR stages are hypercube
    dissemination: a divergent power-of-two fleet converges in exactly
    ceil(log2 R) rounds — the tight bound, not just <= with slack."""
    import random
    rng = random.Random(53)
    state = _random_state(rng, R=16, E=32, A=16)
    rounds, out = gossip.rounds_to_convergence(state, schedule="butterfly")
    assert bool(collectives.converged(out.present, out.vv))
    assert rounds == 4
    with pytest.raises(ValueError, match="power-of-two"):
        gossip.rounds_to_convergence(
            _random_state(rng, R=12, A=12), schedule="butterfly")


def test_dotword_block_ring_shardmap_bitwise_and_converges():
    """packed_block_ring_round_shardmap on the DOT-WORD δ layout
    (uint32 dot words crossing ICI — ~1.5x less ring-cut traffic than
    the bitpacked layout): block-aligned offsets must equal the
    single-device dot-word ring bitwise; the composed dissemination
    schedule must converge."""
    import random

    from go_crdt_playground_tpu.models import packed as packed_mod
    from go_crdt_playground_tpu.ops import pallas_delta
    from tests.test_pallas_delta import _scenario_state

    n, blk = 8, 64
    R, E, A = n * blk, 96, 8
    rng = random.Random(83)
    state = _scenario_state(rng, R, E, A)
    packed = packed_mod.pack_awset_delta_dots(state)
    m = mesh_mod.make_mesh((n, 1))
    sharded = mesh_mod.shard_state(packed, m)

    got = gossip.packed_block_ring_round_shardmap(sharded, m, blk)
    want = pallas_delta.pallas_delta_ring_round_dotpacked(packed, blk)
    for name in want._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)),
            np.asarray(getattr(want, name)), err_msg=f"aligned/{name}")

    st, o = sharded, 1
    while o < R:
        st = gossip.packed_block_ring_round_shardmap(st, m, o)
        o *= 2
    out = packed_mod.unpack_awset_delta_dots(st, E)
    assert bool(collectives.converged(out.present, out.vv))


def test_fullstate_packed_block_ring_shardmap_bitwise():
    """The sharded block ring also serves the FULL-STATE packed layouts
    (bitpacked and dot-word AWSetState): block-aligned offsets bitwise-
    equal the single-device kernels."""
    from go_crdt_playground_tpu.models import packed as packed_mod
    from go_crdt_playground_tpu.ops import pallas_merge
    from tests.test_packed import rand_state

    n, blk = 8, 64
    R, E, A = n * blk, 96, 8
    rng = np.random.default_rng(87)
    state = rand_state(rng, R, E, A)
    m = mesh_mod.make_mesh((n, 1))
    for pack, ring in (
            (packed_mod.pack_awset,
             pallas_merge.pallas_ring_round_rows_packed),
            (packed_mod.pack_awset_dots,
             pallas_merge.pallas_ring_round_rows_dotpacked)):
        p = pack(state)
        sharded = mesh_mod.shard_state(p, m)
        got = gossip.packed_block_ring_round_shardmap(sharded, m, blk)
        want = ring(p, blk)
        for name in want._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(got, name)),
                np.asarray(getattr(want, name)),
                err_msg=f"{pack.__name__}/{name}")
