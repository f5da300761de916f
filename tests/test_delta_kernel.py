"""δ-kernel conformance: the tensor δ path must match the spec AWSetDelta
bit-for-bit — entries, VVs, deletion log, processed vectors — in BOTH
semantics modes, on the reference's δ scenario and randomized soups.
GC (collective-frontier causal stability) is tested for safety and
convergence separately, since the spec tracks per-peer acks while the
batched SPMD design computes the exact global frontier.
"""

import random

import numpy as np
import pytest

from go_crdt_playground_tpu.models import awset_delta
from go_crdt_playground_tpu.models.spec import AWSetDelta, VersionVector
from go_crdt_playground_tpu.ops import delta as delta_ops
from go_crdt_playground_tpu.utils.codec import ElementDict, pack_awset_deltas


class DualWorldDelta:
    """Runs one op sequence on the spec δ model and the packed δ tensor
    path, asserting bitwise equality of all nine arrays after each step."""

    ARRAYS = ("vv", "present", "dot_actor", "dot_counter", "actor",
              "deleted", "del_dot_actor", "del_dot_counter", "processed")

    def __init__(self, num_replicas=2, num_elements=16, num_actors=None,
                 mode="reference", strict=True):
        A = num_actors if num_actors is not None else num_replicas
        self.A, self.E = A, num_elements
        self.mode, self.strict = mode, strict
        self.spec = [
            AWSetDelta(actor=i, version_vector=VersionVector([0] * A),
                       delta_semantics=mode,
                       strict_reference_semantics=strict)
            for i in range(num_replicas)
        ]
        self.state = awset_delta.init(num_replicas, num_elements, A)
        self.dictionary = ElementDict(capacity=num_elements)

    def add(self, r, *keys):
        self.spec[r].add(*keys)
        for k in keys:
            e = self.dictionary.encode(k)
            self.state = awset_delta.add_element(
                self.state, np.uint32(r), np.uint32(e))

    def del_(self, r, *keys):
        """One Del(k...) call — a single clock tick for the whole key set
        (awset-delta_test.go:15)."""
        self.spec[r].del_(*keys)
        sel = np.zeros(self.E, bool)
        for k in keys:
            sel[self.dictionary.encode(k)] = True
        self.state = awset_delta.del_elements(
            self.state, np.uint32(r), np.asarray(sel))

    def merge(self, dst, src):
        self.spec[dst].merge(self.spec[src])
        self.state = delta_ops.delta_merge_one_into(
            self.state, dst, self.state, src,
            delta_semantics=self.mode,
            strict_reference_semantics=self.strict)

    def check(self, context=""):
        packed = pack_awset_deltas(self.spec, self.dictionary, self.A)
        actual = awset_delta.to_arrays(self.state)
        for name in self.ARRAYS:
            assert np.array_equal(packed[name], actual[name]), (
                self.mode, context, name, packed[name], actual[name])

    def members(self, r):
        arr = awset_delta.to_arrays(self.state)
        return sorted(
            self.dictionary.decode(int(e))
            for e in np.nonzero(arr["present"][r])[0]
        )


@pytest.mark.parametrize("mode", ["reference", "v2"])
def test_delta_kernel_reference_scenario(mode):
    """TestAWSetDelta (awset-delta_test.go:168-189) on the tensor path."""
    w = DualWorldDelta(mode=mode)
    w.add(0, "A", "B"); w.add(1, "A", "C"); w.check()
    w.merge(0, 1); w.check("A<-B full")
    w.merge(1, 0); w.check("B<-A delta")
    assert w.members(0) == ["A", "B", "C"]
    w.del_(0, "B"); w.add(0, "D", "E"); w.add(1, "E"); w.check()
    w.merge(1, 0); w.check("B<-A delta 2")
    assert w.members(1) == ["A", "C", "D", "E"]
    w.merge(0, 1); w.check("A<-B delta (empty)")
    assert w.members(0) == ["A", "C", "D", "E"]


def test_delta_kernel_strict_clock_divergence():
    """The strict empty-δ VV-skip quirk must reproduce the exact divergent
    clocks of the reference replay (SURVEY §3.3: A=[5,2], B=[5,3])."""
    w = DualWorldDelta(mode="reference", strict=True)
    w.add(0, "A", "B"); w.add(1, "A", "C")
    w.merge(0, 1); w.merge(1, 0)
    w.del_(0, "B"); w.add(0, "D", "E"); w.add(1, "E")
    w.merge(1, 0); w.merge(0, 1); w.check("final")
    arr = awset_delta.to_arrays(w.state)
    assert arr["vv"][0].tolist() == [5, 2]
    assert arr["vv"][1].tolist() == [5, 3]


@pytest.mark.parametrize("mode,strict", [
    ("reference", True), ("reference", False), ("v2", True)])
@pytest.mark.parametrize("seed", [0, 1])
def test_delta_kernel_randomized_conformance(mode, strict, seed):
    """Randomized 3-replica op soups, bitwise agreement after every op in
    both semantics modes."""
    rng = random.Random(seed + (0 if mode == "reference" else 100)
                        + (0 if strict else 1000))
    universe = [f"k{i}" for i in range(10)]
    w = DualWorldDelta(num_replicas=3, num_elements=12, num_actors=3,
                       mode=mode, strict=strict)
    for step in range(100):
        p = rng.random()
        r = rng.randrange(3)
        if p < 0.4:
            w.add(r, rng.choice(universe))
        elif p < 0.65:
            # multi-key deletes exercise the shared-dot rule
            ks = rng.sample(universe, rng.randint(1, 2))
            w.del_(r, *ks)
        else:
            s = rng.randrange(3)
            if s != r:
                w.merge(r, s)
        w.check(f"mode={mode} seed={seed} step={step}")


def test_delta_payload_masks_match_spec_extraction():
    """delta_extract must produce exactly the (changed, deleted) key sets
    of MakeDeltaMergeData (awset-delta_test.go:79-105), including the
    re-add filter."""
    w = DualWorldDelta(mode="reference")
    w.add(0, "k", "q"); w.add(1, "z")
    w.merge(1, 0); w.merge(0, 1)
    w.del_(0, "k"); w.add(0, "k")   # deleted then re-added: record obsolete
    w.del_(0, "q")                  # genuinely deleted
    w.add(0, "new")
    changed_spec, deleted_spec = w.spec[0].make_delta_merge_data(
        w.spec[1].version_vector)
    import jax
    src = jax.tree.map(lambda x: x[0], w.state)
    dst_vv = w.state.vv[1]
    payload = delta_ops.delta_extract(src, dst_vv)
    changed_ids = {w.dictionary.decode(int(e))
                   for e in np.nonzero(np.asarray(payload.changed))[0]}
    deleted_ids = {w.dictionary.decode(int(e))
                   for e in np.nonzero(np.asarray(payload.deleted))[0]}
    assert changed_ids == set(changed_spec or {})
    assert deleted_ids == set(deleted_spec or {})


def test_gc_frontier_safety_and_convergence():
    """Collective-frontier GC: records drop exactly when every
    participating replica's processed vector covers them, and dropping
    them never breaks convergence."""
    w = DualWorldDelta(num_replicas=3, num_elements=12, num_actors=3,
                       mode="v2")
    w.add(0, "k"); w.add(1, "b"); w.add(2, "c")
    w.merge(1, 0); w.merge(2, 0); w.merge(0, 1); w.merge(0, 2)
    w.merge(1, 2); w.merge(2, 1)
    w.del_(0, "k")
    # Before anyone hears of the deletion, the frontier must not cover it.
    frontier = delta_ops.gc_frontier(w.state.processed)
    arr = awset_delta.to_arrays(w.state)
    e = w.dictionary.encode("k")
    assert arr["deleted"][0][e]
    del_counter = int(arr["del_dot_counter"][0][e])
    assert int(np.asarray(frontier)[0]) < del_counter
    gced = delta_ops.gc_apply(w.state, frontier)
    assert np.asarray(gced.deleted)[0][e], "record must survive"
    # Propagate to everyone, then the frontier covers it and GC drops it.
    w.merge(1, 0); w.merge(2, 0)
    frontier = delta_ops.gc_frontier(w.state.processed)
    assert int(np.asarray(frontier)[0]) >= del_counter
    gced = delta_ops.gc_apply(w.state, frontier)
    assert not np.asarray(gced.deleted).any()
    # State after GC still converges (no entries resurrect).
    for r in range(3):
        assert not np.asarray(gced.present)[r][e]


def test_gc_participation_mask_blocks_frontier():
    """A participating replica that has not processed the deletion blocks
    the frontier; excluding it via the mask unblocks (the operator's
    escape hatch for decommissioned replicas)."""
    w = DualWorldDelta(num_replicas=3, num_elements=8, num_actors=3,
                       mode="v2")
    w.add(0, "k"); w.add(1, "b"); w.add(2, "c")
    w.merge(1, 0); w.merge(2, 0); w.merge(0, 1); w.merge(0, 2)
    w.merge(1, 2); w.merge(2, 1)
    w.del_(0, "k")
    w.merge(1, 0)   # replica 2 never hears of it
    e = w.dictionary.encode("k")
    arr = awset_delta.to_arrays(w.state)
    del_counter = int(arr["del_dot_counter"][0][e])
    frontier = delta_ops.gc_frontier(w.state.processed)
    assert int(np.asarray(frontier)[0]) < del_counter
    masked = delta_ops.gc_frontier(
        w.state.processed, participating=np.array([True, True, False]))
    assert int(np.asarray(masked)[0]) >= del_counter


def test_add_elements_batch_matches_sequential_adds():
    """add_elements (one fused dispatch per Add(k...) call, the add-path
    analogue of the del_elements selector) must be
    bitwise the per-key add_element loop, including the duplicate-key
    case where the loop's later tick overwrites the earlier dot."""
    def seed(st):
        # pre-existing foreign-actor dot with a high counter: the batched
        # overwrite must NOT keep it (Add overwrites unconditionally)
        return st._replace(
            present=st.present.at[0, 9].set(True),
            dot_actor=st.dot_actor.at[0, 9].set(1),
            dot_counter=st.dot_counter.at[0, 9].set(100),
        )

    for ids in ([3, 7, 1], [5], [2, 9, 2, 4, 2], list(range(12))):
        seq = seed(awset_delta.init(2, 16, 2))
        bat = seed(awset_delta.init(2, 16, 2))
        pad = seed(awset_delta.init(2, 16, 2))
        for e in ids:
            seq = awset_delta.add_element(seq, np.uint32(0), np.uint32(e))
        bat = awset_delta.add_elements(
            bat, np.uint32(0), np.asarray(ids, np.uint32))
        # the arity-bucketed form Node.add uses: zero-padded + count
        k = len(ids)
        bucket = 1 << (k - 1).bit_length()
        padded = np.zeros(bucket, np.uint32)
        padded[:k] = ids
        pad = awset_delta.add_elements(
            pad, np.uint32(0), padded, np.uint32(k))
        for name in DualWorldDelta.ARRAYS:
            a = np.asarray(getattr(seq, name))
            for variant, other in (("batch", bat), ("padded", pad)):
                b = np.asarray(getattr(other, name))
                assert np.array_equal(a, b), (ids, variant, name, a, b)


def test_v2_remove_arbitration_on_uncovered_sender_dots():
    """A sender whose VV does NOT cover its own shipped live dot — the
    compact-overflow state (ops/compact.py: partial data, NO clock
    advance) — ships a changed lane plus a matching deletion record.
    v2 removes only when the sender's CLOCK covers the live dot
    (models/spec.py arbitration), so the entry must SURVIVE; a
    'changed lanes are trivially covered' shortcut removes it (r4
    review repro).  Pinned on the XLA path and the fused kernel."""
    import jax.numpy as jnp

    from go_crdt_playground_tpu.ops import pallas_delta
    from go_crdt_playground_tpu.parallel import gossip

    R, E, A = 2, 8, 2
    zE = jnp.zeros((R, E), jnp.uint32)
    state = awset_delta.AWSetDeltaState(
        # row 0: receiver (actor 0) — saw the sender's counter 1 only
        # (delta dispatch engages, counter-2 dots are news), no entries
        # row 1: sender (actor 1) — live dot (1,2) AND deletion record
        # (1,2) on lane 0, with an all-zero VV (overflow state)
        vv=jnp.asarray([[0, 1], [0, 0]], jnp.uint32),
        present=jnp.zeros((R, E), bool).at[1, 0].set(True),
        dot_actor=zE.at[1, 0].set(1),
        dot_counter=zE.at[1, 0].set(2),
        actor=jnp.asarray([0, 1], jnp.uint32),
        deleted=jnp.zeros((R, E), bool).at[1, 0].set(True),
        del_dot_actor=zE.at[1, 0].set(1),
        del_dot_counter=zE.at[1, 0].set(2),
        processed=jnp.zeros((R, A), jnp.uint32),
    )
    perm = jnp.asarray([1, 0], jnp.uint32)
    want = gossip.delta_gossip_round(state, perm, delta_semantics="v2",
                                     kernel="xla")
    # the shipped entry survives: the sender's zero clock covers nothing
    assert bool(want.present[0, 0]), (
        "uncovered sender dot must not trigger removal")
    assert int(want.dot_counter[0, 0]) == 2
    got = pallas_delta.pallas_delta_gossip_round(state, perm,
                                                 delta_semantics="v2")
    for name in want._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(want, name)),
            np.asarray(getattr(got, name)), err_msg=name)
