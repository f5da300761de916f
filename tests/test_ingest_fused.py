"""Fused ingest+δ (ops/ingest.ingest_rows_delta + the Pallas twin):
bitwise pins against the seed two-pass path — apply via
``ingest_rows``, then a separate ``delta_extract`` — across
occupancies, padding rows, and the empty batch (the ISSUE-8 pin, same
style as the batch-vs-sequential pin in tests/test_serve.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from go_crdt_playground_tpu.models import awset_delta
from go_crdt_playground_tpu.ops import compact as compact_ops
from go_crdt_playground_tpu.ops import delta as delta_ops
from go_crdt_playground_tpu.ops import ingest as ingest_ops
from go_crdt_playground_tpu.ops.pallas_ingest import pallas_ingest_rows_delta

E, A = 72, 5


def _seeded_row(seed: int, warm_batches: int = 2):
    """A single-replica slice with history: adds, deletes, and a few
    foreign dots merged in (so δ extraction sees non-self actors)."""
    rng = np.random.default_rng(seed)
    st = awset_delta.init(1, E, A, actors=np.asarray([2], np.uint32))
    row = jax.tree.map(lambda x: x[0], st)
    for _ in range(warm_batches):
        row = ingest_ops.ingest_rows(
            row, jnp.asarray(rng.random((3, E)) < 0.25),
            jnp.asarray(rng.random((3, E)) < 0.15),
            jnp.ones(3, bool))
    # merge one foreign replica's state in (actor 0's dots land here)
    other = awset_delta.init(1, E, A, actors=np.asarray([0], np.uint32))
    orow = jax.tree.map(lambda x: x[0], other)
    orow = ingest_ops.ingest_rows(
        orow, jnp.asarray(rng.random((2, E)) < 0.2),
        jnp.asarray(rng.random((2, E)) < 0.1), jnp.ones(2, bool))
    payload = delta_ops.delta_extract(orow, row.vv)
    return delta_ops.delta_apply(row, payload, "v2")


def _batch(seed: int, b: int, density: float, live_pattern: str):
    rng = np.random.default_rng(seed)
    add = rng.random((b, E)) < density
    dl = rng.random((b, E)) < density / 2
    if live_pattern == "all":
        live = np.ones(b, bool)
    elif live_pattern == "none":
        live = np.zeros(b, bool)
    else:  # holes: padding rows interleaved with live ones
        live = (np.arange(b) % 3) != 1
    return add, dl, live


def _assert_trees_equal(got, want, label):
    for name in want._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)), np.asarray(getattr(want, name)),
            err_msg=f"{label}:{name}")


CASES = [
    (8, 0.15, "all"),      # typical occupancy
    (8, 0.15, "holes"),    # padding rows interleaved
    (8, 0.0, "all"),       # live rows, empty selectors (no-op ticks)
    (4, 0.9, "all"),       # dense batch (compact overflow at small K)
    (1, 0.2, "all"),       # single op
    (6, 0.2, "none"),      # all-padding batch
    (0, 0.0, "all"),       # empty batch axis
]


@pytest.mark.parametrize("b,density,live_pattern", CASES)
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_fused_matches_two_pass_bitwise(b, density, live_pattern, impl):
    """State AND payload of the fused path are bitwise the seed
    two-pass result, per occupancy/padding/empty-batch case."""
    row = _seeded_row(11)
    add, dl, live = _batch(29 + b, b, density, live_pattern)
    pre_vv = row.vv

    want_state = ingest_ops.ingest_rows(
        row, jnp.asarray(add), jnp.asarray(dl), jnp.asarray(live))
    want_payload = delta_ops.delta_extract(want_state, pre_vv)

    fn = (ingest_ops.ingest_rows_delta if impl == "xla"
          else pallas_ingest_rows_delta)
    got_state, got_payload, compact = fn(
        row, jnp.asarray(add), jnp.asarray(dl), jnp.asarray(live),
        k_changed=16, k_deleted=16)

    _assert_trees_equal(got_state, want_state, f"{impl}-state")
    _assert_trees_equal(got_payload, want_payload, f"{impl}-payload")
    # the compact form is the payload through ops/compact.py, verbatim
    want_compact = compact_ops.compact_payload(want_payload, 16, 16)
    _assert_trees_equal(compact, want_compact, f"{impl}-compact")


def test_compact_form_roundtrips_when_it_fits():
    """Non-overflow compact δ expands back to the dense payload
    bitwise — the WAL-record equivalence the replay path relies on."""
    row = _seeded_row(13)
    add, dl, live = _batch(31, 6, 0.05, "all")
    _, payload, compact = ingest_ops.ingest_rows_delta(
        row, jnp.asarray(add), jnp.asarray(dl), jnp.asarray(live),
        k_changed=64, k_deleted=64)
    assert not bool(compact.overflow)
    back = compact_ops.expand_payload(compact, E)
    _assert_trees_equal(back, payload, "roundtrip")


def test_overflow_flag_fires_and_dense_stays_authoritative():
    """A δ claiming more lanes than K sets overflow; the dense payload
    returned alongside is complete (the fallback record source)."""
    row = _seeded_row(17)
    add, dl, live = _batch(37, 8, 0.9, "all")
    _, payload, compact = ingest_ops.ingest_rows_delta(
        row, jnp.asarray(add), jnp.asarray(dl), jnp.asarray(live),
        k_changed=4, k_deleted=4)
    assert bool(compact.overflow)
    assert int(np.asarray(payload.changed).sum()) > 4
    # overflow neutralizes the compact vv (ops/compact.py contract);
    # the dense payload keeps the real one
    assert np.asarray(compact.src_vv).sum() == 0
    assert np.asarray(payload.src_vv).sum() > 0


def test_pallas_twin_covers_uncovered_preexisting_lanes():
    """δ extraction vs the PRE-batch vv must also ship pre-existing
    lanes whose dots the pre-batch vv never covered (the
    compact-overflow gossip path leaves those; the two-pass path
    shipped them and the fused paths must too)."""
    row = _seeded_row(19)
    # graft a foreign dot the vv does NOT cover (overflowed-compact
    # apply shape: data landed, clock never advanced)
    row = row._replace(
        present=row.present.at[7].set(True),
        dot_actor=row.dot_actor.at[7].set(jnp.uint32(4)),
        dot_counter=row.dot_counter.at[7].set(jnp.uint32(90)))
    add = np.zeros((2, E), bool)
    add[0, 3] = True
    dl = np.zeros((2, E), bool)
    live = np.ones(2, bool)
    pre_vv = row.vv
    want = delta_ops.delta_extract(
        ingest_ops.ingest_rows(row, jnp.asarray(add), jnp.asarray(dl),
                               jnp.asarray(live)), pre_vv)
    assert bool(np.asarray(want.changed)[7])  # the uncovered lane ships
    for impl, fn in (("xla", ingest_ops.ingest_rows_delta),
                     ("pallas", pallas_ingest_rows_delta)):
        _, got, _ = fn(row, jnp.asarray(add), jnp.asarray(dl),
                       jnp.asarray(live), k_changed=16, k_deleted=16)
        _assert_trees_equal(got, want, impl)


@pytest.mark.parametrize("num_e", [1024, 5000])
def test_pallas_twin_tiles_lanes_over_sublanes_and_blocks(num_e):
    """The kernel lays the E lanes out as 8 sublanes x E/8 (the layout
    Mosaic's lane gather lowers on) and walks several element blocks
    past 1024 lanes: padding, the lane-to-tile map and the block index
    maps must keep it bitwise the XLA fused path."""
    rng = np.random.default_rng(num_e)
    st = awset_delta.init(1, num_e, A, actors=np.asarray([2], np.uint32))
    row = ingest_ops.ingest_rows(
        jax.tree.map(lambda x: x[0], st),
        jnp.asarray(rng.random((3, num_e)) < 0.2),
        jnp.asarray(rng.random((3, num_e)) < 0.1), jnp.ones(3, bool))
    add = jnp.asarray(rng.random((8, num_e)) < 0.05)
    dl = jnp.asarray(rng.random((8, num_e)) < 0.05)
    live = jnp.asarray(np.arange(8) % 3 != 1)
    want = ingest_ops.ingest_rows_delta(row, add, dl, live,
                                        k_changed=64, k_deleted=64)
    got = pallas_ingest_rows_delta(row, add, dl, live, k_changed=64,
                                   k_deleted=64, block_e=1024)
    for w, g, label in zip(want, got, ("state", "payload", "compact")):
        _assert_trees_equal(g, w, label)
