"""Compile the chip's kernels here, for a described TPU v5e, without one.

Every Pallas kernel of the main path is lowered with ``interpret=False``
and compiled by the TPU compiler for one chip of a described ``v5e:2x2``
topology; the compiled text must hold the Mosaic custom call.  That is
what interpret-mode CI cannot show: a kernel the chip's compiler
refuses (the fused ingest kernel's 1-row gather was one) fails here, at
no chip time.  The on-chip runs and their bitwise checks live in
``chip_smoke.py``.

The topology is described inside a module-scoped fixture, never at
import: one process at a time may load the TPU library, and every
xdist worker imports this file.  Cases that reach a kernel through an
auto dispatch steer it with ``on_tpu`` (``jax.default_backend`` reads
"tpu" for the lowering).
"""

import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from go_crdt_playground_tpu.models import awset, awset_delta
from go_crdt_playground_tpu.models import packed as packed_mod
from go_crdt_playground_tpu.ops import pallas_delta, pallas_merge

R = 2 * pallas_merge._BLOCK_R
E, A = 256, 256


@pytest.fixture(scope="module")
def topo():
    # skip only where the TPU compiler is not installed; with it, a
    # topology that cannot be described is a failure, not a skip
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu is not installed: no TPU compiler here")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _shapes(build, sharding, eager=False):
    """Abstract arguments on the described chip for what ``build``
    makes (``eager``: build it on the CPU first, for packers that check
    concrete values)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        build() if eager else jax.eval_shape(build))


def _observers(num_r):
    return np.arange(num_r, dtype=np.uint32) % A


def _aw(sharding, num_r=R, num_e=E):
    return _shapes(lambda: awset.init(num_r, num_e, A,
                                      actors=_observers(num_r)), sharding)


def _dl(sharding, num_r=R, num_e=E):
    return _shapes(lambda: awset_delta.init(num_r, num_e, A,
                                            actors=_observers(num_r)),
                   sharding)


def _u32(sharding, shape=()):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


def _assert_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("num_r", [R, 1_000_000])
def test_ring_merge_kernel(one_chip, num_r):
    """The ring-fused merge, up to the north-star replica count (1M x
    256: ~3.3 GB of arguments on one chip)."""
    _assert_mosaic(
        lambda s, o: pallas_merge.pallas_ring_round_rows(
            s, o, interpret=False),
        _aw(one_chip, num_r), _u32(one_chip))


def test_rows_merge_kernel(one_chip):
    _assert_mosaic(
        lambda s, p: pallas_merge.pallas_gossip_round_rows(
            s, p, interpret=False),
        _aw(one_chip), _u32(one_chip, (R,)))


def test_onerow_merge_kernel(one_chip):
    _assert_mosaic(
        lambda s, p: pallas_merge.pallas_gossip_round(s, p,
                                                      interpret=False),
        _aw(one_chip), _u32(one_chip, (R,)))


def test_pairwise_rows_kernel(one_chip):
    """The per-shard kernel of butterfly_round_shardmap."""
    _assert_mosaic(
        lambda d, s: pallas_merge.pallas_merge_pairwise_rows(
            d, s, interpret=False),
        _aw(one_chip), _aw(one_chip))


@pytest.mark.parametrize("num_r", [R, 100_032])
@pytest.mark.parametrize("semantics", ["v2", "strict_reference"])
def test_ring_delta_kernel(one_chip, num_r, semantics):
    """The ring-fused δ kernel under both semantics, up to the config-4
    fleet (100,032 x 256)."""
    sem = "v2" if semantics == "v2" else "reference"
    _assert_mosaic(
        lambda s, o: pallas_delta.pallas_delta_ring_round(
            s, o, delta_semantics=sem, strict_reference_semantics=True,
            interpret=False),
        _dl(one_chip, num_r), _u32(one_chip))


def test_rows_delta_kernel(one_chip):
    _assert_mosaic(
        lambda s, p: pallas_delta.pallas_delta_gossip_round(
            s, p, interpret=False),
        _dl(one_chip), _u32(one_chip, (R,)))


def test_entry_forward_step(one_chip, on_tpu):
    """__graft_entry__.entry()'s round at its shape, through the auto
    dispatch a one-chip TPU process takes."""
    from go_crdt_playground_tpu.parallel import gossip

    _assert_mosaic(
        lambda s, o: gossip.ring_gossip_round(s, o, kernel="pallas"),
        _aw(one_chip, 256), _u32(one_chip))


def test_ormap_ring_round(one_chip, on_tpu):
    from go_crdt_playground_tpu.ops import lattices
    from go_crdt_playground_tpu.parallel import gossip

    st = _shapes(lambda: lattices.ormap_init(R, 64, R), one_chip)
    _assert_mosaic(
        lambda s, o: gossip.ormap_ring_gossip_round(s, o, kernel="pallas"),
        st, _u32(one_chip))


@pytest.mark.parametrize("num_e", [E, 4100, 8192])
def test_packed_ring_merge_kernel(one_chip, num_e):
    """Bitpacked membership, including the word-tiled grid past 4096."""
    st = _shapes(lambda: packed_mod.pack_awset(
        awset.init(R, num_e, A, actors=_observers(R))), one_chip)
    _assert_mosaic(
        lambda s, o: pallas_merge.pallas_ring_round_rows_packed(
            s, o, interpret=False),
        st, _u32(one_chip))


@pytest.mark.parametrize("num_e", [E, 8192])
def test_dotpacked_ring_merge_kernel(one_chip, num_e):
    st = _shapes(lambda: packed_mod.pack_awset_dots(
        awset.init(R, num_e, A, actors=_observers(R))), one_chip,
        eager=True)
    _assert_mosaic(
        lambda s, o: pallas_merge.pallas_ring_round_rows_dotpacked(
            s, o, interpret=False),
        st, _u32(one_chip))


@pytest.mark.parametrize("layout", ["packed", "dots"])
@pytest.mark.parametrize("num_e", [E, 8192])
def test_packed_ring_delta_kernel(one_chip, layout, num_e):
    """The δ ring on the bitpacked and dot-word layouts; E=8192 is the
    word-tiled grid with the largest windowed VMEM demand."""
    pack, fn = ((packed_mod.pack_awset_delta,
                 pallas_delta.pallas_delta_ring_round_packed)
                if layout == "packed" else
                (packed_mod.pack_awset_delta_dots,
                 pallas_delta.pallas_delta_ring_round_dotpacked))
    st = _shapes(lambda: pack(awset_delta.init(R, num_e, A,
                                               actors=_observers(R))),
                 one_chip, eager=True)
    _assert_mosaic(lambda s, o: fn(s, o, interpret=False), st,
                   _u32(one_chip))


@pytest.mark.parametrize("num_e", [1024, 1 << 20])
def test_fused_ingest_kernel(one_chip, num_e):
    """The served write path's program exactly as a one-chip frontend
    runs it: the fused ingest+δ kernel with the fixed-K compaction, a
    32-row batch, at the default universe and at E=2^20."""
    from go_crdt_playground_tpu.ops.ingest import WAL_COMPACT_K
    from go_crdt_playground_tpu.ops.pallas_ingest import _fused_ingest

    k = min(WAL_COMPACT_K, num_e)
    row = _shapes(lambda: jax.tree.map(
        lambda x: x[0], awset_delta.init(1, num_e, 16)), one_chip)
    rows = jax.ShapeDtypeStruct((32, num_e), jnp.bool_, sharding=one_chip)
    live = jax.ShapeDtypeStruct((32,), jnp.bool_, sharding=one_chip)
    _assert_mosaic(
        lambda s, a, d, v: _fused_ingest(
            s, a, d, v, k_changed=k, k_deleted=k, block_e=512,
            interpret=False),
        row, rows, rows, live)


def test_ingest_regime_selects_pallas_on_tpu(on_tpu):
    from go_crdt_playground_tpu.ops import ingest as ingest_ops
    from go_crdt_playground_tpu.ops.pallas_ingest import \
        pallas_ingest_rows_delta

    fn, k = ingest_ops.ingest_delta_regime(1 << 20)
    assert fn is pallas_ingest_rows_delta and k == ingest_ops.WAL_COMPACT_K


def test_lane_fingerprint_kernel(one_chip):
    from go_crdt_playground_tpu.ops import pallas_digest

    row = _shapes(lambda: jax.tree.map(
        lambda x: x[0], awset_delta.init(1, E, A)), one_chip)
    _assert_mosaic(
        lambda s: pallas_digest.pallas_lane_fingerprints(s,
                                                         interpret=False),
        row)


@pytest.mark.parametrize("num_e", [E, 1 << 20])
def test_group_digest_kernel(one_chip, num_e):
    from go_crdt_playground_tpu.ops import pallas_digest

    row = _shapes(lambda: jax.tree.map(
        lambda x: x[0], awset_delta.init(1, num_e, 16)), one_chip)
    _assert_mosaic(
        lambda s: pallas_digest.pallas_state_group_digests(
            s, 64, interpret=False),
        row)


def test_mesh2d_ingest_program_2x2(topo):
    """The 2-D dp x mp serve program on the four described chips: it
    compiles, and the dp join moves stripes between chips."""
    from go_crdt_playground_tpu.parallel import meshtarget2d as m2d
    from go_crdt_playground_tpu.parallel.meshtarget import \
        state_partition_specs

    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(2, 2),
                (m2d.DP_AXIS, m2d.MP_AXIS))
    fn = m2d.build_mesh2d_ingest(mesh, awset_delta.AWSetDeltaState, True)
    specs = state_partition_specs(awset_delta.AWSetDeltaState, m2d.MP_AXIS)
    st = jax.eval_shape(lambda: awset_delta.init(1, 1024, 16))
    st = jax.tree.map(
        lambda x, p: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=NamedSharding(mesh, p)),
        st, specs)
    P = jax.sharding.PartitionSpec

    def arg(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    cap = 16
    text = fn.lower(
        st,
        arg((2, cap, 1024), jnp.bool_, P(m2d.DP_AXIS, None, m2d.MP_AXIS)),
        arg((2, cap, 1024), jnp.bool_, P(m2d.DP_AXIS, None, m2d.MP_AXIS)),
        arg((2, cap), jnp.uint32, P(m2d.DP_AXIS, None)),
        arg((2, cap, 2), jnp.uint32, P(m2d.DP_AXIS, None, m2d.MP_AXIS)),
        arg((2, cap), jnp.uint32, P(m2d.DP_AXIS, None)),
        arg((2, cap), jnp.uint32, P(m2d.DP_AXIS, None)),
    ).compile().as_text()
    assert "collective-permute" in text
