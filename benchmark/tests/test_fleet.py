"""The fleet generator's two array modules agree, the program converges
each seeded fleet to the reference's join in the reference's round
count, and the control (strict reference semantics) does not."""

import numpy as np
import pytest

from benchmark import fleetgen
from benchmark.drivers import fleet as driver
from benchmark.reference import fleet as ref

R, E, A, W = 1024, 256, 256, 256
CFG = {"replicas": R, "elements": E, "actors": A, "writers": W,
       "delta_semantics": "v2", "schedule": "dissemination"}


@pytest.mark.parametrize("seed,i", [(0, 0), (2**40 + 7, 3)])
def test_device_and_numpy_fleets_agree(seed, i):
    dev = driver.builder(CFG)(seed, i)
    host = fleetgen.fleet(np, fleetgen.key_of(np, seed, i), R, E, A, W)
    for name, arr in host.items():
        assert np.array_equal(np.asarray(getattr(dev, name)), arr), name
    # diverged: writers, stale copies and deletion records all present
    assert np.asarray(dev.deleted).sum() > 0
    assert (np.asarray(dev.vv).sum(axis=1) > 0).sum() > W


@pytest.mark.parametrize("control", [False, True])
def test_program_converges_to_the_reference(control):
    seed, i = 2**33 + 1, 5
    rounds, final = driver.converge(CFG, driver.builder(CFG)(seed, i),
                                    control)
    mm = ref.mismatches(ref.converged(seed, i, R, E, A, W),
                        np.asarray(final.present), np.asarray(final.vv))
    assert rounds == ref.rounds(R) == 10
    if control:
        assert mm["members"] > 0
    else:
        assert mm == {"members": 0, "vv": 0}


@pytest.mark.parametrize("rows", [1024, 4096])
def test_row_sharded_fleet_is_bitwise_the_unsharded(rows):
    """The same builder with its fleet laid out row by row over four
    devices gives the same fleet, bit for bit."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    cfg = {**CFG, "replicas": rows}
    mesh = Mesh(np.array(jax.devices()[:4]), ("rows",))
    seed, i = 2**40 + 7, 3
    sharded = driver.builder(cfg, NamedSharding(
        mesh, PartitionSpec("rows")))(seed, i)
    plain = driver.builder(cfg)(seed, i)
    for name in plain._fields:
        s = getattr(sharded, name)
        assert len(s.sharding.device_set) == 4, name
        assert s.sharding.shard_shape(s.shape)[0] == rows // 4, name
        assert np.array_equal(np.asarray(s),
                              np.asarray(getattr(plain, name))), name
