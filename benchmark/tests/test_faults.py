"""A run whose timed path is broken underneath must come out not
correct: once per fault a cell can have.  The harness's look for a chip
is skipped (CPU, tiny sizes, as many devices as the cell has chips);
everything else is a whole run.  A cell's faults follow its driver's
``FAMILY``, not the driver's name, so a cell whose new driver declares
a family is tested with no edit here.

Served cells: a batch apply that leaves the state unchanged, half of
every batch left out, an answer altered where it is produced (QUERY).
Fleet cells, each planted in the program's convergence entry
``parallel/gossip.rounds_to_convergence`` (so every fleet driver that
converges through it is covered): a convergence that returns its fleet
unchanged, half of the rows left out of it, an answer altered where it
is produced (the converged fleet), and on more than one chip the
exchange between chips left out (each chip's rows converged alone).
And each cell's control (``control.py``)."""

import os

import numpy as np
import pytest

from benchmark import harness
from conftest import run_cell

_BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


def _family(config: dict) -> str:
    driver = harness.load_json(os.path.join(harness.ROOT,
                                            config["file"]))["driver"]
    return harness.load_module(os.path.join(
        harness.BENCH, "drivers", driver + ".py")).FAMILY


_FAMILY = {c["name"]: _family(c) for c in _BENCH["configs"]}
SERVED = [w["name"] for w in _BENCH["workloads"]
          if _FAMILY[w["config"]] == "served"]
FLEET = [w["name"] for w in _BENCH["workloads"]
         if _FAMILY[w["config"]] == "fleet"]
FLEET_MULTICHIP = [w["name"] for w in _BENCH["workloads"]
                   if _FAMILY[w["config"]] == "fleet" and w["chips"] > 1]


def _node():
    from go_crdt_playground_tpu.net.peer import Node

    return Node


def _unchanged(mp):
    mp.setattr(_node(), "_apply_batch_locked",
               lambda self, *a, **kw: None)


def _half_batch(mp):
    Node = _node()
    orig = Node.ingest_batch

    def half(self, add, dele, live=None, stripe_hint=None):
        live = np.ones(add.shape[0], bool) if live is None else \
            np.array(live)
        live[add.shape[0] // 2:] = False
        return orig(self, add, dele, live, stripe_hint)

    mp.setattr(Node, "ingest_batch", half)


def _altered_answer(mp):
    Node = _node()
    orig = Node.members_vv

    def altered(self):
        ids, vv = orig(self)
        return ids[1:], vv

    mp.setattr(Node, "members_vv", altered)


@pytest.mark.parametrize("fault", [_unchanged, _half_batch,
                                   _altered_answer])
@pytest.mark.parametrize("cell", SERVED)
def test_served_fault_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    line = run_cell(cell, seconds=1.0)
    assert not line["correct"], line["checks"]


def _wrap_convergence(mp, fault):
    """Plant ``fault(state, rounds, final, orig, *a, **kw) -> (rounds,
    final)`` in the program's convergence entry."""
    from go_crdt_playground_tpu.parallel import gossip

    orig = gossip.rounds_to_convergence

    def broken(state, *a, **kw):
        rounds, final = orig(state, *a, **kw)
        return fault(state, rounds, final, orig, *a, **kw)

    mp.setattr(gossip, "rounds_to_convergence", broken)


def _fleet_unchanged(mp):
    _wrap_convergence(mp, lambda state, rounds, final, *_a, **_kw:
                      (rounds, state))


def _fleet_half_rows(mp):
    import jax

    def half(state, rounds, final, *_a, **_kw):
        h = state.vv.shape[0] // 2
        return rounds, jax.tree.map(lambda m, s: m.at[h:].set(s[h:]),
                                    final, state)

    _wrap_convergence(mp, half)


def _fleet_altered_answer(mp):
    def altered(state, rounds, final, *_a, **_kw):
        return rounds, final._replace(
            present=final.present.at[0, 0].set(~final.present[0, 0]))

    _wrap_convergence(mp, altered)


def _fleet_no_exchange(mp):
    """Each chip's block of rows converged alone: nothing crosses
    between chips."""
    import jax
    import jax.numpy as jnp

    def alone(state, rounds, final, orig, *a, **kw):
        n = len(state.vv.sharding.device_set)
        b = state.vv.shape[0] // n
        parts = [orig(jax.tree.map(lambda x: x[i * b:(i + 1) * b], state),
                      *a, **kw) for i in range(n)]
        return (max(r for r, _ in parts),
                jax.tree.map(lambda *xs: jnp.concatenate(xs),
                             *[f for _, f in parts]))

    _wrap_convergence(mp, alone)


FLEET_FAULTS = [
    pytest.param(cell, fault, id=f"{cell}-{fault.__name__}")
    for cell in FLEET
    for fault in (_fleet_unchanged, _fleet_half_rows, _fleet_altered_answer)
    + ((_fleet_no_exchange,) if cell in FLEET_MULTICHIP else ())]


@pytest.mark.parametrize("cell,fault", FLEET_FAULTS)
def test_fleet_fault_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    line = run_cell(cell, seconds=0.5)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("cell", SERVED + FLEET)
def test_control_is_not_correct(cell):
    line = run_cell(cell, seconds=1.0, control=True)
    assert not line["correct"], line["checks"]
