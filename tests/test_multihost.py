"""Multi-host layer (parallel/multihost.py): real 2-process coverage.

The DCN story (SURVEY §5.8) was previously untested — 81 LoC resting on
inspection.  These tests drive it two ways:

* unit tests for ``process_replica_block`` slicing/divisibility at
  ``process_count == 1`` (the in-process contract);
* a genuine 2-process ``jax.distributed`` run on CPU: a localhost
  coordinator, two worker processes each calling
  ``multihost.initialize`` + ``multihost.global_mesh``, running one
  sharded gossip round, and checking the digest agrees on both hosts.
  This is the same program shape a v5e-16 multi-host deployment runs,
  with DCN stood in by the local distributed service.
"""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from go_crdt_playground_tpu.parallel import multihost  # noqa: E402


def test_process_replica_block_single_process():
    """At process_count == 1 the block is the whole replica axis."""
    assert multihost.process_replica_block(64) == (0, 64)


def test_process_replica_block_rejects_ragged_in_worker():
    """The divisibility guard needs process_count > 1 to be reachable —
    it is exercised inside the 2-process worker below (R=9 over 2
    processes raises instead of inventing an unrealizable placement)."""
    assert "process_replica_block(9)" in _WORKER


_WORKER = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, {repo!r})
    import jax
    jax.config.update("jax_platforms", "cpu")

    from go_crdt_playground_tpu.parallel import multihost

    pid = int(sys.argv[1])
    multihost.initialize(coordinator_address=sys.argv[2],
                         num_processes=2, process_id=pid)
    assert jax.process_count() == 2
    assert jax.process_index() == pid
    # every process sees the GLOBAL device set
    devices = jax.devices()
    assert len(devices) == 2, devices
    mesh = multihost.global_mesh()

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from go_crdt_playground_tpu.parallel import collectives, gossip
    from go_crdt_playground_tpu.parallel import mesh as mesh_mod
    from go_crdt_playground_tpu.models import awset

    R, E, A = 8, 16, 8
    lo, hi = multihost.process_replica_block(R)
    assert hi - lo == R // 2 and lo == pid * (R // 2)
    try:
        multihost.process_replica_block(9)
        raise SystemExit("expected ValueError for ragged replica axis")
    except ValueError:
        pass

    # host-local construction of the process's replica block, assembled
    # into one global sharded array per field
    specs = mesh_mod.partition_specs(awset.AWSetState)

    e = np.arange(E, dtype=np.uint32)[None, :]
    r = np.arange(lo, hi, dtype=np.uint32)[:, None]
    present = (e % (r % 3 + 2)) == 0
    counter = np.cumsum(present, axis=1, dtype=np.uint32) * present
    vv = np.zeros((hi - lo, A), np.uint32)
    vv[np.arange(hi - lo), np.arange(lo, hi)] = counter.max(axis=1)

    def globalize(specs, name, local, global_shape):
        sharding = NamedSharding(mesh, getattr(specs, name))
        return jax.make_array_from_process_local_data(
            sharding, local, global_shape)

    def build(state_cls, specs, **extra):
        fields = dict(
            vv=(vv, (R, A)),
            present=(present, (R, E)),
            dot_actor=(np.where(present, r, 0).astype(np.uint32), (R, E)),
            dot_counter=(counter, (R, E)),
            actor=(np.arange(lo, hi, dtype=np.uint32), (R,)),
            **extra,
        )
        return state_cls(**{{name: globalize(specs, name, local, shape)
                             for name, (local, shape) in fields.items()}})

    state = build(awset.AWSetState, specs)

    @jax.jit
    def step(s, perm):
        merged = gossip.gossip_round(s, perm, kernel="xla")
        return merged, collectives.converged(merged.present, merged.vv)

    out, conv = step(state, gossip.ring_perm(R, 1))
    jax.block_until_ready(out)
    # the digest is fully replicated: both hosts can read it
    print(f"WORKER_OK pid={{pid}} converged={{bool(conv)}}")

    # δ path over the same 2-process mesh: payload-compressed rounds +
    # collective GC frontier + digest, driven to convergence — the
    # v5e-16 multi-host program shape for the headline protocol
    from go_crdt_playground_tpu.models import awset_delta
    from go_crdt_playground_tpu.ops import delta as delta_ops

    zE = np.zeros((hi - lo, E), np.uint32)
    dstate = build(
        awset_delta.AWSetDeltaState,
        mesh_mod.partition_specs(awset_delta.AWSetDeltaState),
        deleted=(np.zeros((hi - lo, E), bool), (R, E)),
        del_dot_actor=(zE, (R, E)),
        del_dot_counter=(zE, (R, E)),
        processed=(vv, (R, A)),
    )

    @jax.jit
    def dstep(s, perm):
        s = gossip.delta_gossip_round(s, perm, delta_semantics="v2")
        frontier = delta_ops.gc_frontier(s.processed)
        s = delta_ops.gc_apply(s, frontier)
        return s, collectives.converged(s.present, s.vv)

    dconv = False
    for off in gossip.dissemination_offsets(R):
        dstate, dconv = dstep(dstate, gossip.ring_perm(R, off))
    jax.block_until_ready(dstate)
    print(f"WORKER_DELTA_OK pid={{pid}} converged={{bool(dconv)}}")
""").format(repo=REPO)


@pytest.mark.skipif(os.environ.get("CRDT_SKIP_DISTRIBUTED") == "1",
                    reason="distributed run disabled")
def test_two_process_distributed_gossip_round(tmp_path):
    """Two real OS processes, one jax.distributed service, one sharded
    gossip round over a DCN-spanning (2, 1) mesh."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # exactly one CPU device per process

    procs = [subprocess.Popen(
        [sys.executable, str(script), str(pid), coord],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True) for pid in (0, 1)]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, f"worker failed:\n{err[-3000:]}"
    assert "WORKER_OK pid=0" in outs[0][1]
    assert "WORKER_OK pid=1" in outs[1][1]
    # the δ fleet converged across the process boundary, and both hosts
    # read the same replicated digest
    assert "WORKER_DELTA_OK pid=0 converged=True" in outs[0][1]
    assert "WORKER_DELTA_OK pid=1 converged=True" in outs[1][1]
