"""Drive the store's main path once on the chip and check what comes out.

    python chip_smoke.py             # one chip: phases 1-4
    python chip_smoke.py --chips 4   # four chips: the multi-chip paths only
    python chip_smoke.py --phase kernels   # one phase of either run

Phases of the one-chip run, each printing one JSON line:

1. ``fleet_merge`` — the config-5 AWSet fleet (1M replicas x 256
   elements, ~3.3 GB on the device) through dissemination ring rounds
   with ``ring_gossip_round``'s auto dispatch until converged, each
   round checked bitwise against the XLA merge of the same row pairs,
   and 64 seeded pairs of the last round against the ``models/spec.py``
   oracle through ``utils/codec.py`` renderings.
2. ``delta_fleet`` — the config-4 δ fleet (100,032 x 256) the same way,
   under v2 and strict-reference semantics (whose empty-δ quirk leaves
   clocks apart: there the membership must converge).
3. ``served_store`` — a ``serve --ingest`` frontend at E=2^20, built
   from the CLI's own parser, takes a seeded zipf stream of 20,000 ops
   through ``ServeClient``; every op must ack, and the replica, its
   digest-synced peer (started once the load is acked) and a restart
   from the durable directory must equal the spec oracle.
4. ``kernels`` — every other Pallas kernel (packed and dot-word rings
   up to E=8192, the gather kernels, the OR-Map ring, the butterfly
   shard_map, the digest kernels) once, bitwise against its XLA twin.

``--chips 4`` runs the same op stream into a ``--mesh-devices 2x2``
frontend against a single-device frontend on device 0, then the sharded
δ-sync paths of ``__graft_entry__._dryrun_inproc`` against the unsharded
program.

Everything runs in this one process, which holds the chip(s).  Off the
chip it exits non-zero before any phase; on any failed check it exits
non-zero and prints no ``ok`` line.  The last line on success is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
Each phase is a function of its sizes so tests/test_chip_smoke.py runs
it on CPU at tiny sizes; ``main`` alone sets the real sizes.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import functools
import io
import json
import os
import shutil
import socket
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


class SmokeFailure(AssertionError):
    """A check of the smoke run failed."""


def _check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _peak_bytes():
    """``peak_bytes_in_use`` of every device (None where the backend
    keeps no statistics, as the CPU does)."""
    import jax

    out = []
    for d in jax.devices():
        stats = d.memory_stats()
        out.append(stats.get("peak_bytes_in_use") if stats else None)
    return out


def _timed_compile(fn, *args):
    """(compiled, seconds): ahead-of-time compile of a jitted ``fn``."""
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    return compiled, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# Phases 1 and 2: fleets through dissemination ring rounds
# ---------------------------------------------------------------------------


def _rows_checker(pair_merge, num_replicas: int, max_rows: int = 65_536):
    """``(chunk_equal, n_chunks, size)``: ``chunk_equal(pre, got, offset,
    start)`` says whether rows ``start:start+size`` of ``got`` are
    bitwise the XLA merge of the row pairs ``(r, (r + offset) mod R)``
    of ``pre`` — ``gossip_round(pre, ring_perm(R, offset),
    kernel="xla")`` a row chunk at a time, so that no third fleet is
    ever alive."""
    import jax
    import jax.numpy as jnp

    R = num_replicas
    n_chunks = next(k for k in range(1, R + 1)
                    if R % k == 0 and R // k <= max_rows)
    size = R // n_chunks

    @jax.jit
    def chunk_equal(pre, got, offset, start):
        rows = start + jnp.arange(size, dtype=jnp.uint32)
        partner = (rows + offset) % jnp.uint32(R)

        def take(x):
            return jax.lax.dynamic_slice_in_dim(x, start, size)

        want = pair_merge(jax.tree.map(take, pre),
                          jax.tree.map(lambda x: x[partner], pre))
        have = jax.tree.map(take, got)
        eq = [jnp.all(a == b) for a, b in zip(jax.tree.leaves(want),
                                             jax.tree.leaves(have))]
        return functools.reduce(jnp.logical_and, eq)

    return chunk_equal, n_chunks, size


def _host_rows(state, rows):
    return {f: np.asarray(getattr(state, f)[rows]) for f in state._fields}


def _oracle_pairs(pre, got, offset: int, seed: int, delta_semantics=None,
                  pairs: int = 64):
    """Merge ``pairs`` seeded row pairs of ``pre`` in the spec oracle and
    compare each result's canonical rendering with ``got``'s row byte
    for byte (δ fleets: the deletion log and processed vector too)."""
    from go_crdt_playground_tpu.utils import codec

    R, E = pre.present.shape
    rng = np.random.default_rng(seed)
    rows = np.sort(rng.choice(R, size=min(pairs, R), replace=False))
    partner = (rows + offset) % R
    names = codec.ElementDict(capacity=E, values=[f"e{i}" for i in range(E)])
    if delta_semantics is None:
        unpack = codec.unpack_awsets
    else:
        def unpack(arrays, d):
            return codec.unpack_awset_deltas(arrays, d, delta_semantics)
    dst = unpack(_host_rows(pre, rows), names)
    src = unpack(_host_rows(pre, partner), names)
    have_arrays = _host_rows(got, rows)
    have = unpack(have_arrays, names)
    bad = []
    for r, a, b, h in zip(rows, dst, src, have):
        a.merge(b)
        same = str(a).encode() == str(h).encode()
        if delta_semantics is not None:
            same = same and a.deleted == h.deleted
            if delta_semantics == "v2":
                same = same and a.processed == h.processed
        if not same:
            bad.append(int(r))
    _check(not bad, f"spec oracle disagrees on rows {bad[:8]}")
    return len(rows)


def _run_fleet(build, round_fn, pair_merge, *, seed: int,
               delta_semantics=None, clocks_may_lag: bool = False):
    """One dissemination schedule (ceil(log2 R) ring rounds) of
    ``round_fn`` over the fleet ``build()`` makes, each round checked
    against ``pair_merge``, the last one also against the spec oracle;
    then the fleet must have converged.  ``clocks_may_lag``: only the
    membership must (the strict-reference empty-δ quirk skips the vv
    join, awset-delta_test.go:60-64).  At most two fleets are alive at
    once.  Returns the phase's counts and timings."""
    import jax
    import jax.numpy as jnp

    from go_crdt_playground_tpu.parallel import collectives, gossip

    t0 = time.perf_counter()
    state = jax.block_until_ready(build())
    build_s = time.perf_counter() - t0
    R = state.vv.shape[0]
    offsets = gossip.dissemination_offsets(R)
    off0 = jnp.uint32(offsets[0])
    step, c1 = _timed_compile(jax.jit(round_fn), state, off0)
    chunk_equal, n_chunks, size = _rows_checker(pair_merge, R)
    chunk_equal, c2 = _timed_compile(chunk_equal, state, state, off0,
                                     jnp.uint32(0))

    def check(pre, got, off) -> bool:
        return all(bool(chunk_equal(pre, got, jnp.uint32(off),
                                    jnp.uint32(c * size)))
                   for c in range(n_chunks))

    conv_fn, c3 = _timed_compile(jax.jit(collectives.converged),
                                 state.present, state.vv)
    t0 = time.perf_counter()
    for i, off in enumerate(offsets):
        nxt = step(state, jnp.uint32(off))
        _check(check(state, nxt, off),
               f"round {i} (offset {off}): kernel != XLA merge")
        if i == len(offsets) - 1:
            oracle = _oracle_pairs(state, nxt, off, seed, delta_semantics)
        state = nxt
    members = bool(conv_fn(state.present, jnp.zeros_like(state.vv)))
    clocks = bool(conv_fn(state.present, state.vv))
    _check(members and (clocks or clocks_may_lag),
           f"fleet did not converge in {len(offsets)} rounds "
           f"(members {members}, clocks {clocks})")
    return {"rounds": len(offsets), "members_converged": members,
            "clocks_converged": clocks, "oracle_pairs": oracle,
            "check_rows_per_chunk": size, "build_s": build_s,
            "wall_s": time.perf_counter() - t0,
            "compile_s": c1 + c2 + c3}


def _lowers_to_mosaic(fn, *args) -> bool:
    """Does ``fn`` lower with a Mosaic kernel (not interpret mode)?"""
    import jax

    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def phase_fleet_merge(num_replicas: int, num_elements: int,
                      num_writers: int, *, kernel: str = "auto") -> dict:
    """Phase 1: the full-state AWSet fleet (module docstring)."""
    import jax
    import jax.numpy as jnp

    import bench
    from go_crdt_playground_tpu.ops.merge import merge_pairwise
    from go_crdt_playground_tpu.parallel import gossip

    t0 = time.perf_counter()

    def build():
        return bench.build_state(num_replicas, num_elements, num_writers)

    shapes = jax.eval_shape(build)
    used = gossip._auto_kernel(shapes) if kernel == "auto" else kernel
    round_fn = functools.partial(gossip.ring_gossip_round, kernel=used)
    mosaic = _lowers_to_mosaic(round_fn, shapes, jnp.uint32(1))
    rec = _run_fleet(build, round_fn,
                     lambda d, s: merge_pairwise(d, s)[0], seed=0)
    return {"phase": "fleet_merge",
            "shape": [num_replicas, num_elements, num_writers],
            "kernel": used, "mosaic": mosaic, **rec,
            "total_s": time.perf_counter() - t0,
            "peak_bytes_in_use": _peak_bytes()}


def phase_delta_fleet(num_replicas: int, num_elements: int,
                      num_writers: int, *, kernel: str = "auto") -> dict:
    """Phase 2: the config-4 δ fleet under both semantics."""
    import jax
    import jax.numpy as jnp

    import bench
    from go_crdt_playground_tpu.ops.delta import delta_merge_pairwise
    from go_crdt_playground_tpu.parallel import gossip

    t0 = time.perf_counter()

    def build():
        return bench._config4_delta_fleet(num_replicas, num_elements,
                                          num_writers)[0]

    shapes = jax.eval_shape(build)
    runs = {}
    for name, sem in (("v2", "v2"), ("strict_reference", "reference")):
        used = (gossip._auto_kernel(shapes, sem) if kernel == "auto"
                else kernel)
        round_fn = functools.partial(
            gossip.delta_ring_gossip_round, delta_semantics=sem,
            strict_reference_semantics=True, kernel=used)
        mosaic = _lowers_to_mosaic(round_fn, shapes, jnp.uint32(1))
        rec = _run_fleet(
            build, round_fn,
            lambda d, s, sem=sem: delta_merge_pairwise(d, s, sem, True),
            seed=1, delta_semantics=sem, clocks_may_lag=sem == "reference")
        runs[name] = {"kernel": used, "mosaic": mosaic, **rec}
    return {"phase": "delta_fleet",
            "shape": [num_replicas, num_elements, num_writers],
            "runs": runs, "total_s": time.perf_counter() - t0,
            "peak_bytes_in_use": _peak_bytes()}


# ---------------------------------------------------------------------------
# Phase 3: the served store
# ---------------------------------------------------------------------------

_ACTORS = 16        # actor axis of the served replicas
_STREAM_SEED = 7


def op_stream(num_ops: int, num_elements: int, seed: int):
    """Seeded client ops: 9 in 10 adds, 1 in 10 deletes, each of 1 to 8
    distinct keys drawn zipf(0.99) over the universe (ranks scattered
    over the ids by a seeded permutation)."""
    rng = np.random.default_rng(seed)
    weights = 1.0 / np.arange(1, num_elements + 1) ** 0.99
    cdf = np.cumsum(weights)
    cdf /= cdf[-1]
    ids = rng.permutation(num_elements)
    kinds = np.where(rng.random(num_ops) < 0.9, 0, 1)    # OP_ADD / OP_DEL
    counts = rng.integers(1, 9, num_ops)
    draws = ids[np.minimum(np.searchsorted(cdf, rng.random(counts.sum())),
                           num_elements - 1)]
    out, at = [], 0
    for kind, n in zip(kinds, counts):
        out.append((int(kind), np.unique(draws[at:at + n])))
        at += n
    return out


def oracle_replay(stream, num_actors: int):
    """The spec oracle's (sorted member ids, vv) after ``stream`` at
    actor 0."""
    from go_crdt_playground_tpu.models.spec import AWSetDelta, VersionVector

    rep = AWSetDelta(actor=0,
                     version_vector=VersionVector([0] * num_actors),
                     delta_semantics="v2")
    for kind, keys in stream:
        names = [str(int(k)) for k in keys]
        if kind == 0:
            rep.add(*names)
        else:
            rep.del_(*names)
    ids = np.asarray(sorted(int(k) for k in rep.entries), np.int64)
    return ids, np.asarray(rep.version_vector.v, np.uint32)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _serve(argv):
    """A frontend built from ``serve --ingest`` flags the way the CLI
    builds it, listening; returns (frontend, address, warmup seconds)."""
    from go_crdt_playground_tpu.__main__ import _build_frontend, build_parser

    args = build_parser().parse_args(["serve", "--ingest"] + argv)
    t0 = time.perf_counter()
    fe = _build_frontend(args)
    addr = fe.serve(port=args.port, peer_port=args.peer_port)
    return fe, addr, time.perf_counter() - t0


def drive(addr, stream) -> dict:
    """Submit ``stream`` through one ``ServeClient`` with at most 64 ops
    in flight (under the default admission depth of 256, so none is
    shed); every op must ack."""
    from go_crdt_playground_tpu.serve import ServeClient

    window, timeout_s = 64, 120.0

    client = ServeClient(addr, timeout=timeout_s)
    pending = collections.deque()
    acked = 0
    t0 = time.perf_counter()
    try:
        for kind, keys in stream:
            if len(pending) >= window:
                pending.popleft().wait(timeout_s)
                acked += 1
            pending.append(client.submit_async(kind, keys.tolist()))
        while pending:
            pending.popleft().wait(timeout_s)
            acked += 1
    finally:
        client.close()
    return {"ops": len(stream), "acked": acked,
            "wall_s": time.perf_counter() - t0}


def members(addr):
    from go_crdt_playground_tpu.serve import ServeClient

    client = ServeClient(addr)
    try:
        ids, vv = client.members()
    finally:
        client.close()
    return np.asarray(ids, np.int64), np.asarray(vv, np.uint32)


def _same(got, want) -> bool:
    return all(np.array_equal(g, w) for g, w in zip(got, want))


def _counters(fe) -> dict:
    return fe.recorder.snapshot()["counters"]


def _ingest_regime(fe) -> str:
    """Which fused ingest path the frontend's node runs, and its K."""
    from go_crdt_playground_tpu.ops.pallas_ingest import \
        pallas_ingest_rows_delta

    regime = getattr(fe.node, "_fused_regime", None)
    if regime is None:
        return "none"
    fn, k = regime
    return f"{'pallas' if fn is pallas_ingest_rows_delta else 'xla'}:k={k}"


def phase_served_store(num_elements: int, num_ops: int) -> dict:
    """Phase 3: two peered frontends, a seeded stream, the oracle, a
    restart (module docstring)."""
    t0 = time.perf_counter()
    num_actors, sync_timeout_s = _ACTORS, 120.0
    stream = op_stream(num_ops, num_elements, _STREAM_SEED)
    want = oracle_replay(stream, num_actors)
    tmp = tempfile.mkdtemp(prefix="chip-smoke-")
    pa, pb = _free_port(), _free_port()
    common = ["--elements", str(num_elements), "--actors", str(num_actors),
              "--max-batch", "32", "--flush-ms", "2",
              "--sync-mode", "digest"]
    fes = []
    try:
        a, addr_a, warm_a = _serve(common + [
            "--durable-dir", os.path.join(tmp, "a"),
            "--peer-port", str(pa), "--peer", f"127.0.0.1:{pb}"])
        fes.append(a)
        load = drive(addr_a, stream)
        _check(load["acked"] == num_ops,
               f"{load['acked']}/{num_ops} ops acked")
        ca = _counters(a)
        _check(ca.get("serve.batch_errors", 0) == 0,
               f"serve.batch_errors = {ca.get('serve.batch_errors')}")
        _check(ca.get("ingest.dispatches", 0) > 0, "no ingest dispatch")
        _check(_same(members(addr_a), want), "members(a) != spec oracle")
        # b joins once the load is acked: anti-entropy running DURING
        # ingest loses acknowledged re-adds (a passive peer ships FULL
        # state every round and the reference's full merge overwrites
        # a fresher dot with its lagging one; PERF.md open questions,
        # pinned by tests/test_antientropy_readd.py)
        b, addr_b, warm_b = _serve(common + [
            "--actor", "1", "--durable-dir", os.path.join(tmp, "b"),
            "--peer-port", str(pb), "--peer", f"127.0.0.1:{pa}"])
        fes.append(b)
        deadline = time.monotonic() + sync_timeout_s
        while not _same(members(addr_b), want):
            _check(time.monotonic() < deadline,
                   "members(b) did not converge through digest sync")
            time.sleep(0.2)
        t_sync = time.monotonic() - deadline + sync_timeout_s
        _check(_same(members(addr_a), want),
               "members(a) != spec oracle after digest sync")
        regime, mosaic = _ingest_regime(a), mosaic_ingest(a)
        cb = _counters(b)
        fes.clear()
        a.close()
        b.close()
        r, addr_r, warm_r = _serve([
            "--elements", str(num_elements), "--actors", str(num_actors),
            "--durable-dir", os.path.join(tmp, "a")])
        fes.append(r)
        _check(_same(members(addr_r), want),
               "members after restart != spec oracle")
    finally:
        for fe in fes:
            fe.close()
        shutil.rmtree(tmp, ignore_errors=True)
    return {"phase": "served_store", "elements": num_elements,
            "actors": num_actors, "ops": num_ops, "acked": load["acked"],
            "members": int(want[0].size), "vv_a": int(want[1][0]),
            "ingest_regime": regime, "mosaic_ingest": mosaic,
            "ingest_dispatches": ca.get("ingest.dispatches", 0),
            "batch_errors": ca.get("serve.batch_errors", 0),
            "peer_started": "after_load",
            "digest_exchanges_b": cb.get("digest.exchanges", 0),
            "load_wall_s": load["wall_s"], "sync_wait_s": t_sync,
            "compile_s": warm_a + warm_b + warm_r,
            "total_s": time.perf_counter() - t0,
            "peak_bytes_in_use": _peak_bytes()}


def mosaic_ingest(fe) -> bool:
    """Does the frontend's node lower its own fused ingest program (its
    regime's function and K, at its batcher's width) to a Mosaic
    kernel?"""
    import jax

    fn, k = fe.node._fused_regime
    row = jax.tree.map(lambda x: x[0], fe.node._state)
    rows = jax.ShapeDtypeStruct((fe.batcher.width, fe.node.num_elements),
                                bool)
    live = jax.ShapeDtypeStruct((fe.batcher.width,), bool)
    return k > 0 and _lowers_to_mosaic(
        functools.partial(fn, k_changed=k, k_deleted=k),
        row, rows, rows, live)


# ---------------------------------------------------------------------------
# The other kernels, once each against their XLA twins
# ---------------------------------------------------------------------------


def _random_fleet(seed: int, num_elements: int, delta: bool = False):
    """Two ring blocks of random AWSet rows (δ: with deletion logs)."""
    import jax.numpy as jnp

    from go_crdt_playground_tpu.models import awset, awset_delta
    from go_crdt_playground_tpu.ops.pallas_merge import _BLOCK_R

    R, E, A = 2 * _BLOCK_R, num_elements, 256
    rng = np.random.default_rng(seed)
    present = rng.random((R, E)) < 0.5
    state = awset.AWSetState(
        vv=jnp.asarray(rng.integers(0, 10, (R, A)), jnp.uint32),
        present=jnp.asarray(present),
        dot_actor=jnp.asarray(np.where(present, rng.integers(0, A, (R, E)),
                                       0), jnp.uint32),
        dot_counter=jnp.asarray(np.where(present,
                                         rng.integers(1, 9, (R, E)), 0),
                                jnp.uint32),
        actor=jnp.arange(R, dtype=jnp.uint32) % A)
    if not delta:
        return state
    deleted = rng.random((R, E)) < 0.1
    return awset_delta.AWSetDeltaState(
        **state._asdict(), deleted=jnp.asarray(deleted),
        del_dot_actor=jnp.asarray(np.where(
            deleted, rng.integers(0, A, (R, E)), 0), jnp.uint32),
        del_dot_counter=jnp.asarray(np.where(
            deleted, rng.integers(0, 5, (R, E)), 0), jnp.uint32),
        processed=state.vv)


def _kernel_cases(num_elements: int, wide_elements: int):
    """``(name, want, got)`` thunks: each Pallas kernel the three phases
    do not run (the packed and dot-word rings, the multi-row and
    one-row gather kernels, the OR-Map ring, the butterfly shard_map,
    the digest kernels) against the XLA program it replaces."""
    import jax
    import jax.numpy as jnp

    from go_crdt_playground_tpu.models import packed
    from go_crdt_playground_tpu.ops import digest, lattices
    from go_crdt_playground_tpu.ops import pallas_delta as pd
    from go_crdt_playground_tpu.ops import pallas_digest
    from go_crdt_playground_tpu.ops import pallas_merge as pm
    from go_crdt_playground_tpu.parallel import gossip
    from go_crdt_playground_tpu.parallel import mesh as mesh_mod

    E = num_elements
    aw, dl = _random_fleet(0, E), _random_fleet(1, E, delta=True)
    R = aw.vv.shape[0]
    perm = gossip.random_perm(jax.random.key(0), R)

    def xla(state, p):
        return gossip.gossip_round(state, p, kernel="xla")

    def xla_delta(state, p):
        return gossip.delta_gossip_round(state, p, delta_semantics="v2",
                                         kernel="xla")

    cases = [
        ("rows_merge", lambda: xla(aw, perm),
         lambda: pm.pallas_gossip_round_rows(aw, perm)),
        ("onerow_merge", lambda: xla(aw, gossip.ring_perm(R, 3)),
         lambda: pm.pallas_gossip_round(aw, gossip.ring_perm(R, 3))),
        ("rows_delta", lambda: xla_delta(dl, perm),
         lambda: pd.pallas_delta_gossip_round(dl, perm)),
    ]
    for e in sorted({E, wide_elements}):
        a = _random_fleet(2, e)
        d = _random_fleet(3, e, delta=True)
        for off in (3, 64):
            ring = gossip.ring_perm(R, off)
            cases += [
                (f"packed_merge/E{e}/o{off}",
                 functools.partial(xla, a, ring),
                 functools.partial(
                     lambda a, off, e: packed.unpack_awset(
                         pm.pallas_ring_round_rows_packed(
                             packed.pack_awset(a), off), e), a, off, e)),
                (f"dotword_merge/E{e}/o{off}",
                 functools.partial(xla, a, ring),
                 functools.partial(
                     lambda a, off, e: packed.unpack_awset_dots(
                         pm.pallas_ring_round_rows_dotpacked(
                             packed.pack_awset_dots(a), off), e),
                     a, off, e)),
                (f"packed_delta/E{e}/o{off}",
                 functools.partial(xla_delta, d, ring),
                 functools.partial(
                     lambda d, off, e: packed.unpack_awset_delta(
                         pd.pallas_delta_ring_round_packed(
                             packed.pack_awset_delta(d), off), e),
                     d, off, e)),
                (f"dotword_delta/E{e}/o{off}",
                 functools.partial(xla_delta, d, ring),
                 functools.partial(
                     lambda d, off, e: packed.unpack_awset_delta_dots(
                         pd.pallas_delta_ring_round_dotpacked(
                             packed.pack_awset_delta_dots(d), off), e),
                     d, off, e)),
            ]
    om = lattices.ormap_init(R, 64, R)
    om = lattices.ormap_put(om, jnp.uint32(1), jnp.uint32(3),
                            jnp.uint32(7), jnp.uint32(1))
    om = lattices.ormap_put(om, jnp.uint32(2), jnp.uint32(5),
                            jnp.uint32(9), jnp.uint32(2))
    cases.append((
        "ormap_ring",
        lambda: gossip.ormap_gossip_round(om, gossip.ring_perm(R, 3),
                                          kernel="xla"),
        lambda: gossip.ormap_ring_gossip_round(om, 3, kernel="pallas")))
    one = mesh_mod.make_mesh((1, 1), devices=jax.devices()[:1])
    sharded = mesh_mod.shard_state(aw, one)
    for stage in (0, 6):
        cases.append((
            f"butterfly/stage{stage}",
            functools.partial(xla, aw, gossip.butterfly_perm(R, stage)),
            functools.partial(gossip.butterfly_round_shardmap, sharded,
                              one, stage, kernel="pallas")))
    row = jax.tree.map(lambda x: x[0], dl)
    cases += [
        ("lane_fingerprints", lambda: digest.lane_fingerprints(row),
         lambda: pallas_digest.pallas_lane_fingerprints(row)),
        ("group_digests", lambda: digest.state_group_digests(row, 64),
         lambda: pallas_digest.pallas_state_group_digests(row, 64)),
    ]
    return cases


def phase_kernels(num_elements: int, wide_elements: int) -> dict:
    """Every other Pallas kernel once, bitwise against its XLA twin (on
    the chip: Mosaic, since the kernels leave interpret mode to CPUs)."""
    import jax

    t0 = time.perf_counter()
    cases = _kernel_cases(num_elements, wide_elements)
    bad = []
    for name, want, got in cases:
        w, g = jax.tree.leaves(want()), jax.tree.leaves(got())
        if len(w) != len(g) or not all(
                np.array_equal(np.asarray(x), np.asarray(y))
                for x, y in zip(w, g)):
            bad.append(name)
    _check(not bad, f"kernels != XLA twins: {bad}")
    return {"phase": "kernels", "cases": [n for n, _, _ in cases],
            "elements": sorted({num_elements, wide_elements}),
            "total_s": time.perf_counter() - t0,
            "peak_bytes_in_use": _peak_bytes()}


# ---------------------------------------------------------------------------
# --chips 4: the mesh frontend and the sharded δ-sync paths
# ---------------------------------------------------------------------------


def phase_mesh_serve(num_elements: int, num_ops: int) -> dict:
    """The phase-3 stream into a ``--mesh-devices 2x2`` frontend and
    into a single-device frontend (device 0): members and vv must agree
    with each other and with the oracle, and the mesh state must sit on
    four devices, one shard each."""
    import jax

    t0 = time.perf_counter()
    num_actors, mesh = _ACTORS, "2x2"
    stream = op_stream(num_ops, num_elements, _STREAM_SEED)
    want = oracle_replay(stream, num_actors)
    tmp = tempfile.mkdtemp(prefix="chip-smoke-mesh-")
    common = ["--elements", str(num_elements), "--actors", str(num_actors),
              "--max-batch", "32", "--flush-ms", "2"]
    got, loads, warm, placement = {}, {}, 0.0, None
    try:
        for name, extra in (("single", []),
                            ("mesh", ["--mesh-devices", mesh,
                                      "--sched", "auto"])):
            fe, addr, w = _serve(common + extra + [
                "--durable-dir", os.path.join(tmp, name)])
            warm += w
            try:
                loads[name] = drive(addr, stream)
                got[name] = members(addr)
                c = _counters(fe)
                _check(c.get("serve.batch_errors", 0) == 0,
                       f"{name}: serve.batch_errors")
                if name == "mesh":
                    leaf = fe.node._state.present
                    devs = [s.device for s in leaf.addressable_shards]
                    placement = [str(d) for d in devs]
                    dp, mp = (int(x) for x in mesh.split("x"))
                    _check(len(devs) == dp * mp
                           and len(set(devs)) == dp * mp,
                           f"mesh state on {placement}")
                    sched = c.get("sched.keyruns", 0)
            finally:
                fe.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    for name in got:
        _check(loads[name]["acked"] == num_ops, f"{name}: not all acked")
    _check(_same(got["mesh"], got["single"]), "mesh != single-device")
    _check(_same(got["single"], want), "single-device != spec oracle")
    return {"phase": "mesh_serve", "elements": num_elements,
            "mesh": mesh, "ops": num_ops, "members": int(want[0].size),
            "devices": len(jax.devices()), "mesh_shards": placement,
            "sched_keyruns": sched,
            "load_wall_s": {k: v["wall_s"] for k, v in loads.items()},
            "compile_s": warm, "total_s": time.perf_counter() - t0,
            "peak_bytes_in_use": _peak_bytes()}


def phase_sharded_delta_sync(n_devices: int, block_replicas: int,
                             num_elements: int, num_writers) -> dict:
    """``__graft_entry__._dryrun_inproc`` on the ambient devices."""
    from __graft_entry__ import _dryrun_inproc

    t0 = time.perf_counter()
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        _dryrun_inproc(n_devices, block_replicas=block_replicas,
                       num_elements=num_elements, num_writers=num_writers)
    lines = out.getvalue().splitlines()
    return {"phase": "sharded_delta_sync", "devices": n_devices,
            "replicas": block_replicas * n_devices,
            "elements": num_elements, "writers": num_writers,
            "paths": [ln for ln in lines if "path" in ln],
            "total_s": time.perf_counter() - t0,
            "peak_bytes_in_use": _peak_bytes()}


# ---------------------------------------------------------------------------


def _emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


# config-4's 100,032 replicas rounded up to what the 4-device block
# rings accept: a 64 multiple per device (4 x 25,024 = 100,096)
_MESH_BLOCK_REPLICAS = 25_024


def _one_chip_phases():
    """(name, thunk) of the default run, each thunk emitting its line
    and checking that its kernels ran under Mosaic."""
    def fleet():
        rec = phase_fleet_merge(1_000_000, 256, 256)
        _emit(rec)
        _check(rec["kernel"] == "pallas" and rec["mosaic"],
               "fleet merge did not run the Mosaic ring kernel")

    def delta():
        rec = phase_delta_fleet(100_032, 256, 256)
        _emit(rec)
        _check(all(r["kernel"] == "pallas" and r["mosaic"]
                   for r in rec["runs"].values()),
               "δ fleet did not run the Mosaic ring kernel")

    def served():
        rec = phase_served_store(1 << 20, 20_000)
        _emit(rec)
        _check(rec["ingest_regime"].startswith("pallas:")
               and not rec["ingest_regime"].endswith("k=0")
               and rec["mosaic_ingest"],
               "served ingest did not run the Mosaic fused kernel")

    return [("fleet_merge", fleet), ("delta_fleet", delta),
            ("served_store", served),
            ("kernels", lambda: _emit(phase_kernels(256, 8192)))]


def _four_chip_phases():
    return [("mesh_serve", lambda: _emit(phase_mesh_serve(1 << 20, 20_000))),
            ("sharded_delta_sync", lambda: _emit(phase_sharded_delta_sync(
                4, _MESH_BLOCK_REPLICAS, 256, 256)))]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--phase", action="append", default=None,
                    help="run only this phase of the chosen chip count "
                         "(repeatable; for debugging on the chip)")
    args = ap.parse_args(argv)
    phases = _four_chip_phases() if args.chips == 4 else _one_chip_phases()
    if args.phase:
        unknown = set(args.phase) - {n for n, _ in phases}
        if unknown:
            ap.error(f"no such phase with --chips {args.chips}: "
                     f"{sorted(unknown)}")
        phases = [(n, f) for n, f in phases if n in args.phase]

    from go_crdt_playground_tpu.utils.compile_cache import \
        place_compile_cache

    cache = place_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found {dev.platform!r}, not a TPU",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    _emit({"phase": "start", "devices": len(devices),
           "kind": dev.device_kind, "compile_cache": cache,
           "phases": [n for n, _ in phases]})
    try:
        for _, run in phases:
            run()
    except Exception as e:  # noqa: BLE001 — any failure fails the run
        print(f"chip_smoke FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        raise
    _emit({"ok": True, "device": {"platform": dev.platform,
                                  "kind": dev.device_kind,
                                  "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
