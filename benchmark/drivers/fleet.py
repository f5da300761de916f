"""Driver for fleet cells: back-to-back convergences of freshly
diverged δ-AWSet fleets through the program's convergence driver,
``parallel/gossip.rounds_to_convergence(state, delta=True,
delta_semantics=...)``.

Convergence i of a run starts from the fleet ``fleetgen.fleet(seed,
i)``, made on the device in one jitted call: on the default device
here, laid out by the caller's ``sharding`` where another driver runs
this one (``run(ctx, sharding)``).  Set-up builds and
converges one fleet outside the window's index range, which compiles
every program the window runs.  The window counts convergences until
``--seconds`` have passed; the one in flight then finishes and counts.
Checked after it: every convergence's round count against the
reference's, and the full membership and clock of every row of a few
convergences (the first, and others drawn from the seed) against the
reference's join, read back once the window has closed.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time

import numpy as np

from benchmark import fleetgen, gen, harness
from benchmark.reference import fleet as ref

FAMILY = "fleet"

_WARMUP_INDEX = 1 << 30       # a fleet index the window never reaches
_MAX_SAMPLE_INDEX = 32


def _sizes(cfg):
    return (int(cfg["replicas"]), int(cfg["elements"]), int(cfg["actors"]),
            int(cfg["writers"]))


def builder(cfg: dict, sharding=None):
    """``build(seed, i) -> AWSetDeltaState`` on the device, one jitted
    call per fleet (seed and index ride as data); every field laid out
    by ``sharding`` where one is given."""
    import jax
    import jax.numpy as jnp

    from go_crdt_playground_tpu.models.awset_delta import AWSetDeltaState

    R, E, A, W = _sizes(cfg)

    def fields(seed_lo, seed_hi, i):
        return AWSetDeltaState(**fleetgen.fleet(
            jnp, (seed_lo, seed_hi, i), R, E, A, W))

    build = (jax.jit(fields) if sharding is None
             else jax.jit(fields, out_shardings=sharding))

    def make(seed: int, i: int):
        return build(jnp.uint32(seed & 0xFFFFFFFF),
                     jnp.uint32((seed >> 32) & 0xFFFFFFFF), jnp.uint32(i))

    return make


def converge(cfg: dict, state, control: bool = False):
    """``(rounds, final state)`` from the program's convergence driver;
    -1 rounds where it gives up.  ``control``: the program's strict
    reference delta semantics instead (deletion records travel only
    from their originator), run for the reference's round count - it
    breaks the transitive-deletion guarantee, and the membership check
    must catch it."""
    import jax
    import jax.numpy as jnp

    from go_crdt_playground_tpu.parallel import gossip

    if control:
        R = state.vv.shape[0]
        n = ref.rounds(R)
        advance = gossip._advance_program(True, cfg["schedule"],
                                          "reference", False)
        final, _ = advance(
            state, jax.random.key(0),
            jnp.asarray(gossip.dissemination_offsets(R), jnp.uint32),
            jnp.float32(0.0), jnp.uint32(0), n)
        return n, final
    try:
        return gossip.rounds_to_convergence(
            state, delta=True, delta_semantics=cfg["delta_semantics"],
            schedule=cfg["schedule"])
    except RuntimeError:
        return -1, state


def run(ctx: dict, sharding=None) -> None:
    import jax

    cfg, p = ctx["config"], ctx["traffic"]
    seed, seconds = ctx["seed"], ctx["seconds"]
    R, E, A, W = _sizes(cfg)
    make = builder(cfg, sharding)
    t_jax = time.monotonic() - ctx["t_start"]
    rounds0, final = converge(cfg, make(seed, _WARMUP_INDEX),
                              ctx.get("control", False))
    jax.block_until_ready(final)
    del final
    # convergences whose whole state is checked: the first, and others
    # drawn from the seed among the first few
    rng = gen.rng_for(seed, 7)
    sampled = {0} | set(rng.integers(
        1, _MAX_SAMPLE_INDEX, int(p.get("sampled_checks", 2)) - 1).tolist())
    kept = {}
    rounds = []
    tmp = tempfile.mkdtemp(prefix="bench-fleet-")
    trace_dir = os.path.join(tmp, "trace")
    try:
        if ctx["trace"]:
            harness.start_trace(trace_dir)
        ctx["compiles"].start()
        t0 = time.monotonic()
        ctx["setup_s"] = t0 - ctx["t_start"]
        with jax.profiler.TraceAnnotation("bench.window"):
            i = 0
            while True:
                r, final = converge(cfg, make(seed, i),
                                    ctx.get("control", False))
                rounds.append(int(r))
                if i in sampled:
                    kept[i] = (final.present, final.vv)
                del final
                i += 1
                if time.monotonic() - t0 >= seconds:
                    break
        elapsed = time.monotonic() - t0
        compiles = ctx["compiles"].stop()
        if ctx["trace"]:
            jax.profiler.stop_trace()
        ctx["memory_peak_bytes"] = harness.memory_peak(
            ctx["device"]["count"])
        got = {i: (np.asarray(pr), np.asarray(vv))
               for i, (pr, vv) in kept.items()}
        kept.clear()
        if ctx["trace"]:
            from benchmark import trace_reduce

            ctx["trace_red"] = trace_reduce.reduce(
                trace_reduce.find_xplane(trace_dir))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    t_check = time.monotonic()
    want_rounds = ref.rounds(R)
    bad_members = bad_vv = 0
    for i, (present, vv) in got.items():
        mm = ref.mismatches(ref.converged(seed, i, R, E, A, W), present, vv)
        bad_members += mm["members"]
        bad_vv += mm["vv"]
    ctx["checks"] = [
        ("rounds_vs_reference",
         sum(int(r != want_rounds) for r in rounds), 0),
        ("members_vs_reference", bad_members, 0),
        ("vv_vs_reference", bad_vv, 0),
    ]
    ctx["attempted"] = len(rounds)
    ctx["failed"] = sum(int(r != want_rounds) for r in rounds)
    ctx["fleet"] = {"rounds": rounds, "elapsed_s": elapsed}
    ctx["shapes"] = {"R": R, "E": E, "A": A}
    ctx["info"] = {"compiles_in_window": compiles, "warmup_rounds": rounds0,
                   "setup_phases_s": {"jax_ready": t_jax},
                   "check_s": time.monotonic() - t_check,
                   "checked": sorted(got)}
