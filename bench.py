"""Benchmark: replica-pair merges/sec/chip (AWSet, 256 elems).

Default mode measures BASELINE.json config 3 — 10K replicas x 256
elements, vmapped dot-context merge — as sustained anti-entropy gossip
throughput on the chip, and prints exactly one JSON line:
  {"metric": ..., "value": N, "unit": "merges/sec/chip", "vs_baseline": N}
Off the chip it exits non-zero and prints no rate.

``python bench.py --ladder`` measures every config of the BASELINE.json
measurement ladder (1: conformance-anchor spec rate, 2: GCounter 1K,
3: AWSet 10K x 256 — plus its dot-word layout variant, 4: delta-AWSet
100K gossip — plus its dot-word variant and the strict-reference mode,
5: mixed AWSet+2P-Set 1M — plus the AWSet-only single-family rate),
prints one JSON line per config, and writes BENCH_LADDER.json.

The reference publishes no numbers (SURVEY §6: no Benchmark* functions,
README is one line), and no Go toolchain exists in this environment, so
``vs_baseline`` is the speedup over the single-core executable spec
(models/spec.py) running the SAME pair merge on the same element count —
the go-test-equivalent semantics executed in-process, our only executable
stand-in for the reference implementation.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

_HEADLINE_METRIC = "replica-pair merges/sec/chip (AWSet, 256 elems)"
_HEADLINE_UNIT = "merges/sec/chip"


def build_state(num_replicas: int, num_elements: int, num_writers: int):
    """Vectorized construction of a valid fleet: rows < num_writers are
    writers (unique actors) that each added a row-dependent slice of the
    element universe in element order; the rest are observers (explicit
    aliased actor ids are safe — they never tick a clock)."""
    import jax.numpy as jnp

    from go_crdt_playground_tpu.models import awset

    R, E, W = num_replicas, num_elements, num_writers
    actors = np.arange(R, dtype=np.uint32) % W
    state = awset.init(R, E, W, actors=actors)
    r = jnp.arange(R, dtype=jnp.uint32)[:, None]
    e = jnp.arange(E, dtype=jnp.uint32)[None, :]
    writer = r < W
    present = writer & (
        (e * jnp.uint32(2654435761) + r * jnp.uint32(40503)) % 5 < 2)
    counter = jnp.cumsum(present, axis=1, dtype=jnp.uint32) * present
    vv = jnp.zeros((R, W), jnp.uint32).at[
        jnp.arange(R), jnp.asarray(actors)].max(counter.max(axis=1))
    return state._replace(
        vv=vv,
        present=present,
        dot_actor=jnp.where(present, r % W, 0),
        dot_counter=counter,
    )


def measure_tpu(num_replicas=10_048, num_elements=256, num_writers=256,
                full=False):
    """True sustained device rate for the headline config: rounds fused
    with ``lax.scan`` and timed by the adaptive two-point fit
    (_scan_round_rate), which cancels the fixed dispatch/transfer
    overhead.

    num_replicas defaults to 10,048 — a nearby _BLOCK_R (64) multiple
    of the ladder's nominal 10K, which ring_supported() requires for the
    ring-FUSED kernel; at 10,000 exactly the dispatch would silently
    fall back to the gather-path kernel and measure a different (slower)
    program than production schedules run.  Rates are per-merge, so the
    0.5% size change is comparison-neutral."""
    import jax.numpy as jnp

    from go_crdt_playground_tpu.parallel import gossip

    state = build_state(num_replicas, num_elements, num_writers)
    offsets = jnp.asarray(gossip.dissemination_offsets(num_replicas),
                          jnp.uint32)
    # offset-based ring rounds: the fused ring kernel reads partner rows
    # in place (no state[perm] copy) and takes the offset as data, so
    # the whole dissemination schedule is one compiled program
    meas = _scan_round_rate(gossip.ring_gossip_round, state, offsets,
                            start=64, full=True)
    rate = num_replicas / meas.per_round_s
    if full:
        return rate, meas.stats(num_replicas)
    return rate


def measure_tpu_dotpacked(num_replicas=10_048, num_elements=256,
                          num_writers=256, full=False):
    """measure_tpu's fleet on the DOT-WORD layout
    (models/packed.DotPackedAWSetState): dots fused to one
    uint32/element + bitpacked membership, ~1.6x less HBM per ring
    round than the bool layout and bitwise-pinned against it.  Same
    merge semantics, same metric — the default headline reports
    whichever layout sustains the higher rate (the layout rides in the
    JSON line's ``layout`` field)."""
    import jax.numpy as jnp

    from go_crdt_playground_tpu.models import packed as packed_mod
    from go_crdt_playground_tpu.ops.pallas_merge import (
        pallas_ring_round_rows_dotpacked)
    from go_crdt_playground_tpu.parallel import gossip

    state = packed_mod.pack_awset_dots(
        build_state(num_replicas, num_elements, num_writers))
    offsets = jnp.asarray(gossip.dissemination_offsets(num_replicas),
                          jnp.uint32)
    meas = _scan_round_rate(pallas_ring_round_rows_dotpacked, state,
                            offsets, start=64, full=True)
    rate = num_replicas / meas.per_round_s
    if full:
        return rate, meas.stats(num_replicas)
    return rate


def measure_spec_baseline(num_elements=256, merges=60, runs=5,
                          full=False):
    """Single-core dict-model pair-merge rate at the same element count.

    The yardstick behind every ``vs_baseline`` field, so it must be
    stable: one 60-merge sample on a shared CPU wobbled 2.1x between
    the round-2 bench and ladder runs.  Now the SAME fixed op mix is
    timed ``runs`` times and the MEDIAN rate is the baseline; full=True
    also returns the raw per-run rates so bench artifacts carry the
    evidence."""
    from go_crdt_playground_tpu.models.spec import AWSet, VersionVector

    def writer(actor):
        s = AWSet(actor=actor, version_vector=VersionVector([0, 0]))
        s.add(*(f"e{i}" for i in range(0, num_elements, 2 + actor)))
        return s

    def one_run():
        t0 = time.perf_counter()
        n = 0
        while n < merges:
            a, b = writer(0), writer(1)
            for _ in range(10):
                a.merge(b)
                b.merge(a)
                n += 2
        return n / (time.perf_counter() - t0)

    one_run()  # warm (allocator, string interning)
    rates = sorted(one_run() for _ in range(runs))
    median = rates[len(rates) // 2]
    if full:
        return median, [round(r, 1) for r in rates]
    return median


class RateMeasurement:
    """One overhead-cancelled rate with its full evidence trail.

    per_round_s is the min-based two-point fit (the headline number);
    per_repeat_rates are the per-repeat-index fits (repeat i of the large
    count minus repeat i of the half count), whose min/median/spread
    quantify run-to-run variance; raw_timings_s maps round-count -> the
    repeat wall times, persisted so the ladder numbers are auditable."""

    def __init__(self, per_round_s, fit_counts, raw_timings_s):
        self.per_round_s = per_round_s
        self.fit_counts = fit_counts            # (n_half, n_full)
        self.raw_timings_s = raw_timings_s      # {n: [t_repeat...]}

    def per_repeat_per_round_s(self):
        lo, hi = self.fit_counts
        gap = hi - lo
        return [(b - a) / gap
                for a, b in zip(self.raw_timings_s[lo],
                                self.raw_timings_s[hi])
                if (b - a) > 0]

    def stats(self, work_per_round):
        """Rate fields for a ladder record: min/median across repeats plus
        relative spread, all in work-units/sec."""
        rates = sorted(work_per_round / t
                       for t in self.per_repeat_per_round_s())
        if not rates:  # degenerate repeats; fall back to the min-fit
            rates = [work_per_round / self.per_round_s]
        median = rates[len(rates) // 2]
        return {
            "rate_min": round(rates[0], 1),
            "rate_median": round(median, 1),
            "spread": round((rates[-1] - rates[0]) / median, 3),
            "repeats": len(rates),
            "raw_timings_s": {str(n): [round(t, 6) for t in ts]
                              for n, ts in sorted(self.raw_timings_s.items())},
            "fit_counts": list(self.fit_counts),
        }


def _scan_round_rate(round_fn, state, aux, start=16, max_n=1 << 17,
                     min_delta=0.25, repeats=3, warm_runs=1, full=False):
    """Sustained per-round seconds for ``state <- round_fn(state, aux[i])``
    rounds fused with lax.scan, overhead-cancelled by a two-point fit.

    The round count adapts: it doubles until the (2n - n) timing delta
    clears ``min_delta`` seconds, so the fit cannot drown in the fixed
    dispatch/transfer overhead the way a fixed pair of counts can for very cheap or very expensive
    rounds.  full=True returns the RateMeasurement (repeats + raw
    timings) instead of the scalar.

    warm_runs: post-compile executions discarded before the timed
    repeats at each count.  One suffices for small fleets; multi-GB
    states want 2 — the round-4 config-5 artifact showed the first
    timed repeat 16% slow (allocator/page churn on a fresh 2x1M-replica
    working set)."""
    import jax
    import jax.numpy as jnp

    n_aux = jax.tree.leaves(aux)[0].shape[0]

    @jax.jit
    def run(state, n):
        # DYNAMIC trip count: the adaptive doubling search visits many
        # round counts, and a static-length scan would recompile at
        # every doubling.  One fori_loop program serves every count (loop overhead is
        # negligible against ms-scale rounds).
        def body(i, s):
            return round_fn(s, jax.tree.map(lambda x: x[i % n_aux], aux))
        s = jax.lax.fori_loop(jnp.uint32(0), n, body, state)
        # the sync scalar MUST read every output leaf: the VV join chain
        # depends only on vv, so a vv-only fetch lets XLA dead-code the
        # entire membership/dot merge and the "measurement" collapses to
        # the max-join alone
        return sum(x.astype(jnp.float32).sum() for x in jax.tree.leaves(s))

    memo = {}

    def timed(n):
        if n not in memo:  # each doubling reuses the previous full count
            for _ in range(max(1, warm_runs)):
                float(run(state, jnp.uint32(n)))
            times = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                float(run(state, jnp.uint32(n)))
                times.append(time.perf_counter() - t0)
            memo[n] = times
        return min(memo[n])

    n = max(2, start)
    while True:
        delta = timed(n) - timed(n // 2)
        if delta >= min_delta or n >= max_n:
            if delta <= 0:
                raise RuntimeError(
                    f"timing fit degenerate at n={n} (delta {delta:.4f}s)")
            per_round = delta / (n - n // 2)
            if full:
                return RateMeasurement(per_round, (n // 2, n),
                                       {k: memo[k] for k in (n // 2, n)})
            return per_round
        n *= 2


def measure_config1(num_ops=120, seed=11):
    """Correctness anchor: randomized 3-replica scenario replayed against
    BOTH the executable spec and the packed kernel with byte-equal
    canonical renderings, plus the spec's single-core merge rate at the
    config's element count (E=16)."""
    import random

    import jax

    from go_crdt_playground_tpu.models import awset
    from go_crdt_playground_tpu.models.spec import AWSet, VersionVector
    from go_crdt_playground_tpu.ops.merge import merge_one_into
    from go_crdt_playground_tpu.utils import codec

    rng = random.Random(seed)
    R, E, A = 3, 16, 3
    spec = [AWSet(actor=r, version_vector=VersionVector([0] * A))
            for r in range(R)]
    dictionary = codec.ElementDict(capacity=E,
                                   values=[f"e{i}" for i in range(E)])
    packed = awset.from_arrays(codec.pack_awsets(spec, dictionary, A))
    for _ in range(num_ops):
        r = rng.randrange(R)
        op = rng.random()
        if op < 0.55:
            k = f"e{rng.randrange(E)}"
            spec[r].add(k)
            packed = awset.add_element(
                packed, np.uint32(r), np.uint32(dictionary.encode(k)))
        elif op < 0.75 and spec[r].entries:
            k = rng.choice(sorted(spec[r].entries))
            spec[r].del_(k)
            packed = awset.del_element(
                packed, np.uint32(r), np.uint32(dictionary.encode(k)))
        else:
            src = rng.randrange(R)
            if src != r:
                spec[r].merge(spec[src])
                packed, _ = merge_one_into(packed, r, packed, src)
    jax.block_until_ready(packed.vv)
    rendered = codec.render_packed(awset.to_arrays(packed), dictionary)
    conformant = rendered == [str(s) for s in spec]
    return {
        "metric": "config1: AWSet 3x16 conformance anchor "
                  "(spec merges/sec, 1 CPU core)",
        "value": round(measure_spec_baseline(num_elements=16), 1),
        "unit": "merges/sec",
        "conformant": conformant,
    }


def measure_config2(num_replicas=1000, num_actors=256):
    """GCounter 1K replicas — batched elementwise-max join gossip."""
    import jax.numpy as jnp

    from go_crdt_playground_tpu.ops import lattices
    from go_crdt_playground_tpu.parallel import gossip

    counts = np.random.default_rng(0).integers(
        0, 1 << 20, (num_replicas, num_actors)).astype(np.uint32)
    state = lattices.GCounterState(
        counts=jnp.asarray(counts),
        actor=jnp.arange(num_replicas, dtype=jnp.uint32) % num_actors)
    offsets = gossip.dissemination_offsets(num_replicas)
    perms = jnp.stack([gossip.ring_perm(num_replicas, o) for o in offsets])
    meas = _scan_round_rate(
        lambda s, perm: lattices.gossip_round(lattices.gcounter_join, s,
                                              perm),
        state, perms, start=256, full=True)
    return {
        "metric": "config2: GCounter 1K replicas, elementwise-max join",
        "value": round(num_replicas / meas.per_round_s, 1),
        "unit": "merges/sec/chip",
        **meas.stats(num_replicas),
    }


def _config4_delta_fleet(num_replicas, num_elements, num_writers):
    """The config-4 fleet + its dissemination offsets, shared by the v2
    and strict-reference ladder steps so both measure the SAME state.

    100,032 = a nearby _BLOCK_R multiple of the nominal 100K (see
    measure_tpu: exact 100,000 would silently fall back off the
    ring-fused kernel)."""
    import jax.numpy as jnp

    from go_crdt_playground_tpu.models import awset_delta
    from go_crdt_playground_tpu.parallel import gossip

    base = build_state(num_replicas, num_elements, num_writers)
    zE = jnp.zeros((num_replicas, num_elements), jnp.uint32)
    state = awset_delta.AWSetDeltaState(
        vv=base.vv, present=base.present, dot_actor=base.dot_actor,
        dot_counter=base.dot_counter, actor=base.actor,
        deleted=jnp.zeros((num_replicas, num_elements), bool),
        del_dot_actor=zE, del_dot_counter=zE, processed=base.vv)
    offsets = jnp.asarray(gossip.dissemination_offsets(num_replicas),
                          jnp.uint32)
    return state, offsets


def _measure_config4_variant(metric, num_replicas, num_elements,
                             num_writers, **round_kw):
    """One config-4 ladder measurement: the shared fleet pushed through
    delta_ring_gossip_round with the given semantics kwargs."""
    from go_crdt_playground_tpu.parallel import gossip

    state, offsets = _config4_delta_fleet(num_replicas, num_elements,
                                          num_writers)
    meas = _scan_round_rate(
        lambda s, off: gossip.delta_ring_gossip_round(s, off, **round_kw),
        state, offsets, start=8, max_n=256, full=True)
    return {
        "metric": metric,
        "value": round(num_replicas / meas.per_round_s, 1),
        "unit": "delta-merges/sec/chip",
        **meas.stats(num_replicas),
    }


def measure_config4(num_replicas=100_032, num_elements=256,
                    num_writers=256):
    """delta-AWSet 100K replicas: payload-compressed gossip rounds (the
    single-chip rate of the program that runs on a v5e-4 mesh via
    parallel/mesh.py; the driver environment has one chip)."""
    return _measure_config4_variant(
        "config4: delta-AWSet 100K replicas, v2 delta gossip",
        num_replicas, num_elements, num_writers, delta_semantics="v2")


def measure_config4_reference(num_replicas=100_032, num_elements=256,
                              num_writers=256):
    """config4's fleet under STRICT-REFERENCE δ semantics — the fused
    empty-δ VV-skip path (ops/pallas_delta._strict_vv_epilogue).  Before
    round 3 fused it, reference-mode fleets paid the ~40x XLA HasDot
    path; this measurement is the evidence of the fused rate."""
    return _measure_config4_variant(
        "config4ref: delta-AWSet 100K replicas, STRICT-REFERENCE delta "
        "semantics (fused empty-delta VV-skip)",
        num_replicas, num_elements, num_writers,
        delta_semantics="reference", strict_reference_semantics=True)


def measure_config3_dotpacked(num_replicas=10_048, num_elements=256,
                              num_writers=256):
    """config3's fleet on the DOT-WORD layout (models/packed
    .DotPackedAWSetState): dots fused to one uint32/element + bitpacked
    membership, ~1.6x less HBM per ring round than the bool layout —
    the committed evidence for the layout's traffic win (round 5).
    Delegates to measure_tpu_dotpacked so the ladder step and the
    default headline's dot-word attempt time the SAME program."""
    rate, stats = measure_tpu_dotpacked(num_replicas, num_elements,
                                        num_writers, full=True)
    return {
        "metric": f"config3_dotpacked: AWSet {num_replicas} x "
                  f"{num_elements} ring merge, dot-word + bitpacked "
                  "membership layout",
        "value": round(rate, 1),
        "unit": "merges/sec/chip",
        **stats,
    }


def measure_config4_dotpacked(num_replicas=100_032, num_elements=256,
                              num_writers=256):
    """config4's fleet on the δ DOT-WORD layout (both dot pairs as
    single uint32 words + bitpacked membership): directly comparable to
    config4's v2 rate, evidencing the ~1.6x HBM cut on the δ path."""
    from go_crdt_playground_tpu.models import packed as packed_mod
    from go_crdt_playground_tpu.ops.pallas_delta import (
        pallas_delta_ring_round_dotpacked)

    state, offsets = _config4_delta_fleet(num_replicas, num_elements,
                                          num_writers)
    packed = packed_mod.pack_awset_delta_dots(state)
    meas = _scan_round_rate(pallas_delta_ring_round_dotpacked, packed,
                            offsets, start=8, max_n=256, warm_runs=2,
                            full=True)
    return {
        "metric": f"config4_dotpacked: delta-AWSet {num_replicas} "
                  "replicas, v2 delta gossip, dot-word + bitpacked "
                  "membership layout",
        "value": round(num_replicas / meas.per_round_s, 1),
        "unit": "delta-merges/sec/chip",
        **meas.stats(num_replicas),
    }


def measure_config5(num_replicas=1_000_000, num_elements=256,
                    num_writers=256):
    """Mixed AWSet + 2P-Set at 1M replicas: one anti-entropy round of
    each family per step (the all-families lattice-join workload)."""
    import jax.numpy as jnp

    from go_crdt_playground_tpu.ops import lattices
    from go_crdt_playground_tpu.parallel import gossip

    aw = build_state(num_replicas, num_elements, num_writers)
    rng = np.random.default_rng(1)
    # independent uint8 draws per mask (float64 draws would transiently
    # cost ~2GB per array; correlating the two masks would drop the
    # removed-without-added merge case from the workload)
    tp = lattices.TwoPSetState(
        added=jnp.asarray(rng.integers(
            0, 100, (num_replicas, num_elements), dtype=np.uint8) < 30),
        removed=jnp.asarray(rng.integers(
            0, 100, (num_replicas, num_elements), dtype=np.uint8) < 5))
    offsets = jnp.asarray(
        gossip.dissemination_offsets(num_replicas)[:8], jnp.uint32)

    def both(state, off):
        a, t = state
        perm = gossip.ring_perm(a.present.shape[0], off)
        return (gossip.ring_gossip_round(a, off),
                lattices.gossip_round(lattices.twopset_join, t, perm))

    meas = _scan_round_rate(both, (aw, tp), offsets, start=4,
                            max_n=64, repeats=3, warm_runs=2, full=True)
    return {
        "metric": "config5: mixed AWSet + 2P-Set 1M replicas, "
                  "fused lattice-join round",
        "value": round(2 * num_replicas / meas.per_round_s, 1),
        "unit": "merges/sec/chip",
        **meas.stats(2 * num_replicas),
        "note": "counts 2 merges per replica per round (1 full AWSet "
                "dot-context merge + 1 2P-Set OR-join); config5_awset "
                "is the directly-comparable single-family rate",
    }


def measure_config5_awset(num_replicas=1_000_000, num_elements=256,
                          num_writers=256):
    """config5's AWSet half ALONE at 1M replicas — the directly-measured
    single-family rate (configs 2-4 accounting) that the mixed config's
    value/2 could only bound."""
    import jax.numpy as jnp

    from go_crdt_playground_tpu.parallel import gossip

    aw = build_state(num_replicas, num_elements, num_writers)
    offsets = jnp.asarray(
        gossip.dissemination_offsets(num_replicas)[:8], jnp.uint32)
    meas = _scan_round_rate(gossip.ring_gossip_round, aw, offsets,
                            start=4, max_n=64, repeats=3, warm_runs=2,
                            full=True)
    return {
        "metric": f"config5_awset: AWSet-only {num_replicas} replicas, "
                  "ring-fused dot-context merge",
        "value": round(num_replicas / meas.per_round_s, 1),
        "unit": "merges/sec/chip",
        **meas.stats(num_replicas),
    }


def _time_drop_round(state0, offsets, rate, num_replicas, **scan_kw):
    """Per-round seconds of a drop-masked ring round (mask generation
    included).  Only the round SHAPE must match the convergence runs
    (ring round + bernoulli mask); the mask stream itself is
    timing-neutral, so this does not need gossip.py's exact fold_in
    recipe.  Platform-agnostic so CI can compile/execute the exact
    program the TPU capture times (a latent break here would otherwise
    first surface at the END of an on-chip droprate session)."""
    import jax
    import jax.numpy as jnp

    from go_crdt_playground_tpu.parallel import gossip

    key0 = jax.random.key(99)

    def drop_round(s, i, _rate=rate):
        drop = None
        if _rate > 0.0:
            drop = jax.random.bernoulli(
                jax.random.fold_in(key0, i), _rate, (num_replicas,))
        return gossip.ring_gossip_round(
            s, offsets[i % offsets.shape[0]], drop)

    scan_kw.setdefault("start", 64)
    return _scan_round_rate(drop_round, state0,
                            jnp.arange(1 << 10, dtype=jnp.uint32),
                            **scan_kw)


def measure_droprate(num_replicas=1024, num_elements=256, num_writers=256,
                     drop_rates=(0.0, 0.1, 0.2, 0.3, 0.4, 0.5), seeds=3):
    """Rounds-to-convergence under per-replica exchange drop — the
    north-star resilience metric (BASELINE.json; SURVEY §5.3: lost
    exchanges self-heal, drops only delay convergence).  Dissemination
    schedule; each (drop_rate, seed) is an independent run on the same
    divergent initial fleet."""
    import jax

    from go_crdt_playground_tpu.parallel import gossip

    import jax.numpy as jnp

    state0 = build_state(num_replicas, num_elements, num_writers)
    offsets = jnp.asarray(gossip.dissemination_offsets(num_replicas),
                          jnp.uint32)
    on_tpu = jax.default_backend() == "tpu"
    table = []
    for rate in drop_rates:
        rounds = []
        for seed in range(seeds):
            r, final = gossip.rounds_to_convergence(
                state0, key=jax.random.key(seed), drop_rate=rate,
                max_rounds=600, schedule="dissemination")
            assert bool(gossip.converged_jit(final.present, final.vv))
            rounds.append(r)
        rounds.sort()
        entry = {
            "drop_rate": rate,
            "rounds_min": rounds[0],
            "rounds_median": rounds[len(rounds) // 2],
            "rounds_max": rounds[-1],
            "seeds": seeds,
        }
        if on_tpu:
            # device wall time of a drop-masked round, mask generation
            # included — rounds-to-convergence is platform-independent,
            # but the TIME a drop round costs is the chip-side number
            # the resilience story was missing.
            per_round = _time_drop_round(state0, offsets, rate,
                                         num_replicas)
            entry["tpu_round_ms"] = round(per_round * 1e3, 4)
        table.append(entry)
    return {
        "metric": f"rounds-to-convergence vs drop rate "
                  f"(AWSet {num_replicas}x{num_elements}, dissemination "
                  "schedule, converged digest verified)",
        "value": table[0]["rounds_median"],
        "unit": "rounds (at drop 0)",
        "curve": table,
        "platform": jax.default_backend(),
    }


def _delta_fleet(num_replicas, num_elements, num_writers):
    """A divergent δ-AWSet fleet (the config-4/north-star initial state)."""
    import jax.numpy as jnp

    from go_crdt_playground_tpu.models import awset_delta

    base = build_state(num_replicas, num_elements, num_writers)
    # every field gets its OWN buffer: aliased leaves (processed sharing
    # vv, the two del arrays sharing one zeros) break buffer donation
    # ("attempt to donate the same buffer twice")
    return awset_delta.AWSetDeltaState(
        vv=base.vv, present=base.present, dot_actor=base.dot_actor,
        dot_counter=base.dot_counter, actor=base.actor,
        deleted=jnp.zeros((num_replicas, num_elements), bool),
        del_dot_actor=jnp.zeros((num_replicas, num_elements), jnp.uint32),
        del_dot_counter=jnp.zeros((num_replicas, num_elements), jnp.uint32),
        processed=base.vv + jnp.uint32(0))


def build_diverged_pair(divergence: int, num_elements: int = 1024,
                        num_actors: int = 64, base: int = 256):
    """Two δ-AWSet replicas with a CONTROLLED divergence, for payload
    measurement: both start from an identical converged base (``base``
    elements written by actor 0), then each performs ``divergence``
    fresh adds of its own disjoint element slice plus one δ-Del call
    deleting divergence//4 of its own base slice (one shared deletion
    dot — the reference δ-Del semantics, awset-delta_test.go:15-26).
    Returns the packed 2-row AWSetDeltaState."""
    import jax.numpy as jnp

    from go_crdt_playground_tpu.models import awset_delta

    d = divergence
    assert base + 2 * d <= num_elements and 2 * (d // 4) <= base
    R, E = 2, num_elements
    state = awset_delta.init(R, E, num_actors,
                             actors=np.asarray([1, 2], np.uint32))
    e = np.arange(E, dtype=np.uint32)[None, :]
    r = np.arange(R, dtype=np.uint32)[:, None]
    present = np.broadcast_to(e < base, (R, E)).copy()
    da = np.where(present, 0, 0).astype(np.uint32)
    dc = np.where(present, e + 1, 0).astype(np.uint32)
    vv = np.zeros((R, num_actors), np.uint32)
    vv[:, 0] = base
    # fresh adds: replica r adds [base + r*d, base + (r+1)*d)
    mine = (e >= base + r * d) & (e < base + (r + 1) * d)
    present |= mine
    da = np.where(mine, r + 1, da).astype(np.uint32)
    dc = np.where(mine, e - (base + r * d) + 1, dc).astype(np.uint32)
    vv[np.arange(R), np.arange(R) + 1] = d
    # one δ-Del call per replica: deletes its slice of the base, one
    # shared dot (actor r+1, counter d+1)
    nd = d // 4
    deleted = (e >= r * (base // 2)) & (e < r * (base // 2) + nd)
    present &= ~deleted
    da = np.where(deleted, 0, da).astype(np.uint32)
    dc = np.where(deleted, 0, dc).astype(np.uint32)
    del_da = np.where(deleted, r + 1, 0).astype(np.uint32)
    del_dc = np.where(deleted, d + 1, 0).astype(np.uint32)
    if nd:
        vv[np.arange(R), np.arange(R) + 1] = d + 1
    return awset_delta.AWSetDeltaState(
        vv=jnp.asarray(vv), present=jnp.asarray(present),
        dot_actor=jnp.asarray(da), dot_counter=jnp.asarray(dc),
        actor=jnp.asarray([1, 2], jnp.uint32),
        deleted=jnp.asarray(deleted), del_dot_actor=jnp.asarray(del_da),
        del_dot_counter=jnp.asarray(del_dc), processed=jnp.asarray(vv))


def measure_payload_bytes(num_elements=1024, num_actors_list=(64, 256),
                          divergences=(0, 1, 4, 16, 64, 256)):
    """Bytes per δ exchange vs divergence level — what the reference's
    whole wire-protocol idea (MakeDeltaMergeData's minimal payload,
    awset-delta_test.go:79-105) buys, measured across the framework's
    three payload forms:

      * dense device form (DeltaPayload.nbytes_dense): O(E), what a
        naive tensor exchange ships;
      * compact fixed-K device form (ops/compact): O(K) ICI bytes, K =
        smallest power of two holding the payload;
      * varint wire form (utils/wire, = the C++ codec's format): what
        actually crosses a socket/DCN (net.Node's PAYLOAD frame body);
      * full-state wire form: the first-contact cost (the reference's
        full-merge branch, awset-delta_test.go:53-56) for scale.
    """
    import jax

    from go_crdt_playground_tpu.ops import compact as compact_ops
    from go_crdt_playground_tpu.ops import delta as delta_ops
    from go_crdt_playground_tpu.utils import wire

    table = []
    for num_actors in num_actors_list:
        for d in divergences:
            st = build_diverged_pair(d, num_elements, num_actors)
            src = jax.tree.map(lambda x: x[1], st)
            dst = jax.tree.map(lambda x: x[0], st)
            p = delta_ops.delta_extract(src, dst.vv)
            n_ch = int(p.changed.sum())
            n_del = int(p.deleted.sum())
            k = max(8, 1 << (max(n_ch, n_del, 1) - 1).bit_length())
            comp = compact_ops.compact_payload(p, k, k)
            assert not bool(comp.overflow)
            full = delta_ops.DeltaPayload(
                src_vv=src.vv, changed=src.present, ch_da=src.dot_actor,
                ch_dc=src.dot_counter, deleted=src.deleted,
                del_da=src.del_dot_actor, del_dc=src.del_dot_counter,
                src_actor=src.actor, src_processed=src.processed)
            table.append({
                "num_actors": num_actors,
                "divergence_ops": d,
                "changed_lanes": n_ch,
                "deleted_lanes": n_del,
                "dense_bytes": int(p.nbytes_dense()),
                "compact_bytes": int(comp.nbytes_wire()),
                "compact_k": k,
                "wire_bytes": int(wire.payload_nbytes_wire(p)),
                "full_wire_bytes": int(wire.payload_nbytes_wire(full)),
            })
    first_actors = [t for t in table
                    if t["num_actors"] == num_actors_list[0]]
    sparse = next((t for t in first_actors if t["divergence_ops"] > 0),
                  first_actors[0])
    return {
        "metric": f"delta-payload bytes/exchange vs divergence "
                  f"(E={num_elements}, push-pull extract vs receiver VV)",
        "value": sparse["wire_bytes"],
        "unit": f"bytes/exchange (wire, divergence "
                f"{sparse['divergence_ops']})",
        "curve": table,
        "note": "wire = varint masked-section format (the socket/DCN "
                "bytes, net.Node PAYLOAD body); compact = fixed-K "
                "device lanes (the ICI ring bytes); dense = O(E) "
                "masked tensors; full = first-contact full-state wire "
                "cost",
    }


def run_payload_bytes():
    result = measure_payload_bytes()
    print(json.dumps(result))
    with open("PAYLOAD_BYTES.json", "w") as f:
        json.dump(result, f, indent=2)
    return result


# v5e per-chip constants for the north-star traffic model, from the
# public scaling reference (jax-ml.github.io/scaling-book): ICI one-way
# bandwidth per link; a 4-chip slice is a ring, and a ring ppermute
# keeps each hop on its own link.  HBM bandwidth bounds the fused ring
# rounds (they are traffic-bound, not FLOP-bound).
_V5E_ICI_LINK_GBS = 45.0
_V5E_HBM_GBS = 819.0


def _row_bytes(num_elements, num_actors, family, layout):
    """Bytes one replica row moves through HBM, per family x layout.

    family 'awset': present + birth dots + vv (awset.go:55-59
    tensorized per SURVEY 7.1); 'delta' adds the deletion log
    (deleted + del dots, awset-delta_test.go:9-12) and the processed
    vector.  Layout 'bool': uint8 membership + two uint32 dot arrays;
    'packed' bitpacks membership (E/8 bytes); 'dots' additionally fuses
    each dot pair into ONE uint32 word (DESIGN 11)."""
    e, a = num_elements, num_actors
    member = {"bool": e, "packed": e // 8, "dots": e // 8}[layout]
    dot_words = {"bool": 2, "packed": 2, "dots": 1}[layout]
    vv_rows = {"awset": 1, "delta": 2}[family]      # vv (+ processed)
    member_rows = {"awset": 1, "delta": 2}[family]  # present (+ deleted)
    dot_pairs = {"awset": 1, "delta": 2}[family]    # birth (+ deletion)
    return (member_rows * member + dot_pairs * dot_words * e * 4
            + vv_rows * a * 4)


def run_roofline():
    """Static HBM-traffic model per ladder config x layout — no device
    needed.  An ALIGNED fused ring round reads dst rows + partner rows
    in place and writes dst rows = 3x state through HBM (the measured
    config-3 bound, ops/pallas_merge.py regime notes); the roofline
    rate is replicas / (3 * R * row_bytes / HBM_GBS).  Measured ladder
    rates are joined in from BENCH_LADDER.json where present so the
    model-vs-measured ratio is auditable in one artifact."""
    measured = {}
    try:
        with open("BENCH_LADDER.json") as f:
            measured = {e["metric"].split(":")[0]: e
                        for e in json.load(f)}
    except (OSError, ValueError, KeyError, TypeError, AttributeError):
        pass   # model-only output; the join is optional
    # north-star measurements live in their own artifacts with a
    # per-round fit rather than a rate
    for key, path in (("northstar", "NORTHSTAR.json"),
                      ("northstar_dots", "NORTHSTAR_DOTPACKED.json")):
        try:
            with open(path) as f:
                ns = json.load(f)
            measured[key] = {"per_round_s": float(ns["per_round_fit_s"]),
                             "platform": ns.get("platform")}
        except (OSError, ValueError, KeyError, TypeError):
            pass
    cases = [
        ("config3", "awset", "bool", 10_048, 256, 256),
        ("config3_dotpacked", "awset", "dots", 10_048, 256, 256),
        ("config4", "delta", "bool", 100_032, 256, 256),
        ("config4_dotpacked", "delta", "dots", 100_032, 256, 256),
        ("northstar", "delta", "bool", 1 << 20, 256, 256),
        ("northstar_dots", "delta", "dots", 1 << 20, 256, 256),
    ]
    rows = []
    for name, family, layout, num_r, num_e, num_a in cases:
        rb = _row_bytes(num_e, num_a, family, layout)
        round_bytes = 3 * num_r * rb
        round_s = round_bytes / (_V5E_HBM_GBS * 1e9)
        rate = num_r / round_s
        rec = {
            "config": name, "family": family, "layout": layout,
            "row_bytes": rb, "aligned_round_mb": round(
                round_bytes / 1e6, 1),
            "roofline_round_ms": round(round_s * 1e3, 4),
            "roofline_rate": round(rate, 1),
        }
        if family == "delta":
            rec["bound_note"] = (
                "optimistic for delta: the measured schedule mixes "
                "windowed rounds and the kernel also writes the "
                "deletion-log/processed sections it read, so the "
                "aligned 3x-state bound under-counts delta traffic")
        m = measured.get(name)
        if m and m.get("per_round_s"):
            m = dict(m, value=round(num_r / m["per_round_s"], 1))
        if m and isinstance(m.get("value"), (int, float)):
            rec["measured_rate"] = m["value"]
            rec["measured_platform"] = m.get("platform")
            rec["fraction_of_roofline"] = round(m["value"] / rate, 3)
        rows.append(rec)
    out = {
        "metric": "HBM-roofline model per config x layout "
                  "(aligned fused ring round = 3x state through HBM)",
        "hbm_gbs": _V5E_HBM_GBS,
        "value": next(r for r in rows
                      if r["config"] == "config3_dotpacked"
                      )["roofline_rate"],
        "unit": "merges/sec/chip (config3 dot-word roofline bound)",
        "rows": rows,
        "note": "static model, no device required; measured_rate joins "
                "BENCH_LADDER.json where captured — fraction_of_roofline"
                " ~ 1.0 means the kernel is at the traffic bound",
    }
    print(json.dumps(out))
    with open("ROOFLINE.json", "w") as f:
        json.dump(out, f, indent=2)
    return out


def northstar_ici_model(total_compute_s, num_replicas, num_elements,
                        num_actors, n_chips=4,
                        ici_link_gbs=_V5E_ICI_LINK_GBS,
                        layout="packed"):
    """Traffic-model projection of the north-star schedule onto an
    n-chip ring — the defensible replacement for bare linear-DP
    scaling (the <1s claim must cite a model, not an assumption).

    DP-shards the replica axis: blk = R/n rows per chip.  Dissemination
    offsets below blk are intra-chip (zero ICI); offsets at k*blk ship
    each chip's whole PACKED block (models/packed.py layout — the
    production multi-chip path, gossip.packed_block_ring_round_shardmap)
    k ring hops, so link bytes = blk * row_bytes * ring_distance(k).
    The roofline is max(compute, ICI) — XLA overlaps ppermute with the
    merge compute it feeds — and the no-overlap serialized sum is also
    reported as the pessimistic bound."""
    blk = num_replicas // n_chips
    # bytes/row: 2 VV-shaped uint32 rows (vv, processed) + 2 bitpacked
    # membership rows + 1 actor id, plus the dot arrays — 4 uint32 rows
    # on the packed layout (add + del actor/counter), 2 dot-word rows
    # on the dots layout (models.packed.DotPackedAWSetDeltaState)
    dot_arrays = {"packed": 4, "dots": 2}[layout]
    row_bytes = (2 * num_actors * 4 + dot_arrays * num_elements * 4
                 + 2 * (num_elements // 8) + 4)
    crossing = []
    link_bytes = 0
    for off in dissemination_offsets_for(num_replicas):
        if off < blk:
            continue
        shift = off // blk
        hops = min(shift % n_chips, n_chips - shift % n_chips)
        crossing.append({"offset": off, "ring_hops": hops})
        link_bytes += blk * row_bytes * hops
    ici_s = link_bytes / (ici_link_gbs * 1e9)
    compute_s = total_compute_s / n_chips
    return {
        "n_chips": n_chips,
        "packed_row_bytes": row_bytes,
        "crossing_rounds": crossing,
        "ici_link_bytes": int(link_bytes),
        "ici_link_gbs": ici_link_gbs,
        "ici_s": round(ici_s, 4),
        "compute_s": round(compute_s, 4),
        "model_s": round(max(compute_s, ici_s), 4),
        "serialized_bound_s": round(compute_s + ici_s, 4),
        "note": "model_s = max(single-chip-compute/n, ring-cut ICI "
                "bytes / v5e per-link one-way bandwidth); packed-block "
                "ring ships whole blocks on block-aligned offsets only "
                f"({len(crossing)} of "
                f"{len(dissemination_offsets_for(num_replicas))} rounds)",
    }


def dissemination_offsets_for(num_replicas):
    from go_crdt_playground_tpu.parallel.gossip import (
        dissemination_offsets)

    return dissemination_offsets(num_replicas)


def measure_northstar(num_replicas=None, num_elements=256, num_writers=256):
    """The north-star point (BASELINE.json): 1M x 256-element δ-AWSet
    replicas, all-pairs-converged via ceil(log2 R) dissemination rounds
    of v2 δ gossip, single chip, with the convergence digest VERIFIED.

    The v5e-4 target is <1 s; this measures the single-chip wall time
    (the driver environment has one chip) and reports the 4-chip number
    only as an explicitly-labeled linear-DP extrapolation."""
    import jax
    import jax.numpy as jnp

    from go_crdt_playground_tpu.parallel import gossip

    if num_replicas is None:
        num_replicas = int(os.environ.get(
            "CRDT_NORTHSTAR_REPLICAS", str(1 << 20)))
    offsets = gossip.dissemination_offsets(num_replicas)
    n_rounds = len(offsets)
    offs = jnp.asarray(offsets, jnp.uint32)

    # Ring rounds through the ring-FUSED δ kernel: partner rows are read
    # in place (no state[perm] gather copy — with one, peak HBM is
    # ~3 x 6.5GB and a 16GB v5e OOMs at compile), the offset is DATA so
    # all ceil(log2 R) rounds share one compiled lax.scan program, and
    # donation lets the freed input buffers carry the outputs
    # (steady-state peak = state + outputs ~ 13GB).
    import functools

    # CRDT_NORTHSTAR_PACKED=1 runs the schedule on the bitpacked layout
    # (models/packed.py): membership crosses HBM as uint32[R, E/32] —
    # the measured bitpack round-time delta.
    # =dots runs the DOT-WORD layout (membership bitpacked AND both dot
    # pairs fused to one uint32 word each, ~1.6x less HBM per round).
    packed = os.environ.get("CRDT_NORTHSTAR_PACKED", "")
    if packed not in ("", "0", "1", "dots"):
        raise ValueError(f"CRDT_NORTHSTAR_PACKED={packed!r}: use 1 "
                         "(bitpacked membership) or dots (dot-word)")
    packed = packed if packed in ("1", "dots") else ""
    if packed:
        from go_crdt_playground_tpu.models import packed as packed_mod
        from go_crdt_playground_tpu.ops.pallas_delta import (
            pallas_delta_ring_round_dotpacked,
            pallas_delta_ring_round_packed)
        round_packed = (pallas_delta_ring_round_dotpacked
                        if packed == "dots"
                        else pallas_delta_ring_round_packed)

    @functools.partial(jax.jit, static_argnames=("n",), donate_argnums=0)
    def run_schedule(state, n):
        def body(s, i):
            off = offs[i % n_rounds]
            if packed:
                return round_packed(s, off), None
            return gossip.delta_ring_gossip_round(
                s, off, delta_semantics="v2"), None
        state, _ = jax.lax.scan(body, state, jnp.arange(n))
        return state

    def timed(n):
        """Wall time of n rounds + ONE forced device->host scalar sync.

        Fetching a scalar element of an output buffer cannot be
        answered before the program actually ran, so it is the sync;
        the constant host round-trip it adds is cancelled by the
        (t(2n) - t(n)) fit below.
        """
        state = _make_fleet()
        float(jnp.asarray(state.vv[0, 0]))  # settle construction
        t0 = time.perf_counter()
        state = run_schedule(state, n)
        float(jnp.asarray(state.vv[0, 0]))  # forces the whole scan
        return time.perf_counter() - t0, state

    def _make_fleet():
        fleet = _delta_fleet(num_replicas, num_elements, num_writers)
        if packed == "dots":
            fleet = packed_mod.pack_awset_delta_dots(fleet)
        elif packed:
            fleet = packed_mod.pack_awset_delta(fleet)
        return fleet

    # compile both round counts on throwaway fleets (donation consumes);
    # the scalar fetch drains the execution queue so the timed runs
    # don't inherit warmup work
    for n in (n_rounds, 2 * n_rounds):
        warm = run_schedule(_make_fleet(), n)
        float(jnp.asarray(warm.vv[0, 0]))
        del warm
    t1, state = timed(n_rounds)
    if packed == "dots":
        state = packed_mod.unpack_awset_delta_dots(state, num_elements)
    elif packed:
        state = packed_mod.unpack_awset_delta(state, num_elements)
    converged = bool(gossip.converged_jit(state.present, state.vv))
    del state
    t2, state2 = timed(2 * n_rounds)
    del state2
    if t2 - t1 <= 0:
        # mirror _scan_round_rate: a non-positive delta means the fit is
        # noise (host round-trips swamped the rounds) — reporting 0.0 as a
        # measured per-round cost would be a fabricated result
        raise RuntimeError(
            f"north-star timing fit degenerate: t({n_rounds})={t1:.4f}s "
            f">= t({2 * n_rounds})={t2:.4f}s")
    per_round = (t2 - t1) / n_rounds
    fit_total = per_round * n_rounds
    model = northstar_ici_model(fit_total, num_replicas, num_elements,
                                num_writers,
                                layout="dots" if packed == "dots"
                                else "packed")
    return {
        "metric": f"north star: {num_replicas} x {num_elements}-element "
                  "delta-AWSet replicas, all-pairs converged "
                  f"({n_rounds} dissemination rounds, v2 delta gossip"
                  f"{', dot-word layout' if packed == 'dots' else ', bitpacked membership' if packed else ''})",
        "value": round(t1, 4),
        "unit": "seconds (single chip, incl. one host sync)",
        "converged": converged,
        "rounds": n_rounds,
        "per_round_fit_s": round(per_round, 5),
        "total_fit_s": round(fit_total, 4),
        "fit_note": "per_round_fit_s = (t(2n)-t(n))/n with a forced "
                    "scalar sync per run — cancels the host sync that "
                    "`value` still contains; raw walls: "
                    f"t({n_rounds})={round(t1, 4)}s, "
                    f"t({2 * n_rounds})={round(t2, 4)}s",
        "v5e4_extrapolation_s": round(fit_total / 4, 4),
        "extrapolation_note": "linear DP scaling over 4 chips assumed; "
                              "ICI ring overhead excluded — an estimate, "
                              "not a measurement (one chip available)",
        "v5e4_model": model,
        "v5e4_model_s": model["model_s"],
        "target_s": 1.0,
        "platform": jax.default_backend(),
    }


def run_northstar():
    result = measure_northstar()
    if not result["converged"]:
        print("north-star fleet did not converge", file=sys.stderr)
        sys.exit(1)
    print(json.dumps(result))
    # the packed variants record NEXT TO the bool artifact, so the
    # layout round-time deltas survive as a committed set
    variant = os.environ.get("CRDT_NORTHSTAR_PACKED", "")
    artifact = {"1": "NORTHSTAR_PACKED.json",
                "dots": "NORTHSTAR_DOTPACKED.json"}.get(
                    variant, "NORTHSTAR.json")
    with open(artifact, "w") as f:
        json.dump(result, f, indent=2)
    return result


def run_droprate():
    result = measure_droprate()
    print(json.dumps(result))
    with open("DROP_CURVE.json", "w") as f:
        json.dump(result, f, indent=2)
    return result


# Canonical artifact order for ladder steps (BENCH_LADDER.json keeps
# the config1..config5 positional layout every round's artifact has used)
_LADDER_ORDER = ("config1", "config2", "config3", "config3_dotpacked",
                 "config4", "config4_dotpacked", "config4ref",
                 "config5", "config5_awset")


_INGEST_ARTIFACT = "BENCH_INGEST.json"


def measure_ingest(num_elements=1024, num_actors=8,
                   legs=((8, 1), (32, 1), (128, 1), (32, 16)),
                   repeats=40):
    """Serve ingest ladder (ISSUE 8): per (batch B, keys/op) leg,
    measure the seed two-pass path (``ingest_rows`` apply + a second
    ``delta_extract`` dispatch + dense WAL record encode) against the
    fused path (``ingest_rows_delta`` — one dispatch returning state,
    δ, and the fixed-K compact lanes — + compact record encode):
    dispatches/batch, wall-time/batch, WAL bytes/batch."""
    import jax
    import jax.numpy as jnp

    from go_crdt_playground_tpu.models import awset_delta
    from go_crdt_playground_tpu.net import framing
    from go_crdt_playground_tpu.ops import delta as delta_ops
    from go_crdt_playground_tpu.ops import ingest as ingest_ops

    # the SAME backend/K selection Node.ingest_batch runs — the bench
    # measures the server's actual regime, by construction
    fused_fn, k = ingest_ops.ingest_delta_regime(num_elements)
    rng = np.random.default_rng(7)
    curve = []
    for batch, keys in legs:
        st = awset_delta.init(1, num_elements, num_actors,
                              actors=np.asarray([0], np.uint32))
        row = jax.tree.map(lambda x: x[0], st)
        add = np.zeros((batch, num_elements), bool)
        for b in range(batch):
            add[b, rng.choice(num_elements, size=keys, replace=False)] = True
        dl = np.zeros((batch, num_elements), bool)
        dl[batch // 2, rng.integers(num_elements)] = True
        live = np.ones(batch, bool)
        addj, dlj, livej = (jnp.asarray(add), jnp.asarray(dl),
                            jnp.asarray(live))
        pre_vv = np.asarray(row.vv)

        # both paths build their record through THE shared policy
        # (framing.encode_delta_wal_record — exactly what Node appends)

        def seed_once():
            merged = ingest_ops.ingest_rows(row, addj, dlj, livej)
            payload = delta_ops.delta_extract(merged, jnp.asarray(pre_vv))
            jax.block_until_ready(payload)
            body, _ = framing.encode_delta_wal_record(
                pre_vv, 0, payload, compact_records=False)
            return len(body)

        def fused_once():
            merged, payload, compact = fused_fn(
                row, addj, dlj, livej, k_changed=k, k_deleted=k)
            jax.block_until_ready(payload if compact is None else compact)
            body, _ = framing.encode_delta_wal_record(
                pre_vv, 0, payload, compact)
            return len(body)

        def timed(fn):
            fn()  # warm/compile
            t0 = time.perf_counter()
            nbytes = 0
            for _ in range(repeats):
                nbytes = fn()
            return (time.perf_counter() - t0) / repeats, nbytes

        seed_s, seed_bytes = timed(seed_once)
        fused_s, fused_bytes = timed(fused_once)
        _, payload, compact = fused_fn(row, addj, dlj, livej,
                                       k_changed=k, k_deleted=k)
        curve.append({
            "batch": batch,
            "keys_per_op": keys,
            "changed_lanes": int(np.asarray(payload.changed).sum()),
            "compact_regime": ("device-K" if compact is not None
                               else "host"),
            "compact_overflow": (bool(compact.overflow)
                                 if compact is not None else None),
            "seed": {"dispatches_per_batch": 2,
                     "ms_per_batch": round(seed_s * 1e3, 3),
                     "wal_bytes_per_batch": seed_bytes},
            "fused": {"dispatches_per_batch": 1,
                      "ms_per_batch": round(fused_s * 1e3, 3),
                      "wal_bytes_per_batch": fused_bytes},
            "speedup": round(seed_s / fused_s, 2),
            "wal_bytes_ratio": round(seed_bytes / fused_bytes, 1),
        })
    return curve


def run_ingest(out=_INGEST_ARTIFACT):
    """The `--ingest` verb: measure the serve ingest ladder and commit
    BENCH_INGEST.json.  Backend-guarded: the artifact records the
    platform it was measured on, and a CPU run REFUSES to overwrite an
    on-chip artifact; it prints the refusal and exits clean instead."""
    import jax

    platform = jax.default_backend()
    if os.path.exists(out):
        try:
            with open(out) as f:
                prior = json.load(f)
        except ValueError:
            prior = {}
        if not isinstance(prior, dict):
            prior = {}  # valid-JSON-but-not-an-object: unknown prior
        if prior.get("platform") == "tpu" and platform != "tpu":
            print(json.dumps({
                "metric": "serve ingest ladder",
                "skipped": f"existing {out} is an on-chip artifact; "
                           f"refusing to overwrite it with a "
                           f"{platform} run (pass --out elsewhere)",
                "platform": platform,
            }))
            return None
    curve = measure_ingest()
    artifact = {
        "metric": ("serve ingest path: dispatches/batch, wall-time/"
                   "batch, WAL bytes/batch — fused one-dispatch "
                   "ingest+δ with compact records vs the seed "
                   "two-dispatch path with dense records"),
        "value": curve[0]["wal_bytes_ratio"],
        "unit": "x fewer WAL bytes/batch (sparsest leg)",
        "elements": 1024,
        "actors": 8,
        "platform": platform,
        "curve": curve,
    }
    with open(out, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")
    for leg in curve:
        print(json.dumps(leg))
    print(f"wrote {out}")
    return artifact


_MESH_ARTIFACT = "MESH_CURVE.json"


def measure_mesh(num_elements=8192, num_actors=8, batch=32, keys=4,
                 repeats=30, device_ladder=(1, 2, 4, 8)):
    """Device-mesh replica tier kernel ladder (ISSUE 10, DESIGN.md
    §20): per device count, wall-time/batch of the full mesh write
    path (``MeshApplyTarget.ingest_batch`` — one ``shard_map``
    dispatch + the single δ ``device_get`` + WAL record encode, fsync
    off so disk weather stays out of a kernel curve) and the
    collective digest summary read (the DSUM/member-cache path).  CPU
    runs under forced host devices measure DISPATCH layering, not
    speedup — 2 host cores time-slice every "device"; the curve's
    value off-chip is that the mesh path's overhead vs devices=1 is
    recorded and bounded."""
    import tempfile

    import jax

    from go_crdt_playground_tpu.net import digestsync
    from go_crdt_playground_tpu.parallel.meshtarget import MeshApplyTarget
    from go_crdt_playground_tpu.utils.wal import DeltaWal

    avail = jax.device_count()
    counts = [d for d in device_ladder
              if d <= avail and num_elements % d == 0]
    rng = np.random.default_rng(7)
    add = np.zeros((batch, num_elements), bool)
    for b in range(batch):
        add[b, rng.choice(num_elements, size=keys, replace=False)] = True
    dl = np.zeros((batch, num_elements), bool)
    dl[batch // 2, rng.integers(num_elements)] = True
    live = np.ones(batch, bool)
    curve = []
    for n in counts:
        with tempfile.TemporaryDirectory() as d:
            node = MeshApplyTarget(
                0, num_elements, num_actors, mesh_devices=n,
                wal=DeltaWal(os.path.join(d, "wal"), fsync=False))
            node.ingest_batch(add, dl, live)  # warm/compile
            t0 = time.perf_counter()
            for _ in range(repeats):
                node.ingest_batch(add, dl, live)
            ingest_s = (time.perf_counter() - t0) / repeats
            digestsync.node_summary(node)  # warm the collective read
            t0 = time.perf_counter()
            for _ in range(repeats):
                summary = digestsync.node_summary(node)
            digest_s = (time.perf_counter() - t0) / repeats
        curve.append({
            "devices": n,
            "ingest_ms_per_batch": round(ingest_s * 1e3, 3),
            "ops_per_s": round(batch / ingest_s, 1),
            "digest_read_ms": round(digest_s * 1e3, 3),
            "digest_summary_bytes": len(summary),
        })
    # per-device parallel efficiency (ISSUE 15 satellite): throughput
    # at n devices over n x the 1-device throughput — the number that
    # makes the dispatch-layering fall-off VISIBLE in the artifact
    # (on 2 CPU cores the 8-"device" leg time-slices, eff << 1; an
    # on-chip capture should hold eff near 1 until the batch is too
    # small to fill the lanes)
    if curve and curve[0]["devices"] == 1:
        base = curve[0]["ops_per_s"]
        for leg in curve:
            leg["parallel_efficiency"] = round(
                leg["ops_per_s"] / (leg["devices"] * base), 3)
    # the config rides back with the curve so the artifact records
    # what was MEASURED, not a separately-maintained literal
    return curve, avail, {"elements": num_elements, "batch": batch}


def measure_mesh2d(num_elements=8192, num_actors=8, batch=32, keys=4,
                   repeats=30,
                   shape_ladder=((1, 2), (2, 2), (4, 2), (1, 4),
                                 (2, 4))):
    """2-D dp×mp mesh kernel ladder (ISSUE 15, DESIGN.md §24): per
    (dp, mp) shape, wall-time of the one-dispatch striped super-batch
    apply (``Mesh2DApplyTarget.ingest_batch`` over dp × ``batch``
    KEY-DISJOINT rows — the batcher's width contract — incl. the δ
    device_get + WAL record encode, fsync off) and the collective
    digest summary read.  ``ops_per_s`` counts the SUPER-batch rows,
    so dp scaling shows as throughput at (near-)flat dispatch time;
    ``dp_scaling`` is ops_per_s over the (1, mp) leg's at the same mp
    — the goodput-scales-with-dp claim, kernel edition."""
    import tempfile

    import jax

    from go_crdt_playground_tpu.net import digestsync
    from go_crdt_playground_tpu.parallel.meshtarget2d import \
        Mesh2DApplyTarget
    from go_crdt_playground_tpu.utils.wal import DeltaWal

    avail = jax.device_count()
    shapes = [(dp, mp) for dp, mp in shape_ladder
              if dp * mp <= avail and num_elements % mp == 0]
    rng = np.random.default_rng(7)
    curve = []
    for dp, mp in shapes:
        B = dp * batch
        # key-disjoint rows (each row draws from its own lane band):
        # the striping planner packs them into dp full stripes with
        # zero cuts, so the leg measures the parallel apply, not the
        # conflict fallback
        band = num_elements // B
        add = np.zeros((B, num_elements), bool)
        for b in range(B):
            lanes = b * band + rng.choice(band, size=min(keys, band),
                                          replace=False)
            add[b, lanes] = True
        dl = np.zeros((B, num_elements), bool)
        live = np.ones(B, bool)
        with tempfile.TemporaryDirectory() as d:
            node = Mesh2DApplyTarget(
                0, num_elements, num_actors, mesh_shape=(dp, mp),
                wal=DeltaWal(os.path.join(d, "wal"), fsync=False))
            node.ingest_batch(add, dl, live)  # warm/compile
            t0 = time.perf_counter()
            for _ in range(repeats):
                node.ingest_batch(add, dl, live)
            ingest_s = (time.perf_counter() - t0) / repeats
            digestsync.node_summary(node)  # warm the collective read
            t0 = time.perf_counter()
            for _ in range(repeats):
                summary = digestsync.node_summary(node)
            digest_s = (time.perf_counter() - t0) / repeats
        curve.append({
            "dp": dp, "mp": mp, "rows_per_dispatch": B,
            "ingest_ms_per_batch": round(ingest_s * 1e3, 3),
            "ops_per_s": round(B / ingest_s, 1),
            "digest_read_ms": round(digest_s * 1e3, 3),
            "digest_summary_bytes": len(summary),
        })
    base_by_mp = {leg["mp"]: leg["ops_per_s"] for leg in curve
                  if leg["dp"] == 1}
    for leg in curve:
        base = base_by_mp.get(leg["mp"])
        leg["dp_scaling"] = (round(leg["ops_per_s"] / base, 3)
                             if base else None)
    return curve, avail


def measure_mesh2d_zipf(num_elements=8192, num_actors=8, batch=32,
                        s=1.2, repeats=30, rounds=40,
                        dp_ladder=(1, 2, 4), mp=2):
    """Zipf hot-key kernel ladder for the conflict-aware admission
    scheduler (DESIGN.md §25): per dp at fixed mp, a STREAM of
    ``rounds`` super-batches of dp×``batch`` SINGLE-KEY rows drawn
    zipf(s) over the universe — the serve tier's skewed point-op
    regime, the opposite extreme of ``measure_mesh2d``'s key-disjoint
    bands.  Reports the host-side planning census per super-batch
    (``cuts_before``: plan_stripes on arrival order;
    ``cuts_after``: on the scheduler's emitted order + hint, hot-run
    tails carried batcher-style into the next round — the scheduled
    path's steady state, expected ~0) and the DEVICE time of one
    scheduled apply (``Mesh2DApplyTarget.ingest_batch`` with the
    hint, fsync off), so the artifact pins both the cut reduction and
    that the scheduled path's dispatch cost still amortizes with dp
    (``dp_scaling``)."""
    import tempfile

    import jax

    from go_crdt_playground_tpu.parallel.meshtarget2d import \
        Mesh2DApplyTarget, plan_stripes
    from go_crdt_playground_tpu.serve.scheduler import plan_emit
    from go_crdt_playground_tpu.utils.wal import DeltaWal

    avail = jax.device_count()
    dps = [dp for dp in dp_ladder
           if dp * mp <= avail and num_elements % mp == 0]
    rng = np.random.default_rng(11)
    # zipf(s) over shuffled ranks (hot ids scattered through the
    # universe, tools/workloads.py's ZipfKeys shape)
    p = np.arange(1, num_elements + 1, dtype=np.float64) ** -s
    p /= p.sum()
    keymap = rng.permutation(num_elements)

    def rows_of(keys):
        add = np.zeros((len(keys), num_elements), bool)
        add[np.arange(len(keys)), keys] = True
        dl = np.zeros((len(keys), num_elements), bool)
        return add, dl, np.ones(len(keys), bool)

    curve = []
    for dp in dps:
        B = dp * batch
        cap = batch  # the batcher contract: width = dp * max_batch
        cuts_before = cuts_after = 0
        deferred_rows = 0
        carry = []  # deferred key ids, batcher-style carryover
        sched_keys = sched_hint = None
        for _ in range(rounds):
            fresh = [int(k) for k in
                     keymap[rng.choice(num_elements,
                                       size=B - len(carry), p=p)]]
            keys = carry + fresh
            add, dl, live = rows_of(keys)
            _, c0 = plan_stripes(add, dl, live, dp, cap)
            cuts_before += c0
            order, assign, deferred = plan_emit(
                [[k] for k in keys], dp, cap)
            emitted = [keys[i] for i in order]
            hint = np.asarray(assign, np.int32)
            e_add, e_dl, e_live = rows_of(emitted)
            _, c1 = plan_stripes(e_add, e_dl, e_live, dp, cap,
                                 assign=hint)
            cuts_after += c1
            deferred_rows += len(deferred)
            carry = [keys[i] for i in deferred]
            if sched_keys is None:
                sched_keys, sched_hint = emitted, hint
        # device time of the scheduled apply, one representative
        # emitted super-batch
        s_add, s_dl, s_live = rows_of(sched_keys)
        with tempfile.TemporaryDirectory() as d:
            node = Mesh2DApplyTarget(
                0, num_elements, num_actors, mesh_shape=(dp, mp),
                wal=DeltaWal(os.path.join(d, "wal"), fsync=False))
            node.ingest_batch(s_add, s_dl, s_live,
                              stripe_hint=sched_hint)  # warm/compile
            t0 = time.perf_counter()
            for _ in range(repeats):
                node.ingest_batch(s_add, s_dl, s_live,
                                  stripe_hint=sched_hint)
            ingest_s = (time.perf_counter() - t0) / repeats
        n_rows = len(sched_keys)
        curve.append({
            "dp": dp, "mp": mp, "rows_per_super_batch": B, "zipf_s": s,
            "super_batches": rounds,
            "cuts_before_per_super_batch": round(cuts_before / rounds,
                                                 3),
            "cuts_after_per_super_batch": round(cuts_after / rounds,
                                                3),
            "deferred_rows_per_super_batch": round(
                deferred_rows / rounds, 3),
            "ingest_ms_per_batch": round(ingest_s * 1e3, 3),
            "ops_per_s": round(n_rows / ingest_s, 1),
        })
    base = next((leg["ops_per_s"] for leg in curve if leg["dp"] == 1),
                None)
    for leg in curve:
        leg["dp_scaling"] = (round(leg["ops_per_s"] / base, 3)
                             if base else None)
    return curve, avail


def run_mesh(out=_MESH_ARTIFACT, zipf=False):
    """The `--mesh` verb: measure the mesh kernel ladder and write the
    kernel half of MESH_CURVE.json.  Same TPU-overwrite guard as
    run_ingest (a CPU run refuses to overwrite an on-chip
    artifact), and MERGE-shaped: the fleet soak's serve-level curve
    (``serve_curve``/``crash`` keys, tools/fleet_serve_soak.py --mesh)
    lives in the same artifact and survives a kernel re-measure."""
    import jax

    platform = jax.default_backend()
    prior = {}
    if os.path.exists(out):
        try:
            with open(out) as f:
                prior = json.load(f)
        except ValueError:
            prior = {}
        if not isinstance(prior, dict):
            prior = {}  # valid-JSON-but-not-an-object: unknown prior
        if prior.get("platform") == "tpu" and platform != "tpu":
            print(json.dumps({
                "metric": "mesh replica tier ladder",
                "skipped": f"existing {out} kernel curve is an on-chip "
                           f"artifact; refusing to overwrite it with a "
                           f"{platform} run",
                "platform": platform,
            }))
            return None
    curve, avail, config = measure_mesh()
    curve_2d, _ = measure_mesh2d()
    curve_2d_zipf = prior.get("kernel_curve_2d_zipf", [])
    if zipf:
        curve_2d_zipf, _ = measure_mesh2d_zipf()
        if not curve_2d_zipf and prior.get("kernel_curve_2d_zipf"):
            print(json.dumps({
                "metric": "mesh 2-D zipf ladder",
                "skipped": "no (dp, mp) shape fits this host's "
                           f"{avail} visible devices; keeping the "
                           "prior kernel_curve_2d_zipf",
            }))
            curve_2d_zipf = prior["kernel_curve_2d_zipf"]
    if not curve_2d and prior.get("kernel_curve_2d"):
        # a host without enough (forced) devices measures NOTHING for
        # the 2-D ladder — keep the committed ladder instead of
        # overwriting it with []
        print(json.dumps({
            "metric": "mesh 2-D ladder",
            "skipped": "no (dp, mp) shape fits this host's "
                       f"{avail} visible devices; keeping the prior "
                       "kernel_curve_2d",
        }))
        curve_2d = prior["kernel_curve_2d"]
    # start from the prior artifact and overwrite ONLY the kernel
    # keys (mirror of fleet_serve_soak's run_mesh_mode): the soak's
    # serve-level half survives a kernel re-capture without a
    # hand-maintained allowlist that would silently drop any key the
    # soak adds later (e.g. the bitwise-parity evidence)
    artifact = dict(prior)
    artifact.update({
        "metric": ("device-mesh replica tier: ms/batch of the one-"
                   "dispatch lane-sharded ingest+δ write path and the "
                   "collective digest read, vs mesh device count "
                   "(parallel/meshtarget.py), plus the 2-D dp×mp "
                   "striped super-batch ladder "
                   "(parallel/meshtarget2d.py, DESIGN.md §24)"),
        "platform": platform,
        "devices_visible": avail,
        "kernel_curve": curve,
        "kernel_curve_2d": curve_2d,
        "kernel_curve_2d_zipf": curve_2d_zipf,
        **config,
    })
    with open(out, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")
    for leg in curve:
        print(json.dumps(leg))
    for leg in curve_2d:
        print(json.dumps(leg))
    for leg in curve_2d_zipf:
        print(json.dumps(leg))
    print(f"wrote {out}")
    return artifact


def run_ladder():
    """Configs 1-5 in canonical order, one JSON line each, then
    BENCH_LADDER.json."""
    import jax

    platform = jax.default_backend()

    def config3():
        spec_rate, spec_rates = measure_spec_baseline(full=True)
        tpu_rate, stats3 = measure_tpu(full=True)
        return {
            "metric": "config3: AWSet 10K x 256 ring-fused dot-context "
                      "merge",
            "value": round(tpu_rate, 1),
            "unit": "merges/sec/chip",
            "vs_baseline": round(tpu_rate / spec_rate, 1),
            "baseline_rates_raw": spec_rates,
            **stats3,
        }

    steps = [("config1", measure_config1), ("config2", measure_config2),
             ("config3", config3),
             ("config3_dotpacked", measure_config3_dotpacked),
             ("config4", measure_config4),
             ("config4_dotpacked", measure_config4_dotpacked),
             ("config4ref", measure_config4_reference),
             ("config5", measure_config5),
             ("config5_awset", measure_config5_awset)]
    assert [s for s, _ in steps] == list(_LADDER_ORDER)
    results = []
    for _, fn in steps:
        rec = dict(fn(), platform=platform)
        print(json.dumps(rec), flush=True)
        results.append(rec)
    with open("BENCH_LADDER.json", "w") as f:
        json.dump(results, f, indent=2)
    return results


def measure_headline():
    """The default mode's record: the config-3 rate in the bool layout
    and in the dot-word layout, reporting the faster."""
    import jax

    tpu_rate = measure_tpu()
    dot_rate = measure_tpu_dotpacked()
    spec_rate, spec_rates = measure_spec_baseline(full=True)
    best, layout = ((dot_rate, "dot-word") if dot_rate > tpu_rate
                    else (tpu_rate, "bool"))
    return {
        "metric": _HEADLINE_METRIC,
        "value": round(best, 1),
        "unit": _HEADLINE_UNIT,
        "vs_baseline": round(best / spec_rate, 1),
        "baseline_rates_raw": spec_rates,
        "platform": jax.default_backend(),
        "layout": layout,
        "bool_layout_rate": round(tpu_rate, 1),
        "dotword_rate": round(dot_rate, 1),
    }


def main():
    """Run the selected measurement in this process.  ``--roofline``
    needs no device; ``--ingest`` and ``--mesh`` label their artifacts
    with the platform they ran on.  Every other mode measures the chip
    and fails, printing no rate, when JAX finds none."""
    from go_crdt_playground_tpu.utils.compile_cache import \
        place_compile_cache

    place_compile_cache()
    if "--roofline" in sys.argv:
        run_roofline()
        return
    if "--ingest" in sys.argv:
        # the serve ingest fused-vs-seed comparison; --out PATH
        # redirects the artifact (the escape hatch run_ingest's
        # overwrite refusal names)
        out = _INGEST_ARTIFACT
        if "--out" in sys.argv:
            try:
                out = sys.argv[sys.argv.index("--out") + 1]
            except IndexError:
                print(json.dumps({"metric": "serve ingest ladder",
                                  "error": "--out needs a path"}))
                sys.exit(2)
        run_ingest(out=out)
        return
    if "--mesh" in sys.argv:
        # device-mesh replica tier ladder; CPU multi-device runs need
        # XLA_FLAGS=--xla_force_host_platform_device_count=N exported
        # BEFORE launch (jax reads it at init); --zipf adds the hot-key
        # scheduler ladder (DESIGN.md §25) to the same artifact
        run_mesh(zipf="--zipf" in sys.argv)
        return
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"bench.py measures the chip; JAX found {platform!r}",
              file=sys.stderr)
        sys.exit(1)
    if "--northstar" in sys.argv:
        run_northstar()
    elif "--droprate" in sys.argv:
        run_droprate()
    elif "--payload" in sys.argv:
        run_payload_bytes()
    elif "--ladder" in sys.argv:
        results = run_ladder()
        # the conformance anchor is the point of config 1: a ladder run
        # over a kernel that diverges from the spec must FAIL loudly
        if not all(r.get("conformant", True) for r in results):
            print("packed kernel diverged from the executable spec",
                  file=sys.stderr)
            sys.exit(1)
    else:
        print(json.dumps(measure_headline()))


if __name__ == "__main__":
    main()
