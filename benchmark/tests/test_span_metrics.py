"""The per-layer metrics that read the program's host spans and its
compaction-overflow counter: a traced CPU rehearsal reports each one
its cell lists as a finite number, and a program without those spans
(no stream, no counter) leaves them out instead of raising.  Every
served run, untraced too, carries the phase split per batch in its
``info``."""

import math
import os

import pytest

from benchmark import harness
from conftest import run_cell

BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
SPAN_METRICS = ["wait_ms.closed", "pack_ms.closed", "dispatch_ms.closed",
                "pull_ms.closed", "fsync_ms.closed", "overflow_share.closed",
                "checkpoint_ms.open", "gc_ms.open", "pull_ms.open",
                "overflow_share.open"]
SERVED = ["serve-1m.ycsb-load", "serve-1m.churn.k80"]


def _listed(cell):
    return [m["name"] for m in BENCH["per_layer"]
            if m["name"] in SPAN_METRICS and harness.applies(m, cell)]


@pytest.mark.parametrize("cell", SERVED)
def test_traced_run_reports_every_span_metric(cell):
    line = run_cell(cell, trace=1, seconds=3.0)
    assert line["correct"], line["checks"]
    names = _listed(cell)
    assert names
    for name in names:
        assert name in line["metrics"], (name, sorted(line["metrics"]))
        assert math.isfinite(line["metrics"][name]["value"]), name
    if cell == "serve-1m.churn.k80":
        m = line["metrics"]
        # the CPU regime has no on-device compaction: nothing overflows,
        # every record is built from the dense delta
        assert m["overflow_share.open"]["value"] == 0.0
        assert m["pull_ms.open"]["value"] > 0


@pytest.mark.parametrize("cell", SERVED)
def test_untraced_run_carries_the_phase_split(cell):
    line = run_cell(cell, seconds=2.0)
    info = line["info"]
    split = info["phases"]
    assert split["batches"] > 0 and split["compact_overflow"] == 0
    ms = split["ms_per_batch"]
    for phase in ("wait", "pack", "apply", "dispatch", "pull", "dense",
                  "fsync", "checkpoint", "gc", "cycle", "unspanned"):
        assert math.isfinite(ms[phase]), phase
    # the CPU regime builds every record from the dense delta
    assert ms["dense"] > 0 and ms["pull"] == 0
    assert ms["dispatch"] + ms["dense"] + ms["fsync"] <= ms["apply"]
    assert ms["wait"] + ms["pack"] + ms["apply"] <= ms["cycle"] * 1.05
    assert info["gen_late_ms"]["p50"] <= info["gen_late_ms"]["p99"]
    assert len(info["acked_by_second"]) == 2
    if "acked_ops_per_s" in line["metrics"]:
        acked = line["metrics"]["acked_ops_per_s"]["value"] * 2
        assert abs(sum(info["acked_by_second"]) - acked) <= 1
    assert info["host"]["server_cpu_s"] > 0
    assert info["host"]["loadgen_cpu_s"] > 0


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_reader_finds_nothing_without_the_spans(name):
    empty = {"observations": {}, "counters": {"serve.batches": 10}}
    ctx = {"rec0": {"observations": {}, "counters": {}}, "rec1": empty,
           "t0": 0.0, "t1": 20.0}
    mod = harness.load_module(os.path.join(harness.BENCH, "layers",
                                           name + ".py"))
    assert mod.value(ctx) is None


@pytest.mark.parametrize("name", ["overflow_share.closed",
                                  "overflow_share.open"])
def test_overflow_share_is_exact_at_the_window_edge(name):
    """A batch that overflowed before the window closed but was acked
    after it: 277 overflows, 277 compact pulls, 276 acked batches."""
    obs = {"wal.delta.pull_s": {"n": 277, "sum": 0.4}}
    ctx = {"rec0": {"observations": {}, "counters": {}},
           "rec1": {"observations": obs,
                    "counters": {"serve.batches": 276,
                                 "ingest.compact_overflow": 277}}}
    mod = harness.load_module(os.path.join(harness.BENCH, "layers",
                                           name + ".py"))
    assert mod.value(ctx) == 100.0
