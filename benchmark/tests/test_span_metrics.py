"""The per-layer metrics that read the program's host spans and its
compaction-overflow counter: a traced CPU rehearsal reports each one
its cell lists as a finite number, and a program without those spans
(no stream, no counter) leaves them out instead of raising."""

import math
import os

import pytest

from benchmark import harness
from conftest import run_cell

BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
SPAN_METRICS = ["wait_ms.closed", "pack_ms.closed", "dispatch_ms.closed",
                "pull_ms.closed", "fsync_ms.closed", "overflow_share.closed",
                "checkpoint_ms.open", "gc_ms.open"]


def _listed(cell):
    return [m["name"] for m in BENCH["per_layer"]
            if m["name"] in SPAN_METRICS and harness.applies(m, cell)]


@pytest.mark.parametrize("cell", ["serve-1m.churn.closed",
                                  "serve-1m.churn.k80"])
def test_traced_run_reports_every_span_metric(cell):
    line = run_cell(cell, trace=1, seconds=3.0)
    assert line["correct"], line["checks"]
    names = _listed(cell)
    assert names
    for name in names:
        assert name in line["metrics"], (name, sorted(line["metrics"]))
        assert math.isfinite(line["metrics"][name]["value"]), name
    if cell == "serve-1m.churn.closed":
        m = line["metrics"]
        # the CPU regime has no on-device compaction: nothing overflows,
        # every record is built from the dense delta
        assert m["overflow_share.closed"]["value"] == 0.0
        assert m["dispatch_ms.closed"]["value"] > 0
        assert m["pull_ms.closed"]["value"] > 0


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_reader_finds_nothing_without_the_spans(name):
    empty = {"observations": {}, "counters": {"serve.batches": 10}}
    ctx = {"rec0": {"observations": {}, "counters": {}}, "rec1": empty,
           "t0": 0.0, "t1": 20.0}
    mod = harness.load_module(os.path.join(harness.BENCH, "layers",
                                           name + ".py"))
    assert mod.value(ctx) is None
