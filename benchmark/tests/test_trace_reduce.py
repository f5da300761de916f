"""The trace reduction on a small trace recorded on the chip
(``data/small.xplane.pb``: three fused-ingest batches at E=1024 and one
δ-fleet convergence at 1024 x 256 inside a ``bench.window`` span), and
on a CPU trace, which has no device plane."""

import hashlib
import json
import os

import pytest

from benchmark import harness, trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")
# sha256 of ``json.dumps(reduce(DATA), sort_keys=True)`` before the
# reduction kept collectives: every key it had then reads the same
_BEFORE_COLLECTIVES = (
    "a11053d23cff3a4d8b3c06c420902636ea1dc2146e957c02d6aed80cadc9e320")


def _model(name):
    return harness.load_module(os.path.join(harness.BENCH, "roofline",
                                            name + ".py"))


def test_chip_trace_reduces():
    red = trace_reduce.reduce(DATA)
    assert red["devices"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["busy_s"] <= sum(red["op_s"].values()) + 1e-9
    bd = red["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert all(t > 0 for _, t in bd["device_ops"] + bd["idle_gaps"])
    # idle gaps and busy time tile the window
    assert sum(t for _, t in bd["idle_gaps"]) <= \
        red["window_s"] - red["busy_s"] + 1e-9


def test_existing_keys_read_the_same_with_collectives_kept():
    red = trace_reduce.reduce(DATA)
    old = {k: v for k, v in red.items() if k != "collectives"}
    assert hashlib.sha256(json.dumps(old, sort_keys=True).encode()) \
        .hexdigest() == _BEFORE_COLLECTIVES
    # one chip, no exchange
    assert red["collectives"] == [[]]


def test_chip_trace_names_both_kernels():
    red = trace_reduce.reduce(DATA)
    t, n = trace_reduce.kernel_time(red, _model("ingest").match)
    assert n == 3 and t > 0
    ring = _model("delta_ring")
    # one convergence of 1024 rows: 16 chunked rounds and 7 replayed,
    # offsets 1..32 windowed, 64..512 aligned
    assert trace_reduce.kernel_time(red, ring.windowed)[1] == 14
    assert trace_reduce.kernel_time(red, ring.aligned)[1] == 9


def test_cpu_trace_has_no_device_plane(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    harness.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    assert trace_reduce.reduce(trace_reduce.find_xplane(str(tmp_path))) \
        is None


def test_missing_window_span_is_an_error(tmp_path):
    import jax

    harness.start_trace(str(tmp_path))
    jax.profiler.stop_trace()
    with pytest.raises(ValueError):
        trace_reduce.reduce(trace_reduce.find_xplane(str(tmp_path)))
