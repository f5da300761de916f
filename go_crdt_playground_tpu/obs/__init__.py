"""Observability: merge-decision tracing and metrics.

The reference's only observability is unconditional ``fmt.Printf`` of
every merge decision (awset.go:109-121) with nondeterministic line order
(Go map iteration).  Here tracing is an optional per-element decision
tensor emitted by the kernels (ops/merge.MergeTrace) — array-comparable,
deterministic — plus renderers that reproduce the reference's exact
stdout format for eyeball-debugging, and a small metrics recorder for
the north-star counters (merges/sec, rounds-to-convergence, δ-payload
bytes; SURVEY §5.5).
"""

from go_crdt_playground_tpu.obs.metrics import (  # noqa: F401
    Recorder, payload_metrics, timed, watch_gc)

# trace.py pulls in ops.merge -> jax; keep the metrics-only import path
# (net.Node defers jax the same way) light by lazy-loading the renderers.
_TRACE_EXPORTS = frozenset({
    "format_event", "render_spec_trace", "render_tensor_trace",
    "render_delta_tensor_trace", "trace_counts", "printstate",
    "format_delta_extract", "format_delta_extract_tensor",
})

__all__ = ["Recorder", "payload_metrics", "timed", "watch_gc",
           *sorted(_TRACE_EXPORTS)]


def __getattr__(name: str):
    if name in _TRACE_EXPORTS:
        from go_crdt_playground_tpu.obs import trace

        return getattr(trace, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
