"""pack_ms.closed: scheduler reorder and dense row packing per batch, span serve.batch.pack_s (layer: frontend batching)."""

from benchmark.readers import _counter, _obs


def value(ctx):
    n, s = _obs(ctx, "serve.batch.pack_s")
    batches = _counter(ctx, "serve.batches")
    return s / batches * 1e3 if n and batches else None
