"""wait_ms.closed: the batcher's wait on the admission queue per batch, span serve.batch.wait_s (layer: frontend batching)."""

from benchmark.readers import _counter, _obs


def value(ctx):
    n, s = _obs(ctx, "serve.batch.wait_s")
    batches = _counter(ctx, "serve.batches")
    return s / batches * 1e3 if n and batches else None
