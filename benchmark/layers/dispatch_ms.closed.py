"""dispatch_ms.closed: node lock, pre-vv pull, H2D of the rows, kernel enqueue and write-back per batch, span node.ingest.dispatch_s (layer: replica node apply)."""

from benchmark.readers import _counter, _obs


def value(ctx):
    n, s = _obs(ctx, "node.ingest.dispatch_s")
    batches = _counter(ctx, "serve.batches")
    return s / batches * 1e3 if n and batches else None
