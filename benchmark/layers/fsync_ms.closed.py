"""fsync_ms.closed: WAL write, flush and fsync per batch, span wal.fsync_s (layer: durability)."""

from benchmark.readers import _counter, _obs


def value(ctx):
    n, s = _obs(ctx, "wal.fsync_s")
    batches = _counter(ctx, "serve.batches")
    return s / batches * 1e3 if n and batches else None
