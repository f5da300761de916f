"""pull_ms.closed: the batch delta's pull and record build per batch, spans wal.delta.pull_s plus wal.delta.dense_s (layer: replica node apply)."""

from benchmark.readers import _counter, _obs


def value(ctx):
    n_compact, s_compact = _obs(ctx, "wal.delta.pull_s")
    n_dense, s_dense = _obs(ctx, "wal.delta.dense_s")
    batches = _counter(ctx, "serve.batches")
    if not (n_compact or n_dense) or not batches:
        return None
    return (s_compact + s_dense) / batches * 1e3
