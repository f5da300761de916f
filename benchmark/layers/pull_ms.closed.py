"""pull_ms.closed: the batch delta's pull and record build per batch, spans wal.delta.pull_s plus wal.delta.dense_s (layer: replica node apply); closed loop."""

from benchmark import readers


def value(ctx):
    return readers.pull_ms(ctx)
