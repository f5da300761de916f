"""overflow_share.open: per cent of batches whose on-device fixed-K delta compaction overflowed, counter ingest.compact_overflow (layer: replica node apply); open loop."""

from benchmark import readers


def value(ctx):
    return readers.overflow_share(ctx)
