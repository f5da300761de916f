"""overflow_share.closed: per cent of batches whose on-device fixed-K delta compaction overflowed, counter ingest.compact_overflow (layer: replica node apply)."""

from benchmark.readers import _counter


def value(ctx):
    if "ingest.compact_overflow" not in ctx["rec1"]["counters"]:
        return None  # a program that does not count it
    batches = _counter(ctx, "serve.batches")
    if not batches:
        return None
    return 100.0 * _counter(ctx, "ingest.compact_overflow") / batches
