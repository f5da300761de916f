"""checkpoint_ms.open: checkpoint time per second of window, span node.checkpoint_s (layer: durability)."""

from benchmark.readers import _obs


def value(ctx):
    if "node.checkpoint_s" not in ctx["rec1"]["observations"]:
        return None
    _, s = _obs(ctx, "node.checkpoint_s")
    return s * 1e3 / (ctx["t1"] - ctx["t0"])
