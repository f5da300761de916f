"""An acknowledged re-add must survive anti-entropy with a peer that
never writes.

It does not yet (ROADMAP "Correctness — found by PR 21, not fixed"): a
passive peer's clock never counts one of its own events, so every peer
sees it at first contact and it ships its FULL state on every exchange.
The full merge overwrites the re-added element's fresh dot with the
peer's stale one, the peer then absorbs the old deletion record, and
the next FULL exchange removes the element from the writer.
chip_smoke.py starts its digest peer only after the load is acked for
this reason.  Strict xfail: the fix must flip it, and nothing may hide
the loss meanwhile."""

import numpy as np
import pytest

from go_crdt_playground_tpu.net.digestsync import sync_digest
from go_crdt_playground_tpu.net.peer import Node

E, A = 256, 4


@pytest.mark.xfail(strict=True, reason="acked re-add lost to a passive "
                   "peer's FULL anti-entropy (ROADMAP Correctness)")
@pytest.mark.parametrize("mode", ["delta", "digest"])
def test_readd_survives_passive_peer_sync(mode):
    writer, passive = Node(0, E, A), Node(1, E, A)
    addr = writer.serve()

    def sync():
        if mode == "delta":
            passive.sync_with(addr)
        else:
            sync_digest(passive, addr)

    try:
        writer.add(5)
        sync()                      # passive holds 5 at the first dot
        writer.delete(5)
        writer.add(5)               # acked re-add at a fresher dot
        for _ in range(3):
            sync()
        members = writer.members()
    finally:
        writer.close()
    np.testing.assert_array_equal(members, [5])
