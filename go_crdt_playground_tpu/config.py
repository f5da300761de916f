"""Framework configuration.

The reference has zero config surface (no flags/env/files; its whole
operational interface is ``go test``, README.md:1).  The TPU framework needs
static shapes and mesh geometry up front, so configuration is one small
frozen dataclass threaded through state constructors and kernels.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Config:
    """Static-shape + semantics configuration.

    Attributes:
      num_replicas: replica axis ``R`` — how many independent CRDT replicas
        are packed into one batched state (reference analogue: one Go struct
        per replica, awset_test.go:159-168).
      num_elements: element-universe axis ``E`` — dictionary-encoded element
        ids ``0..E-1`` (the keys of ``Entries``, awset.go:58).  Fixed per
        state; grow-and-repack on host when the dictionary overflows.
      num_actors: actor axis ``A`` — version vector length
        (crdt-misc.go:23).  Zero-padding unseen actors is exact: counter 0
        means "never seen" (crdt-misc.go:29-41).
      counter_dtype: dtype for clocks/counters.  uint32 by default; Go's
        ``uint`` is 64-bit, so overflow guards trip past ~4.29e9 ops/actor
        (utils.guards).
      strict_reference_semantics: preserve reference quirks exactly —
        currently: an all-empty δ payload skips the VV join
        (awset-delta_test.go:60-64).  Disable for clock convergence.
      delta_gc: enable the ack-frontier δ-log GC (the reference's gcDeleted
        is an empty stub, awset-delta_test.go:67-77; False reproduces its
        grow-forever behavior).
      debug_trace: emit the per-element merge-decision tensor (uint8[R, E]
        with the reference's five outcome labels, awset.go:126-156) from
        kernels that support it.
      mesh_shape: (replica_shards, element_shards) for the device mesh used
        by parallel/.  None = mesh.make_mesh's default: every visible
        device on the replica axis.
    """

    num_replicas: int = 2
    num_elements: int = 16
    num_actors: int = 2
    counter_dtype: str = "uint32"
    strict_reference_semantics: bool = True
    delta_gc: bool = False
    debug_trace: bool = False
    mesh_shape: Optional[Tuple[int, int]] = None

    def __post_init__(self) -> None:
        if self.num_replicas < 1 or self.num_elements < 1 or self.num_actors < 1:
            raise ValueError("num_replicas/num_elements/num_actors must be >= 1")
        if self.counter_dtype not in ("uint32", "uint64"):
            raise ValueError(f"unsupported counter dtype {self.counter_dtype}")

    # -- factories (the one place shapes flow from config into states) ----

    def init_awset(self, actors=None):
        from go_crdt_playground_tpu.models import awset

        return awset.init(self.num_replicas, self.num_elements,
                          self.num_actors, actors)

    def init_awset_delta(self, actors=None):
        from go_crdt_playground_tpu.models import awset_delta

        return awset_delta.init(self.num_replicas, self.num_elements,
                                self.num_actors, actors)

    def element_dict(self, values=None):
        from go_crdt_playground_tpu.utils.codec import ElementDict

        return ElementDict(capacity=self.num_elements, values=values)

    def make_mesh(self, devices=None):
        from go_crdt_playground_tpu.parallel import mesh

        return mesh.make_mesh(self.mesh_shape, devices=devices)


# The conformance anchor config: BASELINE.json config 1 (AWSet 3 replicas x 16
# elements, go-test-equivalent semantics).  Each replica is its own actor
# (awset_test.go:159-168 gives actor i to replica i), so the actor axis must
# cover the replica count.
REFERENCE_CONFIG = Config(num_replicas=3, num_elements=16, num_actors=3)
