"""Distribution layer — mesh runtime + ICI collective repartitioning.

Reference: the DistSQL cross-node data plane (SURVEY.md §2.9-2.10):
`colflow.HashRouter` (routers.go:442) hashing rows onto N gRPC FlowStreams
becomes `lax.all_to_all` over ICI inside `shard_map`; MIRROR broadcast
(small build sides) becomes `all_gather`; the two-stage distributed
aggregation (partial per node -> final on gateway) becomes partial-per-chip
-> all_gather -> replicated merge. Control plane (flow setup/liveness)
stays host-side (rpc/ in a later milestone).
"""

from cockroach_tpu.parallel.mesh import make_mesh, host_mesh
from cockroach_tpu.parallel.repartition import (
    hash_repartition_local, shard_batch,
)

__all__ = [
    "make_mesh", "host_mesh", "hash_repartition_local", "shard_batch",
]
