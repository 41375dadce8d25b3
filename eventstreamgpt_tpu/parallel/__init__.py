"""Sequence/context parallelism: ring attention over a device-mesh axis.

The reference has no long-context distribution story (SURVEY §2.10: no
SP/CP/ring). Here sequences longer than one chip's memory shard along the
sequence axis of a ``context`` mesh axis, and attention runs as a ring:
each device holds one query block resident while key/value blocks rotate
around the ring via ``ppermute``, accumulating blockwise-softmax partial
results — communication overlaps compute and no device ever materializes
the full sequence.
"""

from .collectives_audit import (
    audit_step,
    collective_inventory,
    compare_inventory,
    resolve_folded_reduce_scatters,
)
from .context import current_kernel_mesh, current_ring_context, kernel_mesh, per_batch_shard, ring_context
from .ring_attention import ring_attention, ring_attention_shard

__all__ = [
    "audit_step",
    "collective_inventory",
    "compare_inventory",
    "resolve_folded_reduce_scatters",
    "current_kernel_mesh",
    "current_ring_context",
    "kernel_mesh",
    "per_batch_shard",
    "ring_attention",
    "ring_attention_shard",
    "ring_context",
]
