"""Active mesh contexts for model integration: ring attention and the
per-batch-shard wrap of the Pallas kernels.

Flax modules don't carry device meshes; the training driver activates a
`ring_context` around its jitted step, and `InnerSelfAttention` (with
``config.attention_implementation == "ring"``) picks the mesh up here. With
no active context the model falls back to the einsum path — so a
ring-configured checkpoint still loads and runs on a single device.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading

from jax.sharding import Mesh


@dataclasses.dataclass(frozen=True)
class RingContext:
    mesh: Mesh
    axis_name: str = "context"
    data_axis: str | None = "data"
    # Mesh axis carrying Megatron head-split attention (training/sharding.py);
    # ring_attention ignores it unless the mesh actually has it.
    head_axis: str | None = "model"


_STATE = threading.local()


def current_ring_context() -> RingContext | None:
    return getattr(_STATE, "ctx", None)


@contextlib.contextmanager
def ring_context(
    mesh: Mesh,
    axis_name: str = "context",
    data_axis: str | None = "data",
    head_axis: str | None = "model",
):
    """Activates ring attention over ``mesh[axis_name]`` for enclosed traces."""
    prev = current_ring_context()
    _STATE.ctx = RingContext(
        mesh=mesh, axis_name=axis_name, data_axis=data_axis, head_axis=head_axis
    )
    try:
        yield
    finally:
        _STATE.ctx = prev


# ------------------------------------------------------------- kernel mesh
# Mosaic kernels cannot be partitioned by GSPMD ("Mosaic kernels cannot be
# automatically partitioned. Please wrap the call in a shard_map" -- what
# the chip's compiler answers for any multi-device jit that contains one;
# PR 22). The training drivers activate `kernel_mesh` around their jitted
# steps, and every Pallas call site whose rows are independent per batch
# element routes through `per_batch_shard`: with a multi-device batch
# layout active the call runs once per batch shard under `jax.shard_map`
# (no collective, no gather of the operand planes); with no context, or a
# single batch shard, it is a plain call.
_BATCH_AXES = ("data", "fsdp")


def current_kernel_mesh() -> Mesh | None:
    return getattr(_STATE, "kernel_mesh", None)


@contextlib.contextmanager
def kernel_mesh(mesh: Mesh | None):
    """Activates per-batch-shard Pallas calls over ``mesh`` for enclosed traces."""
    prev = current_kernel_mesh()
    _STATE.kernel_mesh = mesh
    try:
        yield
    finally:
        _STATE.kernel_mesh = prev


def per_batch_shard(fn, *args, replicated=()):
    """``fn(*args, *replicated)``, once per batch shard of the active
    `kernel_mesh`.

    Every array in ``args`` and in the result must carry the batch (or the
    batch-major flattened row) dimension first; ``fn`` must be independent
    across it. ``replicated`` are operands every shard sees whole (weights).
    Every mesh axis is manual inside the call (Mosaic refuses a
    partially-manual context), so operands are replicated over any axis
    other than ``data``/``fsdp``; where the leading dimension does not
    divide by the batch shards, over those too.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    mesh = current_kernel_mesh()
    axes = tuple(a for a in _BATCH_AXES if mesh is not None and mesh.shape.get(a, 1) > 1)
    if not axes:
        return fn(*args, *replicated)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    if any(x.shape[0] % n_shards for x in jax.tree_util.tree_leaves(args)):
        # Rows that do not divide over the batch shards are no sharded batch
        # (a serving engine's replicated prefill group): replicated specs,
        # every device runs the whole call.
        axes = None
    spec = lambda x: P(axes, *([None] * (x.ndim - 1)))  # noqa: E731
    out_shape = jax.eval_shape(fn, *args, *replicated)
    whole = jax.tree_util.tree_map(lambda x: P(), tuple(replicated))
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=jax.tree_util.tree_map(spec, args) + whole,
        out_specs=jax.tree_util.tree_map(spec, out_shape),
        check_vma=False,
    )(*args, *replicated)
