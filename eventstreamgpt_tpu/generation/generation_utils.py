"""The autoregressive generation loop.

Rebuild of ``/root/reference/EventStream/transformer/generation/generation_utils.py``
(``StructuredGenerationMixin.generate`` ``:124-308`` and the per-mode event
samplers ``:310-416``) as a function over flax models.

Structure under XLA: the output batch is **preallocated** to
``input_len + max_new_events`` events and every step writes through a cursor,
so each step is a fixed-shape jitted computation. On the common path (KV
caches, no data-dependent stopping criteria) everything after the prefix
pass runs **on device inside one ``lax.scan``** — the CI body is one forward
per event, the NA body the full per-event level walk of the three-phase
cache machine of `NestedAttentionPointProcessTransformer` — so the host
dispatches two programs per generate() call regardless of horizon. With
data-dependent stopping criteria (or ``use_cache=False``) the loop falls
back to per-event Python dispatch. Jitted step closures are memoized per
(model, shape) across generate() calls.

Deliberate divergence: the reference's *uncached* NA generation slices input
embeddings per dep-graph target, attending over a smaller key set than the
training forward (``transformer.py:918-927``); here the uncached NA path runs
full forwards (target=None) each step, which provably matches the cached path
and the training-time attention pattern (see
``tests/models/test_na_model.py::test_cached_dep_graph_decode_matches_uncached``).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flax import struct

from ..data.types import EventStreamBatch
from ..models.config import StructuredEventProcessingMode, StructuredTransformerConfig
from ..models.transformer import NAPast, init_kv_caches, time_from_deltas
from ..ops.tensor_ops import take_event
from ..parallel.context import current_kernel_mesh, kernel_mesh
from .sampling import append_new_event, sample_predictions, update_last_event_data
from .stopping_criteria import MaxLengthCriteria, StoppingCriteriaList

Array = Any


@struct.dataclass
class GenerationOutput:
    """A completed generation plus per-row accounting.

    ``generate(..., return_output=True)`` wraps its result batch with
    per-row ``n_generated`` — the count of REAL events each row produced
    (rows whose prompts end in padding generate only masked events and
    count 0; a fired stopping criterion shortens every row). Previously
    only whole-batch event totals were observable from the result batch.
    """

    batch: EventStreamBatch
    n_generated: Array  # (B,) int32: real generated events per row
    input_len: int = struct.field(pytree_node=False, default=0)


def _with_accounting(batch: EventStreamBatch, input_len: int) -> GenerationOutput:
    n_gen = batch.event_mask[:, input_len:].sum(axis=1).astype(jnp.int32)
    return GenerationOutput(batch=batch, n_generated=n_gen, input_len=input_len)


@jax.jit
def _batch_nonfinite(batch: EventStreamBatch) -> Array:
    """True if any float tensor in the batch holds a NaN/inf (scalar bool).

    The reference validates every batch tensor between generation steps
    (``generation_utils.py:253-269``); here the checks are fused into one
    jitted reduction so the guard costs one scalar readback per step.
    """
    bad = jnp.asarray(False)
    for x in (batch.time_delta, batch.dynamic_values):
        if x is not None:
            bad = bad | ~jnp.isfinite(x).all()
    return bad


def _preallocate(batch: EventStreamBatch, max_new_events: int) -> EventStreamBatch:
    """Right-pads the sequence axis with ``max_new_events`` empty events."""

    def pad_seq(x, fill=0):
        if x is None:
            return None
        pad = [(0, 0)] * x.ndim
        pad[1] = (0, max_new_events)
        return jnp.pad(x, pad, constant_values=fill)

    return batch.replace(
        event_mask=pad_seq(batch.event_mask, False),
        time_delta=pad_seq(batch.time_delta),
        time=None,  # recomputed from deltas as needed
        dynamic_indices=pad_seq(batch.dynamic_indices),
        dynamic_measurement_indices=pad_seq(batch.dynamic_measurement_indices),
        dynamic_values=pad_seq(batch.dynamic_values),
        dynamic_values_mask=pad_seq(batch.dynamic_values_mask),
    )


def _slice_preds_at(preds, idx: Array):
    """Slices (B, L, ...) prediction pytrees down to event ``idx``: (B, ...)."""

    def take(x):
        if x is None:
            return None
        if x.shape[1] == 1:
            # Decode-scan views are one event long — a static slice; the
            # take_along_axis this replaces measured ~1 ms/leaf/event on TPU.
            return x[:, 0]
        return take_event(x, idx)

    return jax.tree_util.tree_map(take, preds)


def _trim_to_event(batch: EventStreamBatch, idx: Array) -> EventStreamBatch:
    """A one-event view of the batch at event ``idx``, with absolute time set.

    Mirrors ``prepare_inputs_for_generation`` trimming
    (``conditionally_independent_model.py:198-248``).
    """
    B = batch.event_mask.shape[0]
    t_full = time_from_deltas(batch)

    def take2(x):  # (B, L) -> (B, 1); masked-reduce, not gather (take_event)
        return take_event(x, idx)[:, None]

    def take3(x):  # (B, L, M) -> (B, 1, M)
        return take_event(x, idx)[:, None, :]

    return batch.replace(
        event_mask=take2(batch.event_mask),
        time_delta=take2(batch.time_delta),
        time=take2(t_full),
        dynamic_indices=take3(batch.dynamic_indices),
        dynamic_measurement_indices=take3(batch.dynamic_measurement_indices),
        dynamic_values=take3(batch.dynamic_values),
        dynamic_values_mask=take3(batch.dynamic_values_mask),
    )


def _mask_through_cursor(batch: EventStreamBatch, cursor: Array) -> EventStreamBatch:
    """Event mask restricted to positions < cursor (hides preallocated tail).

    ``cursor`` may be a scalar (cohort path) or per-row ``(B,)`` (engine
    slots)."""
    positions = jnp.arange(batch.sequence_length)[None, :]
    cur = cursor[:, None] if getattr(cursor, "ndim", 0) == 1 else cursor
    return batch.replace(event_mask=batch.event_mask & (positions < cur))


def generate(
    model,
    params,
    batch: EventStreamBatch,
    config: StructuredTransformerConfig,
    key: jax.Array,
    max_new_events: int | None = None,
    max_length: int | None = None,
    num_return_sequences: int = 1,
    use_cache: bool = True,
    stopping_criteria: StoppingCriteriaList | None = None,
    do_validate_batch: bool = True,
    mesh: Mesh | None = None,
    return_output: bool = False,
) -> EventStreamBatch | GenerationOutput:
    """Autoregressively samples future events (reference ``generate`` ``:124``).

    Args:
        model: A `CIPPTForGenerativeSequenceModeling` or
            `NAPPTForGenerativeSequenceModeling` module instance.
        params: Model parameters.
        batch: The prompt batch. Every sequence should be **right-aligned
            real events** (no interior padding); the returned batch has the
            prompt in place and generated events appended at the cursor.
        config: The model configuration.
        key: PRNG key for sampling.
        max_new_events: Number of events to generate. Exactly one of this and
            ``max_length`` must be set (or ``max_length`` defaults to
            ``config.max_seq_len`` as in the reference ``:176-207``).
        num_return_sequences: Sample count per prompt element; the batch is
            expanded in-order (reference ``:216``).
        use_cache: Use KV caches (one forward per new event/element) instead
            of full forwards each step.
        stopping_criteria: Optional `StoppingCriteriaList` consulted before
            the loop and after every completed event (reference ``:239,297``);
            a `MaxLengthCriteria` inside it also bounds ``max_new_events``. A
            criterion already satisfied by the prompt returns the prompt
            (expanded by ``num_return_sequences``) unchanged.
        do_validate_batch: Check the prompt for NaN/inf and raise (reference
            ``:253-269`` checks every step; here every value *written* during
            generation is already sanitized at the sampling layer —
            ``sampling.py`` ``nan_to_num``/clamps — so only the prompt can
            carry non-finites and one check suffices). The check's device
            reduction is dispatched up front but its host readback is
            deferred until the generation dispatches are in flight, so it
            costs no serial round trip; a bad prompt still raises before any
            result is returned.
        mesh: Optional device mesh with a ``data`` axis. The (expanded) batch
            is sharded over it with replicated params, so every jitted
            generation step runs data-parallel across the mesh — the
            TPU-native analog of the reference's DDP generation
            (``generation_utils.py:240-247``), minus the per-step all-reduce
            handshake (all shards run the same step count, so no peer can
            finish early). The expanded batch size
            (``batch_size * num_return_sequences``) must be divisible by the
            mesh's ``data`` axis size.

        return_output: Return a `GenerationOutput` (result batch + per-row
            ``n_generated`` real-event counts) instead of the bare batch.

    Returns:
        The completed `EventStreamBatch` of ``input_len + max_new_events``
        events (fewer if a stopping criterion fired) — or a
        `GenerationOutput` wrapping it when ``return_output`` is set.
    """
    if config.uses_layer_kinds:
        from ..models.transformer import NO_DECODE_STATE

        raise NotImplementedError(NO_DECODE_STATE)
    if batch.segment_ids is not None:
        raise NotImplementedError(
            "generate() requires padded (one subject per row) prompt batches; packed "
            "segment_ids rows are a training/eval layout. De-pack the prompts first."
        )

    input_len = batch.sequence_length
    if num_return_sequences > 1:
        batch = batch.repeat_batch_elements(num_return_sequences)

    # Prompt validation. Host-array prompts are checked on the host for free
    # (before any device placement). Device-resident prompts need a device
    # reduction whose readback is a host round trip that would serialize
    # in front of the generation program: dispatch it, start the async copy,
    # and defer the
    # bool() until the generation program is in flight. Framework-collated
    # resident prompts (DeviceDataset eval paths) are already NaN-clean by
    # construction and every value *written* during generation is sanitized
    # at the sampling layer — latency-sensitive callers pass
    # ``do_validate_batch=False`` there.
    bad_prompt = None
    if do_validate_batch:
        float_leaves = [
            x for x in (batch.time_delta, batch.dynamic_values) if x is not None
        ]
        if all(isinstance(x, np.ndarray) for x in float_leaves):
            if any(not np.isfinite(x).all() for x in float_leaves):
                raise ValueError(
                    "Non-finite values (NaN/inf) in the prompt batch; generation would "
                    "propagate them. Clean the inputs or pass do_validate_batch=False."
                )
        else:
            bad_prompt = _batch_nonfinite(batch)
            # Start the device->host copy of the scalar now: the copy's
            # latency overlaps the generation
            # dispatches below, so the bool() in _check_prompt finds the value
            # already on the host instead of paying a serial round trip.
            try:
                bad_prompt.copy_to_host_async()
            except AttributeError:  # non-jax array (e.g. test doubles)
                pass

    if mesh is not None:
        if "data" not in mesh.shape:
            raise ValueError(
                f"generate() shards batches over a 'data' mesh axis; the given mesh has "
                f"axes {tuple(mesh.axis_names)}."
            )
        n_data = int(mesh.shape["data"])
        if batch.batch_size % n_data != 0:
            raise ValueError(
                f"Expanded batch size {batch.batch_size} (batch x num_return_sequences) "
                f"must be divisible by the mesh's 'data' axis size ({n_data})."
            )

        # ONE device_put call for the whole batch: per-leaf puts are one
        # host dispatch each (few large device programs, small host traffic).
        shardings = jax.tree_util.tree_map(
            lambda x: NamedSharding(mesh, P("data", *([None] * (np.ndim(x) - 1)))), batch
        )
        batch = jax.device_put(batch, shardings)
        params = jax.device_put(params, NamedSharding(mesh, P()))

    def _check_prompt():
        if bad_prompt is not None and bool(bad_prompt):
            raise ValueError(
                "Non-finite values (NaN/inf) in the prompt batch; generation would "
                "propagate them. Clean the inputs or pass do_validate_batch=False."
            )

    bounds = []
    if stopping_criteria is not None:
        if bool(stopping_criteria(batch, n_events=input_len)):
            _check_prompt()
            return _with_accounting(batch, input_len) if return_output else batch
        if stopping_criteria.max_length is not None:
            bounds.append(stopping_criteria.max_length - input_len)
    if max_new_events is not None:
        bounds.append(max_new_events)
    elif max_length is not None:
        bounds.append(max_length - input_len)
    elif not bounds:
        bounds.append(config.max_seq_len - input_len)
    # Every explicit bound applies; a MaxLengthCriteria cannot loosen an
    # explicit max_length/max_new_events argument (or vice versa).
    max_new_events = min(bounds)
    if max_new_events <= 0:
        raise ValueError(f"max_new_events must be positive; got {max_new_events}")

    # Length bounds are fully folded into max_new_events above, so a criteria
    # list containing only MaxLengthCriteria needs no per-event host sync.
    if stopping_criteria is not None and all(
        isinstance(c, MaxLengthCriteria) for c in stopping_criteria
    ):
        stopping_criteria = None

    mode = config.structured_event_processing_mode
    gen = (
        _generate_ci
        if mode == StructuredEventProcessingMode.CONDITIONALLY_INDEPENDENT
        else _generate_na
    )
    try:
        # The step programs are traced inside `kernel_mesh`, as the trainers'
        # steps are: GSPMD cannot partition a Mosaic call, so the model's
        # kernels (the embedding's plane) run once per batch shard there.
        with kernel_mesh(mesh if mesh is not None else current_kernel_mesh()):
            result = gen(
                model,
                params,
                batch,
                config,
                key,
                max_new_events,
                use_cache,
                stopping_criteria=stopping_criteria,
            )
    except Exception:
        # A non-finite prompt can crash generation itself; surface the clear
        # validation error instead of the downstream failure.
        _check_prompt()
        raise
    _check_prompt()
    return _with_accounting(result, input_len) if return_output else result


def _should_stop(big, cursor, stopping_criteria) -> bool:
    """Consults stopping criteria after a completed event (reference
    ``generation_utils.py:239,297``). Returns True if generation should stop."""
    if stopping_criteria is None:
        return False
    masked = _mask_through_cursor(big, cursor)
    return bool(stopping_criteria(masked, n_events=int(cursor)))


# ------------------------------------------------------- jitted step caching
# generate() runs per batch inside eval loops; rebuilding its @jax.jit
# closures on every call would give each call a fresh (empty) trace cache and
# re-trace the model each time — seconds of pure overhead per batch. Step
# closures are therefore memoized per (mode, config signature, shape
# signature): a flax module's apply() is a pure function of its config, so
# callers that build a fresh model object per generate() call still hit the
# cache (the cached closures keep the first equivalent instance alive). The
# cache is FIFO-bounded (one entry per distinct generation shape — a handful
# per process).
_STEP_CACHE: dict[tuple, dict] = {}
_STEP_CACHE_MAX = 32


def _config_signature(config: StructuredTransformerConfig) -> str:
    import json

    return json.dumps(config.to_dict(), sort_keys=True, default=str)


# Serializing a realistic config (full measurement metadata + vocab maps)
# costs milliseconds; generate() runs once per eval batch, so the signature
# is memoized per live model object (weakly — a dead model's id can be
# recycled, hence the identity re-check).
_SIG_CACHE: dict[int, tuple[Any, str]] = {}


def _model_config_signature(model, config: StructuredTransformerConfig) -> str:
    import weakref

    key = id(model)
    hit = _SIG_CACHE.get(key)
    if hit is not None and hit[0]() is model:
        return hit[1]
    sig = _config_signature(config)
    try:
        ref = weakref.ref(model)
    except TypeError:
        return sig
    if len(_SIG_CACHE) >= 64:
        # Overflow is almost always dead weakrefs (eval loops building a
        # fresh model per batch): evict those first so live models keep
        # their memoized signatures; a full clear — which forfeits every
        # live memo — is the last resort only.
        for dead in [k for k, (r, _) in _SIG_CACHE.items() if r() is None]:
            del _SIG_CACHE[dead]
        if len(_SIG_CACHE) >= 64:
            _SIG_CACHE.clear()
    _SIG_CACHE[key] = (ref, sig)
    return sig


def _cached_steps(cache_key: tuple, build):
    # The kernel mesh is read while a step is traced, and a trace is cached
    # on the step's function object: it is part of what the steps are.
    cache_key = cache_key + (current_kernel_mesh(),)
    hit = _STEP_CACHE.pop(cache_key, None)
    if hit is not None:
        # Re-insert on hit: eviction below is LRU, so steady-state shapes
        # (the eval loop's one batch shape) can't be churned out by
        # one-off shapes.
        _STEP_CACHE[cache_key] = hit
        return hit
    steps = build()
    if len(_STEP_CACHE) >= _STEP_CACHE_MAX:
        _STEP_CACHE.pop(next(iter(_STEP_CACHE)))
    _STEP_CACHE[cache_key] = steps
    return steps


# ------------------------------------------------------------------- CI path
def _build_ci_steps(model, config, B, input_len, max_new_events):
    total_len = input_len + max_new_events

    @jax.jit
    def prefix_step(params, big_batch):
        view = big_batch.slice((slice(None), slice(0, input_len)))
        out = model.apply(
            params,
            view,
            past=init_kv_caches(config, B, max_len=total_len),
            use_cache=True,
            is_generation=True,
        )
        return out.preds, out.past_key_values

    # The caches are consumed and rebound every step (`preds, caches =
    # decode_step(params, big, caches, cursor)`), so they donate: the KV
    # planes update in place instead of double-buffering a second
    # (B, total_len) cache set per dispatch.
    @partial(jax.jit, donate_argnums=(2,))
    def decode_step(params, big_batch, caches, cursor):
        view = _trim_to_event(big_batch, cursor - 1)
        out = model.apply(params, view, past=caches, use_cache=True, is_generation=True)
        return out.preds, out.past_key_values

    @jax.jit
    def full_step(params, big_batch, cursor):
        masked = _mask_through_cursor(big_batch, cursor)
        out = model.apply(params, masked, is_generation=True)
        return out.preds

    def sample_and_write_body(big_batch, preds_last, cursor, key):
        bcols = jnp.arange(B)
        event_mask_last = big_batch.event_mask[bcols, cursor - 1]
        sample = sample_predictions(preds_last, event_mask_last, key)
        new_batch = append_new_event(big_batch, sample, config, cursor)
        return update_last_event_data(new_batch, sample, config, cursor + 1)

    sample_and_write = jax.jit(
        lambda params, big_batch, preds_last, cursor, key: sample_and_write_body(
            big_batch, preds_last, cursor, key
        )
    )

    def decode_scan_body(params, big_batch, caches, cursor, key):
        def body(carry, _):
            big_b, caches_b, cur, k = carry
            k, step_key = jax.random.split(k)
            view = _trim_to_event(big_b, cur - 1)
            out = model.apply(params, view, past=caches_b, use_cache=True, is_generation=True)
            preds_last = _slice_preds_at(out.preds, jnp.asarray(0))
            big_b = sample_and_write_body(big_b, preds_last, cur, step_key)
            return (big_b, out.past_key_values, cur + 1, k), None

        carry, _ = jax.lax.scan(
            body, (big_batch, caches, cursor, key), None, length=max_new_events - 1
        )
        return carry

    # The scan consumes the preallocated batch and the caches and returns
    # their successors in the carry — both donate when dispatched as a
    # standalone program.
    decode_scan = jax.jit(decode_scan_body, donate_argnums=(1, 2))

    @jax.jit
    def generate_program(params, prompt_batch, key):
        """The WHOLE cached generation — tail preallocation, prefix forward,
        first sample, the decode scan, and the final cursor masking — as one
        device program, so `generate()` costs a single dispatch (even the
        eager jnp pads of `_preallocate` would each be a host dispatch).
        Key-split order matches the step-by-step path
        exactly, so all paths sample identical trajectories."""
        big_batch = _preallocate(prompt_batch, max_new_events)
        cursor = jnp.asarray(input_len, jnp.int32)
        key, step_key = jax.random.split(key)
        view = big_batch.slice((slice(None), slice(0, input_len)))
        out = model.apply(
            params,
            view,
            past=init_kv_caches(config, B, max_len=total_len),
            use_cache=True,
            is_generation=True,
        )
        preds_last = _slice_preds_at(out.preds, cursor - 1)
        big_batch = sample_and_write_body(big_batch, preds_last, cursor, step_key)
        cursor = cursor + 1
        if max_new_events > 1:
            big_batch, _, cursor, key = decode_scan_body(
                params, big_batch, out.past_key_values, cursor, key
            )
        return _mask_through_cursor(big_batch, cursor)

    return dict(
        prefix_step=prefix_step,
        decode_step=decode_step,
        full_step=full_step,
        sample_and_write=sample_and_write,
        decode_scan=decode_scan,
        generate_program=generate_program,
    )


def _generate_ci(
    model,
    params,
    batch,
    config,
    key,
    max_new_events,
    use_cache,
    stopping_criteria=None,
):
    B = batch.batch_size
    input_len = batch.sequence_length

    steps = _cached_steps(
        ("ci", _model_config_signature(model, config), B, input_len, max_new_events),
        lambda: _build_ci_steps(model, config, B, input_len, max_new_events),
    )

    # On-device decode loop: with KV caches and no data-dependent stopping
    # criteria (the common path — MaxLength bounds fold into max_new_events),
    # the ENTIRE generation (preallocation, prefix, scan, final masking) is
    # one jitted program — a single dispatch per call.
    # The per-step key-split sequence matches the Python loop
    # exactly, so both paths sample identical trajectories.
    if use_cache and stopping_criteria is None:
        return steps["generate_program"](params, batch, key)

    prefix_step = steps["prefix_step"]
    decode_step = steps["decode_step"]
    full_step = steps["full_step"]
    sample_and_write = steps["sample_and_write"]

    big = _preallocate(batch, max_new_events)
    cursor = jnp.asarray(input_len, jnp.int32)
    caches = None

    for step in range(max_new_events):
        key, step_key = jax.random.split(key)
        if use_cache:
            if step == 0:
                preds, caches = prefix_step(params, big)
                preds_last = _slice_preds_at(preds, cursor - 1)
            else:
                preds, caches = decode_step(params, big, caches, cursor)
                preds_last = _slice_preds_at(preds, jnp.asarray(0))
        else:
            preds = full_step(params, big, cursor)
            preds_last = _slice_preds_at(preds, cursor - 1)
        big = sample_and_write(params, big, preds_last, cursor, step_key)
        cursor = cursor + 1
        if _should_stop(big, cursor, stopping_criteria):
            break

    return _mask_through_cursor(big, cursor)


# ------------------------------------------------------------------- NA path
def _build_na_steps(model, config, B, input_len, max_new_events):
    total_len = input_len + max_new_events
    measurements_to_fill_list = [{"time"}, *config.measurements_per_dep_graph_level[1:]]
    n_levels = len(measurements_to_fill_list)

    @jax.jit
    def prefix_step(params, big_batch):
        view = big_batch.slice((slice(None), slice(0, input_len)))
        out = model.apply(
            params,
            view,
            past=NAPast(seq_past=init_kv_caches(config, B, max_len=total_len), dep_graph_past=None),
            use_cache=True,
            is_generation=True,
        )
        return out.preds, out.past_key_values

    def make_target_step(target):
        @jax.jit
        def target_step(params, big_batch, past, event_idx):
            view = _trim_to_event(big_batch, event_idx)
            out = model.apply(
                params,
                view,
                past=past,
                use_cache=True,
                is_generation=True,
                dep_graph_el_generation_target=target,
            )
            return out.preds, out.past_key_values

        return target_step

    @jax.jit
    def full_step(params, big_batch, cursor):
        masked = _mask_through_cursor(big_batch, cursor)
        out = model.apply(params, masked, is_generation=True)
        return out.preds

    @jax.jit
    def do_append(params, big_batch, preds_last, cursor, key):
        bcols = jnp.arange(B)
        event_mask_last = big_batch.event_mask[bcols, cursor - 1]
        sample = sample_predictions(preds_last, event_mask_last, key)
        return append_new_event(big_batch, sample, config, cursor)

    def make_do_fill(measurements_to_fill):
        frozen = tuple(sorted(measurements_to_fill, key=str))

        @jax.jit
        def do_fill(params, big_batch, preds_last, cursor, key):
            bcols = jnp.arange(B)
            event_mask_last = big_batch.event_mask[bcols, cursor - 1]
            sample = sample_predictions(preds_last, event_mask_last, key)
            return update_last_event_data(
                big_batch, sample, config, cursor, measurements_to_fill=set(frozen)
            )

        return do_fill

    target_steps = {t: make_target_step(t) for t in range(n_levels)}
    do_fills = [None] + [make_do_fill(m) for m in measurements_to_fill_list[1:]]

    def decode_scan_body(params, big_batch, past, cursor, key):
        """All post-first events decoded on device: one lax.scan whose body
        runs the full per-event level walk (target-0 contextualization + one
        decode/fill per dependency-graph level), mirroring the Python loop's
        key-split order exactly."""

        def body(carry, _):
            big_b, past_b, cur, k = carry
            k, step_key = jax.random.split(k)
            preds, past_b = target_steps[0](params, big_b, past_b, cur - 1)
            preds_last = _slice_preds_at(preds, jnp.asarray(0))
            big_b = do_append(params, big_b, preds_last, cur, step_key)
            for level in range(1, n_levels):
                k, step_key = jax.random.split(k)
                preds, past_b = target_steps[level](params, big_b, past_b, cur)
                preds_last = _slice_preds_at(preds, jnp.asarray(0))
                big_b = do_fills[level](params, big_b, preds_last, cur + 1, step_key)
            return (big_b, past_b, cur + 1, k), None

        carry, _ = jax.lax.scan(
            body, (big_batch, past, cursor, key), None, length=max_new_events - 1
        )
        return carry

    # The scan consumes the preallocated batch and the caches and returns
    # their successors in the carry — both donate when dispatched as a
    # standalone program.
    decode_scan = jax.jit(decode_scan_body, donate_argnums=(1, 2))

    @jax.jit
    def generate_program(params, prompt_batch, key):
        """Whole cached NA generation — tail preallocation, prefix pass,
        first event's level walk, decode scan, final masking — as ONE device
        program (one dispatch per `generate()` call).
        Key-split order matches the step-by-step path exactly."""
        cursor = jnp.asarray(input_len, jnp.int32)
        past = None
        big_b = _preallocate(prompt_batch, max_new_events)
        for level in range(n_levels):
            key, step_key = jax.random.split(key)
            if level == 0:
                view = big_b.slice((slice(None), slice(0, input_len)))
                out = model.apply(
                    params,
                    view,
                    past=NAPast(
                        seq_past=init_kv_caches(config, B, max_len=total_len),
                        dep_graph_past=None,
                    ),
                    use_cache=True,
                    is_generation=True,
                )
                preds, past = out.preds, out.past_key_values
                preds_last = _slice_preds_at(preds, cursor - 1)
                big_b = do_append(params, big_b, preds_last, cursor, step_key)
            else:
                view = _trim_to_event(big_b, cursor)
                out = model.apply(
                    params,
                    view,
                    past=past,
                    use_cache=True,
                    is_generation=True,
                    dep_graph_el_generation_target=level,
                )
                preds, past = out.preds, out.past_key_values
                preds_last = _slice_preds_at(preds, jnp.asarray(0))
                big_b = do_fills[level](params, big_b, preds_last, cursor + 1, step_key)
        cursor = cursor + 1
        if max_new_events > 1:
            big_b, past, cursor, key = decode_scan_body(params, big_b, past, cursor, key)
        return _mask_through_cursor(big_b, cursor)

    return dict(
        measurements_to_fill_list=measurements_to_fill_list,
        prefix_step=prefix_step,
        target_steps=target_steps,
        full_step=full_step,
        do_append=do_append,
        do_fills=do_fills,
        decode_scan=decode_scan,
        generate_program=generate_program,
    )


def _generate_na(
    model,
    params,
    batch,
    config,
    key,
    max_new_events,
    use_cache,
    stopping_criteria=None,
):
    B = batch.batch_size
    input_len = batch.sequence_length

    steps = _cached_steps(
        ("na", _model_config_signature(model, config), B, input_len, max_new_events),
        lambda: _build_na_steps(model, config, B, input_len, max_new_events),
    )
    measurements_to_fill_list = steps["measurements_to_fill_list"]
    prefix_step = steps["prefix_step"]
    target_steps = steps["target_steps"]
    full_step = steps["full_step"]
    do_append = steps["do_append"]
    do_fills = steps["do_fills"]

    # On-device NA decode: with caches and no data-dependent stopping
    # criteria, the whole generation (preallocation, prefix, every event's
    # level walk, final masking) is one jitted program — a single dispatch
    # per call. The key-split sequence matches
    # the Python path exactly.
    if use_cache and stopping_criteria is None:
        return steps["generate_program"](params, batch, key)

    big = _preallocate(batch, max_new_events)
    cursor = jnp.asarray(input_len, jnp.int32)

    past = None
    for step in range(max_new_events):
        for level, measurements_to_fill in enumerate(measurements_to_fill_list):
            key, step_key = jax.random.split(key)
            is_first = step == 0

            if use_cache:
                if is_first and level == 0:
                    preds, past = prefix_step(params, big)
                    preds_last = _slice_preds_at(preds, cursor - 1)
                elif level == 0:
                    # Contextualize the just-completed event (target=0).
                    preds, past = target_steps[0](params, big, past, cursor - 1)
                    preds_last = _slice_preds_at(preds, jnp.asarray(0))
                else:
                    # Decode one new graph element of the in-progress event.
                    preds, past = target_steps[level](params, big, past, cursor)
                    preds_last = _slice_preds_at(preds, jnp.asarray(0))
            else:
                if level == 0:
                    preds = full_step(params, big, cursor)
                    preds_last = _slice_preds_at(preds, cursor - 1)
                else:
                    preds = full_step(params, big, cursor + 1)
                    preds_last = _slice_preds_at(preds, cursor)

            if measurements_to_fill == {"time"}:
                big = do_append(params, big, preds_last, cursor, step_key)
            else:
                big = do_fills[level](params, big, preds_last, cursor + 1, step_key)
        cursor = cursor + 1
        if _should_stop(big, cursor, stopping_criteria):
            break

    return _mask_through_cursor(big, cursor)
