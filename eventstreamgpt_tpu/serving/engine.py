"""Continuous-batching generation engine: slot-based decode on device.

``generate()`` (the cohort path) compiles one fused program per
``(B, input_len, max_new_events)`` shape, stops only when the WHOLE batch is
done, and pads every prompt to the cohort max — wasted decode for rows that
finish (or die) early, and a recompile for every new cohort shape. This
engine replaces cohorts with a fixed set of decode **slots**:

* the jitted decode program — one event across all slots per step, scanned
  ``decode_chunk`` steps per dispatch — compiles **once per slot count**.
  Per-slot cursors, done masks, budgets, and PRNG keys live on device;
  finished slots are masked out of sampling and cache writes *on device*
  (``jnp.where`` merges against the pre-step state), so no recompilation
  and no per-event host sync ever happens. The only readback is the done
  mask at each chunk boundary — piggybacking on the dispatch boundary the
  host already owns.
* **prefill is split from decode** and bucketed by prompt length
  (powers-of-two buckets, ``scheduler.Scheduler``): one compiled prefill
  program per (bucket, group-size) pair admits a group of requests into
  free slots in a single dispatch.
* the KV caches carry **per-row lengths** (`models/transformer.py` vector-
  length branch): each slot writes its next key/value at its own cursor, so
  slots at different depths coexist in one program.
* per-request PRNG keys derive as ``fold_in(engine_key, admission_index)``
  (or the request's own key), and each slot's key chain splits exactly like
  ``generate()``'s — results are **bit-deterministic under any refill
  order, slot placement, and co-resident set** (rows never mix in any op).
* the chunk-boundary done-mask readback is **non-blocking**: the packed
  ``(4, n_slots)`` boundary array is computed on device at dispatch and its
  host copy started immediately (``copy_to_host_async``); it is resolved
  one-or-more chunks later (``dispatch_depth`` chunks may be in flight), so
  host admission planning, bucketing, and refill fully overlap device
  decode and the readback leaves the critical path. Because a finished
  slot's row is frozen by the ``where(active)`` merges, harvesting from a
  stale boundary is content-exact — results are bitwise invariant to
  ``dispatch_depth``. The only stale-host-view cost is that a freed slot
  refills up to ``dispatch_depth - 1`` chunks later. Boundaries resolve
  strictly FIFO (the in-flight queue enforces issue order), and each slot
  carries an admission **epoch** (the chunk count at its prefill dispatch)
  so a boundary issued *before* a slot's current request was admitted can
  never harvest that request — the in-order-resolution assumption the
  synchronous loop silently relied on is now an explicit check.

Determinism / parity contract: a request admitted with key ``k`` produces
the same trajectory as ``generate(model, params, prompt, config, k,
max_new_events=budget)`` with ``B=1``. The match is bit-exact when the
engine's ``max_len`` equals that call's ``input_len + max_new_events``
(identical attention-buffer widths ⇒ identical reduction shapes); with
differing widths XLA's gemm blocking may reassociate the same masked
attention reductions, leaving last-ulp float noise (indices and event
structure still match; see ``tests/test_engine.py``). Stopping is
device-evaluated per row (`generation.stopping_criteria.DeviceCriterion`):
per-row max-length/budget first, plus `DeadRowCriteria` (rows whose newest
event is masked can never produce another real event). Whole-batch host
criteria remain supported on ``generate()``'s slow path.
"""

from __future__ import annotations

import functools
import time
from collections import deque
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..data.types import EventStreamBatch
from ..generation.generation_utils import (
    _mask_through_cursor,
    _slice_preds_at,
    _trim_to_event,
)
from ..generation.sampling import (
    GenerativeSequenceModelSamples,
    _named_key,
    append_new_event,
    assemble_event_sample,
    sample_head_draws,
    sample_predictions,
    update_last_event_data,
)
from ..generation.stopping_criteria import DeadRowCriteria, DeviceCriterion
from ..models.config import StructuredEventProcessingMode, StructuredTransformerConfig
from ..models.model_output import GenerativeSequenceModelPredictions
from ..models.transformer import (
    KVCache,
    NAPast,
    PagedKVCache,
    init_kv_caches,
    init_paged_kv_caches,
    mask_batch_to_levels,
    na_level_of_measurement,
    paged_kv_bytes_per_block,
    time_from_deltas,
)
from ..ops.tensor_ops import take_event
from ..parallel.context import kernel_mesh
from .scheduler import (
    EngineResult,
    ForkSpec,
    Request,
    Scheduler,
    check_prompt_finite,
    make_buckets,
)
from .spec import SpecConfig, fold_in_event, select_candidate, spec_accept_level

Array = Any

# EventStreamBatch fields a slot row carries; everything else (labels,
# validity, packing) is host-side request metadata the engine neither needs
# nor preserves on device.
_CORE_FIELDS = (
    "event_mask",
    "time_delta",
    "static_indices",
    "static_measurement_indices",
    "dynamic_indices",
    "dynamic_measurement_indices",
    "dynamic_values",
    "dynamic_values_mask",
    "start_time",
)


class BlockAllocator:
    """Host-side reference-counted free list over the device block pool.

    The pool itself is a device array (`PagedKVCache.pool_*`); this class
    owns WHICH physical blocks are free, shared, or exclusively held — all
    plain Python, never traced. Block 0 is the reserved zero block: it is
    never allocated, every unused block-table entry points at it, and the
    attention gather reads its all-zero bytes for unwritten positions (the
    structural half of the paged == monolithic bit-identity argument).

    Freeing is DEFERRED: a slot's blocks are released when the slot is
    re-admitted (or at `reset()`), not when its request is harvested. Done
    rows keep executing decode writes at their frozen cursor (the step
    merges discard the results, but the pool scatters still land), so a
    block must stay held by its row until no further dispatch can touch
    it. The default pool (`n_slots * blocks_per_slot + 1`) makes deferred
    freeing safe by construction: every slot can hold a full table at once.
    """

    def __init__(self, num_blocks: int, block_size: int):
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        # Popped from the tail: blocks allocate in ascending order, which
        # keeps admissions deterministic given a deterministic free order.
        self._free: list[int] = list(range(self.num_blocks - 1, 0, -1))
        self._rc = np.zeros(self.num_blocks, np.int32)
        # Lifetime counters — survive reset_occupancy() (engine.reset()),
        # per the padding_report contract.
        self.high_water = 0
        self.frag_events = 0
        self.cover_events = 0
        self.allocs_total = 0
        self.frees_total = 0
        # Optional ControlPlaneSanitizer (serving.sanitizer) recording
        # alloc/free provenance; None outside debug/model-check runs.
        self.sanitizer = None

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.num_blocks - 1 - len(self._free)

    def shared_blocks(self) -> int:
        """Blocks currently held by more than one block table (CoW prefix)."""
        return int((self._rc >= 2).sum())

    def alloc(self, n: int) -> list[int]:
        if n > len(self._free):
            raise RuntimeError(
                f"block pool exhausted: need {n} blocks, {len(self._free)} free "
                f"of {self.num_blocks - 1} usable (size the pool with "
                "num_blocks >= n_slots * (max_len // block_size) + 1 for "
                "worst-case occupancy)"
            )
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._rc[b] = 1
        self.allocs_total += n
        self.high_water = max(self.high_water, self.in_use)
        if self.sanitizer is not None:
            self.sanitizer.note_block_event("alloc", out)
        return out

    def incref(self, blocks, n: int = 1) -> None:
        for b in blocks:
            self._rc[b] += n
        if self.sanitizer is not None:
            self.sanitizer.note_block_event("incref", blocks)

    def decref(self, blocks) -> int:
        # Always-on ledger guards (not gated on the sanitizer): a refcount
        # underflow or a zero-block free corrupts the free list, which
        # would hand the same physical block to two tenants on the next
        # admission — fail here, at the event, with provenance.
        from .sanitizer import BlockLedgerError

        freed = 0
        for b in blocks:
            if b == 0:
                raise BlockLedgerError(
                    "decref of the reserved zero block (block 0 backs every "
                    "unwritten table entry and must never be freed)"
                )
            if self._rc[b] <= 0:
                raise BlockLedgerError(
                    f"double-free of block {int(b)}: refcount is "
                    f"{int(self._rc[b])} before this decref"
                )
            self._rc[b] -= 1
            if self._rc[b] == 0:
                self._free.append(b)
                freed += 1
        self.frees_total += freed
        if self.sanitizer is not None:
            self.sanitizer.note_block_event("decref", blocks)
        return freed

    def note_cover(self, cover_events: int, allocated_blocks: int) -> None:
        """Accumulates internal-fragmentation accounting for one admission."""
        self.cover_events += int(cover_events)
        self.frag_events += int(
            allocated_blocks * self.block_size - cover_events
        )

    def reset_occupancy(self) -> None:
        """Returns every block to the free list (engine.reset()), KEEPING
        the lifetime high-water/fragmentation counters."""
        self._free = list(range(self.num_blocks - 1, 0, -1))
        self._rc[:] = 0


@struct.dataclass
class SlotState:
    """Device-resident state of every decode slot (the decode program's carry)."""

    big: EventStreamBatch  # (S, max_len, ...) content buffers
    caches: Any  # tuple[KVCache] (CI) or NAPast (NA); per-row seq lengths
    cursor: Array  # (S,) int32: events held (prompt + written)
    base_len: Array  # (S,) int32: prompt events
    budget: Array  # (S,) int32: per-row max_new_events
    n_generated: Array  # (S,) int32: REAL generated events
    done: Array  # (S,) bool: finished (or empty) slot
    live: Array  # (S,) bool: slot holds an admitted request
    keys: Array  # (S, 2) uint32: per-slot PRNG chains
    active_steps: Array  # () int32: sum over decode steps of active slots
    # Decode health sentinel (the serving analogue of PR 3's train-step
    # health vector): sticky per-tenant "non-finite logits/values detected
    # on device" flag. Set the step the fault appears — the same step also
    # quarantines the slot (done=True) so the poisoned row freezes — and
    # read by the host only through the packed boundary readback (zero new
    # transfers). Admission resets it.
    health: Array = None  # (S,) bool: non-finite detected for this tenant


@struct.dataclass
class SpecState:
    """Device-resident speculative-decoding state carried beside `SlotState`.

    ``draft_caches`` is the draft model's KV-cache pytree at the SAME
    ``max_len`` as the target's (positions must align; the draft is narrow
    in width/depth, not in sequence capacity). The counters are per-tenant:
    admission zeroes a slot's entries, so a finished request's boundary
    readback carries exactly its own proposal/acceptance totals.
    """

    draft_caches: Any  # tuple[KVCache] (CI) or NAPast (NA), draft geometry
    proposed: Array  # (S,) int32: draft events proposed for the resident
    accepted: Array  # (S,) int32: committed events taken from the draft
    rounds: Array  # () int32: spec rounds dispatched
    # NA only: the TARGET model's per-layer contextualized embedding of the
    # event PRECEDING each slot's last committed event — i.e. the history of
    # the next verify window's position 0 (the window starts AT the last
    # committed event, and the NA forward builds histories by shift-right
    # within its view, so that first position's history must be carried
    # like a KV cache). Tuple of (S, hidden) per layer; None for CI.
    history: Any = None


@struct.dataclass
class PrefillHandoff:
    """A prefill-stream admission in flight between replicas: the prefill
    forward's outputs (computed on the dedicated prefill replica) plus the
    request metadata the target decode replica's admit scatter needs.
    Everything array-valued stays on device end to end — the handoff is the
    disaggregated-serving device-to-device transfer, not a host copy."""

    requests: list = struct.field(pytree_node=False)
    group: int = struct.field(pytree_node=False)  # compiled group width
    big: Any = None  # (g, max_len, ...) prefilled content rows
    caches: Any = None  # per-row KV caches (float; target quantizes on admit)
    plen: Any = None  # (g,) true prompt lengths
    budgets: Any = None  # (g,) per-row max_new_events
    keys: Any = None  # (g, 2) post-prefill PRNG chains
    first_event_real: Any = None  # (g,) bool
    # Spec engines only (r20, spec x prefill stream): the draft model's
    # prefilled cache rows — the handoff carries the draft cache seed so
    # the decode replica's admit lands BOTH chains in one scatter — and,
    # for NA targets, the per-layer history head of each prompt's last
    # event. None on non-spec handoffs.
    draft_caches: Any = None
    draft_history: Any = None


def _as_raw_key(key) -> jnp.ndarray:
    """Normalizes a PRNG key to raw (2,) uint32 data."""
    key = jnp.asarray(key)
    if jnp.issubdtype(key.dtype, jnp.integer):
        return key.astype(jnp.uint32)
    return jax.random.key_data(key)


def derive_request_key(base_key, index: int) -> jnp.ndarray:
    """THE per-request key derivation: ``fold_in(base, index)`` as raw key
    data. Engine, service, and fleet all bind accepted request ``index``'s
    key through this one function — the bit-identity parity contract
    (engine ≡ service ≡ fleet on the same accepted set) holds *because*
    the derivation is structurally shared, not comment-enforced."""
    return _as_raw_key(jax.random.fold_in(base_key, index))


def _vmap_split(keys: Array) -> tuple[Array, Array]:
    """Per-slot ``key, step_key = jax.random.split(key)`` (generate()'s order)."""
    pairs = jax.vmap(lambda k: jax.random.split(k))(keys)
    return pairs[:, 0], pairs[:, 1]


class GenerationEngine:
    """Continuous-batching engine over one model/params/config triple.

    Args:
        model: a CI or NA generative model module.
        params: model parameters.
        config: the model configuration.
        template: any `EventStreamBatch` from the same data pipeline — fixes
            the slot rows' data-element width, static width, and dtypes.
        n_slots: decode slot count (the decode program's batch).
        max_len: slot buffer length — prompt + generated events per request
            must fit. Also the KV-cache width (see the parity contract).
        decode_chunk: decode steps per dispatch; the done-mask readback
            happens once per chunk.
        dispatch_depth: decode chunks in flight before the oldest boundary
            readback is resolved. 1 reproduces the synchronous PR-5
            schedule (issue, then resolve the same chunk's boundary —
            though the copy still starts at dispatch); 2 (the default)
            double-buffers: while the device decodes chunk N+1, the host
            resolves chunk N's boundary, harvests, and plans refills.
            Results are bitwise invariant to this knob (frozen-row
            harvests); only refill latency and waste accounting move.
        max_queue: optional bound on the host admission queue
            (`scheduler.Scheduler` ``max_pending``) — submit raises
            `AdmissionRejected` when full (reject-new backpressure).
        max_prompt_len: top prefill bucket (default ``max_len - 1``).
        min_bucket: smallest prefill bucket.
        base_key: engine PRNG key; request keys default to
            ``fold_in(base_key, admission_index)``.
        device_criteria: extra per-row `DeviceCriterion` stops (the per-row
            budget is intrinsic; `MaxLengthCriteria` composes here).
        stop_dead_rows: stop rows whose newest event is masked
            (`DeadRowCriteria`) — semantically loss-free, saves full-horizon
            decode on unpredictable rows.
        mesh: optional device mesh with a ``data`` axis; slots shard over it
            (``n_slots`` divisible by its size). Params replicate — unless
            the mesh also carries a ``model`` axis of size > 1, in which
            case they shard tensor-parallel via the training TP rules
            (`training/sharding.make_param_shardings`) and the decode /
            prefill programs compile with the per-layer TP all-reduces
            GSPMD inserts — the serve-time model parallelism that lets
            widths exceeding one chip (the bench ladder's 4096 rung)
            serve at all (docs/serving.md "The serving fleet").
        hot_swap: enables zero-downtime checkpoint promotion: the engine
            reserves a second (shadow) weight buffer — `load_shadow` puts
            a new checkpoint beside the live one through a compiled
            reshard-to-layout program, `flip` swaps the live pointer at a
            chunk boundary. `slots_report` accounts ``params_bytes × 2``
            while enabled so capacity planning never overcommits HBM
            during a swap window.
        sampling_impl: the decode sampling tail. ``None``/"auto"/"pallas"/
            "pallas_interpret"/"xla" route every categorical head through
            the fused filter+draw+merge op (`ops.fused_sampling
            .fused_categorical`; auto = Pallas kernel on TPU) — bit-exact
            vs the reference tail when ``top_k``/``top_p`` are off, so the
            ``generate()`` parity contract is preserved. ``"multi_op"``
            keeps the r07 per-op tail (the bench A/B baseline arm,
            ``sampling_fused_ab_ms``).
        top_k / top_p: optional tie-inclusive sampling filters applied to
            every categorical head by the fused tail (serving-quality
            knobs; they deliberately change the sampled distribution, so
            parity vs ``generate()`` holds only when both are ``None``).
        spec: a `serving.spec.SpecConfig` — enables **speculative decoding**:
            the draft model proposes ``spec.k`` events per slot per round
            (its own small KV cache rides beside the target's), the full
            model verifies all of them in ONE batched forward over the
            vector-length cache branch, and the accepted prefix (plus one
            correction/bonus event) commits with per-row cursor advances —
            no cache rewind copies, rejected tails just stay masked beyond
            the rolled-back per-row lengths. Sampling runs on the
            per-event-index PRNG sub-chain (``fold_in(request_key, j)``),
            so results stay bit-deterministic under placement/chunking/
            refill order and exact in distribution at any acceptance rate
            (docs/serving.md "Speculative decoding" for the contracts);
            ``greedy=True`` spec mode with zero value tolerances commits
            only the target's own greedy draws — structure/integers
            bit-identical to the greedy non-speculative engine, floats
            within the documented last-ulp fusion envelope (widening to
            the `ops.kv_quant` tolerance envelope under a quantized
            ``kv_cache_dtype``). Composes with ``top_k``/``top_p``
            filtering (the accept rule runs over the same filtered pmfs
            the draws come from), serve-time tensor parallelism, the
            quantized KV cache, and the dedicated prefill stream
            (docs/serving.md "The composition matrix"); unsupported
            beside custom ``device_criteria`` and ``paged_kv``
            (loud errors).
        greedy: deterministic decoding — every head takes its greedy
            statistic (categorical mode, Bernoulli >= 0.5, continuous
            mean) instead of sampling. The PRNG chain is untouched.
        health_sentinel: the decode health sentinel (production default
            True; docs/reliability.md "Serving failure domains"): per-slot
            non-finite logits/values are detected ON DEVICE each step and
            a health row rides the existing packed boundary readback —
            zero new host transfers, zero new collectives (statically
            gated against the uninstrumented ``engine_nohealth``
            budgets). A bad slot quarantines the step it goes bad; its
            request fails with `serving.errors.SlotHealthError` (or
            retries, below) and co-resident slots are bit-untouched.
        health_retries: per-request retry budget after a slot quarantine.
            The request re-queues at the FRONT of the scheduler with its
            ORIGINAL bound key materialized, so a successful retry is
            bit-identical to an unpoisoned run. 0 (default) fails loudly
            on the first quarantine.
        validate_prompts: reject prompts carrying non-finite observed
            values/times/start times at `submit` with a typed
            `MalformedPromptRejected` (counted in ``padding_report``) —
            before an admission index binds, so a dirty request can never
            poison a slot or perturb the admitted set's keys.
        kv_cache_dtype: the decode KV-cache element type. ``None`` keeps
            the model compute dtype (the parity-exact default); ``"bf16"``
            / ``"fp32"`` pin a float width; ``"int8"`` (and ``"fp8"``
            where the jaxlib carries ``float8_e4m3fn``) store quantized
            K/V planes with per-head-per-row fp32 scale tables —
            quantize-on-admission + quantize-on-write at the decode
            cursor, dequantized on read inside the attention contraction
            (`ops.kv_quant`; docs/serving.md "Quantized decode cache" for
            the tolerance contract and the slots-per-chip math).
    """

    def __init__(
        self,
        model,
        params,
        config: StructuredTransformerConfig,
        *,
        template: EventStreamBatch,
        n_slots: int,
        max_len: int,
        decode_chunk: int = 8,
        dispatch_depth: int = 2,
        max_queue: Optional[int] = None,
        max_prompt_len: int | None = None,
        min_bucket: int = 8,
        base_key: Optional[jax.Array] = None,
        device_criteria: Sequence[DeviceCriterion] = (),
        stop_dead_rows: bool = True,
        mesh: Optional[Mesh] = None,
        hot_swap: bool = False,
        sampling_impl: str | None = None,
        top_k: int | None = None,
        top_p: float | None = None,
        kv_cache_dtype: str | None = None,
        paged_kv: bool = False,
        block_size: int = 16,
        num_blocks: int | None = None,
        spec: Optional[SpecConfig] = None,
        greedy: bool = False,
        health_sentinel: bool = True,
        health_retries: int = 0,
        validate_prompts: bool = True,
    ):
        if config.uses_layer_kinds:
            from ..models.transformer import NO_DECODE_STATE

            raise NotImplementedError(NO_DECODE_STATE)
        self.model = model
        self.params = params
        self.config = config
        self.greedy = bool(greedy)
        # Decode health sentinel (docs/reliability.md "Serving failure
        # domains"): per-slot non-finite detection computed inside the
        # decode/verify programs and read back on the existing packed
        # boundary (zero new host transfers, zero new collectives — the
        # detection is row-local elementwise work, statically gated like
        # PR 3's pretrain:dp8_health). A bad slot quarantines on device the
        # step it goes bad; its request fails with a typed `SlotHealthError`
        # or — with health_retries > 0 — is re-queued and re-prefilled from
        # its bound key (bit-deterministic: the key was fixed at accept).
        self.health_sentinel = bool(health_sentinel)
        self.health_retries = int(health_retries)
        self.validate_prompts = bool(validate_prompts)
        # Fault-injection scope (reliability/serving_faults.py): the fleet
        # stamps each service's engines with the service id; None = only
        # scope-less faults match. Plain host metadata, never traced.
        self.fault_scope: Optional[str] = None
        self._health_quarantined = 0
        self._health_failed = 0
        self._health_retried = 0
        self.n_slots = int(n_slots)
        self.max_len = int(max_len)
        self.decode_chunk = int(decode_chunk)
        self.dispatch_depth = int(dispatch_depth)
        if self.dispatch_depth < 1:
            raise ValueError("dispatch_depth must be >= 1")
        self.max_prompt_len = int(max_prompt_len or (max_len - 1))
        if self.max_prompt_len >= self.max_len:
            raise ValueError("max_prompt_len must leave room to generate (< max_len)")
        self.device_criteria = tuple(device_criteria)
        self.stop_dead_rows = bool(stop_dead_rows)
        self.mesh = mesh
        if mesh is not None:
            if "data" not in mesh.shape:
                raise ValueError(
                    f"engine slots shard over a 'data' mesh axis; mesh has {tuple(mesh.axis_names)}"
                )
            if self.n_slots % int(mesh.shape["data"]) != 0:
                raise ValueError(
                    f"n_slots ({self.n_slots}) must divide over the mesh 'data' axis "
                    f"({int(mesh.shape['data'])})"
                )
            extra_axes = set(mesh.axis_names) - {"data", "model"}
            if extra_axes:
                raise ValueError(
                    f"serving meshes carry 'data' (slots) and optionally 'model' "
                    f"(tensor-parallel params) axes only — an '{sorted(extra_axes)[0]}' "
                    "axis would gather weights into every decode chunk; build the "
                    "serve mesh with make_mesh(n_data, n_model)"
                )
        # Serve-time tensor parallelism: a model axis of size > 1 shards the
        # params with the training TP rules; GSPMD inserts the per-layer
        # all-reduces into the decode/prefill compiles.
        self.tensor_parallel = mesh is not None and int(mesh.shape.get("model", 1)) > 1
        if base_key is None:
            base_key = jax.random.PRNGKey(0)
        self._base_key = _as_raw_key(base_key)

        # Decode sampling tail: fused filter+draw+merge by default (bit-
        # exact vs the multi-op reference when unfiltered), "multi_op" for
        # the r07 baseline arm.
        self.sampling_impl = sampling_impl
        self.top_k = None if top_k is None else int(top_k)
        self.top_p = None if top_p is None else float(top_p)
        if sampling_impl == "multi_op":
            if self.top_k is not None or self.top_p is not None:
                raise ValueError(
                    "top_k/top_p filtering requires the fused sampling tail; "
                    "drop sampling_impl='multi_op'"
                )
            self._categorical_sampler = None
            self.sampling_impl_resolved = "multi_op"
            self._shard_sampling = False
        else:
            from ..ops.fused_sampling import fused_categorical
            from ..ops.impl_select import resolve_impl

            impl = sampling_impl
            if impl in (None, "auto") and self.tensor_parallel:
                # Tensor-parallel meshes keep the fused-XLA tail: GSPMD may
                # leave the head logits' vocab axis sharded over `model`,
                # and a slot-axis shard_map over that layout would gather
                # the plane. Pure-data meshes no longer degrade — see the
                # shard_map routing below (r20, retiring the r09 mesh rule).
                impl = "xla"
            # Resolve eagerly (freezing the env/backend choice at engine
            # construction) so stats()/bench can report WHICH tail actually
            # runs — "fused_auto" would hide the TP degrade above.
            impl = resolve_impl(impl, "fused_categorical")
            # r20: on multi-device data meshes the kernel's grid runs UNDER
            # `shard_map` over the slot ('data') axis — each device sweeps
            # its own (n_slots/dp, V) logits shard, so no gather ever
            # reaches the decode hot loop (pinned by the
            # engine_sampling_shard_dp8 collective budget). This retires
            # the r09 "fall back to fused-XLA on any mesh" rule.
            self._shard_sampling = (
                impl in ("pallas", "pallas_interpret")
                and mesh is not None
                and int(mesh.shape["data"]) > 1
            )
            self.sampling_impl_resolved = f"fused_{impl}"
            self._categorical_sampler = functools.partial(
                fused_categorical,
                top_k=self.top_k,
                top_p=self.top_p,
                impl=impl,
            )

        # Decode KV-cache element type (seq caches only — the NA dep-graph
        # caches are a few positions wide and stay in the compute dtype).
        from ..ops.kv_quant import resolve_cache_dtype

        self.kv_cache_dtype = kv_cache_dtype
        self._kv_buf_dtype, self._kv_quantized = resolve_cache_dtype(
            kv_cache_dtype, config.compute_dtype
        )

        mode = config.structured_event_processing_mode
        self._is_na = mode == StructuredEventProcessingMode.NESTED_ATTENTION
        self._measurements_to_fill_list = (
            [{"time"}, *config.measurements_per_dep_graph_level[1:]] if self._is_na else None
        )

        # Speculative decoding (serving/spec.py): the draft model lives
        # beside the target the way hot-swap shadows do — a second weight
        # tree plus per-slot draft caches, replicated on serving meshes.
        self.spec = spec
        self.draft_params = None
        if spec is not None:
            spec.validate_against(config)
            if self.device_criteria:
                raise ValueError(
                    "speculative decoding supports the built-in per-row stops "
                    "(budget, dead rows, max length via budget) only; custom "
                    "device_criteria cannot be re-evaluated per committed "
                    "prefix inside the verify program"
                )
            # r20 composition closure: top_k/top_p filtering (the accept
            # rule runs over the filtered-and-renormalized pmfs —
            # spec.spec_accept_level "Filtered pmfs"), serve-time tensor
            # parallelism (verify/draft programs pin out_shardings to the
            # input layout like the baseline decode), and quantized KV
            # caches (draft AND target quantize-on-write; the greedy
            # bit-identity contract relaxes to the r09 kv_quant envelope
            # on floats, structure/integers still exact) now compose here
            # instead of raising.
            self.draft_params = spec.params
            if self._is_na and getattr(config, "scan_layers", False):
                raise ValueError(
                    "NA speculative decoding requires the unrolled layer stack "
                    "(the verify pass threads per-layer history heads); migrate "
                    "the checkpoint with unstack_layer_params"
                )
            if self._is_na:
                # Static measurement-index -> dep-graph-level map (THE
                # shared builder — the input layer's partial-content slots,
                # the correction-event strip, and the draft-prefill walk
                # replay must agree bit-for-bit): used to strip rejected
                # levels' stale draft elements before re-filling
                # (update_last_event_data keeps existing elements by
                # design). Raises loudly on split-mode levels.
                self._na_level_of_meas = na_level_of_measurement(config)

        # Paged copy-on-write KV cache: the per-slot monolithic seq caches
        # become one refcounted block pool + per-slot block tables, making
        # shared prefixes (fork()) free. Composition matrix (docs/serving.md
        # "Paged KV cache and branched rollouts"): kvq composes (the scale
        # tables page alongside the planes); spec / tensor-parallel / NA /
        # the dedicated prefill stream do not yet — each is a loud error.
        self.paged_kv = bool(paged_kv)
        self.block_size = int(block_size)
        self._block_alloc: Optional[BlockAllocator] = None
        self._tables: Optional[np.ndarray] = None
        self._paged_num_blocks = 0
        self._next_fork_group = 0
        if self.paged_kv:
            if self._is_na:
                raise ValueError(
                    "paged KV cache does not support nested-attention models "
                    "yet: the dep-graph caches reset per event and do not "
                    "page; run NA engines with paged_kv=False"
                )
            if spec is not None:
                raise ValueError(
                    "paged KV cache does not compose with speculative decoding "
                    "yet: the verify window re-reads freshly written positions "
                    "through the draft/target cache pair, which still admits "
                    "monolithically (tracked as ROADMAP item 3, composition "
                    "closure — the paged x spec cell; issue #21). Nearest "
                    "supported configurations: spec with monolithic caches "
                    "(kv_cache_dtype='int8' composes, r20), or paged_kv "
                    "without spec (fork() branched rollouts)"
                )
            if self.tensor_parallel:
                raise ValueError(
                    "paged KV cache on tensor-parallel serve meshes is not "
                    "supported: the block pool replicates over the mesh, which "
                    "would defeat the model-axis KV sharding (tracked as "
                    "ROADMAP item 3, composition closure — the paged x TP "
                    "cell; issue #21). Nearest supported configurations: "
                    "monolithic caches with TP (spec x int8 x TP composes, "
                    "r20), or paged_kv on a pure-'data' mesh"
                )
            if self.block_size < 1 or self.max_len % self.block_size != 0:
                raise ValueError(
                    f"block_size ({self.block_size}) must divide max_len "
                    f"({self.max_len}) — block tables cover the slot width "
                    "exactly"
                )
            blocks_per_slot = self.max_len // self.block_size
            if num_blocks is None:
                # Worst case: every slot holds a full table, + the zero block.
                num_blocks = self.n_slots * blocks_per_slot + 1
            num_blocks = int(num_blocks)
            if num_blocks < blocks_per_slot + 1:
                raise ValueError(
                    f"num_blocks ({num_blocks}) must fit at least one full "
                    f"slot table ({blocks_per_slot}) plus the zero block"
                )
            if num_blocks == self.n_slots:
                # `_tree_shardings` replicates any leaf whose leading dim is
                # not n_slots; a pool that HAPPENS to match n_slots would be
                # row-sharded by accident. One spare block breaks the tie.
                num_blocks += 1
            self._paged_num_blocks = num_blocks
            self._block_alloc = BlockAllocator(num_blocks, self.block_size)
            # Host mirror of the device block tables (0 = zero block): block
            # planning, deferred freeing, and slots_report sharing stats all
            # read this — the device tables are never copied back.
            self._tables = np.zeros((self.n_slots, blocks_per_slot), np.int32)
        elif num_blocks is not None:
            raise ValueError("num_blocks requires paged_kv=True")

        self.scheduler = Scheduler(
            self.n_slots,
            make_buckets(min_bucket, self.max_prompt_len),
            max_pending=max_queue,
        )
        if self.paged_kv:
            self.scheduler.block_pool_stats = self._block_pool_stats

        self._template = self._normalize_prompt(template)
        self._state = self._init_state()
        self._spec_state = self._init_spec_state() if spec is not None else None
        self._param_shardings = None
        if mesh is not None:
            self._state = jax.device_put(self._state, self._state_shardings())
            if self._spec_state is not None:
                self._spec_state = jax.device_put(
                    self._spec_state, self._tree_shardings(self._spec_state)
                )
                self.draft_params = jax.device_put(
                    self.draft_params,
                    jax.tree_util.tree_map(
                        lambda _: NamedSharding(mesh, P()), self.draft_params
                    ),
                )
            if self.tensor_parallel:
                from ..training.sharding import make_param_shardings

                # strict: a model axis whose rules shard (almost) nothing is
                # an HBM budget lie at serve time — the engine exists to host
                # widths past one chip, so a layout that replicates the big
                # tables must fail HERE (per-replica, fast, with the leaf
                # report) rather than OOM on the first admit. verbose=False
                # only mutes the small-leaf warnings a fleet would print once
                # per replica; strict errors still raise.
                self._param_shardings = make_param_shardings(
                    params, mesh, strict=True, verbose=False
                )
            else:
                self._param_shardings = jax.tree_util.tree_map(
                    lambda _: NamedSharding(mesh, P()), params
                )
            self.params = jax.device_put(params, self._param_shardings)

        # Hot-swap double buffering: a second (shadow) weight buffer the
        # fleet loads the next checkpoint into while this one serves; `flip`
        # swaps the live pointer at a drained chunk boundary. Spec engines
        # double-buffer the DRAFT weights too — promotion must swap draft
        # and target atomically or the accept rule would score one
        # checkpoint's proposals with the other's densities.
        self.hot_swap = bool(hot_swap)
        self._shadow_params = None
        self._shadow_draft_params = None
        self._swap_reshard_memo = None
        self._swap_draft_reshard_memo = None
        self.weights_version = 0

        # Optional ControlPlaneSanitizer (serving.sanitizer): attach with
        # `attach_sanitizer(engine)` for debug/model-check oracles; every
        # hook is an `is not None` no-op when detached.
        self.sanitizer = None

        # Tensor-parallel layouts pin the output state to the input layout:
        # without the pin GSPMD propagation reshards small replicated state
        # leaves over `model`, silently dropping their donation (the Tier C
        # donation audit's dp4_tp2 finding, reproduced verbatim on the TP
        # engine) and forcing a reshard per dispatch.
        self._state_out_shardings = (
            self._state_shardings() if self.tensor_parallel else None
        )
        # Compiled-program memos: decode is ONE program; prefill one per
        # (bucket, group), extract one per group width. Spec mode replaces
        # the decode program with the draft-chunk + verify pair (one round
        # = one dispatch of each; ISSUE 13's `engine_spec:draft_chunk` /
        # `engine_spec:verify` census programs).
        self._decode_jit = self._model_jit(
            self._decode_chunk_na if self._is_na else self._decode_chunk_ci,
            donate_argnums=(1,),
            out_shardings=self._state_out_shardings,
        )
        if spec is not None:
            draft_fn = (
                self._spec_draft_chunk_na if self._is_na else self._spec_draft_chunk_ci
            )
            verify_fn = self._spec_verify_na if self._is_na else self._spec_verify_ci
            spec_draft_out = spec_verify_out = None
            if self.tensor_parallel:
                # Same Tier C donation-drop fix as the baseline decode: pin
                # the output state (and the proposal buffers, whose slot
                # plane rides axis 1) to the input layout so GSPMD cannot
                # reshard small replicated leaves over `model` and silently
                # drop their donation.
                st_sh = self._state_out_shardings
                sp_sh = self._tree_shardings(self._spec_state)
                _, _, prop_shape = jax.eval_shape(
                    draft_fn, self.draft_params, self._state, self._spec_state
                )
                prop_sh = jax.tree_util.tree_map(
                    self._spec_proposal_sharding, prop_shape
                )
                spec_draft_out = (st_sh, sp_sh, prop_sh)
                spec_verify_out = (st_sh, sp_sh)
            self._spec_draft_jit = self._model_jit(
                draft_fn, donate_argnums=(1, 2), out_shardings=spec_draft_out
            )
            # The proposal buffers (arg 3) are consumed here but alias no
            # output shape, so donating them would be a no-op the Tier C
            # donation audit rightly flags; they die after the call either
            # way.
            self._spec_verify_jit = self._model_jit(
                verify_fn, donate_argnums=(1, 2), out_shardings=spec_verify_out
            )
        self._prefill_jits: dict[tuple[int, int], Any] = {}
        self._prefill_fork_fwd_jits: dict[int, Any] = {}
        self._prefill_fork_admit_jits: dict[int, Any] = {}
        self._prefill_spec_jits: dict[tuple[int, int], Any] = {}
        # Prefill-stream split programs: the bucketed prefill forward with no
        # slot scatter (runs on a dedicated prefill replica) and the admit
        # scatter alone (runs on the decode replica receiving the handoff).
        self._prefill_compute_jits: dict[tuple[int, int], Any] = {}
        self._admit_jits: dict[int, Any] = {}
        # Spec flavors of the split pair: the compute half adds the draft
        # model's prompt forward (the handoff's draft cache seed), the
        # admit half lands both chains in one program (r20).
        self._prefill_compute_spec_jits: dict[tuple[int, int], Any] = {}
        self._admit_spec_jits: dict[int, Any] = {}
        self._extract_jits: dict[int, Any] = {}
        # Packs done/cursor/base_len/n_generated (+ the health row) into ONE
        # (5, n_slots) array so the boundary readback is a single async host
        # copy. Spec engines pack (7, n_slots): the per-tenant proposed/
        # accepted counters ride the same copy, so per-request acceptance
        # accounting costs zero extra transfers. The health row rides the
        # SAME pack — the sentinel adds zero host transfers by construction.
        health_rows = (
            [lambda st: st.health.astype(jnp.int32)] if self.health_sentinel else []
        )
        if spec is None:
            base_rows = [
                lambda st: st.done.astype(jnp.int32),
                lambda st: st.cursor,
                lambda st: st.base_len,
                lambda st: st.n_generated,
            ]
            rows = base_rows + health_rows
            self._pack_boundary_jit = jax.jit(
                lambda st: jnp.stack([r(st) for r in rows])
            )
            self._boundary_health_row = 4 if self.health_sentinel else None
        else:
            base_rows2 = [
                lambda st, sp: st.done.astype(jnp.int32),
                lambda st, sp: st.cursor,
                lambda st, sp: st.base_len,
                lambda st, sp: st.n_generated,
                lambda st, sp: sp.proposed,
                lambda st, sp: sp.accepted,
            ]
            rows2 = base_rows2 + (
                [lambda st, sp: st.health.astype(jnp.int32)]
                if self.health_sentinel
                else []
            )
            self._pack_boundary_jit = jax.jit(
                lambda st, sp: jnp.stack([r(st, sp) for r in rows2])
            )
            self._boundary_health_row = 6 if self.health_sentinel else None

        # Host-side slot table: slot -> Request or None. `live`/`done` on
        # device gate compute; occupancy/harvest bookkeeping lives here.
        # `_slot_epoch[s]` is the value of `_dispatched_chunks` when slot
        # s's current request was admitted: a boundary packed at chunk
        # index c reflects that admission iff epoch < c (the prefill was
        # enqueued before chunk c) — the guard that makes stale-boundary
        # harvests safe under pipelined dispatch.
        self._table: list[Optional[Request]] = [None] * self.n_slots
        self._slot_epoch: list[int] = [0] * self.n_slots
        self._dispatched_chunks = 0
        self._resolved_chunks = 0
        self._inflight: deque[tuple[int, Any]] = deque()

    # ------------------------------------------------------------ state init
    def _normalize_prompt(self, batch: EventStreamBatch) -> EventStreamBatch:
        updates = {
            f.name: None
            for f in batch.__dataclass_fields__.values()
            if f.name not in _CORE_FIELDS
        }
        out = batch.replace(**updates)
        for f in ("event_mask", "time_delta", "dynamic_indices"):
            if getattr(out, f) is None:
                raise ValueError(f"Engine prompts need `{f}`")
        if out.start_time is None:
            out = out.replace(
                start_time=jnp.zeros((out.batch_size,), jnp.float32)
            )
        return out

    def _init_state(self) -> SlotState:
        S, L, t = self.n_slots, self.max_len, self._template

        def rows(x, seq_axis):
            if x is None:
                return None
            shape = (S, L) + x.shape[2:] if seq_axis else (S,) + x.shape[1:]
            return jnp.zeros(shape, jnp.asarray(x).dtype)

        big = EventStreamBatch(
            event_mask=jnp.zeros((S, L), bool),
            time_delta=rows(t.time_delta, True),
            static_indices=rows(t.static_indices, False),
            static_measurement_indices=rows(t.static_measurement_indices, False),
            dynamic_indices=rows(t.dynamic_indices, True),
            dynamic_measurement_indices=rows(t.dynamic_measurement_indices, True),
            dynamic_values=rows(t.dynamic_values, True),
            dynamic_values_mask=rows(t.dynamic_values_mask, True),
            start_time=rows(t.start_time, False),
        )
        if self.paged_kv:
            seq_caches = tuple(
                init_paged_kv_caches(
                    self.config,
                    S,
                    self._paged_num_blocks,
                    self.block_size,
                    max_len=L,
                    cache_dtype=self.kv_cache_dtype,
                )
            )
        else:
            seq_caches = tuple(
                kv.replace(length=jnp.zeros((S,), jnp.int32))
                for kv in init_kv_caches(
                    self.config, S, max_len=L, cache_dtype=self.kv_cache_dtype
                )
            )
        if self._is_na:
            n_levels = len(self._measurements_to_fill_list)
            max_dep_len = len(self.config.measurements_per_dep_graph_level) + 1
            dep = tuple(
                KVCache.init(
                    S,
                    self.config.num_attention_heads,
                    max_dep_len,
                    self.config.head_dim,
                    dtype=self.config.compute_dtype,
                ).replace(length=jnp.asarray(n_levels, jnp.int32))
                for _ in range(self.config.num_hidden_layers)
            )
            caches = NAPast(seq_past=seq_caches, dep_graph_past=dep)
        else:
            caches = seq_caches
        # Distinct buffers per field: donation rejects aliased arguments.
        return SlotState(
            big=big,
            caches=caches,
            cursor=jnp.ones((S,), jnp.int32),
            base_len=jnp.ones((S,), jnp.int32),
            budget=jnp.zeros((S,), jnp.int32),
            n_generated=jnp.zeros((S,), jnp.int32),
            done=jnp.ones((S,), bool),
            live=jnp.zeros((S,), bool),
            keys=jnp.zeros((S, 2), jnp.uint32),
            active_steps=jnp.zeros((), jnp.int32),
            health=jnp.zeros((S,), bool),
        )

    def _init_spec_state(self) -> SpecState:
        """Preallocates the draft model's per-slot caches + spec counters.

        The draft caches share the target's ``max_len`` (positions must
        align between the two chains) at the draft's own width/depth — the
        capacity cost `slots_report` accounts per slot. They also share the
        target's ``kv_cache_dtype``: under a quantized cache the draft
        quantizes on write/admission through the exact same branches the
        target does (the scale tables ride beside the planes), which is
        what makes the spec x int8 slots-per-chip math compose.
        """
        S, L = self.n_slots, self.max_len
        dcfg = self.spec.config
        seq = tuple(
            kv.replace(length=jnp.zeros((S,), jnp.int32))
            for kv in init_kv_caches(
                dcfg, S, max_len=L, cache_dtype=self.kv_cache_dtype
            )
        )
        if self._is_na:
            n_levels = len(self._measurements_to_fill_list)
            max_dep_len = len(dcfg.measurements_per_dep_graph_level) + 1
            dep = tuple(
                KVCache.init(
                    S,
                    dcfg.num_attention_heads,
                    max_dep_len,
                    dcfg.head_dim,
                    dtype=dcfg.compute_dtype,
                ).replace(length=jnp.asarray(n_levels, jnp.int32))
                for _ in range(dcfg.num_hidden_layers)
            )
            caches = NAPast(seq_past=seq, dep_graph_past=dep)
        else:
            caches = seq
        history = None
        if self._is_na:
            history = tuple(
                jnp.zeros((S, self.config.hidden_size), self.config.compute_dtype)
                for _ in range(self.config.num_hidden_layers)
            )
        return SpecState(
            draft_caches=caches,
            proposed=jnp.zeros((S,), jnp.int32),
            accepted=jnp.zeros((S,), jnp.int32),
            rounds=jnp.zeros((), jnp.int32),
            history=history,
        )

    def _model_jit(self, fn, **jit_kwargs):
        """``jax.jit(fn)`` for a program that runs the model, traced inside
        `parallel.kernel_mesh(self.mesh)` as the trainers' steps are: GSPMD
        cannot partition a Mosaic call, so on a serving mesh the model's
        kernels (the embedding's plane) run once per slot shard
        (`parallel.per_batch_shard`). With no mesh it is `jax.jit`."""
        mesh = self.mesh

        def traced(*args):
            with kernel_mesh(mesh):
                return fn(*args)

        # The compiled program keeps the method's name (a trace shows it).
        traced.__name__ = getattr(fn, "func", fn).__name__
        return jax.jit(traced, **jit_kwargs)

    def _tree_shardings(self, tree):
        mesh = self.mesh

        def spec(x):
            if getattr(x, "ndim", 0) >= 1 and x.shape[0] == self.n_slots:
                return NamedSharding(mesh, P("data", *([None] * (x.ndim - 1))))
            return NamedSharding(mesh, P())

        return jax.tree_util.tree_map(spec, tree)

    def _state_shardings(self):
        return self._tree_shardings(self._state)

    def _spec_proposal_sharding(self, x):
        """Sharding for one stacked proposal leaf: the draft chunk stacks
        K per-event leaves, so the slot plane is axis 1 — ``(K, S, ...)``
        shards over ('data',) on axis 1; anything else replicates."""
        mesh = self.mesh
        if getattr(x, "ndim", 0) >= 2 and x.shape[1] == self.n_slots:
            return NamedSharding(mesh, P(None, "data", *([None] * (x.ndim - 2))))
        if getattr(x, "ndim", 0) >= 1 and x.shape[0] == self.n_slots:
            return NamedSharding(mesh, P("data", *([None] * (x.ndim - 1))))
        return NamedSharding(mesh, P())

    # --------------------------------------------------------- device pieces
    def _shard_rows(self, fn, *args):
        """Runs a row-vmapped sampling call under `shard_map` over the slot
        ('data') mesh axis when the sharded kernel tail is active
        (``_shard_sampling``).

        Each device then sweeps only its own ``(n_slots/dp, V)`` logits
        shard — the Pallas grid never crosses the mesh axis, so SPMD
        inserts no logits-plane gather into the decode hot loop (the r20
        rule retiring the r09 "fall back to fused-XLA on any mesh"
        fallback; pinned by the ``engine_sampling_shard_dp8`` collective
        budget). Calls whose rows are not the slot plane (prefill groups,
        replicated planes) skip the wrap and run replicated, exactly as
        before.
        """
        if not self._shard_sampling:
            return fn(*args)
        S = self.n_slots

        def _rowwise(x):
            return getattr(x, "ndim", 0) >= 1 and x.shape[0] == S

        in_leaves = jax.tree_util.tree_leaves(args)
        if not in_leaves or not all(_rowwise(x) for x in in_leaves):
            return fn(*args)
        out_shape = jax.eval_shape(fn, *args)
        if not all(_rowwise(x) for x in jax.tree_util.tree_leaves(out_shape)):
            return fn(*args)
        row_spec = lambda x: P("data", *([None] * (x.ndim - 1)))  # noqa: E731
        wrapped = jax.shard_map(
            fn,
            mesh=self.mesh,
            in_specs=jax.tree_util.tree_map(row_spec, args),
            out_specs=jax.tree_util.tree_map(row_spec, out_shape),
            check_vma=False,
        )
        return wrapped(*args)

    def _sample_rows(self, preds_last, em_last, step_keys, active=None):
        """Per-slot sampling with per-slot keys: each row draws exactly what a
        B=1 ``generate()`` with that key would (vmapped `sample_predictions`).

        With the fused tail (the default), every categorical head runs as
        one filter+gumbel+argmax pass (`ops.fused_sampling`) and, on decode
        steps, the per-slot ``where(active)`` freeze rides the same scope
        (inactive slots draw ``fill`` without touching results — their rows
        are frozen by the step's merges regardless). Bit-exact vs the
        multi-op tail when ``top_k``/``top_p`` are off.
        """
        base = self._categorical_sampler
        greedy = self.greedy
        if base is None or greedy:
            row = lambda p, e, k: sample_predictions(  # noqa: E731
                p, e, k, categorical_sampler=None if greedy else base, greedy=greedy
            )
            return jax.vmap(row)(preds_last, em_last, step_keys)
        if active is None:
            row = lambda p, e, k: sample_predictions(  # noqa: E731
                p, e, k, categorical_sampler=base
            )
            return self._shard_rows(jax.vmap(row), preds_last, em_last, step_keys)

        def row_active(p, e, k, a):
            sampler = functools.partial(base, active=a)
            return sample_predictions(p, e, k, categorical_sampler=sampler)

        return self._shard_rows(
            jax.vmap(row_active), preds_last, em_last, step_keys, active
        )

    def _draw_rows(self, preds_last, keys):
        """Per-row raw named-head draws (`sample_head_draws`) — the spec
        paths' sampling primitive: draft proposals, verify target draws,
        and the correction walk all come through here, so the coupling
        (same keys, same sampler family) is structural."""
        base = self._categorical_sampler
        greedy = self.greedy
        row = lambda p, k: sample_head_draws(  # noqa: E731
            p, k, categorical_sampler=None if greedy else base, greedy=greedy
        )
        if greedy or base is None:
            return jax.vmap(row)(preds_last, keys)
        return self._shard_rows(jax.vmap(row), preds_last, keys)

    def _row_done(self, big, cursor, base_len, n_generated, budget):
        done = (cursor - base_len) >= budget
        if self.stop_dead_rows:
            done = done | DeadRowCriteria().row_done(
                big=big, cursor=cursor, base_len=base_len
            )
        for crit in self.device_criteria:
            done = done | crit.row_done(
                big=big,
                cursor=cursor,
                base_len=base_len,
                n_generated=n_generated,
                budget=budget,
            )
        return done

    @staticmethod
    def _merge_rows(active, new, old):
        """where(active) over every row-major leaf; done/empty slots freeze."""

        def f(n, o):
            m = active.reshape((active.shape[0],) + (1,) * (n.ndim - 1))
            return jnp.where(m, n, o)

        return jax.tree_util.tree_map(f, new, old)

    def _rows_nonfinite(self, *trees) -> Array:
        """Per-slot any-non-finite over the float leaves of row-major
        pytrees (the health sentinel's detector). Row-local elementwise
        work + a per-row reduce: no cross-slot ops, so the instrumented
        decode program carries a collective inventory byte-identical to
        the uninstrumented one (statically gated, the PR 3 contract)."""
        bad = jnp.zeros((self.n_slots,), bool)
        for tree in trees:
            for leaf in jax.tree_util.tree_leaves(tree):
                if not (
                    hasattr(leaf, "dtype")
                    and jnp.issubdtype(leaf.dtype, jnp.floating)
                ):
                    continue
                if getattr(leaf, "ndim", 0) < 1 or leaf.shape[0] != self.n_slots:
                    continue
                bad = bad | ~jnp.isfinite(leaf.reshape(self.n_slots, -1)).all(axis=1)
        return bad

    def _apply_health(self, st: SlotState, active, bad, done, health) -> tuple:
        """Folds a step's detection into (done, health): a bad slot
        quarantines (its row freezes under the next step's where(active)
        merges) and its sticky health bit rides the boundary pack. With an
        all-finite step ``bad`` is all-False and both outputs equal their
        inputs bitwise — co-residents of a quarantined slot, and every slot
        of a clean run, are untouched (pinned by test)."""
        hit = active & bad
        return done | hit, health | hit

    def _merge_caches(self, active, new, old):
        if self.paged_kv:
            # Pool planes take NEW unconditionally: inactive rows' decode
            # writes land in their own exclusively held blocks at frozen
            # cursors (the allocator defers freeing until re-admission), so
            # the bytes they touch are never read by a live row — and the
            # attention softmax zeroes masked weights exactly (MASK_VALUE
            # underflows exp in fp32), so even the written bytes cannot
            # reach any output. Per-row state merges with where(active).
            return tuple(
                PagedKVCache(
                    pool_key=n.pool_key,
                    pool_value=n.pool_value,
                    block_table=jnp.where(
                        active[:, None], n.block_table, o.block_table
                    ),
                    mask=jnp.where(active[:, None], n.mask, o.mask),
                    length=jnp.where(active, n.length, o.length),
                    pool_key_scale=n.pool_key_scale,
                    pool_value_scale=n.pool_value_scale,
                )
                for n, o in zip(new, old)
            )
        if self._is_na:
            seq = self._merge_rows(active, new.seq_past, old.seq_past)
            # Dep-graph caches advance in lockstep (reset every event, shared
            # scalar phase); done slots' rows carry inert junk that the next
            # admission's prefill overwrites, so no merge is needed — merging
            # would desync their rows from the shared scalar length.
            return NAPast(seq_past=seq, dep_graph_past=new.dep_graph_past)
        return self._merge_rows(active, new, old)

    # CI decode: one event per slot per step, scanned decode_chunk times.
    def _decode_step_ci(self, params, st: SlotState) -> SlotState:
        config = self.config
        active = st.live & ~st.done
        new_keys, step_keys = _vmap_split(st.keys)
        view = _trim_to_event(st.big, st.cursor - 1)
        out = self.model.apply(
            params, view, past=st.caches, use_cache=True, is_generation=True
        )
        preds_last = _slice_preds_at(out.preds, jnp.asarray(0))
        em_last = take_event(st.big.event_mask, st.cursor - 1)
        sample = self._sample_rows(preds_last, em_last, step_keys, active=active)
        big2 = append_new_event(st.big, sample, config, st.cursor)
        big2 = update_last_event_data(big2, sample, config, st.cursor + 1)

        big = self._merge_rows(active, big2, st.big)
        caches = self._merge_caches(active, out.past_key_values, st.caches)
        cursor = jnp.where(active, st.cursor + 1, st.cursor)
        n_generated = st.n_generated + (active & sample.event_mask)
        keys = jnp.where(active[:, None], new_keys, st.keys)
        done = st.done | (
            active
            & self._row_done(big, cursor, st.base_len, n_generated, st.budget)
        )
        health = st.health
        if self.health_sentinel:
            done, health = self._apply_health(
                st, active, self._rows_nonfinite(preds_last, sample), done, health
            )
        return st.replace(
            big=big,
            caches=caches,
            cursor=cursor,
            n_generated=n_generated,
            keys=keys,
            done=done,
            health=health,
            active_steps=st.active_steps + active.sum(),
        )

    def _decode_chunk_ci(self, params, state: SlotState) -> SlotState:
        def body(st, _):
            return self._decode_step_ci(params, st), None

        state, _ = jax.lax.scan(body, state, None, length=self.decode_chunk)
        return state

    # NA decode: the full per-event dependency-graph level walk per step.
    def _decode_step_na(self, params, st: SlotState) -> SlotState:
        config = self.config
        n_levels = len(self._measurements_to_fill_list)
        active = st.live & ~st.done

        keys, step_keys = _vmap_split(st.keys)
        view = _trim_to_event(st.big, st.cursor - 1)
        out = self.model.apply(
            params,
            view,
            past=st.caches,
            use_cache=True,
            is_generation=True,
            dep_graph_el_generation_target=0,
        )
        preds_last = _slice_preds_at(out.preds, jnp.asarray(0))
        em_last = take_event(st.big.event_mask, st.cursor - 1)
        sample = self._sample_rows(preds_last, em_last, step_keys, active=active)
        big = append_new_event(st.big, sample, config, st.cursor)
        n_generated = st.n_generated + (active & sample.event_mask)
        past = out.past_key_values
        bad = (
            self._rows_nonfinite(preds_last, sample)
            if self.health_sentinel
            else None
        )

        for level in range(1, n_levels):
            keys, step_keys = _vmap_split(keys)
            view = _trim_to_event(big, st.cursor)
            out = self.model.apply(
                params,
                view,
                past=past,
                use_cache=True,
                is_generation=True,
                dep_graph_el_generation_target=level,
            )
            past = out.past_key_values
            preds_last = _slice_preds_at(out.preds, jnp.asarray(0))
            em_last = take_event(big.event_mask, st.cursor)
            sample = self._sample_rows(preds_last, em_last, step_keys, active=active)
            if bad is not None:
                bad = bad | self._rows_nonfinite(preds_last, sample)
            big = update_last_event_data(
                big,
                sample,
                config,
                st.cursor + 1,
                measurements_to_fill=set(
                    tuple(sorted(self._measurements_to_fill_list[level], key=str))
                ),
            )

        big = self._merge_rows(active, big, st.big)
        caches = self._merge_caches(active, past, st.caches)
        cursor = jnp.where(active, st.cursor + 1, st.cursor)
        keys = jnp.where(active[:, None], keys, st.keys)
        done = st.done | (
            active
            & self._row_done(big, cursor, st.base_len, n_generated, st.budget)
        )
        health = st.health
        if bad is not None:
            done, health = self._apply_health(st, active, bad, done, health)
        return st.replace(
            big=big,
            caches=caches,
            cursor=cursor,
            n_generated=n_generated,
            keys=keys,
            done=done,
            health=health,
            active_steps=st.active_steps + active.sum(),
        )

    def _decode_chunk_na(self, params, state: SlotState) -> SlotState:
        def body(st, _):
            return self._decode_step_na(params, st), None

        state, _ = jax.lax.scan(body, state, None, length=self.decode_chunk)
        return state

    # ------------------------------------------------- speculative decoding
    def _window_view(self, big: EventStreamBatch, start, W: int) -> EventStreamBatch:
        """A ``W``-event view of the slot rows starting at per-row position
        ``start`` — the verify window. Built from per-offset `take_event`
        stacks with absolute time from the full row buffer, so every window
        position is bitwise the one-event view `_trim_to_event` builds
        there (the greedy bit-identity contract's input half)."""
        t_full = time_from_deltas(big)

        def take(x):
            return jnp.stack([take_event(x, start + t) for t in range(W)], axis=1)

        return big.replace(
            event_mask=take(big.event_mask),
            time_delta=take(big.time_delta),
            time=take(t_full),
            dynamic_indices=take(big.dynamic_indices),
            dynamic_measurement_indices=take(big.dynamic_measurement_indices),
            dynamic_values=take(big.dynamic_values),
            dynamic_values_mask=take(big.dynamic_values_mask),
        )

    def _level_keys(self, base_keys, level: int):
        """Per-row level sub-keys of the event-index base chain (NA)."""
        return jax.vmap(lambda k: _named_key(k, f"level:{level}"))(base_keys)

    def _level_preds(self, preds, level: int):
        """The dep-graph level's head subset of a full NA forward's preds —
        exactly the dists the per-level generation forward would expose
        (mirrors `NestedAttentionGenerativeOutputLayer`'s level loop,
        including CATEGORICAL_ONLY/NUMERICAL_ONLY split modes)."""
        from ..models.embedding import MeasIndexGroupOptions

        if level == 0:
            return GenerativeSequenceModelPredictions(
                time_to_event=preds.time_to_event
            )
        cat, num = set(), set()
        for m in self.config.measurements_per_dep_graph_level[level]:
            mode = MeasIndexGroupOptions.CATEGORICAL_AND_NUMERICAL
            if isinstance(m, (tuple, list)):
                m, mode = m
            if mode in (
                MeasIndexGroupOptions.CATEGORICAL_AND_NUMERICAL,
                MeasIndexGroupOptions.CATEGORICAL_ONLY,
            ):
                cat.add(m)
            if mode in (
                MeasIndexGroupOptions.CATEGORICAL_AND_NUMERICAL,
                MeasIndexGroupOptions.NUMERICAL_ONLY,
            ):
                num.add(m)
        cls = {m: d for m, d in (preds.classification or {}).items() if m in cat}
        reg = {m: d for m, d in (preds.regression or {}).items() if m in num}
        return GenerativeSequenceModelPredictions(
            classification=cls or None, regression=reg or None
        )

    def _level_fill_set(self, level: int):
        return set(
            tuple(sorted(self._measurements_to_fill_list[level], key=str))
        )

    def _spec_draft_chunk_ci(self, draft_params, st: SlotState, sp: SpecState):
        """K draft proposals per slot, written into the row buffers beyond
        the committed cursor. Frozen (done/empty) slots are merged back to
        their pre-round state — proposals for them are inert scratch."""
        config, K = self.config, self.spec.k
        active = st.live & ~st.done

        def body(carry, t):
            big, caches = carry
            pos = st.cursor + t  # the proposed event's row position
            view = _trim_to_event(big, pos - 1)
            out = self.spec.model.apply(
                draft_params, view, past=caches, use_cache=True, is_generation=True
            )
            preds_last = _slice_preds_at(out.preds, jnp.asarray(0))
            em_last = take_event(big.event_mask, pos - 1)
            keys = fold_in_event(st.keys, pos - st.base_len)
            draws = self._draw_rows(preds_last, keys)
            sample = assemble_event_sample(preds_last, draws, em_last)
            big = append_new_event(big, sample, config, pos)
            big = update_last_event_data(big, sample, config, pos + 1)
            return (big, out.past_key_values), (preds_last, draws)

        (big, dcaches), proposals = jax.lax.scan(
            body, (st.big, sp.draft_caches), jnp.arange(K)
        )
        big = self._merge_rows(active, big, st.big)
        dcaches = self._merge_caches(active, dcaches, sp.draft_caches)
        return st.replace(big=big), sp.replace(draft_caches=dcaches), proposals

    def _spec_draft_chunk_na(self, draft_params, st: SlotState, sp: SpecState):
        """The NA draft chunk: K full per-event dep-graph level walks on the
        draft model, recording per-level predictions + raw draws — the
        second speculation axis (the verify pass scores the whole proposed
        measurement chain teacher-forced in one fused forward)."""
        config, K = self.config, self.spec.k
        n_levels = len(self._measurements_to_fill_list)
        active = st.live & ~st.done

        def body(carry, t):
            big, past = carry
            pos = st.cursor + t
            base = fold_in_event(st.keys, pos - st.base_len)
            view = _trim_to_event(big, pos - 1)
            out = self.spec.model.apply(
                draft_params,
                view,
                past=past,
                use_cache=True,
                is_generation=True,
                dep_graph_el_generation_target=0,
            )
            preds0 = _slice_preds_at(out.preds, jnp.asarray(0))
            em0 = take_event(big.event_mask, pos - 1)
            draws0 = self._draw_rows(preds0, self._level_keys(base, 0))
            sample0 = assemble_event_sample(preds0, draws0, em0)
            big = append_new_event(big, sample0, config, pos)
            past = out.past_key_values
            ys = [(preds0, draws0)]
            for level in range(1, n_levels):
                view = _trim_to_event(big, pos)
                out = self.spec.model.apply(
                    draft_params,
                    view,
                    past=past,
                    use_cache=True,
                    is_generation=True,
                    dep_graph_el_generation_target=level,
                )
                past = out.past_key_values
                preds_l = _slice_preds_at(out.preds, jnp.asarray(0))
                em_l = take_event(big.event_mask, pos)
                draws_l = self._draw_rows(preds_l, self._level_keys(base, level))
                sample_l = assemble_event_sample(preds_l, draws_l, em_l)
                big = update_last_event_data(
                    big,
                    sample_l,
                    config,
                    pos + 1,
                    measurements_to_fill=self._level_fill_set(level),
                )
                ys.append((preds_l, draws_l))
            return (big, past), tuple(ys)

        (big, dpast), proposals = jax.lax.scan(
            body, (st.big, sp.draft_caches), jnp.arange(K)
        )
        big = self._merge_rows(active, big, st.big)
        dpast = self._merge_caches(active, dpast, sp.draft_caches)
        return st.replace(big=big), sp.replace(draft_caches=dpast), proposals

    def _spec_round_caps(self, st: SlotState, a, prop_em):
        """Commit-count math shared by both verify programs: acceptance
        (``a + 1`` — accepted prefix plus the correction/bonus event),
        capped by the per-row decode budget and — mirroring the baseline's
        event-at-a-time stopping — at the first committed dead event
        (`DeadRowCriteria` semantics: the dead event commits, nothing
        after it)."""
        K = self.spec.k
        budget_left = st.budget - (st.cursor - st.base_len)
        m = jnp.minimum(a + 1, budget_left)
        if self.stop_dead_rows:
            f = jnp.cumprod(prop_em.astype(jnp.int32), axis=0).sum(0)
            m = jnp.minimum(m, jnp.where(f < a, f + 1, K + 2))
        m = jnp.maximum(m, 1)
        return m, m == a + 1

    def _spec_advance(self, st, sp, active, big, m, needs_corr):
        """Post-commit slot-state advance shared by both verify programs
        (callers have already merged committed content into ``big`` and set
        cache lengths)."""
        c = st.cursor
        m_act = jnp.where(active, m, 0)
        cursor = c + m_act
        positions = jnp.arange(self.max_len)[None, :]
        new_real = (
            big.event_mask & (positions >= c[:, None]) & (positions < cursor[:, None])
        ).sum(1)
        n_generated = st.n_generated + jnp.where(active, new_real, 0)
        done = st.done | (
            active
            & self._row_done(big, cursor, st.base_len, n_generated, st.budget)
        )
        accepted_now = m - needs_corr.astype(jnp.int32)
        # Proposals beyond a row's remaining budget can never commit; count
        # only the committable ones, so the acceptance rate measures draft
        # quality rather than budget truncation.
        budget_left = st.budget - (c - st.base_len)
        proposable = jnp.minimum(self.spec.k, jnp.maximum(budget_left, 0))
        sp = sp.replace(
            proposed=sp.proposed + jnp.where(active, proposable, 0),
            accepted=sp.accepted + jnp.where(active, accepted_now, 0),
            rounds=sp.rounds + 1,
        )
        st = st.replace(
            big=big,
            cursor=cursor,
            n_generated=n_generated,
            done=done,
            active_steps=st.active_steps + active.sum(),
        )
        return st, sp

    def _spec_verify_ci(self, params, st: SlotState, sp: SpecState, proposals):
        """ONE batched target forward over the K+1-event window (last
        committed event + all K proposals) on the vector-length cache
        branch scores every proposal; the accept walk commits the accepted
        prefix plus a correction/bonus event, and per-row cache lengths
        roll back over rejected tails — no copies."""
        config, K = self.config, self.spec.k
        W = K + 1
        active = st.live & ~st.done
        c = st.cursor
        preds_k, draws_k = proposals

        view = self._window_view(st.big, c - 1, W)
        out = self.model.apply(
            params, view, past=st.caches, use_cache=True, is_generation=True
        )

        accept_fn = functools.partial(
            spec_accept_level,
            greedy=self.greedy,
            rtol=self.spec.value_rtol,
            atol=self.spec.value_atol,
            top_k=self.top_k,
            top_p=self.top_p,
        )
        accepts, cands = [], []
        for t in range(1, K + 1):
            tgt_preds_t = jax.tree_util.tree_map(lambda x: x[:, t - 1], out.preds)
            dft_preds_t = jax.tree_util.tree_map(lambda x: x[t - 1], preds_k)
            dft_draws_t = jax.tree_util.tree_map(lambda x: x[t - 1], draws_k)
            em_t = take_event(st.big.event_mask, c + t - 2)
            keys_t = fold_in_event(st.keys, (c + t - 1) - st.base_len)
            tgt_draws_t = self._draw_rows(tgt_preds_t, keys_t)
            acc_t, cand_t = jax.vmap(accept_fn)(
                tgt_preds_t, dft_preds_t, dft_draws_t, tgt_draws_t, keys_t, em_t
            )
            accepts.append(acc_t)
            cands.append(cand_t)
        # The bonus candidate: a pure target sample off the verify
        # forward's last position — the event a fully-accepted round
        # commits for free.
        tgt_preds_b = jax.tree_util.tree_map(lambda x: x[:, K], out.preds)
        em_b = take_event(st.big.event_mask, c + K - 1)
        keys_b = fold_in_event(st.keys, (c + K) - st.base_len)
        cands.append(
            assemble_event_sample(
                tgt_preds_b, self._draw_rows(tgt_preds_b, keys_b), em_b
            )
        )

        a = jnp.cumprod(jnp.stack(accepts, 0).astype(jnp.int32), axis=0).sum(0)
        prop_em = jnp.stack(
            [take_event(st.big.event_mask, c + t - 1) for t in range(1, K + 1)], 0
        )
        m, needs_corr = self._spec_round_caps(st, a, prop_em)

        corr_sample = select_candidate(cands, a)
        corr_cursor = c + m - 1
        big1 = append_new_event(st.big, corr_sample, config, corr_cursor)
        big1 = update_last_event_data(big1, corr_sample, config, corr_cursor + 1)
        big = self._merge_rows(active & needs_corr, big1, st.big)

        st2, sp2 = self._spec_advance(st, sp, active, big, m, needs_corr)
        if self.health_sentinel:
            # The verify forward's preds score every committed event this
            # round — non-finite anywhere in a row's window quarantines
            # that slot exactly like the baseline decode step would.
            done2, health2 = self._apply_health(
                st, active, self._rows_nonfinite(out.preds), st2.done, st2.health
            )
            st2 = st2.replace(done=done2, health=health2)
        caches = self._merge_caches(active, out.past_key_values, st.caches)
        caches = tuple(
            kv.replace(length=jnp.where(active, st2.cursor - 1, kv.length))
            for kv in caches
        )
        dcaches = tuple(
            kv.replace(length=jnp.where(active, st2.cursor - 1, kv.length))
            for kv in sp2.draft_caches
        )
        return st2.replace(caches=caches), sp2.replace(draft_caches=dcaches)

    def _spec_verify_na(self, params, st: SlotState, sp: SpecState, proposals):
        """The NA verify: ONE fused teacher-forced full forward (target=None
        on the vector cache branch) scores the whole proposed dep-graph
        measurement chain of all K events; the correction/bonus event then
        finishes its level walk sequentially (one re-contextualize forward
        plus the standard per-level decodes, per-row frozen at the levels
        the draft already got right).

        Two pieces make the one fused pass EXACT against the sequential
        cached walk: ``partial_content_levels`` embeds graph slot ``l`` from
        the event's levels <= l (what the walk actually wrote — in JOINT
        embedding mode every slot sums all present tokens), and
        ``history_head`` injects each slot's carried per-layer history
        embedding at the window's first position (the NA forward builds
        histories by shift-right within its view; a zero there would poison
        every deeper layer's keys). The round's own contextualized outputs
        refresh the history state for the next round."""
        config, K = self.config, self.spec.k
        W = K + 1
        n_levels = len(self._measurements_to_fill_list)
        active = st.live & ~st.done
        c = st.cursor

        view = self._window_view(st.big, c - 1, W)
        out = self.model.apply(
            params,
            view,
            past=NAPast(seq_past=st.caches.seq_past, dep_graph_past=None),
            use_cache=True,
            is_generation=True,
            partial_content_levels=True,
            history_head=sp.history,
            return_contextualized=True,
        )

        accept_fn = functools.partial(
            spec_accept_level,
            greedy=self.greedy,
            rtol=self.spec.value_rtol,
            atol=self.spec.value_atol,
            top_k=self.top_k,
            top_p=self.top_p,
        )
        acc_events, lrejs = [], []
        level_cands = [[] for _ in range(n_levels)]
        for t in range(1, K + 1):
            base_t = fold_in_event(st.keys, (c + t - 1) - st.base_len)
            level_accs = []
            for level in range(n_levels):
                # Level 0 (the TTE/append chain link) is predicted by the
                # PRECEDING position's whole-event encoding; levels >= 1 by
                # the event's own teacher-forced graph encodings. View index
                # v holds absolute position c - 1 + v.
                src = t - 1 if level == 0 else t
                tgt_preds_l = self._level_preds(
                    jax.tree_util.tree_map(lambda x, s=src: x[:, s], out.preds), level
                )
                dft_preds_l = jax.tree_util.tree_map(
                    lambda x: x[t - 1], proposals[level][0]
                )
                dft_draws_l = jax.tree_util.tree_map(
                    lambda x: x[t - 1], proposals[level][1]
                )
                em_l = take_event(
                    st.big.event_mask, c + t - 2 if level == 0 else c + t - 1
                )
                keys_l = self._level_keys(base_t, level)
                tgt_draws_l = self._draw_rows(tgt_preds_l, keys_l)
                acc_l, cand_l = jax.vmap(accept_fn)(
                    tgt_preds_l, dft_preds_l, dft_draws_l, tgt_draws_l, keys_l, em_l
                )
                level_accs.append(acc_l)
                level_cands[level].append(cand_l)
            acc_stack = jnp.stack(level_accs, 0).astype(jnp.int32)  # (n_levels, S)
            lrejs.append(jnp.cumprod(acc_stack, axis=0).sum(0))  # first reject level
            acc_events.append(acc_stack.prod(0).astype(bool))
        # Bonus level-0 candidate (the fully-accepted round's free event):
        # target TTE off the last view position; its fill levels come from
        # the correction walk below, so levels >= 1 reuse the last
        # candidate as an inert placeholder (never selected).
        tgt_preds_b = self._level_preds(
            jax.tree_util.tree_map(lambda x: x[:, K], out.preds), 0
        )
        em_b = take_event(st.big.event_mask, c + K - 1)
        base_b = fold_in_event(st.keys, (c + K) - st.base_len)
        level_cands[0].append(
            assemble_event_sample(
                tgt_preds_b, self._draw_rows(tgt_preds_b, self._level_keys(base_b, 0)), em_b
            )
        )
        for level in range(1, n_levels):
            level_cands[level].append(level_cands[level][-1])

        a = jnp.cumprod(jnp.stack(acc_events, 0).astype(jnp.int32), axis=0).sum(0)
        prop_em = jnp.stack(
            [take_event(st.big.event_mask, c + t - 1) for t in range(1, K + 1)], 0
        )
        m, needs_corr = self._spec_round_caps(st, a, prop_em)
        # The correction event's first level to resample: its own rejection
        # level, or 0 for the bonus event (whose whole walk is fresh).
        lrej_stack = jnp.stack(lrejs, 0)  # (K, S)
        l_sel = jnp.where(
            a < K,
            jnp.take_along_axis(lrej_stack, jnp.minimum(a, K - 1)[None, :], axis=0)[0],
            0,
        )
        corr_cursor = c + m - 1

        # Commit the correction event's verify-side pieces: level 0 (append)
        # when the chain broke at/under level 0, and the breaking level's
        # residual fill for levels >= 1. Levels BELOW the break keep the
        # draft's content already in the row.
        big = st.big
        cand0 = select_candidate(level_cands[0], a)
        big1 = append_new_event(big, cand0, config, corr_cursor)
        big = self._merge_rows(active & needs_corr & (l_sel == 0), big1, big)
        # Chain broke mid-walk (l_sel >= 1): strip the rejected levels'
        # stale draft elements from the correction event before re-filling
        # (append resets the element set only on the l_sel == 0 path;
        # update_last_event_data keeps existing elements by design). The
        # accepted levels' elements survive in their build order — the
        # stable compaction of the fills below reproduces a baseline-built
        # event's layout exactly.
        bcols = jnp.arange(self.n_slots)
        meas_at = big.dynamic_measurement_indices[bcols, corr_cursor]
        el_level = self._na_level_of_meas[meas_at]  # (S, M)
        drop = (meas_at != 0) & (el_level >= l_sel[:, None])
        strip = (active & needs_corr & (l_sel >= 1))[:, None] & drop
        stripped_idx = jnp.where(strip, 0, big.dynamic_indices[bcols, corr_cursor])
        stripped_meas = jnp.where(strip, 0, meas_at)
        stripped_val = jnp.where(strip, 0.0, big.dynamic_values[bcols, corr_cursor])
        stripped_vmask = jnp.where(
            strip, False, big.dynamic_values_mask[bcols, corr_cursor]
        )
        big = big.replace(
            dynamic_indices=big.dynamic_indices.at[bcols, corr_cursor].set(stripped_idx),
            dynamic_measurement_indices=big.dynamic_measurement_indices.at[
                bcols, corr_cursor
            ].set(stripped_meas),
            dynamic_values=big.dynamic_values.at[bcols, corr_cursor].set(stripped_val),
            dynamic_values_mask=big.dynamic_values_mask.at[bcols, corr_cursor].set(
                stripped_vmask
            ),
        )
        for level in range(1, n_levels):
            cand_l = select_candidate(level_cands[level], jnp.minimum(a, K - 1))
            big1 = update_last_event_data(
                big,
                cand_l,
                config,
                corr_cursor + 1,
                measurements_to_fill=self._level_fill_set(level),
            )
            big = self._merge_rows(active & needs_corr & (l_sel == level), big1, big)

        # The correction walk: re-contextualize the predecessor (a one-event
        # full forward — rebuilds the dep-graph cache seed exactly as
        # admission prefill does) then decode levels above the break with
        # the standard per-level programs, per-row frozen where the draft's
        # levels stand.
        needs_walk = active & needs_corr
        seq_merged = self._merge_rows(active, out.past_key_values.seq_past, st.caches.seq_past)
        seq_walk_in = tuple(
            kv.replace(
                length=jnp.where(
                    needs_walk,
                    corr_cursor - 1,
                    jnp.where(active, c + m - 1, kv.length),
                )
            )
            for kv in seq_merged
        )
        # History head for the re-contextualize forward: the event BEFORE
        # the correction event — the round's input history when the very
        # first proposal broke (a == 0), else the in-window contextualized
        # embedding of the last accepted proposal.
        hist_r = tuple(
            jnp.where(
                (a == 0)[:, None],
                sp.history[layer],
                jnp.take_along_axis(
                    ctx, jnp.clip(a - 1, 0, W - 1)[:, None, None], axis=1
                )[:, 0],
            )
            for layer, ctx in enumerate(out.contextualized)
        )
        view_r = _trim_to_event(big, corr_cursor - 1)
        out_r = self.model.apply(
            params,
            view_r,
            past=NAPast(seq_past=seq_walk_in, dep_graph_past=None),
            use_cache=True,
            is_generation=True,
            partial_content_levels=True,
            history_head=hist_r,
        )
        walk_past = out_r.past_key_values
        base_corr = fold_in_event(st.keys, corr_cursor - st.base_len)
        for level in range(1, n_levels):
            view_l = _trim_to_event(big, corr_cursor)
            out_l = self.model.apply(
                params,
                view_l,
                past=walk_past,
                use_cache=True,
                is_generation=True,
                dep_graph_el_generation_target=level,
            )
            walk_past = out_l.past_key_values
            preds_l = _slice_preds_at(out_l.preds, jnp.asarray(0))
            em_l = take_event(big.event_mask, corr_cursor)
            draws_l = self._draw_rows(preds_l, self._level_keys(base_corr, level))
            sample_l = assemble_event_sample(preds_l, draws_l, em_l)
            big1 = update_last_event_data(
                big,
                sample_l,
                config,
                corr_cursor + 1,
                measurements_to_fill=self._level_fill_set(level),
            )
            big = self._merge_rows(needs_walk & (l_sel < level), big1, big)

        st2, sp2 = self._spec_advance(st, sp, active, big, m, needs_corr)
        if self.health_sentinel:
            done2, health2 = self._apply_health(
                st, active, self._rows_nonfinite(out.preds), st2.done, st2.health
            )
            st2 = st2.replace(done=done2, health=health2)
        # Seq caches: walk rows take the re-contextualize forward's write at
        # the correction position; everyone else keeps the verify pass's.
        # Final per-row length is uniformly cursor' - 1 (the baseline decode
        # invariant); rejected-tail junk sits beyond it, masked.
        seq_final = tuple(
            self._merge_rows(needs_walk, w, s)
            for w, s in zip(walk_past.seq_past, seq_walk_in)
        )
        seq_final = tuple(
            kv.replace(length=jnp.where(active, st2.cursor - 1, kv.length))
            for kv in seq_final
        )
        dep_final = walk_past.dep_graph_past  # lockstep scratch (spec mode
        # never reads dep caches across rounds: verify and the walk's
        # re-contextualize forward both rebuild the seed from content)
        dseq = tuple(
            kv.replace(length=jnp.where(active, st2.cursor - 1, kv.length))
            for kv in sp2.draft_caches.seq_past
        )
        # Refresh the history head: the next round's window starts at the
        # new last committed event, whose PREDECESSOR (absolute c + m - 2 =
        # window index m - 1, always committed content) supplies position-0
        # history.
        history = tuple(
            jnp.where(
                active[:, None],
                jnp.take_along_axis(
                    ctx, jnp.clip(m - 1, 0, W - 1)[:, None, None], axis=1
                )[:, 0],
                sp.history[layer],
            )
            for layer, ctx in enumerate(out.contextualized)
        )
        return (
            st2.replace(caches=NAPast(seq_past=seq_final, dep_graph_past=dep_final)),
            sp2.replace(
                draft_caches=NAPast(
                    seq_past=dseq, dep_graph_past=sp2.draft_caches.dep_graph_past
                ),
                history=history,
            ),
        )

    # ------------------------------------------------------------- prefill
    def _prefill_jit(self, bucket_len: int, group: int):
        key = (bucket_len, group)
        if key not in self._prefill_jits:
            if self.paged_kv:
                fn = functools.partial(self._prefill_paged, bucket_len)
            else:
                fn = functools.partial(
                    self._prefill_na if self._is_na else self._prefill_ci,
                    bucket_len,
                )
            self._prefill_jits[key] = self._model_jit(
                fn, donate_argnums=(1,), out_shardings=self._state_out_shardings
            )
        return self._prefill_jits[key]

    def _prefill_fork_fwd_jit(self, bucket_len: int):
        """Fork stage 1 (paged engines): the batch-1 shared-prompt forward,
        materialized at a program boundary (see `_prefill_fork_fwd`)."""
        if bucket_len not in self._prefill_fork_fwd_jits:
            fn = functools.partial(self._prefill_fork_fwd, bucket_len)
            self._prefill_fork_fwd_jits[bucket_len] = self._model_jit(fn)
        return self._prefill_fork_fwd_jits[bucket_len]

    def _prefill_fork_admit_jit(self, group: int):
        """Fork stage 2 (paged engines): tile the materialized prefill to g
        branches, sample each branch's first event, CoW admit."""
        if group not in self._prefill_fork_admit_jits:
            fn = functools.partial(self._prefill_fork_admit, group)
            self._prefill_fork_admit_jits[group] = jax.jit(
                fn, donate_argnums=(0,), out_shardings=self._state_out_shardings
            )
        return self._prefill_fork_admit_jits[group]

    def _prefill_compute_jit(self, bucket_len: int, group: int):
        """The prefill forward WITHOUT the slot scatter — the program a
        dedicated prefill replica dispatches (`prefill_compute`)."""
        key = (bucket_len, group)
        if key not in self._prefill_compute_jits:
            fn = functools.partial(
                self._prefill_forward_na if self._is_na else self._prefill_forward_ci,
                bucket_len,
            )
            self._prefill_compute_jits[key] = self._model_jit(fn)
        return self._prefill_compute_jits[key]

    def _admit_jit(self, group: int):
        """The admit scatter alone — the (cheap) program a decode replica
        runs to take a prefill-stream handoff at a chunk boundary."""
        if group not in self._admit_jits:

            def fn(state, big1, caches1, plen, budgets, keys1, first_event_real, slots):
                return self._admit(
                    state, big1, caches1, plen, budgets, keys1, slots, first_event_real
                )

            self._admit_jits[group] = jax.jit(
                fn, donate_argnums=(0,), out_shardings=self._state_out_shardings
            )
        return self._admit_jits[group]

    def _prefill_compute_spec_jit(self, bucket_len: int, group: int):
        """The spec-mode prefill forward WITHOUT the slot scatters: the
        target's bucketed prefill on the per-event-index chain PLUS the
        draft model's prompt forward — the compute half a dedicated
        prefill replica runs for a speculative target tier. The handoff
        carries the draft cache seed (`PrefillHandoff.draft_caches`), so
        both chains admit on the decode replica in one program."""
        key = (bucket_len, group)
        if key not in self._prefill_compute_spec_jits:

            def fn(params, draft_params, pbig, plen, keys):
                if self._is_na:
                    big1, caches1, fer, history1 = self._prefill_forward_na_spec(
                        bucket_len, params, pbig, plen, keys
                    )
                else:
                    big1, caches1, fer = self._prefill_forward_ci_spec(
                        bucket_len, params, pbig, plen, keys
                    )
                    history1 = None
                dcaches1 = self._prefill_draft_forward(
                    bucket_len, draft_params, pbig, big1, plen
                )
                return big1, caches1, fer, dcaches1, history1

            self._prefill_compute_spec_jits[key] = self._model_jit(fn)
        return self._prefill_compute_spec_jits[key]

    def _admit_spec_jit(self, group: int):
        """Both chains' admit scatters as ONE program: the target's row
        scatter (quantize-on-admission under a quantized cache dtype) and
        the draft cache + spec-counter scatter. Donates both state trees;
        TP layouts pin outputs to the input layout (Tier C fix)."""
        if group not in self._admit_spec_jits:

            def fn(
                state, sp, big1, caches1, plen, budgets, keys1,
                first_event_real, dcaches1, history1, slots,
            ):
                state = self._admit(
                    state, big1, caches1, plen, budgets, keys1, slots,
                    first_event_real=first_event_real,
                )
                sp = self._admit_draft(sp, dcaches1, plen, slots, history1=history1)
                return state, sp

            spec_out = None
            if self.tensor_parallel:
                spec_out = (
                    self._state_out_shardings,
                    self._tree_shardings(self._spec_state),
                )
            self._admit_spec_jits[group] = jax.jit(
                fn, donate_argnums=(0, 1), out_shardings=spec_out
            )
        return self._admit_spec_jits[group]

    def _prefill_forward_ci(self, Lb, params, pbig, plen, keys):
        """The bucketed prefill forward + first-event sample, WITHOUT the
        slot scatter — the compute half the dedicated prefill stream runs on
        its own replica. Returns ``(big1, caches1, keys1, first_event_real)``
        exactly as `_admit` consumes them."""
        n = pbig.batch_size
        view = pbig.slice((slice(None), slice(0, Lb)))
        out = self.model.apply(
            params,
            view,
            past=init_kv_caches(self.config, n, max_len=self.max_len),
            use_cache=True,
            is_generation=True,
        )
        new_keys, step_keys = _vmap_split(keys)
        preds_last = _slice_preds_at(out.preds, plen - 1)
        em_last = take_event(pbig.event_mask, plen - 1)
        sample = self._sample_rows(preds_last, em_last, step_keys)
        big1 = append_new_event(pbig, sample, self.config, plen)
        big1 = update_last_event_data(big1, sample, self.config, plen + 1)
        return big1, out.past_key_values, new_keys, sample.event_mask

    def _prefill_ci(self, Lb, params, state, pbig, plen, budgets, keys, slots):
        big1, caches1, keys1, fer = self._prefill_forward_ci(
            Lb, params, pbig, plen, keys
        )
        return self._admit(
            state, big1, caches1, plen, budgets, keys1, slots, first_event_real=fer
        )

    def _prefill_paged(
        self, Lb, params, state, pbig, plen, budgets, keys, slots,
        read_table, scatter_table,
    ):
        """The paged-engine prefill program: the SAME bucketed forward +
        first-event sample as the monolithic path (`_prefill_forward_ci` —
        prefill itself always runs on small monolithic caches), admitted
        through the block-pool scatter instead of the row scatter."""
        big1, caches1, keys1, fer = self._prefill_forward_ci(
            Lb, params, pbig, plen, keys
        )
        src_rows = jnp.arange(plen.shape[0], dtype=jnp.int32)
        return self._admit(
            state, big1, caches1, plen, budgets, keys1, slots,
            first_event_real=fer,
            paged_tables=(read_table, scatter_table, src_rows),
        )

    def _prefill_fork_fwd(self, Lb, params, prow, plen1):
        """ONE batch-1 prefill forward of a fork group's shared prompt,
        MATERIALIZED at a program boundary. The split is load-bearing for
        bitwise parity with independent submissions: sampling fused into a
        batch-1-forward+tile program compiles a (1-ulp) different tail than
        the fused batch-g prefill, whereas sampling over materialized
        arrays is bitwise identical to the fused batch-g program (pinned by
        test) — so the fork pipeline is forward here, tile + sample + admit
        in `_prefill_fork_admit`."""
        view = prow.slice((slice(None), slice(0, Lb)))
        out = self.model.apply(
            params,
            view,
            past=init_kv_caches(self.config, 1, max_len=self.max_len),
            use_cache=True,
            is_generation=True,
        )
        preds1 = _slice_preds_at(out.preds, plen1 - 1)
        em1 = take_event(prow.event_mask, plen1 - 1)
        return out.past_key_values, preds1, em1

    def _prefill_fork_admit(
        self, g, state, prow, caches1, preds1, em1, plen, budgets, keys,
        slots, read_table, scatter_table,
    ):
        """Tiles the materialized batch-1 prefill to ``g`` branch rows,
        samples each branch's first event on its own key
        (``fold_in(session_key, branch_index)``), and admits the group
        copy-on-write: branch 0's scatter_table writes the shared prefix
        blocks (+ its own tail); branches > 0 write only their private
        tails; src_rows all point at the single prefilled cache row
        (`_scatter_kv_paged`). Row-wise identical to the fused batch-g
        prefill of g copies of the prompt — the fork == independent
        bit-identity contract."""

        def tile(x):
            return jnp.concatenate([x] * g, axis=0)

        big = jax.tree_util.tree_map(tile, prow)
        new_keys, step_keys = _vmap_split(keys)
        preds_g = jax.tree_util.tree_map(tile, preds1)
        em_g = tile(em1)
        sample = self._sample_rows(preds_g, em_g, step_keys)
        big1 = append_new_event(big, sample, self.config, plen)
        big1 = update_last_event_data(big1, sample, self.config, plen + 1)
        src_rows = jnp.zeros((g,), jnp.int32)
        return self._admit(
            state, big1, caches1, plen, budgets, new_keys, slots,
            first_event_real=sample.event_mask,
            paged_tables=(read_table, scatter_table, src_rows),
        )

    def _prefill_na(self, Lb, params, state, pbig, plen, budgets, keys, slots):
        big, past, keys1, fer = self._prefill_forward_na(Lb, params, pbig, plen, keys)
        return self._admit(
            state, big, past, plen, budgets, keys1, slots, first_event_real=fer
        )

    def _prefill_forward_na(self, Lb, params, pbig, plen, keys):
        n = pbig.batch_size
        config = self.config
        n_levels = len(self._measurements_to_fill_list)
        cursor = plen
        view = pbig.slice((slice(None), slice(0, Lb)))
        new_keys, step_keys = _vmap_split(keys)
        out = self.model.apply(
            params,
            view,
            past=NAPast(
                seq_past=init_kv_caches(config, n, max_len=self.max_len),
                dep_graph_past=None,
            ),
            use_cache=True,
            is_generation=True,
            # Bucket-padded prompts: the dep-graph history seed must be each
            # row's last REAL event, not the padded tail position.
            last_event_index=plen - 1,
        )
        past = out.past_key_values
        # Vectorize the seq-cache cursors to each row's TRUE prompt length
        # before the level walk: the target>=1 forwards place their query at
        # the cache cursor, and a bucket-width cursor would shift q-positions
        # so sliding-window masks count padding holes as history (same
        # contract as `_admit`).
        past = NAPast(
            seq_past=tuple(kv.replace(length=plen) for kv in past.seq_past),
            dep_graph_past=past.dep_graph_past,
        )
        preds_last = _slice_preds_at(out.preds, cursor - 1)
        em_last = take_event(pbig.event_mask, cursor - 1)
        sample = self._sample_rows(preds_last, em_last, step_keys)
        big = append_new_event(pbig, sample, config, cursor)
        first_event_real = sample.event_mask

        for level in range(1, n_levels):
            new_keys, step_keys = _vmap_split(new_keys)
            view = _trim_to_event(big, cursor)
            out = self.model.apply(
                params,
                view,
                past=past,
                use_cache=True,
                is_generation=True,
                dep_graph_el_generation_target=level,
            )
            past = out.past_key_values
            preds_last = _slice_preds_at(out.preds, jnp.asarray(0))
            em_last = take_event(big.event_mask, cursor)
            sample = self._sample_rows(preds_last, em_last, step_keys)
            big = update_last_event_data(
                big,
                sample,
                config,
                cursor + 1,
                measurements_to_fill=set(
                    tuple(sorted(self._measurements_to_fill_list[level], key=str))
                ),
            )
        return big, past, new_keys, first_event_real

    def _scatter_kv(
        self, dst: KVCache, src: KVCache, vector_len: bool, slots, plen
    ) -> KVCache:
        """One prefilled cache's rows scattered into the slot cache (the
        admission write; shared by the target and draft admits)."""
        if dst.key_scale is not None:
            # Quantize-on-admission: prefill ran (exactly) on float
            # caches; the admitted rows land in the slot cache as
            # int8/fp8 planes + per-head-per-row scales (ops/kv_quant).
            from ..ops.kv_quant import quantize_kv

            k_q, k_s = quantize_kv(src.key, dst.key.dtype)
            v_q, v_s = quantize_kv(src.value, dst.value.dtype)
            key = dst.key.at[slots].set(k_q, mode="drop")
            value = dst.value.at[slots].set(v_q, mode="drop")
            key_scale = dst.key_scale.at[slots].set(k_s, mode="drop")
            value_scale = dst.value_scale.at[slots].set(v_s, mode="drop")
        else:
            key = dst.key.at[slots].set(src.key.astype(dst.key.dtype), mode="drop")
            value = dst.value.at[slots].set(
                src.value.astype(dst.value.dtype), mode="drop"
            )
            key_scale = value_scale = None
        return KVCache(
            key=key,
            value=value,
            mask=dst.mask.at[slots].set(src.mask, mode="drop"),
            length=(
                dst.length.at[slots].set(plen, mode="drop")
                if vector_len
                else src.length
            ),
            key_scale=key_scale,
            value_scale=value_scale,
        )

    def _scatter_caches(self, dst, src, slots, plen):
        """Scatters a prefilled cache pytree (tuple or NAPast) into slots."""
        if isinstance(dst, NAPast):
            return NAPast(
                seq_past=tuple(
                    self._scatter_kv(d, s, True, slots, plen)
                    for d, s in zip(dst.seq_past, src.seq_past)
                ),
                dep_graph_past=tuple(
                    self._scatter_kv(d, s, False, slots, plen)
                    for d, s in zip(dst.dep_graph_past, src.dep_graph_past)
                ),
            )
        return tuple(
            self._scatter_kv(d, s, True, slots, plen) for d, s in zip(dst, src)
        )

    def _scatter_kv_paged(
        self, dst: PagedKVCache, src: KVCache, slots, plen,
        read_table, scatter_table, src_rows,
    ) -> PagedKVCache:
        """One prefilled (monolithic, full-``max_len``) cache admitted into
        the block pool. ``read_table``/``scatter_table`` are ``(g, T)``
        physical-block tables: `read_table` is what the row's attention
        gather will see (shared CoW prefix + private tail); `scatter_table`
        is what THIS row's admit writes — fork branches > 0 carry 0 for the
        shared prefix entries (redirected to the drop index) so each shared
        block is written exactly once, by branch 0, from the identical
        batch-1 prefill bytes. ``src_rows`` maps group row -> source cache
        row (identity normally; all-zeros for a fork's batch-1 source).

        Bit-identity vs the monolithic admit: the prefill forward runs on
        full-width monolithic caches, so ``src`` carries the same bytes the
        monolithic path scatters — prompt rows, bucket-pad rows, and zeros
        past the bucket. Every position covered by an allocated block gets
        those bytes; positions beyond the table's coverage gather the zero
        block's zeros, which is byte-equal to the monolithic buffer's
        untouched zeros. The dense gathered view is therefore equal to the
        monolithic buffer at EVERY position."""
        bs = self.block_size
        T = self.max_len // bs
        N = self._paged_num_blocks
        if dst.pool_key_scale is not None:
            from ..ops.kv_quant import quantize_kv

            k_src, k_s = quantize_kv(src.key, dst.pool_key.dtype)
            v_src, v_s = quantize_kv(src.value, dst.pool_value.dtype)
        else:
            k_src = src.key.astype(dst.pool_key.dtype)
            v_src = src.value.astype(dst.pool_value.dtype)
            k_s = v_s = None
        pk, pv = dst.pool_key, dst.pool_value
        pks, pvs = dst.pool_key_scale, dst.pool_value_scale
        for j in range(T):
            phys = scatter_table[:, j]
            phys = jnp.where(phys == 0, N, phys)  # zero block: never written
            kb = k_src[src_rows, :, j * bs : (j + 1) * bs, :]
            vb = v_src[src_rows, :, j * bs : (j + 1) * bs, :]
            pk = pk.at[phys].set(kb, mode="drop")
            pv = pv.at[phys].set(vb, mode="drop")
            if pks is not None:
                pks = pks.at[phys].set(
                    k_s[src_rows, :, j * bs : (j + 1) * bs], mode="drop"
                )
                pvs = pvs.at[phys].set(
                    v_s[src_rows, :, j * bs : (j + 1) * bs], mode="drop"
                )
        return PagedKVCache(
            pool_key=pk,
            pool_value=pv,
            block_table=dst.block_table.at[slots].set(read_table, mode="drop"),
            mask=dst.mask.at[slots].set(src.mask[src_rows], mode="drop"),
            length=dst.length.at[slots].set(plen, mode="drop"),
            pool_key_scale=pks,
            pool_value_scale=pvs,
        )

    def _admit(
        self, state, big1, caches1, plen, budgets, keys1, slots, first_event_real,
        paged_tables=None,
    ):
        """Scatters prefilled rows into the slot state. ``slots`` may carry
        out-of-range indices for inert padded group rows (dropped).

        Seq-cache rows admit with per-row length = the TRUE prompt length
        (not the bucket width): the first decode then overwrites the first
        bucket-padding hole, cache positions stay contiguous with
        ``generate()``'s, and position-based masking (the sliding-window
        rule `k > q - window`) sees exactly the history generate() would —
        holes never consume window slots.

        ``paged_tables`` (paged engines only) is the
        ``(read_table, scatter_table, src_rows)`` triple the block-pool
        admit consumes (`_scatter_kv_paged`)."""
        cursor1 = plen + 1

        def scatter(dst, src):
            def f(d, s):
                return d.at[slots].set(s.astype(d.dtype), mode="drop")

            return jax.tree_util.tree_map(f, dst, src)

        big = scatter(state.big, big1)
        if paged_tables is not None:
            read_table, scatter_table, src_rows = paged_tables
            caches = tuple(
                self._scatter_kv_paged(
                    d, s, slots, plen, read_table, scatter_table, src_rows
                )
                for d, s in zip(state.caches, caches1)
            )
        else:
            caches = self._scatter_caches(state.caches, caches1, slots, plen)

        n_gen1 = first_event_real.astype(jnp.int32)
        done1 = self._row_done(big1, cursor1, plen, n_gen1, budgets)
        return state.replace(
            big=big,
            caches=caches,
            cursor=state.cursor.at[slots].set(cursor1, mode="drop"),
            base_len=state.base_len.at[slots].set(plen, mode="drop"),
            budget=state.budget.at[slots].set(budgets, mode="drop"),
            n_generated=state.n_generated.at[slots].set(n_gen1, mode="drop"),
            done=state.done.at[slots].set(done1, mode="drop"),
            live=state.live.at[slots].set(True, mode="drop"),
            keys=state.keys.at[slots].set(keys1, mode="drop"),
            health=state.health.at[slots].set(False, mode="drop"),
        )

    # ------------------------------------------------------- spec prefill
    def _prefill_spec_jit(self, bucket_len: int, group: int):
        """The spec-mode prefill program: the target's bucketed prefill with
        the first generated event drawn on the per-event-index chain
        (``fold_in(request_key, 0)``), plus the draft model's prefill of
        its own cache rows — one dispatch admits a group into BOTH chains.
        """
        key = (bucket_len, group)
        if key not in self._prefill_spec_jits:
            fn = functools.partial(
                self._prefill_spec_na if self._is_na else self._prefill_spec_ci,
                bucket_len,
            )
            spec_out = None
            if self.tensor_parallel:
                # Tier C donation-drop fix, spec flavor (constructor note).
                spec_out = (
                    self._state_out_shardings,
                    self._tree_shardings(self._spec_state),
                )
            self._prefill_spec_jits[key] = self._model_jit(
                fn, donate_argnums=(2, 3), out_shardings=spec_out
            )
        return self._prefill_spec_jits[key]

    def _prefill_draft_forward(self, Lb, draft_params, pbig, big1, plen):
        """The draft model's prompt forward: fills its per-slot cache rows
        for positions ``0..plen-1`` (the committed-prefix invariant both
        chains share). For NA, the dep-graph cache must additionally hold
        the first sampled event's graph-element kvs — the state the
        target's prefill walk leaves behind — so the draft replays the walk
        teacher-forced on ``big1`` (the target-prefilled content), with each
        level's view masked to the content the incremental walk would have
        seen."""
        n = pbig.batch_size
        view = pbig.slice((slice(None), slice(0, Lb)))
        if not self._is_na:
            out = self.spec.model.apply(
                draft_params,
                view,
                past=init_kv_caches(self.spec.config, n, max_len=self.max_len),
                use_cache=True,
                is_generation=True,
            )
            return out.past_key_values
        out = self.spec.model.apply(
            draft_params,
            view,
            past=NAPast(
                seq_past=init_kv_caches(self.spec.config, n, max_len=self.max_len),
                dep_graph_past=None,
            ),
            use_cache=True,
            is_generation=True,
            last_event_index=plen - 1,
        )
        past = NAPast(
            seq_past=tuple(
                kv.replace(length=plen) for kv in out.past_key_values.seq_past
            ),
            dep_graph_past=out.past_key_values.dep_graph_past,
        )
        n_levels = len(self._measurements_to_fill_list)
        for level in range(1, n_levels):
            masked = mask_batch_to_levels(big1, self._na_level_of_meas, level - 1)
            walk_out = self.spec.model.apply(
                draft_params,
                _trim_to_event(masked, plen),
                past=past,
                use_cache=True,
                is_generation=True,
                dep_graph_el_generation_target=level,
            )
            past = walk_out.past_key_values
        return past

    def _admit_draft(self, sp: SpecState, caches1, plen, slots, history1=None) -> SpecState:
        """Scatters draft prefill rows (and, for NA, the target's history
        head of each prompt's last event) and zeroes the slots' per-tenant
        spec counters (so a finished request's boundary carries exactly its
        own acceptance accounting)."""
        history = sp.history
        if history1 is not None:
            history = tuple(
                h.at[slots].set(h1.astype(h.dtype), mode="drop")
                for h, h1 in zip(sp.history, history1)
            )
        return sp.replace(
            draft_caches=self._scatter_caches(sp.draft_caches, caches1, slots, plen),
            proposed=sp.proposed.at[slots].set(0, mode="drop"),
            accepted=sp.accepted.at[slots].set(0, mode="drop"),
            history=history,
        )

    def _prefill_forward_ci_spec(self, Lb, params, pbig, plen, keys):
        """`_prefill_forward_ci` on the spec PRNG chain: the first generated
        event (index 0) draws under ``fold_in(request_key, 0)``; request
        keys never advance (the chain is addressed per event index)."""
        n = pbig.batch_size
        view = pbig.slice((slice(None), slice(0, Lb)))
        out = self.model.apply(
            params,
            view,
            past=init_kv_caches(self.config, n, max_len=self.max_len),
            use_cache=True,
            is_generation=True,
        )
        base0 = fold_in_event(keys, jnp.zeros_like(plen))
        preds_last = _slice_preds_at(out.preds, plen - 1)
        em_last = take_event(pbig.event_mask, plen - 1)
        draws = self._draw_rows(preds_last, base0)
        sample = assemble_event_sample(preds_last, draws, em_last)
        big1 = append_new_event(pbig, sample, self.config, plen)
        big1 = update_last_event_data(big1, sample, self.config, plen + 1)
        return big1, out.past_key_values, sample.event_mask

    def _prefill_spec_ci(
        self, Lb, params, draft_params, state, sp, pbig, plen, budgets, keys, slots
    ):
        big1, caches1, fer = self._prefill_forward_ci_spec(Lb, params, pbig, plen, keys)
        state = self._admit(
            state, big1, caches1, plen, budgets, keys, slots, first_event_real=fer
        )
        dcaches1 = self._prefill_draft_forward(Lb, draft_params, pbig, big1, plen)
        return state, self._admit_draft(sp, dcaches1, plen, slots)

    def _prefill_forward_na_spec(self, Lb, params, pbig, plen, keys):
        """`_prefill_forward_na` on the spec chain: the first event's level
        walk draws under ``fold_in(request_key, 0)`` sub-chained per level."""
        n = pbig.batch_size
        config = self.config
        n_levels = len(self._measurements_to_fill_list)
        cursor = plen
        view = pbig.slice((slice(None), slice(0, Lb)))
        base0 = fold_in_event(keys, jnp.zeros_like(plen))
        out = self.model.apply(
            params,
            view,
            past=NAPast(
                seq_past=init_kv_caches(config, n, max_len=self.max_len),
                dep_graph_past=None,
            ),
            use_cache=True,
            is_generation=True,
            last_event_index=plen - 1,
            return_contextualized=True,
        )
        # The history-head seed: each row's last REAL prompt event's
        # per-layer contextualized embedding (the verify window's position-0
        # history once decode starts).
        history1 = tuple(take_event(ctx, plen - 1) for ctx in out.contextualized)
        past = out.past_key_values
        past = NAPast(
            seq_past=tuple(kv.replace(length=plen) for kv in past.seq_past),
            dep_graph_past=past.dep_graph_past,
        )
        preds_last = _slice_preds_at(out.preds, cursor - 1)
        em_last = take_event(pbig.event_mask, cursor - 1)
        draws0 = self._draw_rows(preds_last, self._level_keys(base0, 0))
        sample = assemble_event_sample(preds_last, draws0, em_last)
        big = append_new_event(pbig, sample, config, cursor)
        first_event_real = sample.event_mask

        for level in range(1, n_levels):
            view = _trim_to_event(big, cursor)
            out = self.model.apply(
                params,
                view,
                past=past,
                use_cache=True,
                is_generation=True,
                dep_graph_el_generation_target=level,
            )
            past = out.past_key_values
            preds_last = _slice_preds_at(out.preds, jnp.asarray(0))
            em_last = take_event(big.event_mask, cursor)
            draws_l = self._draw_rows(preds_last, self._level_keys(base0, level))
            sample = assemble_event_sample(preds_last, draws_l, em_last)
            big = update_last_event_data(
                big,
                sample,
                config,
                cursor + 1,
                measurements_to_fill=self._level_fill_set(level),
            )
        return big, past, first_event_real, history1

    def _prefill_spec_na(
        self, Lb, params, draft_params, state, sp, pbig, plen, budgets, keys, slots
    ):
        big1, caches1, fer, history1 = self._prefill_forward_na_spec(
            Lb, params, pbig, plen, keys
        )
        state = self._admit(
            state, big1, caches1, plen, budgets, keys, slots, first_event_real=fer
        )
        dcaches1 = self._prefill_draft_forward(Lb, draft_params, pbig, big1, plen)
        return state, self._admit_draft(sp, dcaches1, plen, slots, history1=history1)

    # -------------------------------------------------------------- extract
    def _extract_jit(self, group: int):
        if group not in self._extract_jits:

            def fn(state, slots):
                rows = jax.tree_util.tree_map(lambda x: x[slots], state.big)
                rows = _mask_through_cursor(rows, state.cursor[slots])
                return (
                    rows,
                    state.cursor[slots],
                    state.base_len[slots],
                    state.n_generated[slots],
                )

            self._extract_jits[group] = jax.jit(fn)
        return self._extract_jits[group]

    # ------------------------------------------------------ fault injection
    def _poison_jit(self, n: int):
        """The NaN-injection program (`reliability/serving_faults.py`
        ``nan_slot``): writes NaN into the chosen slots' last committed
        event's ``time_delta``, so their NEXT forward produces non-finite
        logits/values through the time embedding — driving the health
        sentinel exactly the way a real on-device numerics fault would.
        Row-local by construction (rows never mix in any decode op), so
        co-resident slots are bit-untouched. Compiled lazily and only when
        a plan is installed; deliberately NOT part of `aot_programs` — it
        is a test harness, not a serving program."""
        jits = getattr(self, "_poison_jits", None)
        if jits is None:
            jits = self._poison_jits = {}
        if n not in jits:

            def poison(state: SlotState, slots):
                # The delta BEHIND the last committed event: it feeds the
                # cumulative-time input of every later forward (the last
                # event's own delta is overwritten by the next append and
                # never consumed — poisoning it would be a silent no-op).
                cols = jnp.maximum(state.cursor[slots] - 2, 0)
                td = state.big.time_delta.at[slots, cols].set(
                    jnp.nan, mode="drop"
                )
                return state.replace(big=state.big.replace(time_delta=td))

            jits[n] = jax.jit(
                poison,
                donate_argnums=(0,),
                out_shardings=self._state_out_shardings,
            )
        return jits[n]

    # ---------------------------------------------------------- host pieces
    def _pad_prompt_row(self, prompt: EventStreamBatch) -> EventStreamBatch:
        """One request row, normalized and padded to the slot buffer length."""
        p = self._normalize_prompt(prompt)
        if p.batch_size != 1:
            raise ValueError("Requests hold one-row prompts; split cohorts first")
        if p.n_data_elements != self._template.n_data_elements:
            raise ValueError(
                f"Prompt data-element width {p.n_data_elements} != engine width "
                f"{self._template.n_data_elements}"
            )
        pad = self.max_len - p.sequence_length
        if pad < 0:
            raise ValueError(
                f"Prompt of {p.sequence_length} events exceeds max_len={self.max_len}"
            )

        def pad_seq(x, template_x):
            if x is None:
                return None
            cfg = [(0, 0)] * x.ndim
            cfg[1] = (0, pad)
            return jnp.pad(jnp.asarray(x), cfg).astype(jnp.asarray(template_x).dtype)

        t = self._template
        return p.replace(
            event_mask=pad_seq(p.event_mask, t.event_mask),
            time_delta=pad_seq(p.time_delta, t.time_delta),
            dynamic_indices=pad_seq(p.dynamic_indices, t.dynamic_indices),
            dynamic_measurement_indices=pad_seq(
                p.dynamic_measurement_indices, t.dynamic_measurement_indices
            ),
            dynamic_values=pad_seq(p.dynamic_values, t.dynamic_values),
            dynamic_values_mask=pad_seq(p.dynamic_values_mask, t.dynamic_values_mask),
        )

    def _request_key(self, req: Request) -> jnp.ndarray:
        if req.key is not None:
            return _as_raw_key(req.key)
        if req.fork is not None:
            # The fork key-derivation contract (docs/serving.md): branch j
            # draws from fold_in(session_key, j), where the session key is
            # the caller's explicit key or — unkeyed — the engine key folded
            # with branch 0's admission index. Bitwise equal to submitting
            # the j-th branch independently with that explicit key.
            session = req.fork.session_key
            if session is None:
                session = derive_request_key(
                    self._base_key, req.fork.session_admission_index
                )
            return derive_request_key(session, req.branch_index)
        return derive_request_key(self._base_key, req.admission_index)

    def _group_arrays(self, requests: list, g: int):
        """Stacks a same-bucket request group into the prefill program's
        array arguments, padded to compiled group width ``g`` with inert
        rows. Shared by the local prefill dispatch and the prefill-stream
        compute half — identical inputs are half of the handoff's
        bit-identity contract."""
        n = len(requests)
        rows = [self._pad_prompt_row(r.prompt) for r in requests]
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.concatenate(xs, axis=0), *rows)
        if g > n:
            # Inert pad rows: slot index == n_slots scatters with mode="drop".
            stacked = jax.tree_util.tree_map(
                lambda x: jnp.pad(x, [(0, g - n)] + [(0, 0)] * (x.ndim - 1)), stacked
            )
        plen = jnp.asarray([r.prompt_len for r in requests] + [1] * (g - n), jnp.int32)
        budgets = jnp.asarray(
            [r.max_new_events for r in requests] + [1] * (g - n), jnp.int32
        )
        keys = jnp.stack(
            [self._request_key(r) for r in requests]
            + [jnp.zeros((2,), jnp.uint32)] * (g - n)
        )
        return stacked, plen, budgets, keys

    def _free_slot_blocks(self, slot: int) -> None:
        """Releases the blocks the slot's PREVIOUS tenant held (deferred
        freeing — see `BlockAllocator`). Called at re-admission and reset."""
        row = self._tables[slot]
        held = [int(b) for b in row if b != 0]
        if held:
            self._block_alloc.decref(held)
        row[:] = 0

    def _plan_admission_tables(self, group) -> tuple[np.ndarray, np.ndarray]:
        """Host-side block planning for one admission group: frees the
        target slots' previous blocks, allocates coverage for each row's
        ``prompt + budget`` events, and returns the ``(read, scatter)``
        table pair the paged admit consumes. Fork groups allocate the
        shared full-prompt blocks ONCE (refcount = n_branches) and only the
        partial prompt block + generation tail per branch — the CoW layout:
        decode's first write lands at position ``plen >= n_full * bs``, so
        shared blocks are frozen for their whole refcounted lifetime."""
        g = group.group_size
        bs = self.block_size
        T = self.max_len // bs
        alloc = self._block_alloc
        read = np.zeros((g, T), np.int32)
        scat = np.zeros((g, T), np.int32)
        covers = [
            min(r.prompt_len + r.max_new_events, self.max_len)
            for r in group.requests
        ]
        blocks_per_row = [-(-c // bs) for c in covers]
        for s in group.slots:
            self._free_slot_blocks(s)
        n_full = 0
        if group.fork is not None:
            n_full = group.requests[0].prompt_len // bs
        need = sum(blocks_per_row) - n_full * max(len(group.requests) - 1, 0)
        if need > alloc.free_blocks:
            raise RuntimeError(
                f"block pool exhausted planning an admission: need {need} "
                f"blocks, {alloc.free_blocks} free of {alloc.num_blocks - 1} "
                "usable (size the pool with num_blocks >= n_slots * "
                "(max_len // block_size) + 1 for worst-case occupancy)"
            )
        if group.fork is None:
            for i, (s, cover, n) in enumerate(
                zip(group.slots, covers, blocks_per_row)
            ):
                blocks = alloc.alloc(n)
                read[i, :n] = blocks
                scat[i, :n] = blocks
                self._tables[s, :] = read[i]
                alloc.note_cover(cover, n)
            return read, scat
        shared = alloc.alloc(n_full)
        if len(group.requests) > 1:
            alloc.incref(shared, len(group.requests) - 1)
        for i, (s, cover, n) in enumerate(
            zip(group.slots, covers, blocks_per_row)
        ):
            priv = alloc.alloc(n - n_full)
            read[i, :n_full] = shared
            read[i, n_full:n] = priv
            if i == 0:
                scat[i, :n] = read[i, :n]
            else:
                # Branches > 0 never write the shared prefix: each shared
                # block is admitted exactly once, by branch 0, from the
                # single prefilled source row.
                scat[i, n_full:n] = priv
            self._tables[s, :] = read[i]
            alloc.note_cover(cover, n)
        return read, scat

    def _dispatch_group(self, group) -> None:
        n, g = len(group.requests), group.group_size
        slots = jnp.asarray(group.slots + [self.n_slots] * (g - n), jnp.int32)
        if self.paged_kv:
            read_np, scat_np = self._plan_admission_tables(group)
            read_t = jnp.asarray(read_np)
            scat_t = jnp.asarray(scat_np)
            if group.fork is not None:
                r0 = group.requests[0]
                prow = self._pad_prompt_row(r0.prompt)
                plen = jnp.full((g,), r0.prompt_len, jnp.int32)
                budgets = jnp.asarray(
                    [r.max_new_events for r in group.requests]
                    + [1] * (g - n),
                    jnp.int32,
                )
                keys = jnp.stack(
                    [self._request_key(r) for r in group.requests]
                    + [jnp.zeros((2,), jnp.uint32)] * (g - n)
                )
                plen1 = jnp.full((1,), r0.prompt_len, jnp.int32)
                caches1, preds1, em1 = self._prefill_fork_fwd_jit(
                    group.bucket_len
                )(self.params, prow, plen1)
                self._state = self._prefill_fork_admit_jit(g)(
                    self._state, prow, caches1, preds1, em1, plen, budgets,
                    keys, slots, read_t, scat_t,
                )
            else:
                stacked, plen, budgets, keys = self._group_arrays(
                    group.requests, g
                )
                self._state = self._prefill_jit(group.bucket_len, g)(
                    self.params, self._state, stacked, plen, budgets, keys,
                    slots, read_t, scat_t,
                )
            for r, s in zip(group.requests, group.slots):
                self._table[s] = r
                self._slot_epoch[s] = self._dispatched_chunks
            return
        stacked, plen, budgets, keys = self._group_arrays(group.requests, g)
        if self.spec is not None:
            self._state, self._spec_state = self._prefill_spec_jit(
                group.bucket_len, g
            )(
                self.params,
                self.draft_params,
                self._state,
                self._spec_state,
                stacked,
                plen,
                budgets,
                keys,
                slots,
            )
        else:
            self._state = self._prefill_jit(group.bucket_len, g)(
                self.params, self._state, stacked, plen, budgets, keys, slots
            )
        for r, s in zip(group.requests, group.slots):
            self._table[s] = r
            self._slot_epoch[s] = self._dispatched_chunks

    # ------------------------------------------------- prefill-stream handoff
    def prefill_compute(self, requests: list, bucket_len: int, group: int):
        """Runs the bucketed prefill forward on THIS engine without touching
        its slot state — the dedicated-prefill-stream compute half
        (`serving/fleet.PrefillStream`). Returns a `PrefillHandoff` whose
        arrays are exactly what the target replica's `admit_prefilled`
        scatter consumes; because the forward, the sampling tail, and the
        per-request keys are identical to the local `_dispatch_group` path,
        the admitted slot state — and every decode after it — is
        bit-identical to local prefill.

        Every request must carry an explicit PRNG key: the stream crosses
        engines, and a key derived from THIS engine's base key would break
        the target's determinism contract (the service/fleet assign keys at
        accept time, so theirs always do)."""
        if self.paged_kv:
            raise NotImplementedError(
                "paged engines do not serve behind a dedicated prefill "
                "stream yet: the handoff admit would need the decode "
                "replica's block tables planned at compute time; prefill "
                "locally (the paged admit is a block scatter either way)"
            )
        for r in requests:
            if r.key is None:
                raise ValueError(
                    "prefill_compute requires explicit request keys (the "
                    "service/fleet assign them at accept time); a key derived "
                    "from the prefill replica's base key would not survive the "
                    "cross-engine handoff"
                )
        stacked, plen, budgets, keys = self._group_arrays(requests, group)
        if self.spec is not None:
            # Spec chain: the first generated event draws under
            # fold_in(request_key, 0) and the request keys never advance;
            # the handoff additionally carries the draft cache seed (r20,
            # spec x prefill stream).
            big1, caches1, fer, dcaches1, history1 = self._prefill_compute_spec_jit(
                bucket_len, group
            )(self.params, self.draft_params, stacked, plen, keys)
            return PrefillHandoff(
                requests=list(requests),
                group=group,
                big=big1,
                caches=caches1,
                plen=plen,
                budgets=budgets,
                keys=keys,
                first_event_real=fer,
                draft_caches=dcaches1,
                draft_history=history1,
            )
        big1, caches1, keys1, fer = self._prefill_compute_jit(bucket_len, group)(
            self.params, stacked, plen, keys
        )
        return PrefillHandoff(
            requests=list(requests),
            group=group,
            big=big1,
            caches=caches1,
            plen=plen,
            budgets=budgets,
            keys=keys1,
            first_event_real=fer,
        )

    def admit_prefilled(self, handoff: "PrefillHandoff", slots: list[int]) -> None:
        """Scatters a prefill-stream handoff into this engine's slots — the
        only work the decode replica pays for an admission when a dedicated
        prefill tier runs (the full prefill forward happened on the prefill
        replica's dispatch stream)."""
        if self.paged_kv:
            raise NotImplementedError(
                "paged engines do not take prefill-stream handoffs "
                "(see prefill_compute)"
            )
        n, g = len(handoff.requests), handoff.group
        if len(slots) != n:
            raise ValueError(f"{n} handoff rows need {n} slots, got {len(slots)}")
        if (handoff.draft_caches is not None) != (self.spec is not None):
            raise ValueError(
                "prefill-stream handoff/engine spec-mode mismatch: a "
                "speculative decode replica needs the draft cache seed in "
                "the handoff (and a non-spec replica cannot admit one) — "
                "pair spec targets with a spec-configured prefill stream"
            )
        slots_arr = jnp.asarray(list(slots) + [self.n_slots] * (g - n), jnp.int32)
        if self.spec is not None:
            self._state, self._spec_state = self._admit_spec_jit(g)(
                self._state,
                self._spec_state,
                handoff.big,
                handoff.caches,
                handoff.plen,
                handoff.budgets,
                handoff.keys,
                handoff.first_event_real,
                handoff.draft_caches,
                handoff.draft_history,
                slots_arr,
            )
        else:
            self._state = self._admit_jit(g)(
                self._state,
                handoff.big,
                handoff.caches,
                handoff.plen,
                handoff.budgets,
                handoff.keys,
                handoff.first_event_real,
                slots_arr,
            )
        for r, s in zip(handoff.requests, slots):
            self._table[s] = r
            self._slot_epoch[s] = self._dispatched_chunks

    def _harvest(
        self, boundary: np.ndarray, chunk_index: int, now: float, fetch_results: bool
    ) -> list[EngineResult]:
        """``boundary`` is one chunk's single packed readback (see
        `issue_chunk`): rows [done, cursor, base_len, n_generated], each
        ``(n_slots,)``, packed right after chunk ``chunk_index`` was
        dispatched. Only slots whose current request was admitted BEFORE
        that chunk (`_slot_epoch` < ``chunk_index``) are harvested — a
        pipelined boundary predates any newer admission into a recycled
        slot, and its stale done bit must not harvest the new tenant."""
        done_np = boundary[0].astype(bool)
        health_np = (
            boundary[self._boundary_health_row].astype(bool)
            if self._boundary_health_row is not None
            else np.zeros(self.n_slots, bool)
        )
        finished = [
            s
            for s in range(self.n_slots)
            if self._table[s] is not None
            and done_np[s]
            and self._slot_epoch[s] < chunk_index
        ]
        if not finished:
            return []
        # Health triage BEFORE extraction: a quarantined slot's request is
        # either re-queued for a deterministic retry from its bound key
        # (health_retries budget; the key was fixed at accept, so the retry
        # reproduces exactly what an unpoisoned run would have produced) or
        # fails loudly with a typed `SlotHealthError` — its garbage row is
        # never extracted, never returned as content.
        emit: list[tuple[int, bool]] = []  # (slot, failed)
        for s in finished:
            bad = bool(health_np[s]) and self.health_sentinel
            if bad:
                self._health_quarantined += 1
                req = self._table[s]
                if req.health_retries < self.health_retries:
                    self._table[s] = None
                    if req.key is None:
                        # Materialize the bound key so the re-queued request
                        # survives re-admission under a NEW admission index
                        # with its ORIGINAL derivation intact.
                        req.key = self._request_key(req)
                    req.health_retries += 1
                    self._health_retried += 1
                    self.scheduler.requeue_front(req)
                    continue
                self._health_failed += 1
            emit.append((s, bad))
        if not emit:
            return []
        fetch_slots = [s for s, bad in emit if not bad]
        if fetch_results and fetch_slots:
            g = self.scheduler.group_size_for(len(fetch_slots))
            slots = jnp.asarray(fetch_slots + [0] * (g - len(fetch_slots)), jnp.int32)
            rows, cursors, base_lens, n_gens = self._extract_jit(g)(self._state, slots)
            rows = jax.tree_util.tree_map(
                lambda x: None if x is None else np.asarray(x), rows
            )  # graftcheck: allow GC001 -- result-content harvest readback (fetch mode) by design
            cursors = np.asarray(cursors)  # graftcheck: allow GC001 -- result-content harvest readback (fetch mode) by design
            base_lens = np.asarray(base_lens)
            n_gens = np.asarray(n_gens)
            acct = {
                s: (int(cursors[i]), int(base_lens[i]), int(n_gens[i]))
                for i, s in enumerate(fetch_slots)
            }
            row_of = {s: i for i, s in enumerate(fetch_slots)}
        else:
            # Accounting-only harvest (offline throughput benches): no
            # second transfer at all — the per-slot accounting already rode
            # the chunk's one packed readback.
            rows = None
            row_of = {}
            acct = {}
        for s, _bad in emit:
            if s not in acct:
                acct[s] = (int(boundary[1][s]), int(boundary[2][s]), int(boundary[3][s]))
        results = []
        for s, bad in emit:
            req = self._table[s]
            self._table[s] = None
            if self.sanitizer is not None:
                self.sanitizer.note_harvest(s, req, chunk_index)
            spec_proposed = spec_accepted = 0
            if self.spec is not None:
                # Rows 4/5 of the spec boundary pack: this tenant's proposal
                # and draft-acceptance totals (zeroed at admission). The
                # scheduler keeps the engine-wide accepted-event budget
                # accounting from the same numbers.
                spec_proposed = int(boundary[4][s])
                spec_accepted = int(boundary[5][s])
                self.scheduler.note_spec_harvest(
                    proposed=spec_proposed,
                    accepted=spec_accepted,
                    committed=int(boundary[1][s]) - int(boundary[2][s]),
                )
            n_events, prompt_len, n_gen = acct[s]
            if rows is not None and s in row_of:
                i = row_of[s]
                row = jax.tree_util.tree_map(
                    lambda x: None if x is None else x[i : i + 1], rows
                )
                row = row.replace(
                    event_mask=row.event_mask[:, :n_events],
                    time_delta=row.time_delta[:, :n_events],
                    dynamic_indices=row.dynamic_indices[:, :n_events],
                    dynamic_measurement_indices=row.dynamic_measurement_indices[
                        :, :n_events
                    ],
                    dynamic_values=row.dynamic_values[:, :n_events],
                    dynamic_values_mask=row.dynamic_values_mask[:, :n_events],
                )
            else:
                row = None
            error = None
            if bad:
                from .errors import SlotHealthError

                error = SlotHealthError(
                    f"non-finite logits/values detected in decode slot {s} "
                    f"(request {req.request_id!r}, admission index "
                    f"{req.admission_index}); the slot was quarantined at "
                    f"chunk {chunk_index} and its co-residents are untouched",
                    request_id=req.request_id,
                    admission_index=req.admission_index,
                    slot=s,
                    chunk_index=chunk_index,
                )
            results.append(
                EngineResult(
                    request_id=req.request_id,
                    admission_index=req.admission_index,
                    batch=row,
                    prompt_len=prompt_len,
                    n_events=n_events,
                    n_generated=n_gen,
                    completion_time=now,
                    spec_proposed=spec_proposed,
                    spec_accepted=spec_accepted,
                    error=error,
                )
            )
        return results

    # ------------------------------------------------------------- run loop
    # THE admission finiteness door (one rule set for engine, service, and
    # ingester — `scheduler.check_prompt_finite`), re-exported here because
    # the engine is the canonical place callers look for it.
    check_prompt_finite = staticmethod(check_prompt_finite)

    def submit(self, request: Request) -> Request:
        if request.max_new_events < 1:
            raise ValueError("max_new_events must be >= 1")
        if request.prompt_len + request.max_new_events > self.max_len:
            raise ValueError(
                f"prompt ({request.prompt_len}) + budget ({request.max_new_events}) "
                f"exceeds max_len ({self.max_len})"
            )
        if self.validate_prompts and not request.prompt_validated:
            reason = self.check_prompt_finite(request.prompt)
            if reason is not None:
                from .errors import MalformedPromptRejected

                self.scheduler.note_malformed_reject()
                raise MalformedPromptRejected(
                    f"request {request.request_id!r}: {reason} — rejected at "
                    "the door (no admission index bound; a non-finite prompt "
                    "would poison its decode slot)"
                )
        return self.scheduler.submit(request)

    def fork(
        self,
        prompt: EventStreamBatch,
        n_branches: int,
        max_new_events: int,
        *,
        key=None,
        request_id=None,
        request_ids=None,
        arrival_time: float = 0.0,
    ) -> list[Request]:
        """Submits one shared prompt as ``n_branches`` copy-on-write
        branches: ONE prefill forward lands the shared history in frozen
        refcounted blocks; each branch holds only its partial prompt block
        + generation tail privately, and draws from
        ``fold_in(session_key, branch_index)`` — results are bitwise
        identical to ``n_branches`` independent submissions of the same
        prompt with those explicit keys, at 1/n_branches of the prefill
        compute and ~1/n_branches of the prefix HBM.

        ``key`` (optional) is the session key; without it the session key
        is ``fold_in(engine_key, branch-0 admission index)``, exactly what
        an independent submission of branch 0 would have bound.
        ``request_id`` (optional) stamps branch results as
        ``(request_id, branch_index)``; ``request_ids`` (optional,
        exclusive with ``request_id``) gives each branch its caller id
        directly — the service tier routes results by its own admission
        indices this way. The fork group admits atomically (all branches
        in one prefill dispatch, strict FIFO)."""
        if not self.paged_kv:
            raise ValueError(
                "fork() needs the paged KV cache (paged_kv=True): branched "
                "rollouts share prefix blocks copy-on-write, which the "
                "monolithic per-slot cache cannot express"
            )
        n_branches = int(n_branches)
        if n_branches < 1:
            raise ValueError("n_branches must be >= 1")
        if request_ids is not None:
            if request_id is not None:
                raise ValueError("pass request_id or request_ids, not both")
            if len(request_ids) != n_branches:
                raise ValueError(
                    f"request_ids has {len(request_ids)} entries for "
                    f"{n_branches} branches"
                )
        if n_branches > self.n_slots:
            raise ValueError(
                f"a fork group admits atomically: n_branches ({n_branches}) "
                f"cannot exceed n_slots ({self.n_slots})"
            )
        sched = self.scheduler
        if (
            sched.max_pending is not None
            and len(sched.queue) + n_branches > sched.max_pending
        ):
            from .scheduler import AdmissionRejected

            sched._rejected += 1
            raise AdmissionRejected(
                f"admission queue cannot hold a {n_branches}-branch fork "
                f"group ({len(sched.queue)}/{sched.max_pending}); rejecting "
                "the whole group (branches admit atomically)"
            )
        spec = ForkSpec(
            group_id=self._next_fork_group,
            n_branches=n_branches,
            session_key=None if key is None else _as_raw_key(key),
        )
        self._next_fork_group += 1
        out = []
        for j in range(n_branches):
            if request_ids is not None:
                rid = request_ids[j]
            else:
                rid = None if request_id is None else (request_id, j)
            r = Request(
                prompt=prompt,
                max_new_events=max_new_events,
                key=None,
                request_id=rid,
                arrival_time=arrival_time,
                fork=spec,
                branch_index=j,
            )
            if out:
                # Branch 0's door validation covered the shared prompt.
                r.prompt_validated = True
            out.append(self.submit(r))
        return out

    @property
    def occupied(self) -> int:
        return sum(t is not None for t in self._table)

    @property
    def inflight_chunks(self) -> int:
        """Decode chunks dispatched whose boundary has not been resolved."""
        return len(self._inflight)

    def free_slots(self) -> list[int]:
        """Slot indices with no resident request (host view — a slot that
        finished on device stays occupied until its boundary resolves)."""
        return [s for s in range(self.n_slots) if self._table[s] is None]

    def plan_and_dispatch(
        self, now: float | None = None, max_padded_events: int | None = None
    ) -> int:
        """Plans admissions for the current free slots and dispatches the
        prefill groups; returns the number of requests admitted.
        ``max_padded_events`` is the per-boundary prefill budget (prefill/
        decode disaggregation — see `scheduler.Scheduler.plan_admissions`)."""
        free = self.free_slots()
        if not free or not self.scheduler.pending:
            return 0
        groups = self.scheduler.plan_admissions(
            free, now=now, max_padded_events=max_padded_events
        )
        for g in groups:
            self._dispatch_group(g)
        return sum(len(g.requests) for g in groups)

    def issue_chunk(self) -> None:
        """Dispatches one decode chunk and starts its boundary readback.

        The packed ``(4, n_slots)`` boundary (done mask + per-slot
        accounting — ONE small device->host copy per chunk) is computed on
        device immediately after the decode dispatch and its host copy
        started with ``copy_to_host_async``; nothing blocks. The boundary
        queues on `_inflight` (strict FIFO: boundaries resolve in issue
        order regardless of when their copies land).

        Spec mode dispatches ``decode_chunk`` draft-chunk + verify rounds
        per boundary (each round commits 1..K+1 events per active slot)
        instead of ``decode_chunk`` single-event steps; the boundary pack
        additionally carries the per-tenant proposed/accepted counters."""
        from ..reliability import serving_faults as _sfaults

        if _sfaults.active_serving_fault_plan() is not None:
            # Deterministic fault injection (reliability/serving_faults.py),
            # keyed on this engine's dispatched-chunk counter — no wall
            # clock. One `None` check when no plan is installed.
            _sfaults.maybe_die(self.fault_scope, self._dispatched_chunks)
            _sfaults.maybe_hang(self.fault_scope, self._dispatched_chunks)
            poison = [
                s
                for s in _sfaults.poison_slots(
                    self.fault_scope, self._dispatched_chunks
                )
                if 0 <= s < self.n_slots and self._table[s] is not None
            ]
            if poison:
                self._state = self._poison_jit(len(poison))(
                    self._state, jnp.asarray(poison, jnp.int32)
                )
        if self.spec is not None:
            for _ in range(self.decode_chunk):
                self._state, self._spec_state, proposals = self._spec_draft_jit(
                    self.draft_params, self._state, self._spec_state
                )
                self._state, self._spec_state = self._spec_verify_jit(
                    self.params, self._state, self._spec_state, proposals
                )
            self._dispatched_chunks += 1
            boundary = self._pack_boundary_jit(self._state, self._spec_state)
        else:
            self._state = self._decode_jit(self.params, self._state)
            self._dispatched_chunks += 1
            boundary = self._pack_boundary_jit(self._state)
        try:
            boundary.copy_to_host_async()
        except AttributeError:  # older jax Array impls: resolve() blocks
            pass
        self._inflight.append((self._dispatched_chunks, boundary))
        if self.sanitizer is not None:
            self.sanitizer.note_issue(self._dispatched_chunks)

    def resolve_chunk(self, now: float, fetch_results: bool = True) -> list[EngineResult]:
        """Resolves the OLDEST in-flight boundary and harvests its finished
        rows. Blocks only if that boundary's async copy has not landed yet
        (in steady state it has — the device raced ahead)."""
        chunk_index, boundary = self._inflight.popleft()
        if self.sanitizer is not None:
            self.sanitizer.note_resolve(chunk_index)
        host = np.asarray(boundary)  # graftcheck: allow GC001 -- chunk-boundary readback by design (async copy started at dispatch)
        self._resolved_chunks += 1
        return self._harvest(host, chunk_index, now, fetch_results)

    def run(
        self,
        requests: Sequence[Request] = (),
        *,
        use_arrival_times: bool = False,
        fetch_results: bool = True,
        max_padded_events: int | None = None,
    ) -> list[EngineResult]:
        """Drains the queue (plus ``requests``) to completion.

        The dispatch loop is pipelined: up to ``dispatch_depth`` decode
        chunks are issued before the oldest boundary readback is resolved,
        so host harvest/refill planning overlaps device decode (results are
        bitwise identical at any depth; depth 1 reproduces the synchronous
        PR-5 schedule). With ``use_arrival_times`` the loop replays each
        request's ``arrival_time`` (seconds, relative) against a wall clock
        — the Poisson-arrival latency benchmark mode; ``completion_time``
        on each result is measured on the same clock. ``fetch_results=
        False`` skips the finished-row content transfer (results carry
        accounting only) — the offline-throughput benchmark mode.
        ``max_padded_events`` caps per-boundary prefill admission work.
        """
        for r in requests:
            self.submit(r)
        results: list[EngineResult] = []
        t0 = time.perf_counter()

        while self.scheduler.pending or self.occupied or self._inflight:
            now = time.perf_counter() - t0
            self.plan_and_dispatch(
                now=now if use_arrival_times else None,
                max_padded_events=max_padded_events,
            )
            if self.occupied:
                self.issue_chunk()
                if len(self._inflight) < self.dispatch_depth and self.occupied:
                    # Keep the pipe full before paying a resolve.
                    continue
            if self._inflight:
                results.extend(
                    self.resolve_chunk(time.perf_counter() - t0, fetch_results)
                )
            elif self.scheduler.pending:
                time.sleep(1e-3)  # waiting on arrivals
        return sorted(results, key=lambda r: r.admission_index)

    # ---------------------------------------------------- hot weight swap
    def _swap_reshard_jit(self):
        """The shadow-load program: an identity jit pinned to the live
        params' layout, so a host-loaded checkpoint lands in the shadow
        buffer already resharded/laid out exactly like the weights the
        decode program reads — the flip is then a pure pointer swap, no
        compile, no reshard, no dispatch. Gated by graftcheck like any
        canonical program (``engine_swap:swap_reshard``)."""
        if self._swap_reshard_memo is None:
            if self._param_shardings is not None:
                self._swap_reshard_memo = jax.jit(
                    lambda p: p, out_shardings=self._param_shardings
                )
            else:
                self._swap_reshard_memo = jax.jit(lambda p: p)
        return self._swap_reshard_memo

    def load_shadow(self, new_params, new_draft_params=None) -> None:
        """Loads ``new_params`` into the shadow weight buffer beside the
        live weights (`hot_swap` must be enabled — `slots_report` has been
        accounting the second buffer since construction, so this allocation
        never overcommits HBM). Serving continues on the live buffer; call
        `flip` at a drained chunk boundary to promote.

        Spec engines stage ``new_draft_params`` alongside; `flip` then swaps
        draft and target **atomically** — scoring one checkpoint's
        proposals with the other's densities would silently change the
        sampled distribution mid-promotion. ``None`` keeps the live draft
        (a target-only promotion — correct, the draft only buys speed, but
        expect the acceptance rate to sag until the draft catches up)."""
        if not self.hot_swap:
            raise RuntimeError(
                "hot_swap is disabled for this engine; construct with "
                "hot_swap=True to reserve the shadow weight buffer"
            )
        live = jax.tree_util.tree_structure(self.params)
        new = jax.tree_util.tree_structure(new_params)
        if live != new:
            raise ValueError(
                "shadow checkpoint's parameter tree does not match the live "
                f"weights: {new} vs {live}"
            )
        if new_draft_params is None:
            # Target-only staging keeps the LIVE draft: drop any armed
            # rollback draft from a previous promotion, or the next flip
            # would silently swap a two-generations-old draft back in.
            self._shadow_draft_params = None
        else:
            if self.spec is None:
                raise ValueError(
                    "new_draft_params on a non-speculative engine; construct "
                    "with spec=SpecConfig(...) to serve a draft model"
                )
            d_live = jax.tree_util.tree_structure(self.draft_params)
            d_new = jax.tree_util.tree_structure(new_draft_params)
            if d_live != d_new:
                raise ValueError(
                    "shadow draft checkpoint's parameter tree does not match "
                    f"the live draft: {d_new} vs {d_live}"
                )
            if self._swap_draft_reshard_memo is None:
                self._swap_draft_reshard_memo = (
                    jax.jit(
                        lambda p: p,
                        out_shardings=jax.tree_util.tree_map(
                            lambda _: NamedSharding(self.mesh, P()), self.draft_params
                        ),
                    )
                    if self.mesh is not None
                    else jax.jit(lambda p: p)
                )
            self._shadow_draft_params = self._swap_draft_reshard_memo(new_draft_params)
        from ..reliability import serving_faults as _sfaults

        # Deterministic corruption injection (a torn/garbled staged
        # checkpoint); `ServingFleet.promote`'s verification probe must
        # catch it before any flip. No-op without an installed plan.
        new_params = _sfaults.maybe_corrupt_shadow(self.fault_scope, new_params)
        self._shadow_params = self._swap_reshard_jit()(new_params)

    @property
    def shadow_loaded(self) -> bool:
        return self._shadow_params is not None

    def probe_shadow(self) -> Optional[str]:
        """Finite-output probe on the staged shadow checkpoint — the
        promotion verification gate. Runs the bucketed prefill forward
        (the engine's own program shape, on the engine's own template) on
        the SHADOW weights and checks every float output leaf finite.
        Returns ``None`` when healthy, else a reason string; never touches
        live slot state or the live weights, so probing under traffic is
        safe. A spec engine's staged shadow draft is probed through its own
        prompt forward in the same call."""
        if self._shadow_params is None:
            raise RuntimeError("no shadow checkpoint loaded (call load_shadow first)")
        t = self._template
        Lb = min(t.sequence_length, self.max_prompt_len)
        row = self._pad_prompt_row(t.slice((slice(0, 1), slice(0, Lb))))
        plen = jnp.asarray([Lb], jnp.int32)
        keys = jnp.zeros((1, 2), jnp.uint32)
        fwd = self._prefill_forward_na if self._is_na else self._prefill_forward_ci
        big1, caches1, _, _ = fwd(Lb, self._shadow_params, row, plen, keys)

        def first_nonfinite(tree, what: str) -> Optional[str]:
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
                if leaf is None or not jnp.issubdtype(
                    jnp.asarray(leaf).dtype, jnp.floating
                ):
                    continue
                if not bool(np.isfinite(np.asarray(leaf)).all()):  # graftcheck: allow GC001 -- promotion-gate verification readback by design
                    return (
                        f"staged shadow checkpoint produced non-finite {what} "
                        f"at {jax.tree_util.keystr(path)}"
                    )
            return None

        reason = first_nonfinite(big1, "prompt-forward outputs")
        if reason is None:
            reason = first_nonfinite(caches1, "prefill cache values")
        if reason is None and self._shadow_draft_params is not None:
            dcaches = self._prefill_draft_forward(
                Lb, self._shadow_draft_params, row, big1, plen
            )
            reason = first_nonfinite(dcaches, "draft prefill cache values")
        return reason

    def flip(self) -> None:
        """Swaps the live and shadow weight pointers — the zero-downtime
        promotion step. Requires a loaded shadow and a drained engine (no
        resident slots, no in-flight boundaries): a flip under residents
        would decode half a request on each checkpoint, breaking the
        post-flip bit-identity contract (pending queued requests are fine —
        they prefill after the flip, wholly on the new weights). The old
        weights stay in the shadow buffer for rollback until the next
        `load_shadow` or `drop_shadow`."""
        if self._shadow_params is None:
            raise RuntimeError("no shadow checkpoint loaded (call load_shadow first)")
        if self.occupied or self._inflight:
            raise RuntimeError(
                f"flip requires a drained engine: {self.occupied} resident "
                f"slots, {len(self._inflight)} in-flight boundaries — drain "
                "(stop admitting, resolve every boundary) before flipping"
            )
        self.params, self._shadow_params = self._shadow_params, self.params
        if self._shadow_draft_params is not None:
            # Atomic with the target flip: both pointers move in this one
            # host step between dispatches — no round ever scores one
            # checkpoint's proposals with the other's densities.
            self.draft_params, self._shadow_draft_params = (
                self._shadow_draft_params,
                self.draft_params,
            )
        self.weights_version += 1

    def drop_shadow(self) -> None:
        """Releases the shadow buffer's arrays (the rollback checkpoint)."""
        self._shadow_params = None
        self._shadow_draft_params = None

    def reset(self) -> None:
        """Clears all slot/queue state, keeping every compiled program.

        Benchmarks warm the (bucket, group) program set with a full dry run,
        reset, and time the second pass — compile time never lands in the
        measured window (mirroring every other bench section's discipline).
        """
        self._state = self._init_state()
        if self.spec is not None:
            self._spec_state = self._init_spec_state()
        if self.mesh is not None:
            self._state = jax.device_put(self._state, self._state_shardings())
            if self._spec_state is not None:
                self._spec_state = jax.device_put(
                    self._spec_state, self._tree_shardings(self._spec_state)
                )
        self._table = [None] * self.n_slots
        self._slot_epoch = [0] * self.n_slots
        self._dispatched_chunks = 0
        self._resolved_chunks = 0
        self._health_quarantined = 0
        self._health_failed = 0
        self._health_retried = 0
        self._inflight.clear()
        self.scheduler = Scheduler(
            self.n_slots,
            self.scheduler.buckets,
            group_sizes=self.scheduler.group_sizes,
            max_pending=self.scheduler.max_pending,
        )
        if self.paged_kv:
            # All occupancy returns to the pool; the lifetime high-water and
            # fragmentation counters deliberately survive (padding_report
            # contract), as does the fork-group id sequence.
            self._block_alloc.reset_occupancy()
            self._tables[:] = 0
            self.scheduler.block_pool_stats = self._block_pool_stats
        if self.sanitizer is not None:
            # Re-hook the fresh Scheduler (and keep allocator/engine wiring);
            # the event log restarts with the control-plane state.
            self.sanitizer.rebind(self)
            self.sanitizer.reset_log()

    # ---------------------------------------------------------- accounting
    def _block_pool_stats(self) -> dict:
        """The block-pool counters `Scheduler.padding_report` merges in
        (installed as ``scheduler.block_pool_stats`` — on the scheduler
        each `reset()` builds, so high-water/fragmentation survive reset
        by living on the allocator, not the scheduler)."""
        a = self._block_alloc
        return {
            "block_pool_num_blocks": a.num_blocks,
            "block_pool_block_size": a.block_size,
            "block_pool_in_use": a.in_use,
            "block_pool_free": a.free_blocks,
            "block_pool_high_water": a.high_water,
            "block_pool_utilization": round(
                a.in_use / max(a.num_blocks - 1, 1), 4
            ),
            "block_pool_shared_blocks": a.shared_blocks(),
            "block_pool_frag_events": a.frag_events,
            "block_pool_frag_frac": round(
                a.frag_events / max(a.frag_events + a.cover_events, 1), 4
            ),
            "block_pool_allocs_total": a.allocs_total,
            "block_pool_frees_total": a.frees_total,
        }

    def _paged_report(
        self, branch_factor: int = 1, pool_budget_bytes: int | None = None
    ) -> dict:
        """Block-granular capacity accounting for the paged engine.

        ``effective_slots`` is MEASURED from the resident block tables:
        usable pool blocks divided by the mean unique-block footprint per
        resident row — with B branches sharing a long prefix, each row's
        footprint shrinks toward ``prefix_blocks / B`` and effective slots
        grow toward B x the monolithic count.
        ``effective_slots_at_branch_factor`` is the analytic figure for a
        hypothetical prefix-dominated workload at ``branch_factor``."""
        cfg = self.config
        a = self._block_alloc
        T = self.max_len // self.block_size
        usable = a.num_blocks - 1
        bpb = paged_kv_bytes_per_block(
            cfg.num_hidden_layers,
            cfg.num_attention_heads,
            self.block_size,
            cfg.head_dim,
            self.kv_cache_dtype,
            cfg.compute_dtype,
        )
        resident_rows = int((self._tables != 0).any(axis=1).sum())
        logical_blocks = int((self._tables != 0).sum())
        unique_blocks = a.in_use
        sharing = logical_blocks / max(unique_blocks, 1)
        if resident_rows:
            per_row_unique = unique_blocks / resident_rows
            effective = usable / max(per_row_unique, 1e-9)
        else:
            effective = float(usable) / max(T, 1) * 1.0
        B = max(int(branch_factor), 1)
        # Prefix-dominated analytic bound: a full-table tenant whose prompt
        # prefix (all but one block) is shared B ways.
        per_branch = (T - 1) / B + 1
        # Budget-aware pool sizing: how many blocks an ``hbm_gb`` budget
        # could hold net of weights. The budget arrives from `slots_report`
        # with hot-swap params already doubled EXACTLY ONCE (the shadow
        # buffer is one extra copy, reserved for the swap lifetime) — this
        # report must never re-double it, and `pool_bytes` itself (the
        # allocated pool) is invariant to hot_swap.
        budget_blocks = (
            None if pool_budget_bytes is None else int(pool_budget_bytes // bpb)
        )
        return {
            "pool_budget_bytes": pool_budget_bytes,
            "max_pool_blocks_in_budget": budget_blocks,
            "block_size": self.block_size,
            "num_blocks": a.num_blocks,
            "blocks_per_slot": T,
            "bytes_per_block": bpb,
            "pool_bytes": usable * bpb,
            "blocks_in_use": unique_blocks,
            "pool_utilization": round(unique_blocks / max(usable, 1), 4),
            "high_water": a.high_water,
            "resident_rows": resident_rows,
            "sharing_ratio": round(sharing, 3),
            "effective_slots": round(effective, 2),
            "effective_slots_at_branch_factor": round(usable / per_branch, 2),
            "branch_factor": B,
        }

    def slots_report(
        self,
        hbm_gb: float = 16.0,
        config=None,
        max_len: int | None = None,
        params_bytes: int | None = None,
        branch_factor: int = 1,
    ) -> dict:
        """Per-cache-dtype HBM capacity accounting (no allocation).

        For each supported cache dtype (`ops.kv_quant.CACHE_DTYPES`):
        the seq KV-cache bytes one decode slot pins at this engine's
        ``max_len`` (planes + scale tables for quantized dtypes), and the
        max admissible slot count against an ``hbm_gb`` budget net of the
        replicated parameters and the per-slot content rows. The active
        dtype and its slot-capacity ratio vs bf16 head the report — the
        bench surfaces the ratio as ``kvq_slots_per_chip_ratio``.

        ``config`` / ``max_len`` / ``params_bytes`` override the engine's
        own geometry so capacity stays honest at widths this engine was not
        built at: a wider config (hidden 1024 → 4096) reads slots/chip
        through the SAME accounting instead of
        extrapolating from the probe shape (r10 satellite). The per-slot
        content-row term is measured from THIS engine's state and re-scaled
        by the ``max_len`` ratio (content rows grow with sequence capacity,
        not hidden width) — an estimate, but one that errs alongside the
        dominant KV term instead of ignoring the override.

        Paged engines add a ``paged`` sub-dict (`_paged_report`):
        bytes/block, pool utilization + high-water, the measured
        block-sharing ratio over resident tables, and ``effective_slots``
        (measured, plus the analytic figure at ``branch_factor``).
        """
        from ..ops.kv_quant import (
            CACHE_DTYPES,
            cache_dtype_name,
            kv_cache_bytes_per_slot,
        )

        cfg = config if config is not None else self.config
        max_len = max_len if max_len is not None else self.max_len
        # Non-cache per-slot state: the content rows + cursors (and the NA
        # dep-graph caches, which stay in the compute dtype by design).
        state_bytes = sum(
            x.nbytes for x in jax.tree_util.tree_leaves(self._state)
        )
        seq_caches = (
            self._state.caches.seq_past if self._is_na else self._state.caches
        )
        seq_cache_bytes = sum(
            x.nbytes for x in jax.tree_util.tree_leaves(seq_caches)
        )
        row_bytes = max((state_bytes - seq_cache_bytes) // self.n_slots, 1)
        if max_len != self.max_len:
            row_bytes = max(int(row_bytes * max_len / self.max_len), 1)
        if params_bytes is None:
            params_bytes = sum(
                x.nbytes for x in jax.tree_util.tree_leaves(self.params)
            )
        # Speculative decoding: the draft model's params are a second
        # resident weight tree (doubled again under hot_swap — promotion
        # stages a shadow draft too) and every slot pins a draft KV-cache
        # row at the same max_len. Omitting either would let capacity
        # planning overcommit HBM exactly when spec mode is on.
        draft_params_bytes = 0
        draft_kv_bytes = 0
        if self.spec is not None:
            draft_params_bytes = sum(
                x.nbytes for x in jax.tree_util.tree_leaves(self.draft_params)
            )
            dcfg = self.spec.config
            # The draft rows share the engine's cache dtype (they quantize
            # on write exactly like the target's — `_init_spec_state`), so
            # they are charged at the ACTIVE cache dtype, not the draft's
            # float compute dtype: under spec x int8 the old float estimate
            # overcharged every slot and understated max_slots.
            draft_kv_bytes = kv_cache_bytes_per_slot(
                dcfg.num_hidden_layers,
                dcfg.num_attention_heads,
                max_len,
                dcfg.head_dim,
                cache_dtype_name(self._kv_buf_dtype),
                dcfg.compute_dtype,
            )
        if self.hot_swap:
            # Double-buffered weights: the shadow buffer is reserved for the
            # whole hot-swap lifetime (not just while a checkpoint is staged),
            # so capacity planning never overcommits HBM during a swap window.
            params_bytes = 2 * params_bytes
            draft_params_bytes = 2 * draft_params_bytes
        budget = max(int(hbm_gb * 1e9) - params_bytes - draft_params_bytes, 0)

        per_dtype = {}
        for name in CACHE_DTYPES:
            kv_bytes = kv_cache_bytes_per_slot(
                cfg.num_hidden_layers,
                cfg.num_attention_heads,
                max_len,
                cfg.head_dim,
                name,
                cfg.compute_dtype,
            )
            per_dtype[name] = {
                "kv_bytes_per_slot": kv_bytes,
                "max_slots": int(budget // (kv_bytes + row_bytes + draft_kv_bytes)),
            }
        # Canonical name (not the raw constructor string — aliases like
        # "bfloat16"/"f32" are accepted and must index per_dtype).
        active_name = cache_dtype_name(self._kv_buf_dtype)
        ratio = per_dtype[active_name]["max_slots"] / max(
            per_dtype["bf16"]["max_slots"], 1
        )
        paged = (
            self._paged_report(
                branch_factor=branch_factor, pool_budget_bytes=budget
            )
            if self.paged_kv
            else None
        )
        return {
            "paged_kv": self.paged_kv,
            "paged": paged,
            "kv_cache_dtype": active_name,
            "hbm_budget_gb": hbm_gb,
            "hot_swap": self.hot_swap,
            "params_bytes": params_bytes,
            "spec": self.spec is not None,
            "draft_params_bytes": draft_params_bytes,
            "draft_kv_bytes_per_slot": draft_kv_bytes,
            "row_bytes_per_slot": int(row_bytes),
            "per_dtype": per_dtype,
            "slots_per_chip_ratio_vs_bf16": round(ratio, 3),
        }

    def stats(self) -> dict:
        total = self._dispatched_chunks * self.decode_chunk * self.n_slots
        active = int(np.asarray(self._state.active_steps))  # graftcheck: allow GC001 -- post-run accounting readback
        report = dict(self.scheduler.padding_report())
        report.update(
            {
                "n_slots": self.n_slots,
                "decode_chunk": self.decode_chunk,
                "dispatch_depth": self.dispatch_depth,
                "dispatched_chunks": self._dispatched_chunks,
                "resolved_chunks": self._resolved_chunks,
                "slot_steps": total,
                "active_slot_steps": active,
                "wasted_decode_frac": round(1.0 - active / max(total, 1), 4),
                "sampling_impl": self.sampling_impl_resolved,
                "greedy": self.greedy,
                "health_sentinel": self.health_sentinel,
                "health_quarantined_total": self._health_quarantined,
                "health_failed_total": self._health_failed,
                "health_retried_total": self._health_retried,
                "slots_report": self.slots_report(),
            }
        )
        if self.spec is not None:
            rounds = int(np.asarray(self._spec_state.rounds))  # graftcheck: allow GC001 -- post-run accounting readback
            report.update(
                {
                    "spec_k": self.spec.k,
                    "spec_rounds": rounds,
                    "spec_value_rtol": self.spec.value_rtol,
                    "spec_value_atol": self.spec.value_atol,
                    "spec_draft_hidden_size": self.spec.config.hidden_size,
                    "spec_draft_num_layers": self.spec.config.num_hidden_layers,
                }
            )
        return report

    def spec_signature(self):
        """The spec-mode identity the service's placement-invariance
        contract hangs on: two replicas produce bit-identical results for
        the same request only if their draft/K/tolerance/greedy knobs agree
        (sampled-mode committed values depend on the draft's proposals).
        ``(greedy, None)`` for non-speculative engines."""
        if self.spec is None:
            return (self.greedy, None)
        # Draft WEIGHTS are deliberately not part of the tuple (object
        # identity is meaningless across independently loaded copies of one
        # checkpoint); the service compares them with the fleet's
        # weight-fingerprint check instead.
        return (
            self.greedy,
            (
                self.spec.k,
                self.spec.value_rtol,
                self.spec.value_atol,
                self.spec.config.hidden_size,
                self.spec.config.num_hidden_layers,
            ),
        )

    # -------------------------------------------------- AOT (graftcheck B)
    def aot_programs(
        self,
        bucket_len: int | None = None,
        group: int = 1,
        include_prefill_stream: bool = False,
    ) -> dict:
        """(fn, args) pairs for the engine's compiled programs — graftcheck
        Tier B AOT-lowers these on the virtual mesh and gates them
        host-transfer-free / f64-free / within the collective budget.

        ``include_prefill_stream`` adds the dedicated-prefill split halves
        (``prefill_compute_b{L}``: the scatter-free forward a prefill
        replica dispatches; ``admit``: the state-donating scatter a decode
        replica runs on a handoff) — the fleet's canonical tp/hot-swap
        builders enable it so those hot-path programs get the same f64 /
        host-transfer / collective-budget / HBM / donation gates as the
        fused prefill, instead of escaping the census."""
        bucket_len = bucket_len or max(self.scheduler.buckets)
        t = self._template

        def tile(x, reps):
            return None if x is None else jnp.concatenate([jnp.asarray(x)] * reps, 0)

        prompt = jax.tree_util.tree_map(lambda x: x, t)
        row = self._pad_prompt_row(
            prompt.slice((slice(0, 1), slice(0, min(t.sequence_length, bucket_len))))
        )
        pbig = jax.tree_util.tree_map(lambda x: tile(x, group), row)
        plen = jnp.full((group,), min(t.sequence_length, bucket_len), jnp.int32)
        budgets = jnp.ones((group,), jnp.int32)
        keys = jnp.zeros((group, 2), jnp.uint32)
        slots = jnp.arange(group, dtype=jnp.int32)
        if self.spec is not None:
            # Spec engines compile the draft-chunk + verify pair instead of
            # the single-event decode program; the verify program's args are
            # the draft chunk's abstract outputs (AOT lowering needs shapes
            # only). The ISSUE-13 gates: the verify program must carry zero
            # NEW collective kinds vs the baseline decode (engine_dp8) — an
            # all-gather of the slot-sharded logits plane into the verify
            # hot loop is exactly the regression the budget would catch.
            dc_args = (self.draft_params, self._state, self._spec_state)
            _, _, proposals = jax.eval_shape(self._spec_draft_jit, *dc_args)
            programs = {
                "draft_chunk": (self._spec_draft_jit, dc_args),
                "verify": (
                    self._spec_verify_jit,
                    (self.params, self._state, self._spec_state, proposals),
                ),
                f"prefill_b{bucket_len}": (
                    self._prefill_spec_jit(bucket_len, group),
                    (
                        self.params,
                        self.draft_params,
                        self._state,
                        self._spec_state,
                        pbig,
                        plen,
                        budgets,
                        keys,
                        slots,
                    ),
                ),
                "boundary_pack": (
                    self._pack_boundary_jit,
                    (self._state, self._spec_state),
                ),
            }
            if include_prefill_stream:
                # The spec split pair (r20): the scatter-free target+draft
                # prefill a dedicated prefill replica dispatches, and the
                # both-chains admit the decode replica runs on a handoff.
                pc_jit = self._prefill_compute_spec_jit(bucket_len, group)
                pc_args = (self.params, self.draft_params, pbig, plen, keys)
                programs[f"prefill_compute_b{bucket_len}"] = (pc_jit, pc_args)
                big1, caches1, fer, dcaches1, history1 = jax.eval_shape(
                    pc_jit, *pc_args
                )
                programs["admit"] = (
                    self._admit_spec_jit(group),
                    (
                        self._state, self._spec_state, big1, caches1, plen,
                        budgets, keys, fer, dcaches1, history1, slots,
                    ),
                )
            return programs
        if self.paged_kv:
            # Paged prefill programs take the host-planned block tables as
            # array arguments; any in-range physical indices lower the same
            # program, so a disjoint per-row layout stands in.
            T = self.max_len // self.block_size
            tab = np.zeros((group, T), np.int32)
            for i in range(group):
                tab[i] = 1 + i * T + np.arange(T)
            read_t = jnp.asarray(tab)
            programs = {
                "decode": (self._decode_jit, (self.params, self._state)),
                f"prefill_b{bucket_len}": (
                    self._prefill_jit(bucket_len, group),
                    (
                        self.params, self._state, pbig, plen, budgets, keys,
                        slots, read_t, read_t,
                    ),
                ),
                "boundary_pack": (self._pack_boundary_jit, (self._state,)),
            }
            # The fork pipeline: one batch-1 shared-prompt forward
            # (materialized) + the g-branch tile/sample/CoW-admit program
            # (the r16 engine_paged fork programs). AOT lowering needs the
            # forward's output shapes only, so eval_shape stands in.
            plen1 = jnp.full((1,), min(t.sequence_length, bucket_len), jnp.int32)
            fwd_fn = self._prefill_fork_fwd_jit(bucket_len)
            fwd_args = (self.params, row, plen1)
            caches1, preds1, em1 = jax.eval_shape(fwd_fn, *fwd_args)
            programs[f"prefill_fork_fwd_b{bucket_len}"] = (fwd_fn, fwd_args)
            programs["prefill_fork_admit"] = (
                self._prefill_fork_admit_jit(group),
                (
                    self._state, row, caches1, preds1, em1, plen, budgets,
                    keys, slots, read_t, read_t,
                ),
            )
            if self.hot_swap:
                programs["swap_reshard"] = (
                    self._swap_reshard_jit(), (self.params,)
                )
            if include_prefill_stream:
                raise NotImplementedError(
                    "paged engines do not serve behind a dedicated prefill "
                    "stream (see prefill_compute)"
                )
            return programs
        programs = {
            "decode": (self._decode_jit, (self.params, self._state)),
            f"prefill_b{bucket_len}": (
                self._prefill_jit(bucket_len, group),
                (self.params, self._state, pbig, plen, budgets, keys, slots),
            ),
            # The boundary pack is the only program between decode and the
            # host: it must stay a pure pack (no host callbacks, no f64).
            "boundary_pack": (self._pack_boundary_jit, (self._state,)),
        }
        if self.hot_swap:
            # The shadow-load reshard (hot swap leg): must stay a pure
            # layout pin — no collectives beyond the reshard itself, no
            # host traffic — or the swap window would stall live decode.
            programs["swap_reshard"] = (self._swap_reshard_jit(), (self.params,))
        if include_prefill_stream:
            pc_jit = self._prefill_compute_jit(bucket_len, group)
            pc_args = (self.params, pbig, plen, keys)
            programs[f"prefill_compute_b{bucket_len}"] = (pc_jit, pc_args)
            # The admit scatter consumes exactly the compute half's outputs;
            # abstract shapes suffice for AOT lowering (nothing executes).
            big1, caches1, keys1, fer = jax.eval_shape(pc_jit, *pc_args)
            programs["admit"] = (
                self._admit_jit(group),
                (self._state, big1, caches1, plen, budgets, keys1, fer, slots),
            )
        return programs


# ------------------------------------------------- graftcheck Tier C census
def _census_programs():
    """The engine fleet for the Tier C census: every program the canonical
    float, quantized-cache, and fused-sampling engines compile (straight
    from their ``aot_programs`` — a new program key shows up here, or the
    census-completeness gate fails). Decode and prefill donate the engine
    state (argnum 1, matching `GenerationEngine.__init__`'s jits); the
    boundary pack is a read-only pack and must NOT donate."""
    from ..analysis import program_checks as pc
    from ..analysis.program_census import CensusProgram

    donate = {
        "decode": (1,),
        "prefill_b8": (1,),
        # The fork pipeline: the batch-1 forward materializes (no donation);
        # the admit donates the engine state it rewrites (argnum 0).
        "prefill_fork_fwd_b8": (),
        "prefill_fork_admit": (0,),
        "boundary_pack": (),
    }
    spec_donate = {
        "draft_chunk": (1, 2),
        "verify": (1, 2),
        "prefill_b8": (2, 3),
        "boundary_pack": (),
        # The r20 spec prefill-stream split: the compute half materializes
        # (a prefill replica ships its outputs across the handoff); the
        # admit donates BOTH chains' states it scatters into.
        "prefill_compute_b8": (),
        "admit": (0, 1),
    }
    budget_keys = {
        "engine:decode": "engine_dp8",
        "engine:prefill_b8": "engine_prefill_dp8",
        # The uninstrumented (health_sentinel=False) engine gates against
        # the SAME budgets as the instrumented default above — the decode
        # health sentinel must carry a byte-identical collective inventory
        # (zero new collectives, zero host transfers; the PR 3
        # dp8-vs-dp8_health contract on the serving side).
        "engine_nohealth:decode": "engine_dp8",
        "engine_nohealth:prefill_b8": "engine_prefill_dp8",
        "engine_kvq:decode": "engine_kvq_dp8",
        "engine_kvq:prefill_b8": "engine_kvq_prefill_dp8",
        # The r16 paged CoW engine: the decode budget's inventory must stay
        # within engine_dp8's KIND SET (the block gather adds zero new
        # collective kinds on dp8 — the pool replicates, so its updates ride
        # the all-gather kind the monolithic merge already carries).
        "engine_paged:decode": "engine_paged_dp8",
        "engine_paged:prefill_b8": "engine_paged_prefill_dp8",
        "engine_paged:prefill_fork_fwd_b8": "engine_paged_fork_prefill_dp8",
        "engine_paged:prefill_fork_admit": "engine_paged_fork_admit_dp8",
        "engine_sampling:decode": "engine_sampling_1dev",
        "engine_spec:draft_chunk": "engine_spec_draft_dp8",
        "engine_spec:verify": "engine_spec_verify_dp8",
        "engine_spec:prefill_b8": "engine_spec_prefill_dp8",
        "engine_spec_na:draft_chunk": "engine_spec_na_draft_1dev",
        "engine_spec_na:verify": "engine_spec_na_verify_1dev",
        # r20 composition closure: the slot-sharded fused-sampling decode
        # (the Pallas grid runs on each slot shard — its budget pins "no
        # slot-plane gather") and the composed spec × int8 × TP engine on
        # dp4×tp2 (every program's budget pins "the per-layer TP reduce
        # pattern and nothing more" on top of the spec budgets).
        "engine_sampling_shard:decode": "engine_sampling_shard_dp8",
        "engine_composed:draft_chunk": "engine_composed_draft_dp4_tp2",
        "engine_composed:verify": "engine_composed_verify_dp4_tp2",
        "engine_composed:prefill_b8": "engine_composed_prefill_dp4_tp2",
        "engine_composed:prefill_compute_b8": "engine_composed_prefill_compute_dp4_tp2",
        "engine_composed:admit": "engine_composed_admit_dp4_tp2",
    }
    out = {}
    for prefix, programs in (
        ("engine", pc.canonical_engine_programs(8)),
        ("engine_nohealth", pc.canonical_nohealth_engine_programs(8)),
        ("engine_kvq", pc.canonical_kvq_engine_programs(8)),
        ("engine_paged", pc.canonical_paged_engine_programs(8)),
        ("engine_sampling", pc.canonical_sampling_engine_program()),
        # The r13 speculative-decoding programs: the slot-sharded CI spec
        # engine on dp8 (the verify program's budget pins "zero new
        # collective kinds vs engine_dp8" — the fused-sampling mesh rule
        # must keep holding inside the K-event verify forward) and the NA
        # variant (whole dep-graph walk verified in one fused pass).
        ("engine_spec", pc.canonical_spec_engine_programs(8)),
        ("engine_spec_na", pc.canonical_spec_engine_na_programs()),
        # r20: the sharded-sampling engine (slot-sharded Pallas grid, int8
        # cache) and the composed spec × int8 × TP engine with its prefill
        # stream split — the full production composition, censused as ONE
        # engine so every program it compiles carries committed budgets.
        ("engine_sampling_shard", pc.canonical_sharded_sampling_engine_programs(8)),
        ("engine_composed", pc.canonical_composed_engine_programs(4, 2)),
    ):
        # Composed engines run the spec program set (draft/verify/...), so
        # they take the spec donation map.
        spec_prefix = prefix.startswith(("engine_spec", "engine_composed"))
        for key, (fn, args) in programs.items():
            label = f"{prefix}:{key}"
            out[label] = CensusProgram(
                label,
                fn,
                args,
                donate_argnums=(spec_donate if spec_prefix else donate).get(key, ()),
                budget_key=budget_keys.get(label),
            )
    return out


def _register_census() -> None:
    from ..analysis.program_census import register_aot_provider

    register_aot_provider("engine", _census_programs)


_register_census()
