"""Device-resident dataset: CSR arrays in HBM, collation on device.

Why this exists: a host-collated feed pays, for every batch, the collation
on the host and a ``device_put`` of the ~2.6 MB dense batch, serialized in
front of the step. Caching
*host* collation would not touch the per-batch transfer. The design reason
that holds on any host: few large device programs, small per-step host
traffic.

The TPU-native design instead moves the whole dataset to the device once and
re-derives every batch there:

* `DeviceDataset` uploads the `JaxDataset`'s flattened CSR arrays
  (values + offsets; tens of MB for tutorial-scale cohorts) to HBM a single
  time per training run.
* Each step sends only a `BatchPlan` — subject indices, crop starts, and the
  fill-row validity mask, ~100 bytes — and a jitted collate kernel rebuilds
  the static-shape ``(B, L, M)`` batch with pure gathers on the TPU, where
  gathers at these shapes cost microseconds.
* The plan stream (`JaxDataset.plan_batches`) consumes the identical rng
  stream host collation uses, so device- and host-collated epochs are
  bit-identical (tested) and the ``skip_batches`` mid-epoch-resume contract
  is unchanged.

The reference's analog is the DataLoader worker pool re-padding per item per
epoch (``/root/reference/EventStream/data/pytorch_dataset.py:568-683``);
there is no reference analog of device-side collation — it is only possible
because the CSR redesign made collation a fixed set of dense gathers.

Light per-subject fields (``subject_id``, ``start_time``, subsequence
bounds, ``stream_labels``) stay host-computed from the plan: they are O(B)
bytes, and keeping them on the host preserves bit-exact parity with host
collation for free.

Multi-host pods (``data_shards > 1``): the dense tables become ONE global
``jax.Array`` laid out over the mesh's ``data`` axis — subjects are
partitioned into per-shard pools (`JaxDataset.subject_shards`), each shard's
tables are stacked along a leading shard axis sharded ``P("data")``, and
each process materializes/uploads ONLY the shards its addressable devices
own (``jax.make_array_from_callback``). The plan stream
(`JaxDataset.plan_batches(n_shards=K)`) deals every batch shard-major —
``batch_size / K`` rows per pool — from one shared rng stream, so all
processes derive identical plans and every data-axis shard collates its own
rows with purely LOCAL gathers (a vmap over the shard axis; GSPMD inserts no
collectives). The ``skip_batches`` rng-exact resume contract carries over
unchanged. Single-process stays on the replicated layout and the historical
global plan stream, bit-for-bit.
"""

from __future__ import annotations

from functools import partial
from itertools import islice
from typing import Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils.scopes import host_span
from .config import SeqPaddingSide
from .jax_dataset import BatchPlan, JaxDataset
from .types import EventStreamBatch

__all__ = ["DeviceDataset", "padded_collate_kernel", "packed_collate_kernel"]

# Dense per-event tables shipped to HBM, in kernel argument order. The CSR
# representation the host uses is re-materialized into dense ``(n_events, M)``
# tables at upload time: collation then needs NO element-level gathers — TPU
# gathers at (B, L, M) element granularity measured ~1.6 ms each on this
# chip, while the dynamic-slice/row-gather formulations over dense tables run
# the whole collate in ~0.25 ms (before PR 22). The dense tables
# cost ``M / avg_fill`` more HBM than CSR (~1.6x on the bench cohort); both
# representations stop fitting HBM at roughly the same cohort scale, which is
# what the residency gate is for.
_RESIDENT_FIELDS = (
    "subject_event_offsets",  # (n_subjects + 1,) int32
    "time_delta",  # (L + n_events + L,) float32, zero-padded both sides
    "dynamic_indices",  # (L + n_events + L, M) int32, 0 in empty slots
    "dynamic_measurement_indices",  # same layout
    "dynamic_values",  # same layout, float32, 0 where unobserved
    "dynamic_values_obs",  # same layout, bool: slot filled AND observed
    "static_indices",  # (n_subjects, S) int32, 0 in empty slots
    "static_measurement_indices",  # (n_subjects, S) int32
)


def padded_collate_kernel(
    arrays: dict,
    subject_indices,
    starts,
    valid,
    *,
    L: int,
    M: int,
    S: int,
    pad_right: bool,
    do_static: bool,
) -> dict:
    """The on-device mirror of ``JaxDataset._collate_with_starts``.

    Every padded row is a CONTIGUOUS range of the event axis (``ev_lo + start
    + pos``), so the whole collate is a batch of ``lax.dynamic_slice``s over
    the dense per-event tables — no element gathers. The tables carry ``L``
    zero rows on both ends so slice starts stay in range for left padding
    (start can reach ``ev_lo - L``) and slice ends for short subjects
    (overrun reads zeros, which the event mask then zeroes anyway — matching
    host collation bit-for-bit).

    The fill-row convention also matches the host path: ``valid`` blanks only
    the two masks; sliced payloads of fill rows are left in place, exactly as
    host collation leaves them after its post-collation blanking.
    """
    offsets = arrays["subject_event_offsets"]
    ev_lo = offsets[subject_indices]
    seq_lens = offsets[subject_indices + 1] - ev_lo
    kept = jnp.minimum(seq_lens, L)

    pos = jnp.arange(L, dtype=jnp.int32)[None, :]
    if pad_right:
        event_mask = pos < kept[:, None]
        slice_starts = L + ev_lo + starts
    else:
        pad = L - kept
        event_mask = pos >= pad[:, None]
        slice_starts = L + ev_lo + starts - pad
    out = _slice_event_payload(arrays, slice_starts, event_mask, L)
    out["event_mask"] = event_mask & valid[:, None]
    out["dynamic_values_mask"] = out["dynamic_values_mask"] & valid[:, None, None]

    if do_static:
        # (B, S) row gathers over small dense per-subject tables.
        out["static_indices"] = arrays["static_indices"][subject_indices]
        out["static_measurement_indices"] = arrays["static_measurement_indices"][
            subject_indices
        ]
    return out


def _slice_event_payload(arrays: dict, slice_starts, event_mask, L: int) -> dict:
    """Contiguous per-row slices of the dense tables + host-parity masking."""

    def row(s):
        return tuple(
            jax.lax.dynamic_slice_in_dim(arrays[k], s, L)
            for k in (
                "time_delta",
                "dynamic_indices",
                "dynamic_measurement_indices",
                "dynamic_values",
                "dynamic_values_obs",
            )
        )

    td, di, dm, dv, dobs = jax.vmap(row)(slice_starts)
    return _mask_event_payload(td, di, dm, dv, dobs, event_mask)


def _mask_event_payload(td, di, dm, dv, dobs, event_mask) -> dict:
    """Applies the host path's exact zeroing: positions outside the event
    mask are zero in every payload field (empty slots inside valid events are
    already zero in the dense tables, as host ``np.where`` leaves them)."""
    m3 = event_mask[..., None]
    return {
        "time_delta": jnp.where(event_mask, td, 0.0),
        "dynamic_indices": jnp.where(m3, di, 0),
        "dynamic_measurement_indices": jnp.where(m3, dm, 0),
        "dynamic_values": jnp.where(m3, dv, 0.0),
        "dynamic_values_mask": dobs & m3,
    }


def packed_collate_kernel(
    arrays: dict, event_ids, event_mask, *, L_PAD: int, M: int
) -> dict:
    """On-device payload fetch for packed rows.

    Packed rows interleave several subjects, so the event axis is not one
    contiguous range; instead each ``(b, l)`` position row-gathers an M-wide
    row of the dense tables (~30x faster than element gathers on this chip).
    The host still runs the (cheap, sequential) first-fit packing and sends
    the ``(B, L)`` event-id/segment plan; the ``(B, L, M)`` payload — ~97% of
    the batch bytes — never crosses the wire.

    ``L_PAD`` is the dense tables' front zero-pad (the dataset's
    ``max_seq_len``); masked positions carry event id 0, which lands on a
    real row after the offset but is zeroed by the mask, as on the host.
    """
    eids = event_ids + L_PAD
    td = arrays["time_delta"][eids]
    di = arrays["dynamic_indices"][eids]
    dm = arrays["dynamic_measurement_indices"][eids]
    dv = arrays["dynamic_values"][eids]
    dobs = arrays["dynamic_values_obs"][eids]
    out = _mask_event_payload(td, di, dm, dv, dobs, event_mask)
    out["event_mask"] = event_mask
    return out


def plan_kept_lengths(plans: dict, dataset: JaxDataset) -> np.ndarray:
    """Events each row of a stacked padded plan chunk keeps (a history is
    cropped at the row; a cyclic fill row keeps none)."""
    off = np.asarray(dataset.data.subject_event_offsets, np.int64)
    idx = np.asarray(plans["subject_indices"], np.int64)
    return np.where(np.asarray(plans["valid_mask"]), np.minimum(off[idx + 1] - off[idx], dataset.max_seq_len), 0)


def padded_segment_ids(kept: np.ndarray, dataset: JaxDataset) -> np.ndarray:
    """``kept.shape + (L,)`` segment ids as the global layers see padded rows
    that keep ``kept`` events: one segment, padding ``-1`` on the dataset's side."""
    L = dataset.max_seq_len
    pos = np.arange(L)
    real = pos < kept[..., None] if dataset.seq_padding_side == SeqPaddingSide.RIGHT else pos >= L - kept[..., None]
    return np.where(real, 0, -1)


class PlanEvents(int):
    """A chunk's real events, the planner's own sum, as the plan iterators
    yield it beside the stacked plans. It carries the chunk's ``es.host/plan``
    span's ``id`` (the chunk's index in the dataset's life: the dispatch that
    runs it) and ``counts`` (`DeviceDataset.plan_counts`), so that whoever
    dispatches the chunk, however many chunks after it was planned, reads that
    chunk's and no other's."""

    id: int
    counts: dict


def _dense_pre_sliced(src, rows, cols, keep, n_rows: int, M: int, dtype) -> np.ndarray:
    """Dense-table scatter for a source array already sliced to the range."""
    t = np.zeros((n_rows, M), dtype)
    t[rows, cols] = np.asarray(src)[keep]
    return t


class DeviceDataset:
    """HBM-resident view of a `JaxDataset` with on-device collation.

    Args:
        dataset: the host dataset to mirror. Its CSR index arrays must be
            int32-narrow (`JaxDataset` shrinks them whenever sizes permit; a
            >2B-element cohort would not fit HBM anyway).
        mesh: optional device mesh. Resident arrays are replicated over it
            (``data_shards == 1``) or sharded over its ``data`` axis;
            collated batches come out sharded batch-dim-over-``data`` (and,
            with ``context_parallel``, event-dim-over-``context``) — the
            layouts ``shard_batch`` / ``shard_batch_cp`` would have produced.
        context_parallel: emit ring-attention input layout.
        data_shards: 1 for the replicated single-process layout; the mesh's
            ``data``-axis size for the sharded (pod) layout, where each
            data-axis device holds one subject-pool's tables and each process
            uploads only its addressable shards. Use `create` / `try_create`
            to pick this from the topology.
    """

    def __init__(
        self,
        dataset: JaxDataset,
        mesh: Mesh | None = None,
        context_parallel: bool = False,
        data_shards: int = 1,
    ):
        self.dataset = dataset
        self.mesh = mesh
        self.context_parallel = context_parallel
        self.data_shards = int(data_shards)
        d = dataset.data
        for name in ("subject_event_offsets", "event_data_offsets", "dynamic_indices"):
            if getattr(d, name).dtype == np.int64:
                raise ValueError(
                    f"JaxDataset.data.{name} did not narrow to int32 "
                    "(>2^31 elements); such a cohort cannot be device-resident."
                )
        # One host-side finiteness pass over the CSR arrays (values are
        # stored observed-masked, so any non-finite IS an observed value).
        # This is what lets resident zero-shot prompts skip the per-batch
        # device-side NaN readback without weakening the guarantee: a
        # poisoned DL cache fails loudly here, at table-build time.
        if not np.isfinite(d.time_delta).all():
            raise ValueError(
                "non-finite time_delta in the DL cache; refusing to build "
                "device-resident tables (resident batches skip per-batch NaN "
                "validation on the strength of this check)."
            )
        if not np.isfinite(d.dynamic_values).all():
            raise ValueError(
                "non-finite observed dynamic_values in the DL cache; refusing "
                "to build device-resident tables (resident batches skip "
                "per-batch NaN validation on the strength of this check)."
            )

        if self.data_shards > 1:
            if mesh is None or "data" not in mesh.shape:
                raise ValueError(
                    "data_shards > 1 requires a mesh with a 'data' axis to lay "
                    "the shard axis over."
                )
            if int(mesh.shape["data"]) != self.data_shards:
                raise ValueError(
                    f"data_shards ({self.data_shards}) must equal the mesh's "
                    f"'data' axis size ({int(mesh.shape['data'])}): the sharded "
                    "layout places exactly one subject-pool per data-axis row."
                )
            self.arrays = self._build_and_upload_sharded()
        else:
            if jax.process_count() > 1:
                raise ValueError(
                    "replicated resident tables cannot span processes — on "
                    f"{jax.process_count()} processes use the sharded layout "
                    "(DeviceDataset.create picks data_shards from the mesh), "
                    "or set trainer_config.device_resident_data='auto'/false."
                )
            host = self._build_dense_tables()
            self.nbytes = sum(a.nbytes for a in host.values())
            if mesh is not None:
                replicated = NamedSharding(mesh, P())
                self.arrays = {k: jax.device_put(v, replicated) for k, v in host.items()}
            else:
                self.arrays = {k: jnp.asarray(v) for k, v in host.items()}
        self._kernel_cache: dict = {}
        # How many chunk pairs of how many the step's flash op visits on rows
        # with given segment ids, per row; `training.make_chunked_train_step`
        # sets it where the model's global layers run the op (`plan_counts`).
        self.flash_pairs: Callable[[np.ndarray], tuple[np.ndarray, int] | None] | None = None
        self._pairs_by_kept: tuple | None = None  # (the counter it was made with, a padded row's pairs by its kept length)
        self._chunks_planned = 0  # in this dataset's life: the next plan span's id

    # Default HBM budget for auto-residency: conservative against a 16 GB
    # v5e chip that also holds params, optimizer state, and activations.
    DEFAULT_BUDGET_BYTES = 2 * 1024**3

    @staticmethod
    def estimate_nbytes(dataset: JaxDataset) -> int:
        """Predicted HBM footprint of residency, without building anything.

        Lets callers (``training.train`` in ``device_resident_data='auto'``
        mode) gate residency on an HBM budget before paying the host-side
        dense-table build.
        """
        n_rows = len(dataset.data.time_delta) + 2 * dataset.max_seq_len
        per_row = 4 + dataset.max_n_dynamic * (4 + 4 + 4 + 1)
        static = 2 * 4 * dataset.max_n_static * max(dataset.data.n_subjects, 1)
        return n_rows * per_row + static + dataset.data.subject_event_offsets.nbytes

    @staticmethod
    def estimate_sharded_nbytes(dataset: JaxDataset, n_shards: int) -> int:
        """Predicted GLOBAL footprint of the sharded layout, without building.

        Not ``estimate_nbytes``: every shard pads to the largest pool (plus
        its own 2L slice guard), so a skewed cohort — one subject holding
        most events — can cost up to ``n_shards ×`` the unsharded estimate.
        Raises ``ValueError`` when the cohort cannot shard ``n_shards`` ways.
        """
        bounds = dataset.subject_shards(n_shards)
        ev = np.asarray(dataset.data.subject_event_offsets, np.int64)[bounds]
        n_rows = int(np.diff(ev).max()) + 2 * dataset.max_seq_len
        n_subj_rows = int(np.diff(bounds).max())
        per_row = 4 + dataset.max_n_dynamic * (4 + 4 + 4 + 1)
        static = 2 * 4 * dataset.max_n_static * n_subj_rows
        return n_shards * (n_rows * per_row + static + (n_subj_rows + 1) * 4 + 8)

    @classmethod
    def create(
        cls,
        dataset: JaxDataset,
        mesh: Mesh | None = None,
        context_parallel: bool = False,
        batch_sizes: tuple[int, ...] = (),
    ) -> "DeviceDataset":
        """Topology-aware constructor (no budget gate).

        Single-process → the replicated layout. Multi-process → the sharded
        layout over the mesh's ``data`` axis (one subject pool per data-axis
        row; each process uploads only its addressable shards). Raises
        ``ValueError`` with an actionable message on unsupported topologies
        (no mesh / no ``data`` axis / fewer subjects than shards) instead of
        silently misbehaving — this is the path explicit
        ``device_resident_data: true`` configs take. ``batch_sizes`` (every
        size the caller will stream, train AND eval) is validated against
        the shard count HERE, at startup — the alternative is a full epoch
        of pod time before the first dealt eval stream raises.
        """
        with host_span("startup/device_tables", id="startup"):
            made = cls._create(dataset, mesh, context_parallel, batch_sizes)
            jax.block_until_ready(made.arrays)  # the span closes when the tables are on the device
        return made

    @classmethod
    def _create(cls, dataset, mesh, context_parallel, batch_sizes) -> "DeviceDataset":
        if jax.process_count() == 1:
            return cls(dataset, mesh=mesh, context_parallel=context_parallel)
        if mesh is None or "data" not in mesh.shape:
            raise ValueError(
                f"device-resident data on {jax.process_count()} processes "
                "requires a device mesh with a 'data' axis (the dense tables "
                "shard over it); this caller passed "
                f"mesh={'None' if mesh is None else tuple(mesh.shape.items())}."
            )
        n_shards = int(mesh.shape["data"])
        bad = [int(b) for b in batch_sizes if int(b) % n_shards]
        if bad:
            raise ValueError(
                f"device-resident data shards the plan stream {n_shards} ways, so "
                f"every streamed batch size must be divisible by {n_shards}; got "
                f"{bad}. Adjust the batch/validation batch size or disable "
                "device_resident_data."
            )
        return cls(
            dataset,
            mesh=mesh,
            context_parallel=context_parallel,
            data_shards=n_shards,
        )

    @classmethod
    def try_create(
        cls,
        dataset: JaxDataset,
        mesh: Mesh | None = None,
        context_parallel: bool = False,
        max_bytes: int | None = None,
        batch_sizes: tuple[int, ...] = (),
    ) -> "DeviceDataset | None":
        """`DeviceDataset` when residency is eligible, else ``None``.

        The single auto-residency gate every harness shares: estimated tables
        within ``max_bytes`` (default `DEFAULT_BUDGET_BYTES`), CSR arrays
        int32-narrow, finite values. Multi-process topologies take the
        sharded layout (each process uploads ~1/P of the tables, so the
        budget applies to the per-process share) and additionally need a
        mesh with a ``data`` axis, plus every batch size the caller will
        stream (``batch_sizes``) divisible by the shard count — checked HERE
        so an ineligible eval batch size falls back to host collation at
        startup instead of killing the run at its first dealt stream.
        Callers fall back to host collation on ``None`` — every ``None``
        prints its reason, so the fallback is never silent.
        """

        def decline(reason: str) -> None:
            print(f"DeviceDataset.try_create: not device-resident ({reason}); "
                  "the caller falls back to host collation.")
            return None

        budget = max_bytes or cls.DEFAULT_BUDGET_BYTES
        n_proc = jax.process_count()
        if n_proc == 1:
            est = cls.estimate_nbytes(dataset)
            if est > budget:
                return decline(f"estimated {est} bytes exceed the {budget}-byte budget")
            try:
                return cls.create(dataset, mesh=mesh, context_parallel=context_parallel)
            except ValueError as e:
                return decline(str(e))
        if mesh is None or "data" not in mesh.shape:
            return decline("multi-process residency needs a mesh with a 'data' axis")
        if any(int(b) % int(mesh.shape["data"]) for b in batch_sizes):
            return decline(
                f"batch sizes {batch_sizes} do not divide by the {mesh.shape['data']} data shards"
            )
        try:
            # The sharded estimate, not estimate_nbytes // K: shards pad to
            # the largest pool, so skewed cohorts cost more than total/K —
            # the budget must bound what a process will actually upload.
            global_bytes = cls.estimate_sharded_nbytes(dataset, int(mesh.shape["data"]))
            if global_bytes // n_proc > budget:
                return decline(
                    f"estimated {global_bytes // n_proc} bytes per process exceed "
                    f"the {budget}-byte budget"
                )
            return cls.create(dataset, mesh=mesh, context_parallel=context_parallel)
        except ValueError as e:
            return decline(str(e))

    def _build_dense_tables(self) -> dict:
        """CSR → dense per-event tables (see `_RESIDENT_FIELDS` for why)."""
        return self._dense_tables_for_subjects(0, self.dataset.data.n_subjects)

    def _dense_tables_for_subjects(
        self,
        s_lo: int,
        s_hi: int,
        n_rows_pad: int | None = None,
        n_subj_pad: int | None = None,
    ) -> dict:
        """Dense tables for the subject range ``[s_lo, s_hi)``, with all
        offsets LOCAL to the range (event row 0 = the range's first event).

        The full-range call is the replicated layout; the sharded layout
        builds one range per shard, padded (``n_rows_pad`` event rows,
        ``n_subj_pad`` subject rows) so every shard stacks to one uniform
        global array. Padding subject rows repeat the final offset (zero-
        length subjects that dealing never references); padding event rows
        are zeros, indistinguishable from the slice-guard pad.
        """
        ds = self.dataset
        d = ds.data
        L = ds.max_seq_len
        M = ds.max_n_dynamic
        ev_lo = int(d.subject_event_offsets[s_lo])
        ev_hi = int(d.subject_event_offsets[s_hi])
        n_events = ev_hi - ev_lo
        n_rows = n_rows_pad if n_rows_pad is not None else n_events + 2 * L

        off = np.asarray(d.event_data_offsets[ev_lo : ev_hi + 1], np.int64)
        counts = np.diff(off)
        el_lo, el_hi = int(off[0]), int(off[-1])
        # Clip slots beyond M (possible when config.max_n_dynamic caps below
        # the data's true max — host collation drops them the same way).
        slot = np.arange(el_hi - el_lo, dtype=np.int64) - np.repeat(off[:-1] - el_lo, counts)
        keep = slot < M
        rows = np.repeat(np.arange(n_events), counts)[keep] + L
        cols = slot[keep]

        def dense(src, dtype):
            return _dense_pre_sliced(src[el_lo:el_hi], rows, cols, keep, n_rows, M, dtype)

        td = np.zeros(n_rows, np.float32)
        td[L : L + n_events] = d.time_delta[ev_lo:ev_hi]

        S = ds.max_n_static
        n_subjects = s_hi - s_lo
        n_subj_rows = n_subj_pad if n_subj_pad is not None else max(n_subjects, 1)
        st_idx = np.zeros((n_subj_rows, S), np.int32)
        st_meas = np.zeros((n_subj_rows, S), np.int32)
        if ds.do_produce_static_data and n_subjects:
            st_off = np.asarray(d.static_offsets[s_lo : s_hi + 1], np.int64)
            st_counts = np.diff(st_off)
            st_el_lo, st_el_hi = int(st_off[0]), int(st_off[-1])
            st_slot = np.arange(st_el_hi - st_el_lo, dtype=np.int64) - np.repeat(
                st_off[:-1] - st_el_lo, st_counts
            )
            st_keep = st_slot < S
            st_rows = np.repeat(np.arange(n_subjects), st_counts)[st_keep]
            st_idx[st_rows, st_slot[st_keep]] = np.asarray(
                d.static_indices[st_el_lo:st_el_hi]
            )[st_keep]
            st_meas[st_rows, st_slot[st_keep]] = np.asarray(
                d.static_measurement_indices[st_el_lo:st_el_hi]
            )[st_keep]

        offsets = np.asarray(d.subject_event_offsets[s_lo : s_hi + 1], np.int64) - ev_lo
        if n_subj_pad is not None and len(offsets) < n_subj_pad + 1:
            offsets = np.concatenate(
                [offsets, np.full(n_subj_pad + 1 - len(offsets), offsets[-1], np.int64)]
            )

        vals = np.where(
            d.dynamic_values_observed[el_lo:el_hi], d.dynamic_values[el_lo:el_hi], 0.0
        )
        return {
            "subject_event_offsets": offsets.astype(np.int32),
            "time_delta": td,
            "dynamic_indices": dense(d.dynamic_indices, np.int32),
            "dynamic_measurement_indices": dense(d.dynamic_measurement_indices, np.int32),
            "dynamic_values": _dense_pre_sliced(vals, rows, cols, keep, n_rows, M, np.float32),
            "dynamic_values_obs": dense(d.dynamic_values_observed, bool),
            "static_indices": st_idx,
            "static_measurement_indices": st_meas,
        }

    # ----------------------------------------------------- sharded layout
    def _shard_layout(self) -> tuple[np.ndarray, int, int]:
        """``(bounds, n_rows, n_subj_rows)`` for the stacked shard tables.

        ``bounds`` are the subject-pool boundaries; every shard's event table
        pads to ``n_rows`` (largest shard + the 2L slice guard) and its
        subject axes to ``n_subj_rows`` so the stack is one uniform global
        array.
        """
        ds = self.dataset
        bounds = ds.subject_shards(self.data_shards)
        ev = np.asarray(ds.data.subject_event_offsets, np.int64)[bounds]
        n_rows = int(np.diff(ev).max()) + 2 * ds.max_seq_len
        n_subj_rows = int(np.diff(bounds).max())
        return bounds, n_rows, n_subj_rows

    def _build_and_upload_sharded(self) -> dict:
        """Stacked per-shard tables as global arrays sharded over ``data``.

        Each process materializes ONLY the shards its addressable devices
        hold (``jax.make_array_from_callback`` requests exactly those global
        slices), which is what makes pod-scale residency per-host-bounded:
        host RAM and HBM per process scale with its subject share, not the
        cohort.
        """
        ds = self.dataset
        K = self.data_shards
        bounds, n_rows, n_subj_rows = self._shard_layout()
        ev_base = np.asarray(ds.data.subject_event_offsets, np.int64)[bounds[:-1]]

        shard_cache: dict[int, dict] = {}

        def shard_tables(k: int) -> dict:
            if k not in shard_cache:
                shard_cache[k] = self._dense_tables_for_subjects(
                    int(bounds[k]), int(bounds[k + 1]),
                    n_rows_pad=n_rows, n_subj_pad=n_subj_rows,
                )
            return shard_cache[k]

        M, S = ds.max_n_dynamic, ds.max_n_static
        field_shapes: dict[str, tuple] = {
            "subject_event_offsets": (K, n_subj_rows + 1),
            "time_delta": (K, n_rows),
            "dynamic_indices": (K, n_rows, M),
            "dynamic_measurement_indices": (K, n_rows, M),
            "dynamic_values": (K, n_rows, M),
            "dynamic_values_obs": (K, n_rows, M),
            "static_indices": (K, n_subj_rows, S),
            "static_measurement_indices": (K, n_subj_rows, S),
        }
        bases = {
            "shard_subject_base": bounds[:-1].astype(np.int32),
            "shard_event_base": ev_base.astype(np.int32),
        }

        arrays: dict = {}
        self.nbytes = 0
        for name, shape in field_shapes.items():
            sharding = NamedSharding(self.mesh, P("data", *([None] * (len(shape) - 1))))

            def cb(index, name=name):
                ks = range(*index[0].indices(K))
                return np.stack([shard_tables(k)[name] for k in ks])

            arrays[name] = jax.make_array_from_callback(shape, sharding, cb)
            self.nbytes += int(np.prod(shape)) * arrays[name].dtype.itemsize
        for name, host in bases.items():
            sharding = NamedSharding(self.mesh, P("data"))
            arrays[name] = jax.make_array_from_callback(
                (K,), sharding, lambda index, host=host: host[index[0]]
            )
            self.nbytes += host.nbytes
        shard_cache.clear()
        return arrays

    # ----------------------------------------------------------- shardings
    # Fields whose dim 1 is the event (sequence) axis — sharded over the
    # ``context`` mesh axis in ring-attention layouts (mirrors
    # ``training.pretrain._CP_SEQ_FIELDS`` for the heavy fields).
    _SEQ_FIELDS = frozenset(
        {
            "event_mask",
            "time_delta",
            "dynamic_indices",
            "dynamic_measurement_indices",
            "dynamic_values",
            "dynamic_values_mask",
            "segment_ids",
        }
    )

    def _out_sharding(self, ndim: int, seq_axis: bool):
        if self.mesh is None:
            return None
        if seq_axis and self.context_parallel and "context" in self.mesh.shape:
            return NamedSharding(self.mesh, P("data", "context", *([None] * (ndim - 2))))
        return NamedSharding(self.mesh, P("data", *([None] * (ndim - 1))))

    def constrain_fields(self, fields: dict) -> dict:
        """Applies mesh sharding constraints to collate outputs inside jit.

        The in-jit counterpart of the ``out_shardings`` the standalone
        kernels use — scanned train programs
        (``training.make_chunked_train_step``) call this so batches
        materialize in the same layout ``shard_batch`` / ``shard_batch_cp``
        would have produced.
        """
        if self.mesh is None:
            return fields
        return {
            k: jax.lax.with_sharding_constraint(
                v, self._out_sharding(v.ndim, k in self._SEQ_FIELDS)
            )
            for k, v in fields.items()
        }

    def padded_kernel(self):
        """The un-jitted padded collate kernel, bound to this dataset's
        shapes — the single source of the config→kernel mapping.

        Sharded layouts wrap the same per-shard kernel in a vmap over the
        shard axis: plan indices (global, dealt shard-major) rebase to each
        pool's local subject axis, every lane gathers ONLY its own table
        shard (no cross-shard collectives under GSPMD — the batched gather's
        leading axis matches the tables' ``data`` sharding), and the outputs
        merge back to the plain ``(B, ...)`` global batch the train step
        already consumes.
        """
        ds = self.dataset
        base = partial(
            padded_collate_kernel,
            L=ds.max_seq_len,
            M=ds.max_n_dynamic,
            S=ds.max_n_static,
            pad_right=ds.seq_padding_side == SeqPaddingSide.RIGHT,
            do_static=ds.do_produce_static_data,
        )
        if self.data_shards == 1:
            return base
        K = self.data_shards

        def sharded(arrays, subject_indices, starts, valid):
            B = subject_indices.shape[0]
            bl = B // K
            tables = {k: arrays[k] for k in _RESIDENT_FIELDS}

            def lane(tab, subj_base, si, st, va):
                return base(tab, si - subj_base, st, va)

            out = jax.vmap(lane)(
                tables,
                arrays["shard_subject_base"],
                jnp.asarray(subject_indices).reshape(K, bl),
                jnp.asarray(starts).reshape(K, bl),
                jnp.asarray(valid).reshape(K, bl),
            )
            return {k: v.reshape((B,) + v.shape[2:]) for k, v in out.items()}

        return sharded

    def packed_kernel(self):
        """The un-jitted packed collate kernel bound to this dataset.

        Sharded layouts mirror `padded_kernel`: global event ids rebase to
        each shard's local event axis (masked slots carry global id 0, which
        goes negative after rebasing — clamped to 0 and zeroed by the mask,
        exactly the host convention) and the row gathers stay shard-local.
        """
        base = partial(
            packed_collate_kernel,
            L_PAD=self.dataset.max_seq_len,
            M=self.dataset.max_n_dynamic,
        )
        if self.data_shards == 1:
            return base
        K = self.data_shards

        def sharded(arrays, event_ids, event_mask):
            B, L = event_ids.shape
            bl = B // K
            tables = {k: arrays[k] for k in _RESIDENT_FIELDS}

            def lane(tab, ev_base, eids, mask):
                return base(tab, jnp.maximum(eids - ev_base, 0), mask)

            out = jax.vmap(lane)(
                tables,
                arrays["shard_event_base"],
                jnp.asarray(event_ids).reshape(K, bl, L),
                jnp.asarray(event_mask).reshape(K, bl, L),
            )
            return {k: v.reshape((B,) + v.shape[2:]) for k, v in out.items()}

        return sharded

    def _jit_kernel(self, key: tuple, kern) -> "jax.stages.Wrapped":
        if key not in self._kernel_cache:
            out_shardings = None
            if self.mesh is not None:
                # Shapes don't matter for sharding specs — evaluate on ndim.
                ndims = {
                    "event_mask": 2,
                    "time_delta": 2,
                    "dynamic_indices": 3,
                    "dynamic_measurement_indices": 3,
                    "dynamic_values": 3,
                    "dynamic_values_mask": 3,
                }
                if key[0] == "padded" and self.dataset.do_produce_static_data:
                    ndims["static_indices"] = 2
                    ndims["static_measurement_indices"] = 2
                out_shardings = {
                    k: self._out_sharding(nd, k in self._SEQ_FIELDS)
                    for k, nd in ndims.items()
                }
            self._kernel_cache[key] = jax.jit(kern, out_shardings=out_shardings)
        return self._kernel_cache[key]

    def _jit_padded(self, B: int):
        return self._jit_kernel(("padded", B), self.padded_kernel())

    def _jit_packed(self, B: int, L: int):
        return self._jit_kernel(("packed", B, L), self.packed_kernel())

    # ----------------------------------------------------------- collation
    def collate(self, plan: BatchPlan) -> EventStreamBatch:
        """Collates one `BatchPlan` on device → static-shape batch.

        Heavy ``(B, L[, M])`` fields are device arrays; light per-subject
        fields ride along as host arrays (transferred with the step's
        arguments, O(B) bytes).
        """
        ds = self.dataset
        B = len(plan.subject_indices)
        fields = self._jit_padded(B)(
            self.arrays, plan.subject_indices, plan.starts, plan.valid_mask
        )

        if ds.config.do_include_start_time_min:
            if plan.start_time is None:
                raise ValueError(
                    "do_include_start_time_min is set but the plan carries no "
                    "start_time — regenerate plans from this config."
                )
            fields["start_time"] = plan.start_time
        if ds.config.do_include_subsequence_indices:
            # int32, matching host _collate_with_starts (bit-identical incl.
            # dtype; the parity tests assert dtypes too).
            fields["start_idx"] = plan.starts
            fields["end_idx"] = plan.starts + plan.kept
        if ds.config.do_include_subject_id:
            fields["subject_id"] = np.asarray(
                [ds.subject_ids[i] for i in plan.subject_indices], dtype=np.int64
            )
        if ds.has_task:
            fields["stream_labels"] = {
                t: np.asarray(
                    ds.stream_labels[t][plan.subject_indices],
                    dtype=np.int64
                    if ds.task_types[t] == "multi_class_classification"
                    else np.float32,
                )
                for t in ds.tasks
            }
        fields["valid_mask"] = plan.valid_mask
        return EventStreamBatch(**fields)

    def batches(
        self,
        batch_size: int,
        shuffle: bool = True,
        seed: int | None = None,
        drop_last: bool | None = None,
        skip_batches: int = 0,
        with_counts: bool = False,
    ) -> Iterator:
        """Device-collated mirror of `JaxDataset.batches` (same rng stream).

        With ``with_counts=True`` yields ``(batch, n_events)`` — the event
        count comes from the plan, so throughput accounting never syncs the
        device.
        """
        for plan in self.dataset.plan_batches(
            batch_size,
            shuffle=shuffle,
            seed=seed,
            drop_last=drop_last,
            skip_batches=skip_batches,
            n_shards=self.data_shards,
        ):
            b = self.collate(plan)
            yield (b, plan.n_events) if with_counts else b

    def packed_batches(
        self,
        batch_size: int,
        seq_len: int | None = None,
        shuffle: bool = True,
        seed: int | None = None,
        with_counts: bool = False,
    ) -> Iterator:
        """Device-collated mirror of `JaxDataset.packed_batches`.

        Packing order and row contents are identical to the host path (same
        ``_pack_rows`` call, same rng); the host ships the ``(B, L)``
        event-id plan (~KBs) and the device gathers the ``(B, L, M)``
        payload.
        """
        ds = self.dataset
        L = seq_len or ds.max_seq_len
        rows = ds.packed_rows_dealt(
            batch_size, seq_len=L, shuffle=shuffle, seed=seed, n_shards=self.data_shards
        )

        for lo_idx in range(0, len(rows), batch_size):
            chunk = rows[lo_idx : lo_idx + batch_size]
            kernel = self._jit_packed(len(chunk), L)
            event_ids, seg, mask, n_events = ds.packed_row_plan(chunk, L)
            fields = kernel(self.arrays, event_ids.astype(np.int32), mask)
            batch = EventStreamBatch(
                segment_ids=seg, valid_mask=np.ones(len(chunk), dtype=bool), **fields
            )
            yield (batch, n_events) if with_counts else batch

    # ------------------------------------------------------- chunked plans
    def plan_chunks(
        self,
        batch_size: int,
        chunk_steps: int,
        shuffle: bool = True,
        seed: int | None = None,
        drop_last: bool | None = None,
        skip_batches: int = 0,
    ) -> Iterator[tuple[dict, int]]:
        """Yields ``(plans, n_events)`` with ``chunk_steps`` stacked plans.

        ``plans`` maps plan fields to ``(k, B)`` numpy arrays — the payload a
        scanned multi-step train program (``training.make_chunked_train_step``)
        consumes to run ``k`` collate+step iterations in ONE device program,
        amortizing per-dispatch host overhead ``k``-fold. The final chunk
        may be shorter (``k < chunk_steps``); callers get one extra
        compilation for it at most.
        """
        plans = self.dataset.plan_batches(
            batch_size,
            shuffle=shuffle,
            seed=seed,
            drop_last=drop_last,
            skip_batches=skip_batches,
            n_shards=self.data_shards,
        )
        return self._chunks_under_span(plans, chunk_steps, self._stack_plans)

    @staticmethod
    def _stack_plans(plans: list[BatchPlan]) -> tuple[dict, int]:
        return (
            {
                "subject_indices": np.stack([p.subject_indices for p in plans]),
                "starts": np.stack([p.starts for p in plans]),
                "valid_mask": np.stack([p.valid_mask for p in plans]),
            },
            sum(p.n_events for p in plans),
        )

    def _chunks_under_span(self, items: Iterator, k: int, stack) -> Iterator[tuple[dict, PlanEvents]]:
        """``(plans, n_events)`` of up to ``k`` stacked items of ``items`` (the
        last may be shorter). Making a chunk, which is where a plan iterator
        does its work, is one ``es.host/plan`` span, whose ``id`` is the
        chunk's index in this dataset's life and whose counts are
        `plan_counts` of the stacked chunk; both go on with the chunk
        (`PlanEvents`). The span closes before the chunk is handed on, and a
        span that found the iterator exhausted is not recorded."""
        while True:
            with host_span("plan", id=self._chunks_planned) as span:
                buf = list(islice(items, k))
                if buf:
                    plans, events = stack(buf)
                    span.counts = self.plan_counts(plans, events)
                else:
                    span.drop()
            if not buf:
                return
            self._chunks_planned += 1
            n_events = PlanEvents(events)
            n_events.id, n_events.counts = span.id, span.counts
            yield plans, n_events

    def plan_counts(self, plans: dict, events: int | None = None) -> dict:
        """The feed's counts of a stacked plan chunk: real ``events`` (the
        planner's sum where it is given, else recounted from the plans);
        the ``slots`` of its rows (rows x row length); and, where the step's
        global layers run the flash op on rows of this length (`flash_pairs`),
        ``pairs_visited`` of ``pairs_dense`` chunk pairs. Packed rows are
        walked by the op's own bounds; a padded row is one segment, so its
        walk depends on its kept length alone and is looked up."""
        pairs = None
        if "event_mask" in plans:  # packed plans carry the mask directly
            mask = np.asarray(plans["event_mask"])
            rows, L = mask.shape[:-1], mask.shape[-1]
            events = int(mask.sum()) if events is None else events
            if self.flash_pairs is not None:
                pairs = self.flash_pairs(np.where(mask, np.asarray(plans["segment_ids"]), -1))
        else:
            rows, L = plans["valid_mask"].shape, self.dataset.max_seq_len
            if events is None or self.flash_pairs is not None:
                kept = plan_kept_lengths(plans, self.dataset)
                events = int(kept.sum()) if events is None else events
            if self.flash_pairs is not None:
                if self._pairs_by_kept is None or self._pairs_by_kept[0] is not self.flash_pairs:
                    by_kept = self.flash_pairs(padded_segment_ids(np.arange(L + 1), self.dataset))
                    self._pairs_by_kept = (self.flash_pairs, by_kept)
                by_kept = self._pairs_by_kept[1]
                pairs = None if by_kept is None else (by_kept[0][kept], by_kept[1])
        counts = {"events": int(events), "slots": int(np.prod(rows)) * L}
        if pairs is not None:
            counts.update(pairs_visited=int(pairs[0].sum()), pairs_dense=pairs[0].size * pairs[1])
        return counts

    def packed_plan_chunks(
        self,
        batch_size: int,
        chunk_steps: int,
        seq_len: int | None = None,
        shuffle: bool = True,
        seed: int | None = None,
        skip_batches: int = 0,
        drop_short: bool = True,
    ) -> Iterator[tuple[dict, int]]:
        """Packed-row analog of `plan_chunks`: ``(k, B, L)`` event-id plans.

        ``drop_short`` skips the final under-filled packed batch (it would
        retrigger compilation — the training loop drops it too).
        """
        ds = self.dataset
        L = seq_len or ds.max_seq_len

        def batch_plans():
            # The epoch's repacking runs when the first plan is asked for,
            # so it falls under the first chunk's span.
            rows = ds.packed_rows_dealt(
                batch_size, seq_len=L, shuffle=shuffle, seed=seed, n_shards=self.data_shards
            )
            n_seen = 0
            for lo_idx in range(0, len(rows), batch_size):
                chunk = rows[lo_idx : lo_idx + batch_size]
                if drop_short and len(chunk) < batch_size:
                    continue
                n_seen += 1
                if n_seen <= skip_batches:
                    continue
                event_ids, seg, mask, n_events = ds.packed_row_plan(chunk, L)
                yield event_ids.astype(np.int32), seg.astype(np.int32), mask, n_events

        return self._chunks_under_span(batch_plans(), chunk_steps, self._stack_packed)

    @staticmethod
    def _stack_packed(buf: list[tuple]) -> tuple[dict, int]:
        return (
            {
                "event_ids": np.stack([e for e, _, _, _ in buf]),
                "segment_ids": np.stack([s for _, s, _, _ in buf]),
                "event_mask": np.stack([m for _, _, m, _ in buf]),
            },
            sum(n for _, _, _, n in buf),
        )
