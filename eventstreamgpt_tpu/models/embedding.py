"""Sparse event-data embedding for TPU: gathers + weighted sums on the MXU.

TPU-native re-design of the reference ``DataEmbeddingLayer``
(``/root/reference/EventStream/data/data_embedding_layer.py:55``). The
reference leans on ``torch.nn.EmbeddingBag(mode="sum", padding_idx=0)``; here
the same contract — sum-pooled, value-weighted embeddings of (index,
measurement-index, value) triples with an implicit zero row at padding index
0 — is `ops.embedding_bag`, which reads its formulation from static shapes:
at training sizes one weighted-multihot plane (a Pallas kernel) and a matmul
against the table in each direction, elsewhere ``jnp.take`` + an einsum. XLA
does not fuse the gather into the downstream matmuls: at N = 16,384 slots of
M = 24 measurements, V = 4,057, D = 1,024 in bf16 it was an operation of its
own of 9.0 ms forward, with 9.9 ms more for the table gradient's plane, of a
188.6 ms train step; the plane path takes 2.2 + 0.8 ms there (device trace,
TPU v5e; PERF.md section 6, PR 27). Dep-graph bucketing masks are
computed per batch from the static ``split_by_measurement_indices`` config, so
the output keeps a static ``(B, L, levels, D)`` shape under ``jit``.
"""

from __future__ import annotations

import enum
from typing import Union

import flax.linen as nn
import jax.numpy as jnp

from ..data.types import EventStreamBatch
from ..ops import embedding_bag, grouped_embedding_bag, measurement_index_normalization
from ..utils import StrEnum


class EmbeddingMode(StrEnum):
    """The different ways that the data can be embedded."""

    JOINT = enum.auto()
    SPLIT_CATEGORICAL_NUMERICAL = enum.auto()


class MeasIndexGroupOptions(StrEnum):
    """How a measurement's categorical/numerical parts join a dep-graph group."""

    CATEGORICAL_ONLY = enum.auto()
    CATEGORICAL_AND_NUMERICAL = enum.auto()
    NUMERICAL_ONLY = enum.auto()


MEAS_INDEX_GROUP_T = Union[int, tuple[int, MeasIndexGroupOptions]]


class StaticEmbeddingMode(StrEnum):
    """How static embeddings combine with dynamic embeddings."""

    DROP = enum.auto()
    SUM_ALL = enum.auto()


class DataEmbeddingLayer(nn.Module):
    """Embeds an `EventStreamBatch` into fixed-size per-event embeddings.

    Two modes, matching the reference semantics exactly:

    * **joint** (``categorical_embedding_dim is None``): one table; observed
      values act as per-sample weights with missing values imputed to **1**
      (``data_embedding_layer.py:351-388``).
    * **split** (both split dims set): separate categorical (weight 1/0 by
      ``cat_mask``) and numerical (weight = value, 0 if unobserved) tables,
      each projected to ``out_dim`` and combined by a weighted sum
      (``data_embedding_layer.py:390-452``).

    If ``split_by_measurement_indices`` is given, output is
    ``(B, L, n_groups, out_dim)`` with per-group masks built from the batch's
    ``dynamic_measurement_indices`` (``:505-561``); otherwise ``(B, L,
    out_dim)``. Static embeddings are dropped or sum-combined per
    `StaticEmbeddingMode` with event-mask zeroing (``:609-707``).

    Attributes mirror the reference constructor arguments.
    """

    n_total_embeddings: int
    out_dim: int
    static_embedding_mode: str = StaticEmbeddingMode.SUM_ALL
    categorical_embedding_dim: int | None = None
    numerical_embedding_dim: int | None = None
    split_by_measurement_indices: tuple | None = None
    do_normalize_by_measurement_index: bool = False
    static_weight: float = 0.5
    dynamic_weight: float = 0.5
    categorical_weight: float = 0.5
    numerical_weight: float = 0.5
    embed_dtype: jnp.dtype = jnp.float32
    # Activation/matmul dtype (mixed precision); params stay in embed_dtype.
    # None means "same as embed_dtype" (the fp32 default).
    compute_dtype: jnp.dtype | None = None

    def __post_init__(self):
        super().__post_init__()
        if type(self.out_dim) is not int:
            raise TypeError("`out_dim` must be an `int`.")
        if self.out_dim <= 0:
            raise ValueError("`out_dim` must be positive.")
        if type(self.n_total_embeddings) is not int:
            raise TypeError("`n_total_embeddings` must be an `int`.")
        if self.n_total_embeddings <= 0:
            raise ValueError("`n_total_embeddings` must be positive.")
        if self.static_embedding_mode not in StaticEmbeddingMode.values():
            raise TypeError(
                "`static_embedding_mode` must be a `StaticEmbeddingMode` enum member: "
                f"{StaticEmbeddingMode.values()}."
            )
        cat_dim, num_dim = self.categorical_embedding_dim, self.numerical_embedding_dim
        if (cat_dim is not None) or (num_dim is not None):
            if (cat_dim is None) or (num_dim is None):
                raise ValueError(
                    "If either `categorical_embedding_dim` or `numerical_embedding_dim` is not `None`, "
                    "then both must be not `None`."
                )
            for nm, v in (("categorical_embedding_dim", cat_dim), ("numerical_embedding_dim", num_dim)):
                if type(v) is not int:
                    raise TypeError(f"`{nm}` must be an `int`.")
                if v <= 0:
                    raise ValueError(f"`{nm}` must be positive.")
        if self.split_by_measurement_indices is not None:
            for group in self.split_by_measurement_indices:
                if not isinstance(group, (list, tuple)):
                    raise TypeError("`split_by_measurement_indices` must be a list of lists.")
                for index in group:
                    if not isinstance(index, (int, tuple, list)):
                        raise TypeError(
                            "`split_by_measurement_indices` must be a list of lists of ints and/or tuples."
                        )
                    if isinstance(index, (tuple, list)):
                        if len(index) != 2:
                            raise ValueError(
                                "Each tuple in `split_by_measurement_indices` must have length 2."
                            )
                        idx, mode = index
                        if type(idx) is not int:
                            raise TypeError(
                                "The first element of each tuple in each list of "
                                "`split_by_measurement_indices` must be an int."
                            )
                        if mode not in MeasIndexGroupOptions.values():
                            raise TypeError(
                                "The second element of each tuple in each sublist of "
                                "`split_by_measurement_indices` must be a member of the "
                                f"`MeasIndexGroupOptions` enum: {MeasIndexGroupOptions.values()}."
                            )

    @property
    def _compute(self) -> jnp.dtype:
        return self.compute_dtype if self.compute_dtype is not None else self.embed_dtype

    @property
    def embedding_mode(self) -> EmbeddingMode:
        if self.categorical_embedding_dim is None and self.numerical_embedding_dim is None:
            return EmbeddingMode.JOINT
        return EmbeddingMode.SPLIT_CATEGORICAL_NUMERICAL

    @property
    def _static_frac(self) -> float:
        return self.static_weight / (self.static_weight + self.dynamic_weight)

    @property
    def _dynamic_frac(self) -> float:
        return self.dynamic_weight / (self.static_weight + self.dynamic_weight)

    @property
    def _categorical_frac(self) -> float:
        return self.categorical_weight / (self.categorical_weight + self.numerical_weight)

    @property
    def _numerical_frac(self) -> float:
        return self.numerical_weight / (self.categorical_weight + self.numerical_weight)

    def setup(self):
        init = nn.initializers.normal(stddev=0.02)
        if self.embedding_mode == EmbeddingMode.JOINT:
            self.embed_table = self.param(
                "embed_table", init, (self.n_total_embeddings, self.out_dim), self.embed_dtype
            )
        else:
            self.categorical_embed_table = self.param(
                "categorical_embed_table",
                init,
                (self.n_total_embeddings, self.categorical_embedding_dim),
                self.embed_dtype,
            )
            self.cat_proj = nn.Dense(self.out_dim, dtype=self._compute, name="cat_proj")
            self.numerical_embed_table = self.param(
                "numerical_embed_table",
                init,
                (self.n_total_embeddings, self.numerical_embedding_dim),
                self.embed_dtype,
            )
            self.num_proj = nn.Dense(self.out_dim, dtype=self._compute, name="num_proj")

    def _joint_embed(self, indices, measurement_indices, values=None, values_mask=None):
        if values is None:
            values = jnp.ones(indices.shape, dtype=self._compute)
        else:
            values = jnp.where(values_mask, values, 1.0)
        if self.do_normalize_by_measurement_index:
            values = values * measurement_index_normalization(measurement_indices)
        return embedding_bag(self.embed_table.astype(self._compute), indices, values)

    def _split_embed(self, indices, measurement_indices, values=None, values_mask=None, cat_mask=None):
        cat_values = jnp.ones(indices.shape, dtype=self._compute)
        if cat_mask is not None:
            cat_values = jnp.where(cat_mask, cat_values, 0.0)
        if self.do_normalize_by_measurement_index:
            meas_norm = measurement_index_normalization(measurement_indices)
            cat_values = cat_values * meas_norm

        cat_embeds = self.cat_proj(
            embedding_bag(self.categorical_embed_table.astype(self._compute), indices, cat_values)
        )

        if values is None:
            return cat_embeds

        num_values = jnp.where(values_mask, values, 0.0)
        if self.do_normalize_by_measurement_index:
            num_values = num_values * meas_norm
        num_embeds = self.num_proj(
            embedding_bag(self.numerical_embed_table.astype(self._compute), indices, num_values)
        )

        return self._categorical_frac * cat_embeds + self._numerical_frac * num_embeds

    def _embed(self, indices, measurement_indices, values=None, values_mask=None, cat_mask=None):
        if self.embedding_mode == EmbeddingMode.JOINT:
            return self._joint_embed(indices, measurement_indices, values, values_mask)
        return self._split_embed(indices, measurement_indices, values, values_mask, cat_mask)

    def _static_embedding(self, batch: EventStreamBatch):
        return self._embed(batch.static_indices, batch.static_measurement_indices)

    def _split_batch_into_measurement_index_buckets(self, batch: EventStreamBatch):
        """Builds per-group categorical/numerical masks of shape (B, L, G, M).

        Reference: ``data_embedding_layer.py:505-561``. Group membership is a
        static config property, so the masks are computed by comparing the
        batch's measurement indices against constant index sets — no gather.
        """
        meas_idx = batch.dynamic_measurement_indices  # (B, L, M)
        categorical_masks, numerical_masks = [], []
        for i, meas_index_group in enumerate(self.split_by_measurement_indices):
            if len(meas_index_group) == 0 and i > 0:
                raise ValueError(
                    f"Empty measurement index group: {meas_index_group} at index {i}! "
                    "Only the first (i=0) group can be empty (in cases where there are no "
                    "FUNCTIONAL_TIME_DEPENDENT measurements)."
                )
            group_cat = jnp.zeros(meas_idx.shape, dtype=bool)
            group_num = jnp.zeros(meas_idx.shape, dtype=bool)
            for meas_index in meas_index_group:
                if isinstance(meas_index, (tuple, list)):
                    meas_index, group_mode = meas_index
                else:
                    group_mode = MeasIndexGroupOptions.CATEGORICAL_AND_NUMERICAL
                new_mask = meas_idx == meas_index
                if group_mode == MeasIndexGroupOptions.CATEGORICAL_AND_NUMERICAL:
                    group_cat = group_cat | new_mask
                    group_num = group_num | new_mask
                elif group_mode == MeasIndexGroupOptions.CATEGORICAL_ONLY:
                    group_cat = group_cat | new_mask
                elif group_mode == MeasIndexGroupOptions.NUMERICAL_ONLY:
                    group_num = group_num | new_mask
                else:
                    raise ValueError(f"Invalid group mode: {group_mode}")
            categorical_masks.append(group_cat)
            numerical_masks.append(group_num)
        return jnp.stack(categorical_masks, axis=-2), jnp.stack(numerical_masks, axis=-2)

    def _joint_embed_grouped(self, indices, measurement_indices, values, values_mask_g):
        """JOINT embedding over G dep-graph groups with ONE table gather.

        Groups share the same token indices — only the per-group weights
        differ (a token weighs its value inside its group's numerical mask,
        1 elsewhere; reference ``data_embedding_layer.py:575-588`` +
        ``:380-388``, which broadcasts the gather G-fold). Gathering once
        and applying the ``(B, L, G, M)`` weights as an einsum computes the
        identical sum with a G-fold smaller gather and — the expensive part
        — a G-fold smaller backward scatter into the table (profiling the
        NA step showed that scatter as its single largest op).
        """
        w = jnp.where(values_mask_g, values[:, :, None, :], 1.0)
        if self.do_normalize_by_measurement_index:
            w = w * measurement_index_normalization(measurement_indices)[:, :, None, :]
        return grouped_embedding_bag(self.embed_table.astype(self._compute), indices, w)

    def _split_embed_grouped(self, indices, measurement_indices, values, values_mask_g, cat_mask):
        """SPLIT_CATEGORICAL_NUMERICAL over G groups, one gather per table."""
        norm = (
            measurement_index_normalization(measurement_indices)
            if self.do_normalize_by_measurement_index
            else jnp.ones(indices.shape, dtype=self._compute)
        )
        cat_w = jnp.where(cat_mask, norm[:, :, None, :], 0.0)
        cat_embeds = self.cat_proj(
            grouped_embedding_bag(
                self.categorical_embed_table.astype(self._compute), indices, cat_w
            )
        )

        num_w = jnp.where(values_mask_g, values[:, :, None, :] * norm[:, :, None, :], 0.0)
        num_embeds = self.num_proj(
            grouped_embedding_bag(
                self.numerical_embed_table.astype(self._compute), indices, num_w
            )
        )

        return self._categorical_frac * cat_embeds + self._numerical_frac * num_embeds

    def _dynamic_embedding(self, batch: EventStreamBatch):
        if self.split_by_measurement_indices:
            cat_mask, num_mask = self._split_batch_into_measurement_index_buckets(batch)
            values_mask_g = batch.dynamic_values_mask[:, :, None, :] & num_mask
            if self.embedding_mode == EmbeddingMode.JOINT:
                return self._joint_embed_grouped(
                    batch.dynamic_indices,
                    batch.dynamic_measurement_indices,
                    batch.dynamic_values,
                    values_mask_g,
                )
            return self._split_embed_grouped(
                batch.dynamic_indices,
                batch.dynamic_measurement_indices,
                batch.dynamic_values,
                values_mask_g,
                cat_mask,
            )
        return self._embed(
            batch.dynamic_indices,
            batch.dynamic_measurement_indices,
            batch.dynamic_values,
            batch.dynamic_values_mask,
            None,
        )

    def __call__(self, batch: EventStreamBatch) -> jnp.ndarray:
        """Returns (B, L, out_dim) or (B, L, n_groups, out_dim) embeddings."""
        embedded = self._dynamic_embedding(batch)

        mask = batch.event_mask
        while mask.ndim < embedded.ndim:
            mask = mask[..., None]
        embedded = jnp.where(mask, embedded, 0.0)

        # Batches without static data (e.g. packed long-context batches, where
        # statics are per-subject and don't pack) degrade to DROP.
        if self.static_embedding_mode == StaticEmbeddingMode.DROP or batch.static_indices is None:
            return embedded

        static_embedded = self._static_embedding(batch)[:, None]  # (B, 1, D)
        if self.split_by_measurement_indices:
            static_embedded = static_embedded[:, :, None]  # (B, 1, 1, D)

        embedded = self._dynamic_frac * embedded + self._static_frac * static_embedded
        return jnp.where(mask, embedded, 0.0)
