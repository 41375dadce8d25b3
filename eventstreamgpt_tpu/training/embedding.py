"""Embedding extraction: encoder-only inference over all splits.

Rebuild of
``/root/reference/EventStream/transformer/lightning_modules/embedding.py:19-155``:
an encoder-only model (pretrained weights grafted from a generative
checkpoint) pooled per subject (``last``/``max``/``mean``/``none``), written
per split to ``{load_from_model_dir}/embeddings/{task_df_name}/
{split}_embeddings.npy`` (numpy instead of torch.save — the consumer surface
is numpy arrays either way). Fill rows in short final batches are dropped via
``valid_mask`` so every subject appears exactly once.
"""

from __future__ import annotations

from pathlib import Path

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..data.jax_dataset import JaxDataset
from ..data.prefetch import prefetch_to_device
from ..models.config import StructuredEventProcessingMode, StructuredTransformerConfig
from ..models.transformer import (
    ConditionallyIndependentPointProcessTransformer,
    NestedAttentionPointProcessTransformer,
)
from ..ops.tensor_ops import safe_masked_max, safe_weighted_avg
from ..parallel.context import kernel_mesh
from .fine_tuning import FinetuneConfig, init_from_pretrained_encoder
from .pretrain import data_parallel_mesh, replicate, shard_batch


class EmbeddingsOnlyModel(nn.Module):
    """Encoder-only wrapper (reference ``embedding.py:19``)."""

    config: StructuredTransformerConfig

    @nn.compact
    def __call__(self, batch, **kwargs):
        cfg = self.config
        if cfg.structured_event_processing_mode == StructuredEventProcessingMode.NESTED_ATTENTION:
            encoder = NestedAttentionPointProcessTransformer(cfg, name="encoder")
        else:
            encoder = ConditionallyIndependentPointProcessTransformer(cfg, name="encoder")
        return encoder(batch, **kwargs)


def embed_batch(model, params, config, batch, pooling_method: str):
    """Pooled per-subject embeddings for one batch (reference ``predict_step``)."""
    encoded = model.apply(params, batch).last_hidden_state
    uses_dep_graph = (
        config.structured_event_processing_mode == StructuredEventProcessingMode.NESTED_ATTENTION
    )
    event_encoded = encoded[:, :, -1, :] if uses_dep_graph else encoded

    if pooling_method == "last":
        B, L, _ = event_encoded.shape
        positions = jnp.arange(L)[None, :]
        last_idx = jnp.max(jnp.where(batch.event_mask, positions, 0), axis=1)
        return event_encoded[jnp.arange(B), last_idx]
    if pooling_method == "max":
        return safe_masked_max(jnp.swapaxes(event_encoded, 1, 2), batch.event_mask)
    if pooling_method == "mean":
        return safe_weighted_avg(jnp.swapaxes(event_encoded, 1, 2), batch.event_mask)[0]
    if pooling_method == "none":
        return event_encoded
    raise ValueError(f"{pooling_method} is not a supported pooling method.")


def get_embeddings(cfg: FinetuneConfig) -> dict[str, Path]:
    """Extracts + writes embeddings for train/tuning/held_out (reference ``:89-155``).

    Returns the written file paths per split.
    """
    config = cfg.config
    oc = cfg.optimization_config

    train_pyd = JaxDataset(cfg.data_config, split="train")
    config.set_to_dataset(train_pyd)

    pooling_method = (config.task_specific_params or {}).get("pooling_method", "last")

    model = EmbeddingsOnlyModel(config)
    init_batch = next(
        train_pyd.batches(min(oc.validation_batch_size, len(train_pyd)), shuffle=False)
    )
    template = model.init(jax.random.PRNGKey(0), init_batch)
    # The generative checkpoint also carries output-layer params; graft just
    # the encoder subtree into the encoder-only template.
    params = init_from_pretrained_encoder(template, cfg.pretrained_weights_fp)

    # Batch-shard extraction over a data mesh (replicated params): the
    # encoder forward runs on every chip.
    mesh = data_parallel_mesh(oc.validation_batch_size)
    params = replicate(params, mesh)

    @jax.jit
    def embed_step(params, batch):
        with kernel_mesh(mesh):  # traced as the trainers' steps are
            return embed_batch(model, params, config, batch, pooling_method)

    out_dir = Path(cfg.load_from_model_dir) / "embeddings" / (cfg.task_df_name or "all")
    written: dict[str, Path] = {}
    from ..data.device_dataset import DeviceDataset

    for sp in ("train", "tuning", "held_out"):
        dataset = train_pyd if sp == "train" else JaxDataset(cfg.data_config, split=sp)
        chunks = []
        # Device-resident batches when the split fits HBM (r05 feed-path
        # redesign: no per-batch wire transfer); host prefetch otherwise.
        # valid_mask is a host array either way, so reading it costs no
        # device sync.
        # Multi-process topologies take the sharded resident layout, whose
        # dealt stream interleaves subject pools — but the saved .npy
        # contract is dataset row order; extraction is a one-shot job, so
        # take the ordered host path there WITHOUT first paying the sharded
        # table build + HBM upload that try_create would do.
        dd = (
            DeviceDataset.try_create(
                dataset, mesh=mesh, batch_sizes=(oc.validation_batch_size,)
            )
            if jax.process_count() == 1
            else None
        )
        if dd is not None:
            batch_iter = (
                (b, np.asarray(b.valid_mask) if b.valid_mask is not None else None)  # graftcheck: allow GC001 -- valid_mask is a host array on device batches, no sync
                for b in dd.batches(
                    oc.validation_batch_size, shuffle=False, drop_last=False, seed=0
                )
            )
        else:
            batch_iter = prefetch_to_device(
                dataset.batches(oc.validation_batch_size, shuffle=False, drop_last=False, seed=0),
                lambda b: shard_batch(b, mesh),
                host_stats_fn=lambda b: (
                    np.asarray(b.valid_mask) if b.valid_mask is not None else None  # graftcheck: allow GC001 -- runs in the prefetch worker on the host batch
                ),
            )
        try:
            for batch, valid in batch_iter:
                emb = np.asarray(embed_step(params, batch))  # graftcheck: allow GC001 -- extraction readback IS the job (embeddings stream to .npy)
                if valid is not None:
                    emb = emb[valid]
                chunks.append(emb)
        finally:
            batch_iter.close()
        embeddings = np.concatenate(chunks, axis=0)

        embeddings_fp = out_dir / f"{sp}_embeddings.npy"
        if jax.process_index() == 0:
            if embeddings_fp.is_file() and not cfg.do_overwrite:
                print(
                    f"Embeddings already exist at {embeddings_fp}. To overwrite, set "
                    "`do_overwrite=True`."
                )
            else:
                embeddings_fp.parent.mkdir(parents=True, exist_ok=True)
                print(f"Saving {sp} embeddings to {embeddings_fp}.")
                np.save(embeddings_fp, embeddings)
        written[sp] = embeddings_fp
    return written
