"""Scanned resident training must be numerically identical to per-batch steps.

`make_chunked_train_step` runs k on-device-collate + train-step iterations in
one ``lax.scan`` program. Contract: given the same plan stream, the final
TrainState and per-step losses match k sequential `make_train_step` calls on
host-collated batches — same dropout rng fold-in, same optimizer updates.
This is what makes the fast path safe to enable by default in ``train()``.
"""

import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eventstreamgpt_tpu.data import DeviceDataset, JaxDataset, PytorchDatasetConfig
from eventstreamgpt_tpu.models.config import OptimizationConfig, StructuredTransformerConfig
from eventstreamgpt_tpu.training import (
    TrainState,
    build_model,
    build_optimizer,
    data_parallel_mesh,
    make_chunked_train_step,
    make_train_step,
    replicate,
    shard_batch,
)

pytestmark = pytest.mark.slow  # compiles train steps; excluded from the fast loop

from tests import SAMPLE_DIR as REF_SAMPLE  # noqa: E402  (the committed artifact)

MODEL_KWARGS = dict(
    hidden_size=32,
    head_dim=8,
    num_attention_heads=4,
    num_hidden_layers=2,
    intermediate_size=32,
    TTE_generation_layer_type="log_normal_mixture",
    TTE_lognormal_generation_num_components=2,
    # Dropout off: the scan and loop paths fold the rng identically, but
    # equality of the *test* is cleaner without stochastic layers.
    resid_dropout=0.0,
    input_dropout=0.0,
    attention_dropout=0.0,
)


@pytest.fixture(scope="module")
def sample_dir(tmp_path_factory):
    dst = tmp_path_factory.mktemp("sample_ds_resident")
    for name in ("vocabulary_config.json", "inferred_measurement_configs.json"):
        shutil.copy(REF_SAMPLE / name, dst / name)
    shutil.copytree(REF_SAMPLE / "DL_reps", dst / "DL_reps")
    return dst


@pytest.fixture(scope="module")
def setup(sample_dir):
    ds = JaxDataset(
        PytorchDatasetConfig(save_dir=sample_dir, max_seq_len=8, min_seq_len=2), "tuning"
    )
    config = StructuredTransformerConfig(**MODEL_KWARGS)
    config.set_to_dataset(ds)
    oc = OptimizationConfig(init_lr=1e-3, batch_size=4, max_epochs=1)
    oc.set_to_dataset(ds)
    model = build_model(config)
    tx, _ = build_optimizer(oc)
    init_batch = next(ds.batches(4, shuffle=True, seed=0))
    # Host copy: train steps donate their state, so each run needs fresh
    # device buffers.
    params_host = jax.device_get(model.init(jax.random.PRNGKey(0), init_batch))

    def fresh_state():
        params = jax.tree_util.tree_map(jnp.asarray, params_host)
        return TrainState(
            step=jnp.zeros((), jnp.int32), params=params, opt_state=tx.init(params)
        )

    return ds, config, model, tx, fresh_state


def _tree_close(a, b, **kw):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), **kw)


class TestChunkedEquivalence:
    def test_padded_chunk_matches_sequential_steps(self, setup):
        ds, config, model, tx, fresh_state = setup
        dd = DeviceDataset(ds)
        rng = jax.random.PRNGKey(3)

        # Reference: sequential per-batch steps on host-collated batches.
        step = make_train_step(model, tx)
        ref_state = fresh_state()
        ref_losses = []
        for b in ds.batches(4, shuffle=True, seed=9):
            ref_state, loss = step(ref_state, b, rng)
            ref_losses.append(float(loss))

        # Chunked: same plan stream, one scan program per chunk.
        chunk_step = make_chunked_train_step(model, tx, dd)
        state = fresh_state()
        losses = []
        for plans, n_events in dd.plan_chunks(4, chunk_steps=2, shuffle=True, seed=9):
            assert n_events > 0
            state, chunk_losses = chunk_step(state, dd.arrays, plans, rng)
            losses.extend(np.asarray(chunk_losses).tolist())

        assert len(losses) == len(ref_losses)
        np.testing.assert_allclose(losses, ref_losses, rtol=1e-5, atol=1e-6)
        _tree_close(state.params, ref_state.params, rtol=1e-5, atol=1e-7)
        assert int(state.step) == int(ref_state.step)

    def test_packed_chunk_matches_sequential_steps(self, setup):
        ds, config, model, tx, fresh_state = setup
        dd = DeviceDataset(ds)
        rng = jax.random.PRNGKey(5)

        host_batches = [
            b
            for b in ds.packed_batches(2, seq_len=16, shuffle=True, seed=4)
            if b.event_mask.shape[0] == 2
        ]
        step = make_train_step(model, tx)
        ref_state = fresh_state()
        ref_losses = []
        for b in host_batches:
            ref_state, loss = step(ref_state, b, rng)
            ref_losses.append(float(loss))

        chunk_step = make_chunked_train_step(model, tx, dd, packed=True)
        state = fresh_state()
        losses = []
        for plans, n_events in dd.packed_plan_chunks(
            2, chunk_steps=2, seq_len=16, shuffle=True, seed=4
        ):
            state, chunk_losses = chunk_step(state, dd.arrays, plans, rng)
            losses.extend(np.asarray(chunk_losses).tolist())

        assert len(losses) == len(ref_losses) and len(losses) > 0
        np.testing.assert_allclose(losses, ref_losses, rtol=1e-5, atol=1e-6)
        _tree_close(state.params, ref_state.params, rtol=1e-5, atol=1e-7)

    def test_mesh_chunked_matches_single_device(self, setup):
        """The scan program under a dp mesh reproduces the unsharded result."""
        ds, config, model, tx, fresh_state = setup
        mesh = data_parallel_mesh(4)
        dd_mesh = DeviceDataset(ds, mesh=mesh)
        dd_solo = DeviceDataset(ds)
        rng = jax.random.PRNGKey(7)

        results = []
        for dd, place in ((dd_solo, None), (dd_mesh, mesh)):
            chunk_step = make_chunked_train_step(model, tx, dd)
            state = fresh_state()
            if place is not None:
                state = replicate(state, place)
            losses = []
            for plans, _ in dd.plan_chunks(4, chunk_steps=2, shuffle=True, seed=2):
                state, chunk_losses = chunk_step(state, dd.arrays, plans, rng)
                losses.extend(np.asarray(chunk_losses).tolist())
            results.append((losses, jax.device_get(state.params)))

        (l0, p0), (l1, p1) = results
        np.testing.assert_allclose(l0, l1, rtol=1e-5, atol=1e-6)
        _tree_close(p0, p1, rtol=1e-5, atol=1e-7)
