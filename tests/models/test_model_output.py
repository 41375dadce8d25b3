"""Exact-NLL parity tests for the generative output layer.

Plays the role of the reference's ``tests/transformer/test_model_output.py``
(its largest test file): the losses produced by
`ConditionallyIndependentGenerativeOutputLayer` are recomputed here with
torch following the reference implementation's exact formulas
(``model_output.py:1311-1721``) using the same weights, and must agree.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch
import torch.nn.functional as F

from eventstreamgpt_tpu.data.types import EventStreamBatch
from eventstreamgpt_tpu.models.ci_model import ConditionallyIndependentGenerativeOutputLayer
from eventstreamgpt_tpu.models.config import StructuredTransformerConfig

from .. import _programs as programs

RTOL, ATOL = 1e-4, 1e-5


def make_config(**kwargs):
    defaults = dict(
        vocab_sizes_by_measurement={"event_type": 3, "multi_lab": 4, "lab_vals": 4, "uni_val": 1},
        vocab_offsets_by_measurement={"event_type": 1, "multi_lab": 4, "lab_vals": 8, "uni_val": 12},
        measurements_idxmap={"event_type": 1, "multi_lab": 2, "lab_vals": 3, "uni_val": 4},
        measurements_per_generative_mode={
            "single_label_classification": ["event_type"],
            "multi_label_classification": ["multi_lab", "lab_vals"],
            "multivariate_regression": ["lab_vals"],
            "univariate_regression": ["uni_val"],
        },
        max_seq_len=8,
        hidden_size=12,
        head_dim=3,
        num_attention_heads=4,
        num_hidden_layers=2,
        intermediate_size=12,
    )
    defaults.update(kwargs)
    return StructuredTransformerConfig(**defaults)


def make_batch(seed=0, B=3, L=5, M=4):
    rng = np.random.default_rng(seed)
    event_mask = np.ones((B, L), dtype=bool)
    event_mask[1, 3:] = False
    event_mask[2, 4:] = False

    # Data elements: event_type in [1, 4), multi_lab in [4, 8), lab_vals in
    # [8, 12), uni_val == 12.
    dynamic_indices = np.zeros((B, L, M), dtype=np.int64)
    dynamic_measurement_indices = np.zeros((B, L, M), dtype=np.int64)
    dynamic_values = np.zeros((B, L, M), dtype=np.float32)
    dynamic_values_mask = np.zeros((B, L, M), dtype=bool)
    for b in range(B):
        for l in range(L):
            if not event_mask[b, l]:
                continue
            dynamic_indices[b, l, 0] = rng.integers(1, 4)
            dynamic_measurement_indices[b, l, 0] = 1
            dynamic_indices[b, l, 1] = rng.integers(4, 8)
            dynamic_measurement_indices[b, l, 1] = 2
            if rng.random() < 0.8:
                dynamic_indices[b, l, 2] = rng.integers(8, 12)
                dynamic_measurement_indices[b, l, 2] = 3
                dynamic_values[b, l, 2] = rng.normal()
                dynamic_values_mask[b, l, 2] = True
            if rng.random() < 0.6:
                dynamic_indices[b, l, 3] = 12
                dynamic_measurement_indices[b, l, 3] = 4
                dynamic_values[b, l, 3] = rng.normal()
                dynamic_values_mask[b, l, 3] = True

    return EventStreamBatch(
        event_mask=jnp.asarray(event_mask),
        time_delta=jnp.asarray(rng.uniform(0.5, 20.0, size=(B, L)).astype(np.float32)),
        dynamic_indices=jnp.asarray(dynamic_indices),
        dynamic_measurement_indices=jnp.asarray(dynamic_measurement_indices),
        dynamic_values=jnp.asarray(dynamic_values),
        dynamic_values_mask=jnp.asarray(dynamic_values_mask),
    )


def torch_weighted_loss(loss_per_event, event_mask):
    """Reference ``transformer/utils.py:209`` in torch."""
    w = event_mask.float()
    denom = w.sum(-1)
    safe = torch.where(denom > 0, denom, torch.ones_like(denom))
    per_subj = torch.where(denom > 0, (loss_per_event * w).sum(-1) / safe, torch.zeros_like(denom))
    w2 = (denom > 0).float()
    denom2 = w2.sum(-1)
    return torch.where(denom2 > 0, (per_subj * w2).sum(-1) / denom2, torch.zeros_like(denom2))


class TestCIOutputLayerParity:
    @pytest.fixture(autouse=True, scope="class")
    def built(self, request):
        cls = request.cls
        cls.config = make_config()
        cls.batch = make_batch()
        B, L = cls.batch.event_mask.shape
        rng = np.random.default_rng(7)
        cls.encoded = rng.normal(size=(B, L, cls.config.hidden_size)).astype(np.float32) * 0.5

        cls.layer = ConditionallyIndependentGenerativeOutputLayer(cls.config)
        cls.params = programs.init(cls.layer, jax.random.PRNGKey(0), cls.batch, jnp.asarray(cls.encoded))
        cls.out = programs.apply(cls.layer, cls.params, cls.batch, jnp.asarray(cls.encoded))

        p = cls.params["params"]
        # Shifted encodings used for event-content prediction.
        shifted = np.concatenate(
            [np.zeros_like(cls.encoded[:, :1]), cls.encoded[:, :-1]], axis=1
        )
        cls.t_shifted = torch.from_numpy(shifted)
        cls.t_encoded = torch.from_numpy(cls.encoded)
        cls.cls_scores = cls.t_shifted @ torch.from_numpy(
            np.asarray(p["ClassificationLayer"]["kernel"])
        ) + torch.from_numpy(np.asarray(p["ClassificationLayer"]["bias"]))
        cls.obs_scores = cls.t_shifted @ torch.from_numpy(
            np.asarray(p["IsObservedLayer"]["kernel"])
        ) + torch.from_numpy(np.asarray(p["IsObservedLayer"]["bias"]))
        cls.p = p

        cls.t_event_mask = torch.from_numpy(np.asarray(cls.batch.event_mask))
        cls.t_dyn_idx = torch.from_numpy(np.asarray(cls.batch.dynamic_indices))
        cls.t_dyn_meas = torch.from_numpy(np.asarray(cls.batch.dynamic_measurement_indices))
        cls.t_dyn_vals = torch.from_numpy(np.asarray(cls.batch.dynamic_values))
        cls.t_dyn_vmask = torch.from_numpy(np.asarray(cls.batch.dynamic_values_mask))

    def test_single_label_classification_loss(self):
        scores = self.cls_scores[:, :, 1:4]
        is_obs = self.obs_scores[:, :, 0]
        tensor_idx = self.t_dyn_meas == 1
        events_with_label = tensor_idx.any(-1)
        is_obs_loss = F.binary_cross_entropy_with_logits(
            is_obs, events_with_label.float(), reduction="none"
        )
        labels = ((self.t_dyn_idx * tensor_idx.long()).sum(-1) - 1) * events_with_label.long()
        ce = F.cross_entropy(scores.transpose(1, 2), labels, reduction="none")
        expected = torch_weighted_loss(ce + is_obs_loss, self.t_event_mask & events_with_label)
        actual = float(self.out.losses.classification["event_type"])
        np.testing.assert_allclose(actual, expected.item(), rtol=RTOL, atol=ATOL)

    def test_multi_label_classification_loss(self):
        scores = self.cls_scores[:, :, 4:8]
        tensor_idx = self.t_dyn_meas == 2
        data_labels_or_zero = torch.where(tensor_idx, self.t_dyn_idx - 4 + 1, torch.zeros_like(self.t_dyn_idx))
        labels = torch.zeros(scores.shape[0], scores.shape[1], 1 + scores.shape[2]).scatter(
            2, data_labels_or_zero, 1.0
        )[:, :, 1:]
        bce = F.binary_cross_entropy_with_logits(scores, labels, reduction="none").mean(-1)
        expected = torch_weighted_loss(bce, self.t_event_mask)
        actual = float(self.out.losses.classification["multi_lab"])
        np.testing.assert_allclose(actual, expected.item(), rtol=RTOL, atol=ATOL)

    def test_multivariate_regression_loss(self):
        p = self.p["regression_layer_lab_vals"]["proj"]
        Z = self.t_shifted @ torch.from_numpy(np.asarray(p["kernel"])) + torch.from_numpy(
            np.asarray(p["bias"])
        )
        Z_mean, Z_std = Z[..., 0::2], F.elu(Z[..., 1::2]) + 1 + torch.finfo(torch.float32).tiny
        tensor_idx = (self.t_dyn_meas == 3) & self.t_dyn_vmask
        idx = torch.where(tensor_idx, self.t_dyn_idx - 8, torch.zeros_like(self.t_dyn_idx))
        mean = Z_mean.gather(-1, idx)
        std = Z_std.gather(-1, idx)
        vals = torch.where(tensor_idx, self.t_dyn_vals, torch.zeros_like(self.t_dyn_vals))
        nll = -torch.distributions.Normal(mean, std).log_prob(vals)
        w = tensor_idx.float()
        denom = w.sum(-1)
        safe = torch.where(denom > 0, denom, torch.ones_like(denom))
        loss_per_event = torch.where(denom > 0, (nll * w).sum(-1) / safe, torch.zeros_like(denom))
        events_with_label = self.t_event_mask & tensor_idx.any(-1)
        expected = torch_weighted_loss(loss_per_event, events_with_label)
        actual = float(self.out.losses.regression["lab_vals"])
        np.testing.assert_allclose(actual, expected.item(), rtol=RTOL, atol=ATOL)

    def test_univariate_regression_loss(self):
        p = self.p["regression_layer_uni_val"]["proj"]
        Z = self.t_shifted @ torch.from_numpy(np.asarray(p["kernel"])) + torch.from_numpy(
            np.asarray(p["bias"])
        )
        mean, std = Z[..., 0::2], F.elu(Z[..., 1::2]) + 1 + torch.finfo(torch.float32).tiny
        tensor_idx = self.t_dyn_meas == 4
        is_obs = self.obs_scores[:, :, 3]
        is_obs_loss = F.binary_cross_entropy_with_logits(
            is_obs, tensor_idx.any(-1).float(), reduction="none"
        )
        with_labels = tensor_idx & self.t_dyn_vmask
        events_with_label = with_labels.any(-1)
        vals = (
            torch.where(with_labels, self.t_dyn_vals, torch.zeros_like(self.t_dyn_vals)).sum(-1)
            * events_with_label.float()
        ).unsqueeze(-1)
        nll = -torch.distributions.Normal(mean, std).log_prob(vals).squeeze(-1)
        expected = torch_weighted_loss(nll + is_obs_loss, self.t_event_mask & events_with_label)
        actual = float(self.out.losses.regression["uni_val"])
        np.testing.assert_allclose(actual, expected.item(), rtol=RTOL, atol=ATOL)

    def test_tte_loss_exponential(self):
        p = self.p["TTE_layer"]["proj"]
        rate = (
            F.elu(self.t_encoded @ torch.from_numpy(np.asarray(p["kernel"])) + torch.from_numpy(np.asarray(p["bias"])))
            + 1
            + torch.finfo(torch.float32).tiny
        ).squeeze(-1)
        em = self.t_event_mask
        obs_mask = em[:, 1:] & em[:, :-1]
        delta = torch.from_numpy(np.asarray(self.batch.time_delta))[:, :-1]
        tte_true = torch.where(obs_mask, delta, torch.ones_like(delta))
        tte_true_exp = torch.cat([tte_true, torch.ones_like(tte_true[:, -1:])], dim=-1)
        obs_exp = torch.cat([obs_mask, torch.zeros_like(obs_mask[:, -1:])], dim=-1).float()
        LL = torch.distributions.Exponential(rate).log_prob(tte_true_exp)
        per_patient = (LL * obs_exp).sum(-1) / obs_exp.sum(-1)
        expected = -per_patient.mean()
        actual = float(self.out.losses.time_to_event)
        np.testing.assert_allclose(actual, expected.item(), rtol=RTOL, atol=ATOL)

    def test_total_loss_is_sum(self):
        total = (
            sum(float(v) for v in self.out.losses.classification.values())
            + sum(float(v) for v in self.out.losses.regression.values())
            + float(self.out.losses.time_to_event)
        )
        np.testing.assert_allclose(float(self.out.loss), total, rtol=1e-5)


class TestLogNormalTTEParity:
    def test_tte_loss_lognormal(self):
        config = make_config(
            TTE_generation_layer_type="log_normal_mixture",
            TTE_lognormal_generation_num_components=2,
            mean_log_inter_event_time_min=0.8,
            std_log_inter_event_time_min=1.2,
        )
        batch = make_batch()
        B, L = batch.event_mask.shape
        rng = np.random.default_rng(3)
        encoded = rng.normal(size=(B, L, config.hidden_size)).astype(np.float32) * 0.5

        layer = ConditionallyIndependentGenerativeOutputLayer(config)
        params = programs.init(layer, jax.random.PRNGKey(0), batch, jnp.asarray(encoded))
        out = programs.apply(layer, params, batch, jnp.asarray(encoded))

        p = params["params"]["TTE_layer"]["proj"]
        t_enc = torch.from_numpy(encoded)
        Z = t_enc @ torch.from_numpy(np.asarray(p["kernel"])) + torch.from_numpy(np.asarray(p["bias"]))
        locs, log_scales, log_weights = Z[..., 0::3], Z[..., 1::3], Z[..., 2::3]
        gmm = torch.distributions.MixtureSameFamily(
            torch.distributions.Categorical(logits=log_weights),
            torch.distributions.Normal(locs, log_scales.exp()),
        )
        dist = torch.distributions.TransformedDistribution(
            gmm,
            [
                torch.distributions.transforms.AffineTransform(loc=0.8, scale=1.2),
                torch.distributions.transforms.ExpTransform(),
            ],
        )
        em = torch.from_numpy(np.asarray(batch.event_mask))
        obs_mask = em[:, 1:] & em[:, :-1]
        delta = torch.from_numpy(np.asarray(batch.time_delta))[:, :-1]
        tte_true = torch.where(obs_mask, delta, torch.ones_like(delta))
        tte_true_exp = torch.cat([tte_true, torch.ones_like(tte_true[:, -1:])], dim=-1)
        obs_exp = torch.cat([obs_mask, torch.zeros_like(obs_mask[:, -1:])], dim=-1).float()
        LL = dist.log_prob(tte_true_exp)
        expected = -((LL * obs_exp).sum(-1) / obs_exp.sum(-1)).mean()
        np.testing.assert_allclose(float(out.losses.time_to_event), expected.item(), rtol=RTOL, atol=ATOL)


class TestGenerationMode:
    def test_is_generation_returns_dists_without_losses(self):
        config = make_config()
        batch = make_batch()
        B, L = batch.event_mask.shape
        encoded = jnp.asarray(
            np.random.default_rng(1).normal(size=(B, L, config.hidden_size)).astype(np.float32)
        )
        layer = ConditionallyIndependentGenerativeOutputLayer(config)
        params = programs.init(layer, jax.random.PRNGKey(0), batch, encoded)
        out = programs.apply(layer, params, batch, encoded, is_generation=True)
        assert out.loss is None
        assert out.preds.time_to_event is not None
        assert set(out.preds.classification.keys()) == {"event_type", "multi_lab", "lab_vals"}
        assert set(out.preds.regression.keys()) == {"lab_vals", "uni_val"}
        # Unshifted: content predictions at the last position are usable for
        # sampling the next event.
        cat = out.preds.classification["event_type"][1]
        assert cat.logits.shape == (B, L, 3)


# ------------------------------------------------- the multi-label plane
# The multi-label heads take their dense 0/1 label plane from
# `ops.pallas_multihot.multihot_any`: on a TPU a kernel in one of two
# orientations, elsewhere (and under one lane tile of columns) the broadcast
# compare-any the parent wrote in place. Each case plants, in measurement
# ``multi_lab`` of ``V`` columns at slots of ``M``: an index twice in one
# event, padding slots (index 0), slots of ``other`` whose index lies below
# and above the span (clipped into it they would name its edge columns), an
# out-of-range index under ``multi_lab``'s own name, and an event with no
# label at all. (name, V, M, S, columns in all, compute precision); S and the
# columns in all decide the kernel's orientation: events on the lanes where S
# is whole lane tiles and the columns in all are not.
LABEL_CASES = [
    ("events_minor_v300_m24", 300, 24, 128, 1000, "fp32"),
    ("events_minor_v129_m1", 129, 1, 128, 1000, "fp32"),
    ("events_minor_v300_m24_bf16", 300, 24, 128, 1000, "bf16"),
    ("events_minor_columns_not_whole_sublane_tiles_v200_m5", 200, 5, 256, 777, "fp32"),
    ("vocab_minor_v300_m24", 300, 24, 128, 1024, "fp32"),
    ("vocab_minor_v300_m24_bf16", 300, 24, 128, 1024, "bf16"),
    ("vocab_minor_rows_not_whole_lane_tiles_v2300_m3", 2300, 3, 5, 3000, "fp32"),
    ("vocab_minor_v128_m1", 128, 1, 7, 512, "fp32"),
    ("under_one_lane_tile_v40_m24", 40, 24, 128, 1000, "fp32"),
    ("under_one_lane_tile_v17_m1", 17, 1, 9, 256, "bf16"),
]


def label_case(V, M, S, columns, precision, B=2):
    n_et = 3
    start = 1 + n_et  # ``multi_lab``'s first column in the unified vocabulary
    config = make_config(
        vocab_sizes_by_measurement={"event_type": n_et, "multi_lab": V, "other": columns - start - V},
        vocab_offsets_by_measurement={"event_type": 1, "multi_lab": start, "other": start + V},
        measurements_idxmap={"event_type": 1, "multi_lab": 2, "other": 3},
        measurements_per_generative_mode={
            "single_label_classification": ["event_type"],
            "multi_label_classification": ["multi_lab"],
        },
        max_seq_len=S,
        hidden_size=16,
        head_dim=4,
        precision=precision,
    )
    assert config.vocab_size == columns
    rng = np.random.default_rng(V * 31 + M)
    meas = rng.choice([0, 2, 3], size=(B, S, M), p=[0.3, 0.5, 0.2])
    idx = np.where(meas == 2, rng.integers(start, start + V, (B, S, M)), 0)
    idx = np.where(meas == 3, rng.integers(start + V, columns, (B, S, M)), idx)
    idx[0, 0], meas[0, 0] = 0, 0  # an event of padding alone
    idx[0, 1], meas[0, 1] = start + V + 1, 3  # an event of the other measurement alone
    idx[1, 0], meas[1, 0] = start + V - 1, 2  # every slot names the last column
    if M >= 3:
        idx[1, 1, :3], meas[1, 1, :3] = [start + 5, start + 5, start], 2  # a pair and the first column
        idx[1, 2, :3], meas[1, 2, :3] = [1, start + V + 2, 0], [3, 3, 0]  # other's, below and above the span
        idx[1, 3, :2], meas[1, 3, :2] = [start + V, start - 1], 2  # out of range under its own name
    event_mask = np.ones((B, S), bool)
    event_mask[0, S - 2 :] = False
    batch = EventStreamBatch(
        event_mask=jnp.asarray(event_mask),
        time_delta=jnp.ones((B, S), jnp.float32),
        dynamic_indices=jnp.asarray(idx),
        dynamic_measurement_indices=jnp.asarray(meas),
        dynamic_values=jnp.zeros((B, S, M), jnp.float32),
        dynamic_values_mask=jnp.zeros((B, S, M), bool),
    )
    encoded = jnp.asarray(rng.normal(size=(B, S, config.hidden_size)).astype(np.float32) * 0.5)
    return config, batch, encoded.astype(config.compute_dtype), (start, idx, meas)


@pytest.mark.parametrize("name, V, M, S, columns, precision", LABEL_CASES, ids=[c[0] for c in LABEL_CASES])
def test_multi_label_plane_from_the_kernel_is_the_compare_any_bit_for_bit(monkeypatch, name, V, M, S, columns, precision):
    """Labels, loss and gradient of the multi-label head with the plane's
    kernel (interpreted) against the compare-any formulation (``xla``). Run
    op by op and not under ``jit``, so that what is compared is the plane and
    what the head does with it, and not how XLA's CPU backend orders a fused
    reduction's sums around a different producer."""
    from eventstreamgpt_tpu.ops import pallas_multihot

    config, batch, encoded, (start, idx, meas) = label_case(V, M, S, columns, precision)
    layer = ConditionallyIndependentGenerativeOutputLayer(config)
    params = programs.init(layer, jax.random.PRNGKey(0), batch, encoded)

    def head(p, e):
        losses, _, labels = layer.apply(p, batch, e, {"multi_lab"}, method=layer.get_classification_outputs)
        return losses["multi_lab"], labels["multi_lab"]

    launched, kernels = [], (pallas_multihot._anyhot_2d, pallas_multihot._anyhot_events_minor)
    for kernel in kernels:
        monkeypatch.setattr(
            pallas_multihot, kernel.__name__, lambda *a, _k=kernel, **kw: launched.append(_k.__name__) or _k(*a, **kw)
        )
    got = {}
    for impl in ("xla", "pallas_interpret"):
        monkeypatch.setenv("ESGPT_PALLAS_IMPL", impl)
        (loss, labels), grads = jax.value_and_grad(head, argnums=(0, 1), has_aux=True)(params, encoded)
        got[impl] = jax.tree_util.tree_map(np.asarray, (labels, loss, grads))
    # the orientation the static shapes choose, or none under one lane tile
    events_minor = S % 128 == 0 and columns % 128 != 0
    want = [] if V < 128 else ["_anyhot_events_minor" if events_minor else "_anyhot_2d"]
    assert sorted(set(launched)) == want
    labels = got["xla"][0]
    assert labels.dtype == np.float32 and labels.shape == (2, S, V)
    by_hand = np.zeros((2, S, V + 2), np.float32)  # a column of spill on either side
    b, s, m = np.nonzero(meas == 2)
    np.add.at(by_hand, (b, s, np.clip(idx[b, s, m] - start + 1, 0, V + 1)), 1.0)
    np.testing.assert_array_equal(labels, np.minimum(by_hand[..., 1:-1], 1.0))
    assert labels[0, 0].sum() == 0 and labels[0, 1].sum() == 0 and labels[1, 0].sum() == 1
    for a, b_ in zip(jax.tree_util.tree_leaves(got["xla"]), jax.tree_util.tree_leaves(got["pallas_interpret"])):
        assert a.dtype == b_.dtype
        np.testing.assert_array_equal(a, b_)


@pytest.mark.parametrize("events_minor", [False, True], ids=["vocab_minor", "events_minor"])
@pytest.mark.parametrize("name, V, M, S, columns, precision", LABEL_CASES, ids=[c[0] for c in LABEL_CASES])
def test_multihot_any_kernels_at_every_case_and_orientation(name, V, M, S, columns, precision, events_minor):
    """The op alone, asked for by name (the least width is the head's rule on
    ``auto``): both kernels on every case's indices, the cases under one lane
    tile too, in the case's dtype, against the compare-any."""
    from eventstreamgpt_tpu.ops.pallas_multihot import multihot_any

    _, _, _, (start, idx, meas) = label_case(V, M, S, columns, precision)
    dtype = jnp.bfloat16 if precision == "bf16" else jnp.float32
    sent = jnp.where(meas == 2, idx - start, -1)
    want = multihot_any(sent, V, dtype, impl="xla")
    got = multihot_any(sent, V, dtype, events_minor=events_minor, impl="pallas_interpret")
    assert got.dtype == want.dtype == dtype and got.shape == want.shape == (2, S, V)
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)), np.asarray(want.astype(jnp.float32)))
    assert set(np.unique(np.asarray(got.astype(jnp.float32)))) <= {0.0, 1.0}
