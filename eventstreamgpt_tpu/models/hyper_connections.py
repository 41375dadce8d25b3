"""Manifold-constrained hyper-connections: ``n`` residual streams around a sublayer.

The residual path of the kinds block (`models/blocks.py`) where
``config.hc_mult`` = ``n`` > 1 (arXiv:2512.24880; Xing4.0's ``hc_mult``,
``hc_sinkhorn_iters``, ``hc_eps``, ``mhc_h_res_clamp_min/max``;
docs/layer_kinds.md). An event's state is ``X`` in ``R^{n x C}``; every
sublayer ``F`` (a layer's mixer, then its feed-forward, each with maps of its
own) is wrapped so::

    r = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)       float32, no learned scale
    [p | q | R] = r Phi                                     Phi: nC x (n + n + n^2), float32 at highest
    H_pre = sigmoid(a_pre p + b_pre)                        n
    H_post = 2 sigmoid(a_post q + b_post)                   n
    M = exp(clip(a_res mat(R) + b_res, -clamp, clamp))      n x n
    hc_sinkhorn_iters times: every column of M over its sum + hc_eps, then every row over its sum + hc_eps
    H_res = M                                               doubly stochastic to the iteration's accuracy
    u = sum_i H_pre[i] X[i];  y = F(RMSNorm(u))
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

The streams ride as a tuple of ``n`` planes ``[B, S, C]`` in the compute dtype
(nothing is tiled over the 4-wide axis, and no pass stacks, slices or converts
the ``n C`` values of an event as one array), the maps as planes ``[n, B, S]``
and ``[n, n, B, S]`` in float32 with the events on the lanes; the two mixes
accumulate in float32. `transformer.py` replicates the event embedding into
the streams and sums them before ``ln_f``.

Spans: ``es.hc_maps`` (the norm, ``Phi``'s product, the three maps) and
``es.hc_mix`` (the pre-mix's sum and the post/res mix).
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..utils.scopes import scope
from .config import StructuredTransformerConfig

# At the seed's weights: H_pre near 1/n, H_post near 1, H_res near the identity
# with `3 / (exp(RES_DIAGONAL) + 3)` = 8% of a row's mass off the diagonal, and
# gains large enough that Phi's product moves every map by a tenth and more.
GAIN = 0.1
RES_DIAGONAL = 3.5


def sinkhorn(logits, iters: int, eps: float, clamp: float):
    """``[n, n, ...]`` logits (row, column, events) -> the matrices after
    ``iters`` rounds of: columns over their sums, then rows over theirs."""
    m = jnp.exp(jnp.clip(logits, -clamp, clamp))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    return m


def pre_mix(streams, h_pre):
    """``u = sum_i H_pre[i] X[i]``: ``n`` planes ``[B, S, C]``, ``[n, B, S]`` -> ``[B, S, C]``."""
    u = sum(h_pre[i][..., None] * x.astype(jnp.float32) for i, x in enumerate(streams))
    return u.astype(streams[0].dtype)


def post_mix(streams, y, h_post, h_res):
    """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``, plane by plane."""
    return tuple(
        (
            sum(h_res[i, j][..., None] * x.astype(jnp.float32) for j, x in enumerate(streams))
            + h_post[i][..., None] * y.astype(jnp.float32)
        ).astype(x_i.dtype)
        for i, x_i in enumerate(streams)
    )


def _float32_product(x, w):
    """``x @ w`` for a float32 ``w`` to float32's accuracy. A bfloat16 ``x`` is
    exact in three bfloat16 pieces of ``w`` (one pass over ``x`` against the
    pieces side by side, float32 accumulation: what ``highest`` computes for
    an ``x`` that has no lower pieces); any other ``x`` goes at ``highest``
    (float32 streams: the comparison with the reference in float32,
    `tests/benchmark/test_routed_hc.py`, and `check_limits.py --witness-fp32`)."""
    if x.dtype != jnp.bfloat16:
        return jnp.dot(x.astype(jnp.float32), w, precision=jax.lax.Precision.HIGHEST)
    pieces, rest = [], w
    for _ in range(3):
        pieces.append(rest.astype(jnp.bfloat16))
        rest = rest - pieces[-1].astype(jnp.float32)
    out = jnp.dot(x, jnp.concatenate(pieces, axis=1), preferred_element_type=jnp.float32)
    return sum(jnp.split(out, 3, axis=-1))


def _bias_init(n: int):
    def init(key, shape, dtype):
        del key
        pre = jnp.full((n,), -math.log(n - 1.0), dtype)  # sigmoid = 1 / n
        return jnp.concatenate([pre, jnp.zeros((n,), dtype), (RES_DIAGONAL * jnp.eye(n, dtype=dtype)).reshape(-1)])

    return init


class HyperConnection(nn.Module):
    """One sublayer's maps from its streams (``n`` planes ``[B, S, C]``):
    ``(H_pre [n, B, S], H_post [n, B, S], H_res [n, n, B, S])``, float32."""

    config: StructuredTransformerConfig

    @nn.compact
    def __call__(self, streams):
        cfg = self.config
        n, width = len(streams), streams[0].shape[-1]
        phi = self.param("phi", nn.initializers.normal(stddev=cfg.init_std), (n * width, 2 * n + n * n), jnp.float32)
        gain = self.param("gain", nn.initializers.constant(GAIN), (3,), jnp.float32)
        bias = self.param("bias", _bias_init(n), (2 * n + n * n,), jnp.float32)
        with scope("hc_maps"):
            # r Phi with r's one scalar an event taken out of the product.
            raw = sum(_float32_product(x, phi[i * width : (i + 1) * width]) for i, x in enumerate(streams))
            mean_sq = sum(jnp.sum(jnp.square(x.astype(jnp.float32)), axis=-1) for x in streams) / (n * width)
            proj = raw * jax.lax.rsqrt(mean_sq + cfg.layer_norm_epsilon)[..., None]
            proj = jnp.moveaxis(proj, -1, 0)  # [2n + n^2, B, S]: the events on the lanes
            b = bias[:, None, None]
            h_pre = jax.nn.sigmoid(gain[0] * proj[:n] + b[:n])
            h_post = 2.0 * jax.nn.sigmoid(gain[1] * proj[n : 2 * n] + b[n : 2 * n])
            logits = (gain[2] * proj[2 * n :] + b[2 * n :]).reshape((n, n) + proj.shape[1:])
            h_res = sinkhorn(logits, cfg.hc_sinkhorn_iters, cfg.hc_eps, cfg.hc_res_clamp)
        return h_pre, h_post, h_res


def hyper_connected(cfg, name: str, streams, sublayer):
    """``sublayer`` (normed input -> output, its norm inside) on the pre-mix of
    ``streams``, written back through the post and res maps. Called inside
    the block's ``@nn.compact`` method; the maps' parameters are ``name``'s."""
    h_pre, h_post, h_res = HyperConnection(cfg, name=name)(streams)
    with scope("hc_mix"):
        u = pre_mix(streams, h_pre)
    y = sublayer(u)
    with scope("hc_mix"):
        return post_mix(streams, y, h_post, h_res)
