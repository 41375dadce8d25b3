"""The Mamba-2 mixer of the kinds block (`models/blocks.py`), as ``nemotron_h``
writes it; no bias but the convolution's::

    [z | xBC | dt] = u W_in                       widths H P | H P + 2 G N | H
    xBC = silu(conv(xBC))                          depthwise, causal, `mamba_conv_kernel` taps and a bias a channel
    [x | B | C] = xBC                              heads H x P; groups G x N, head h reads group h // (H / G)
    Delta = softplus(dt + dt_bias),  A = -exp(A_log)
    S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t B_t^T,    y_t = S_t C_t + D x_t        per head, S of P x N
    y = RMSNorm_grouped(y * silu(z))               gate first, then RMS over each of the G groups of H P / G channels
    out = y W_out

**Packed rows.** A row holds several subjects, and nothing of one reaches the
next: a tap of the convolution that would reach before its segment's first
event reads zero, and ``S`` is zero before a segment's first event. A padding
slot is a segment of its own, gets ``x = 0`` and ``Delta = 0``, and the block
zeroes its output. On the normal path the recurrence is the chunked form of
`ops/ssd_scan.py` at ``mamba_chunk_size`` events a chunk.

Initialisation: matrices normal with ``init_std``; ``A_log = log(1..H)``,
``D = 1``, ``dt_bias`` the inverse softplus of a step size drawn log-uniformly
from `DT_RANGE` and floored at `DT_FLOOR` (the ``nemotron_h`` defaults), the
convolution's weights uniform in ``+-1/2`` and its bias zero, the norm's 1.
There is no decode state yet (the recurrent state and the convolution's last
taps in a generation slot: ROADMAP R4).
"""

from __future__ import annotations

import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import segment_starts
from ..ops.impl_select import LANE
from ..ops.ssd_scan import ssd_scan
from ..utils.scopes import scope
from .config import StructuredTransformerConfig
from .latent_attention import bias_free_dense

DT_RANGE = (1e-3, 1e-1)
DT_FLOOR = 1e-4


def dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of ``exp(U(log lo, log hi))`` floored at `DT_FLOOR`."""
    lo, hi = (math.log(v) for v in DT_RANGE)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, dtype, lo, hi)), DT_FLOOR)
    return dt + jnp.log(-jnp.expm1(-dt))


def segment_ordinal(segment_ids, attention_mask, batch_size: int, seq_len: int):
    """``[B, S]`` int32 that never decreases along a row: the running count of
    segment starts, a padding slot counted as a segment of its own. Two
    positions lie in one segment exactly where their ordinals are equal."""
    seg = jnp.zeros((batch_size, seq_len), jnp.int32) if segment_ids is None else segment_ids.astype(jnp.int32)
    if attention_mask is not None:
        seg = jnp.where(attention_mask, seg, -1)
    return jnp.cumsum(segment_starts(seg), axis=1, dtype=jnp.int32)


def causal_conv(x, kernel, bias, ordinal):
    """Depthwise causal convolution over axis 1 of ``x`` ``[B, S, C]`` with
    ``kernel`` ``[K, C]`` (the last tap is the event itself) and ``bias``
    ``[C]``, over the events of the same segment only. Float32 inside."""
    taps, seq_len = kernel.shape[0], x.shape[1]
    out = bias + kernel[-1] * x.astype(jnp.float32)
    for back in range(1, min(taps, seq_len)):
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :seq_len]  # shifted as it is held; float32 in the sum
        same = jnp.pad(ordinal, ((0, 0), (back, 0)), constant_values=-1)[:, :seq_len] == ordinal
        out = out + kernel[-1 - back] * jnp.where(same[..., None], shifted, 0).astype(jnp.float32)
    return out


# Both run in float32 on planes of `[B, S, d_inner]` and wider; what the backward keeps of them is their
# inputs in the compute dtype (they are computed again there), not a float32 plane a shifted tap or a factor.
@jax.checkpoint
def _conv_silu(xbc, kernel, bias, ordinal):
    return nn.silu(causal_conv(xbc, kernel, bias, ordinal)).astype(xbc.dtype)


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _gate_norm(y, z, scale, groups: int, eps: float):
    """``RMSNorm_grouped(y * silu(z))``: gate first, then RMS over each of the
    ``groups`` groups of channels, times ``scale``.

    The scan's kernels hand ``y`` over row-major, and a group's channels are
    whole lane tiles there; reshaped to ``[..., groups, width]`` the compiler
    re-lays the float32 plane with a group down the sublanes (three copies of
    268 MB a layer at 16 rows of 1,024 events, PERF.md section 6, PR 33). So
    where the shapes allow, the mean runs over the plane as the chip tiles it,
    ``[row tiles, 8 rows, groups, lane tiles, 128]``, and nothing is moved."""
    gated = y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
    width = y.shape[-1] // groups
    if width % LANE == 0 and (gated.size // y.shape[-1]) % 8 == 0:
        shape, over = (-1, 8, groups, width // LANE, LANE), (3, 4)
    else:
        shape, over = y.shape[:-1] + (groups, width), (-1,)
    gated = gated.reshape(shape)
    gated = gated * jax.lax.rsqrt(jnp.mean(jnp.square(gated), axis=over, keepdims=True) + eps)
    return (scale * gated.reshape(y.shape)).astype(y.dtype)


class Mamba2Mixer(nn.Module):
    config: StructuredTransformerConfig

    @nn.compact
    def __call__(self, u, attention_mask=None, segment_ids=None):
        cfg = self.config
        heads, p, groups, n = cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.mamba_n_groups, cfg.ssm_state_size
        inner, conv_dim = heads * p, heads * p + 2 * groups * n
        batch, seq_len = u.shape[:2]
        dense = functools.partial(bias_free_dense, cfg)
        with scope("ssm_proj"):
            projected = dense(inner + conv_dim + heads, "in_proj")(u)
        with scope("ssm_conv"):
            z, xbc, dt = projected[..., :inner], projected[..., inner : inner + conv_dim], projected[..., -heads:]
            ordinal = segment_ordinal(segment_ids, attention_mask, batch, seq_len)
            half = lambda key, shape, dtype: jax.random.uniform(key, shape, dtype, -0.5, 0.5)  # noqa: E731
            kernel = self.param("conv_kernel", half, (cfg.mamba_conv_kernel, conv_dim), jnp.float32)
            bias = self.param("conv_bias", nn.initializers.zeros, (conv_dim,), jnp.float32)
            xbc = _conv_silu(xbc, kernel, bias, ordinal)
            x = xbc[..., :inner].reshape(batch, seq_len, heads, p)
            bmat = xbc[..., inner : inner + groups * n].reshape(batch, seq_len, groups, n)
            cmat = xbc[..., inner + groups * n :].reshape(batch, seq_len, groups, n)
            if attention_mask is not None:
                x = jnp.where(attention_mask[..., None, None], x, 0)
        with scope("ssm_scan"):
            dt_bias = self.param("dt_bias", dt_bias_init, (heads,), jnp.float32)
            a_log = self.param(
                "A_log", lambda key, shape, dtype: jnp.log(jnp.arange(1, shape[0] + 1, dtype=dtype)), (heads,), jnp.float32
            )
            skip = self.param("D", nn.initializers.ones, (heads,), jnp.float32)
            dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            if attention_mask is not None:
                dt = jnp.where(attention_mask[..., None], dt, 0.0)
            y = ssd_scan(x, dt, -jnp.exp(a_log), bmat, cmat, ordinal, chunk=cfg.mamba_chunk_size, skip=skip)
        with scope("ssm_gate"):
            scale = self.param("norm_scale", nn.initializers.ones, (inner,), jnp.float32)
            y = _gate_norm(y.reshape(batch, seq_len, inner), z, scale, groups, cfg.layer_norm_epsilon)
        with scope("ssm_proj"):
            return dense(cfg.hidden_size, "out_proj")(y)
