"""The gated short convolution of the kinds block (`models/blocks.py`), as
LFM2's ``lfm2_moe`` writes it; no bias::

    [B | C | x~] = u W_in                  three widths of hidden_size, in that order
    v = B * x~
    c_t = sum_{j=0..L-1} w_j * v_{t-j}     depthwise, causal, `conv_L_cache` taps a channel
    out = (C * c) W_out

**Packed rows.** A row holds several subjects, and nothing of one reaches the
next: a tap that would reach before its segment's first event reads zero (the
segments by `state_space.segment_ordinal`, the taps by
`state_space.causal_conv`, which the Mamba-2 mixer's convolution also runs). A
padding slot's ``v`` is zero, and the block zeroes its output.

Initialisation: matrices normal with ``init_std``; the taps uniform in
``+-1/2``, as the Mamba-2 mixer's. There is no decode state yet (the last
``L - 1`` values of ``v`` in a generation slot: ROADMAP R4).
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..utils.scopes import scope
from .config import StructuredTransformerConfig
from .latent_attention import bias_free_dense
from .state_space import causal_conv, segment_ordinal


# Float32 inside, on `[B, S, hidden]` planes: what the backward keeps is the inputs in the compute dtype
# (the gates and the taps are computed again there), not a float32 plane of a shifted tap or a product.
@jax.checkpoint
def _gated_taps(b, c, x, kernel, ordinal, mask):
    v = b.astype(jnp.float32) * x.astype(jnp.float32)
    if mask is not None:
        v = jnp.where(mask[..., None], v, 0.0)
    return (c.astype(jnp.float32) * causal_conv(v, kernel, 0.0, ordinal)).astype(x.dtype)


class ShortConvMixer(nn.Module):
    config: StructuredTransformerConfig

    @nn.compact
    def __call__(self, u, attention_mask=None, segment_ids=None):
        cfg = self.config
        width = cfg.hidden_size
        batch, seq_len = u.shape[:2]
        dense = functools.partial(bias_free_dense, cfg)
        with scope("conv_proj"):
            projected = dense(3 * width, "in_proj")(u)
        with scope("conv_gate"):
            b, c, x = (projected[..., i * width : (i + 1) * width] for i in range(3))
            half = lambda key, shape, dtype: jax.random.uniform(key, shape, dtype, -0.5, 0.5)  # noqa: E731
            kernel = self.param("conv_kernel", half, (cfg.conv_L_cache, width), jnp.float32)
            ordinal = segment_ordinal(segment_ids, attention_mask, batch, seq_len)
            y = _gated_taps(b, c, x, kernel, ordinal, attention_mask)
        with scope("conv_proj"):
            return dense(width, "out_proj")(y)
