"""RoPE's frequencies, plain and under YaRN (``rope_scaling`` of type ``yarn``).

What `models.latent_attention.rotate` and `ops.pallas_rope_join.rope_tables`
both read, so that the XLA formulation and the in-place pass turn by the same
angles. YaRN as DeepSeek-V3's public modelling code writes it.
"""

from __future__ import annotations

import math

import jax.numpy as jnp


def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention factor ``0.1 mscale ln(scale) + 1`` (1 at no stretch)."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_inv_freq(d: int, theta: float, scaling: dict | None = None):
    """The ``d / 2`` float32 frequencies of RoPE over ``d`` dims: ``theta^(-2i/d)``,
    or under a ``yarn`` group (DeepSeek-V3's public modelling code) blended with
    the interpolated ``f_i / factor`` by a linear ramp between the dims that turn
    ``beta_fast`` and ``beta_slow`` times over the original context."""
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if scaling is None:
        return inv_freq
    original = scaling["original_max_position_embeddings"]

    def turns_dim(turns):
        return d * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(turns_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(turns_dim(scaling["beta_slow"])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low) / max(high - low, 0.001), 0.0, 1.0)
    return inv_freq / scaling["factor"] * ramp + inv_freq * (1.0 - ramp)


def rope_cos_sin(positions, d: int, theta: float, scaling: dict | None = None):
    """``(cos, sin)`` float32 ``positions.shape + (d / 2,)`` of RoPE over ``d``
    dims at these positions, YaRN's table scale applied."""
    ang = positions.astype(jnp.float32)[..., None] * rope_inv_freq(d, theta, scaling)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    table_scale = rope_table_scale(scaling)
    return (cos, sin) if table_scale == 1.0 else (cos * table_scale, sin * table_scale)


def rope_table_scale(scaling: dict | None) -> float:
    """What YaRN multiplies cos and sin by: ``m(mscale) / m(mscale_all_dim)``."""
    if scaling is None:
        return 1.0
    return yarn_mscale(scaling["factor"], scaling.get("mscale", 1)) / yarn_mscale(
        scaling["factor"], scaling.get("mscale_all_dim", 0)
    )
