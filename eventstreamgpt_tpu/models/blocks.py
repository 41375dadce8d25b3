"""The kinds block: a layer that names its mixer and its feed-forward.

``h <- h + part(norm(h))`` once or twice a layer: the mixer of layer
``layer_id`` first, where it names one, then its feed-forward, where it names
one, each behind a norm of its own (``mixer_layers``, ``ffn_layers``;
docs/layer_kinds.md). Under ``hc_mult`` > 1 the state is that many residual
streams, a tuple of ``[B, S, C]`` planes, and each part reads and writes them
through learned maps of its own (`models/hyper_connections.py`). Each kind is
a small module: latent attention (`models/latent_attention.py`), the Mamba-2
mixer (`models/state_space.py`), grouped-query attention (here), the gated and
the routed feed-forward (`models/moe.py`). The classic block (`InnerBlock`: LayerNorm, multi-head
attention, GELU MLP, every cache branch) is not migrated here; the encoder
builds one stack or the other.

The call signature is `InnerBlock`'s, so that the encoder's loop and
`remat_block_cls` serve both. There is no decode state yet: a call that asks
for a cache raises.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..utils.scopes import scope
from .config import StructuredTransformerConfig
from .hyper_connections import hyper_connected
from .latent_attention import LatentAttention, RMSNorm, bias_free_dense, causal_core
from .moe import RoutedFeedForward, SwiGLU
from .state_space import Mamba2Mixer
from .transformer import ATTENTION_CHECKPOINT_NAME, NO_DECODE_STATE


class GroupedQueryAttention(nn.Module):
    """``softmax(q k^T / sqrt(d)) v`` with ``num_attention_heads`` query heads
    of ``head_dim`` over ``num_key_value_heads`` key/value heads (query head
    ``i`` reads key/value head ``i // (heads / kv heads)``), causal inside the
    packed segment, no bias and no rotary embedding (``nemotron_h`` applies
    none). The core is `latent_attention.causal_core`; the key/value heads are
    repeated to the query heads before it (the flash op takes one head count)."""

    config: StructuredTransformerConfig

    @nn.compact
    def __call__(self, x, attention_mask=None, segment_ids=None):
        cfg = self.config
        heads, d = cfg.num_attention_heads, cfg.head_dim
        kv_heads = cfg.num_key_value_heads or heads
        batch, seq_len = x.shape[:2]

        dense = functools.partial(bias_free_dense, cfg)
        with scope("attn_proj"):
            query = dense(heads * d, "q_proj")(x).reshape(batch, seq_len, heads, d)
            key = dense(kv_heads * d, "k_proj")(x).reshape(batch, seq_len, kv_heads, d)
            value = dense(kv_heads * d, "v_proj")(x).reshape(batch, seq_len, kv_heads, d)
        with scope("attn_global"):
            key, value = (jnp.repeat(a, heads // kv_heads, axis=2) for a in (key, value))
            out = causal_core(cfg, query, key, value, attention_mask, segment_ids, "grouped-query attention")
            out = checkpoint_name(out, ATTENTION_CHECKPOINT_NAME)
        with scope("attn_proj"):
            return dense(cfg.hidden_size, "o_proj")(out.reshape(batch, seq_len, heads * d))


# A mixer kind's module and the name of its parameters in the layer's tree.
MIXERS = {"latent": (LatentAttention, "self_attn"), "mha": (GroupedQueryAttention, "self_attn"), "ssm": (Mamba2Mixer, "mixer")}


class KindsBlock(nn.Module):
    config: StructuredTransformerConfig
    layer_id: int = 0
    is_seq: bool = True

    @nn.compact
    def __call__(
        self,
        hidden_states,
        attention_mask=None,
        layer_past=None,
        use_cache=False,
        output_attentions=False,
        static_kv_first: bool = False,
        segment_ids=None,
    ):
        cfg = self.config
        if layer_past is not None or use_cache:
            raise NotImplementedError(NO_DECODE_STATE)
        if output_attentions or static_kv_first or not self.is_seq:
            raise NotImplementedError("the kinds block gives no attention weights and serves sequence layers only")
        mixer, ffn = cfg.mixer_layers[self.layer_id], cfg.ffn_layers[self.layer_id]

        def norm(name, x):
            with scope("norm"):
                return RMSNorm(cfg.layer_norm_epsilon, cfg.compute_dtype, name=name)(x)

        def residual(name, x, sublayer):
            """``x + sublayer(x)`` on one stream; on ``hc_mult`` streams the sublayer between
            its learned mixes (`models/hyper_connections.py`), maps ``name``'s."""
            if cfg.hc_mult == 1:
                return x + sublayer(x)
            return hyper_connected(cfg, name, x, sublayer)

        if mixer != "none":
            module, name = MIXERS[mixer]
            hidden_states = residual(
                "mixer_hc",
                hidden_states,
                lambda x: module(cfg, name=name)(norm("input_layernorm", x), attention_mask, segment_ids),
            )
        if ffn != "none":
            # One norm a part: a layer that is a feed-forward alone has the layer's one norm.
            norm_name = "post_attention_layernorm" if mixer != "none" else "input_layernorm"

            def feed_forward(x):
                normed = norm(norm_name, x)
                if ffn == "routed":
                    return RoutedFeedForward(cfg, name="mlp")(normed, attention_mask)
                with scope("mlp"):
                    return SwiGLU(cfg, cfg.intermediate_size, name="mlp")(normed)

            hidden_states = residual("ffn_hc", hidden_states, feed_forward)
        return hidden_states, {}
