"""The kinds block: a layer that names its mixer and its feed-forward.

``h <- h + mixer(norm(h))``, ``h <- h + ffn(norm(h))`` with the mixer and the
feed-forward of layer ``layer_id`` read from the configuration
(``mixer_layers``, ``ffn_layers``; docs/layer_kinds.md). Each kind is a small
module of its own: latent attention (`models/latent_attention.py`), the gated
and the routed feed-forward (`models/moe.py`). The classic block
(`InnerBlock`: LayerNorm, multi-head attention, GELU MLP, every cache branch)
is not migrated here; the encoder builds one stack or the other.

The call signature is `InnerBlock`'s, so that the encoder's loop and
`remat_block_cls` serve both. There is no decode state yet: a call that asks
for a cache raises.
"""

from __future__ import annotations

import flax.linen as nn

from ..utils.scopes import scope
from .config import StructuredTransformerConfig
from .latent_attention import LatentAttention, RMSNorm
from .moe import RoutedFeedForward, SwiGLU
from .transformer import NO_DECODE_STATE

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

        def norm(name, x):
            with scope("norm"):
                return RMSNorm(cfg.layer_norm_epsilon, cfg.compute_dtype, name=name)(x)

        mixed = LatentAttention(cfg, name="self_attn")(
            norm("input_layernorm", hidden_states), attention_mask, segment_ids
        )
        hidden_states = hidden_states + mixed
        normed = norm("post_attention_layernorm", hidden_states)
        if cfg.ffn_layers[self.layer_id] == "routed":
            fed = RoutedFeedForward(cfg, name="mlp")(normed, attention_mask)
        else:
            with scope("mlp"):
                fed = SwiGLU(cfg, cfg.intermediate_size, name="mlp")(normed)
        return hidden_states + fed, {}
