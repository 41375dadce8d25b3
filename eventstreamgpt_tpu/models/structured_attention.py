"""Dependency-graph structured attention.

Rebuild of ``/root/reference/EventStream/transformer/structured_attention.py``:
pool each event (last dep-graph element), contextualize pooled events with a
sequence module, build history embeddings by shift-right, then run a
dep-graph module over ``(B*L, G(+1))`` flattened graphs with the history as a
key/value-only first position.

XLA divergence: the reference *compacts* away padding events before the
dep-graph module (``dep_graph_seq[flat_event_mask]``, ``:160-211``) — a
dynamic shape. Here padding rows are processed and the outputs re-zeroed,
which keeps shapes static; padding rows cost flops but never data movement
or recompilation.
"""

from __future__ import annotations

from typing import Any, Callable

import flax.linen as nn
import jax.numpy as jnp

from ..ops import segment_starts
from ..utils.scopes import scope


class StructuredAttention(nn.Module):
    """Wraps a sequence module and a dep-graph module (reference ``:7``).

    ``seq_module`` / ``dep_graph_module`` are constructor callables returning
    flax modules with the `InnerAttention`/`InnerBlock` call signature.
    """

    seq_module: Callable[..., nn.Module]
    dep_graph_module: Callable[..., nn.Module]

    @nn.compact
    def __call__(
        self,
        hidden_states: jnp.ndarray,  # (B, L, G, H)
        seq_attention_mask: jnp.ndarray | None = None,  # (B, L) bool
        event_mask: jnp.ndarray | None = None,  # (B, L) bool
        seq_module_kwargs: dict[str, Any] | None = None,
        dep_graph_module_kwargs: dict[str, Any] | None = None,
        prepend_graph_with_history_embeddings: bool = True,
        update_last_graph_el_to_history_embedding: bool = True,
        segment_ids: jnp.ndarray | None = None,  # (B, L): packed subjects
        history_head: jnp.ndarray | None = None,  # (B, H): position-0 history
        return_contextualized: bool = False,
    ):
        seq_module_kwargs = seq_module_kwargs or {}
        dep_graph_module_kwargs = dep_graph_module_kwargs or {}

        bsz, seq_len, dep_graph_len, hidden_size = hidden_states.shape

        seq_mod = self.seq_module()
        dep_mod = self.dep_graph_module()

        compute_contextualized = (
            prepend_graph_with_history_embeddings or update_last_graph_el_to_history_embedding
        )

        seq_module_return_kwargs = None
        if compute_contextualized:
            with scope("dep_graph"):
                # Whole-event embeddings: the last dep-graph element (input cumsum
                # guarantees it summarizes the event), zeroed at padding events.
                per_event = hidden_states[:, :, -1, :]
                if event_mask is not None:
                    per_event = jnp.where(event_mask[..., None], per_event, 0.0)

            out = seq_mod(
                per_event,
                attention_mask=seq_attention_mask,
                segment_ids=segment_ids,
                **seq_module_kwargs,
            )
            with scope("dep_graph"):
                if isinstance(out, tuple):
                    contextualized_events, seq_module_return_kwargs = out
                else:
                    contextualized_events = out

                if event_mask is not None:
                    contextualized_events = jnp.where(
                        event_mask[..., None], contextualized_events, 0.0
                    )

                if prepend_graph_with_history_embeddings:
                    # History prior to event i = contextualized event i-1 (zeros
                    # for i=0); prepended as a KV-only graph position.
                    # ``history_head`` overrides the i=0 zeros: a WINDOWED
                    # forward's first event is usually not the subject's first —
                    # the speculative-decoding verify pass injects the previous
                    # committed event's contextualized embedding here (carried
                    # in the engine's spec state like a KV cache), so every
                    # window position sees exactly the history the sequential
                    # walk would.
                    head = (
                        history_head[:, None, :]
                        if history_head is not None
                        else jnp.zeros_like(contextualized_events[:, :1, :])
                    )
                    contextualized_history = jnp.concatenate(
                        (head, contextualized_events[:, :-1, :]),
                        axis=1,
                    )
                    if segment_ids is not None:
                        # Packed rows: a segment's first event has no history —
                        # never the previous subject's last contextualized event.
                        contextualized_history = jnp.where(
                            segment_starts(segment_ids)[..., None], 0.0, contextualized_history
                        )
                    dep_graph_seq = jnp.concatenate(
                        (contextualized_history[:, :, None, :], hidden_states), axis=2
                    )
                    static_kv_first = True
                else:
                    dep_graph_seq = hidden_states
                    static_kv_first = False

                if update_last_graph_el_to_history_embedding:
                    dep_graph_seq = dep_graph_seq.at[:, :, -1, :].set(contextualized_events)
        else:
            static_kv_first = False
            dep_graph_seq = hidden_states

        with scope("dep_graph"):
            flat = dep_graph_seq.reshape(bsz * seq_len, -1, hidden_size)

            out = dep_mod(flat, attention_mask=None, static_kv_first=static_kv_first, **dep_graph_module_kwargs)
            if isinstance(out, tuple):
                dep_graph_out, dep_graph_module_return_kwargs = out
            else:
                dep_graph_out, dep_graph_module_return_kwargs = out, None

            dep_graph_all = dep_graph_out.reshape(bsz, seq_len, -1, hidden_size)
            if event_mask is not None:
                dep_graph_all = jnp.where(event_mask[:, :, None, None], dep_graph_all, 0.0)

        extra = {
            "seq_module": seq_module_return_kwargs,
            "dep_graph_module": dep_graph_module_return_kwargs,
        }
        if return_contextualized:
            extra["contextualized"] = (
                contextualized_events if compute_contextualized else None
            )
        return dep_graph_all, extra
