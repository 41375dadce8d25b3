"""The names the program writes into a profiler trace.

`scope` puts ``es.<name>`` on the name stack, so that every operation traced
under it carries the name in its ``op_name`` metadata; the innermost ``es.``
component of an ``op_name`` path is the operation's scope, and the phase
(forward, backward, recompute) is read from what JAX writes around it
(``transpose(...)``, ``rematted_computation``). `host_span` is a
``TraceAnnotation`` on the host's thread line, ``es.host/<name>``. Both are
metadata: always on, nothing to switch, and the compiled arithmetic is the
same with and without them. ``benchmark/harness/scopes.py`` reads the names
back from a device trace; a name that is not listed here raises, so the list
below is the whole contract.
"""

from __future__ import annotations

import functools

import jax

SCOPES = (
    "collate",  # the scan body's device-side collation
    "embed",  # the input layer: data embedding, time encoding, static codes
    "norm",  # every LayerNorm of a block and ln_f
    "attn_proj",  # q, k, v and output projections
    "attn_latent",  # latent attention between the normed input and q, k, v: its four
    # down/up projections, the two latent norms, RoPE, the concatenations
    "attn_global",  # what lies between the projections in a global layer
    "attn_local",  # ... in a local layer
    "dep_graph",  # NA's dependency-graph attention and its plumbing
    "ssm_proj",  # a state-space (Mamba-2) mixer's input and output projections
    "ssm_conv",  # its causal convolution inside the segment, silu, the splits
    "ssm_scan",  # softplus, the decays, the chunked scan, the D skip
    "ssm_gate",  # the gate and the grouped norm
    "hc_maps",  # a hyper-connected sublayer's maps: the streams' norm, Phi's product, the sigmoids, Sinkhorn
    "hc_mix",  # its pre-mix of the streams, its post/res mix back into them, the sum before ln_f
    "mlp",  # the feed-forward block (the classic MLP, the dense SwiGLU)
    "moe_router",  # a routed layer's logits, sigmoid, top-k and weights
    "moe_dispatch",  # ordering the (row, expert) pairs by expert, gathering rows, combining back
    "moe_experts",  # the grouped products of the experts held here
    "moe_shared",  # the shared expert
    "heads_tte",  # time-to-event head and its log-likelihood
    "heads_cls",  # classification heads and their losses
    "heads_reg",  # regression heads and their losses
    "loss",  # what the output layer does after the three
    "optimizer",  # tx.update + apply_updates
    "health",  # the divergence sentinel's vector
)

HOST_SPANS = ("plan", "dispatch", "checkpoint", "log_flush", "eval")


def scope(name: str):
    """``jax.named_scope("es.<name>")``."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not one of the program's scopes {SCOPES}")
    return jax.named_scope("es." + name)


def scoped(name: str):
    """Decorator: the whole function runs under `scope` ``name`` (a fresh
    context each call, so it nests and is safe across tracing threads)."""
    scope(name)  # a wrong name raises where the function is defined

    def decorate(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)

        return wrapped

    return decorate


def host_span(name: str):
    """``TraceAnnotation("es.host/<name>")``: free when no trace runs."""
    if name not in HOST_SPANS:
        raise ValueError(f"{name!r} is not one of the program's host spans {HOST_SPANS}")
    return jax.profiler.TraceAnnotation("es.host/" + name)
