"""The fused CI decode-step megakernel (r20, ISSUE 20 tentpole leg 2).

``_decode_step_ci`` (serving/engine.py) is the serving engine's hot loop:
one event per slot per step, scanned ``decode_chunk`` times per dispatch.
Its per-layer body — pre-LN, q/k/v projection, the per-row-cursor cache
write (quantize-on-write for int8/fp8 caches), the full-buffer attention
read, out-projection, MLP, and the between-layer event-mask zeroing — is
a chain of tiny ``(B, E)``-scale ops that XLA schedules as separate HBM
round-trips. `decode_stack_step` re-expresses the whole transformer stack
as ONE persistent Pallas kernel: a sequential grid over layers whose
carried hidden state lives in a revisited VMEM block, with per-layer
weights and KV planes streamed through leading-axis ``(1, ...)`` blocks.

Fusion boundary (docs/performance.md "The decode megakernel"): the kernel
covers everything BETWEEN the input embedding and the final layer norm —
per-layer LN1 → q/k/v → cursor cache write (+ scale tables) → masked
attention → out-proj residual → LN2 → MLP residual → event-mask zeroing.
It deliberately does NOT absorb:

* the input layer (data embedding + temporal encoding: gather-heavy,
  vocabulary-shaped, already one fusion scope under XLA);
* ``ln_f`` + the generative output layer (distribution heads fan out to
  many small per-measurement projections);
* the sampling tail (already fused — `ops.fused_sampling`, r07) and the
  engine's ``where(active)`` / health-sentinel merges, which must see the
  SAMPLED event and therefore cannot move before the output heads.

Numerics contract (the ``pallas_dep_graph`` discipline): every impl runs
the IDENTICAL jnp formulation of the layer body (`_layer_math`), so the
only divergence left between ``pallas_interpret`` and ``xla`` is backend
reassociation across compilation contexts — structure and all integer
outputs (quantized KV planes, masks, lengths, sampled events) are exact,
floats agree to a last-ulp envelope that compounds over the layer stack
(~1e-5 relative at depth 2; pinned in tests/test_decode_megakernel.py).
`_layer_math` itself mirrors the model's cached S=1 attention branch
(models/transformer.py, `InnerSelfAttention`) op for op — flax LayerNorm
stat order, unscaled fp32 logits, the mask/clamp/softmax chain,
quantize-on-write against `ops.kv_quant` — and the XLA variant is
observed BITWISE against ``model.apply`` at the engine level on CPU fp32,
including int8 caches (the engine parity tests pin it).

Scope: the kernel fuses the monolithic-cache CI decode step. NA models
(per-event dep-graph walks), paged block-pool caches (table-indirect
reads), scanned layer stacks (``scan_layers`` param layout), and serving
meshes are loud typed errors at engine construction (issue #21 tracks
the closure); speculative decoding replaces this step with its own
draft/verify programs and is gated the same way. ``impl`` resolution is
shared package-wide (`ops.impl_select`); hardware ``"pallas"`` lowering
wants lane-aligned ``head_dim``/``hidden_size`` — the CI parity gate runs
the interpreter, and ``auto`` resolves to the A/B-measured production
default (fused XLA, bench.py ``decode_step_impl_winner``).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.transformer import ACT2FN
from .impl_select import ENV_VAR, resolve_impl
from .kv_quant import dequantize_kv, quantize_kv


__all__ = ["decode_stack_step", "stack_layer_weights", "WEIGHT_NAMES", "MOSAIC_REFUSAL"]

# PR 22 asked the chip's compiler (AOT, described v5e device): this kernel
# has never lowered. Its per-layer 2D operands use (1, E) blocks of (L, E)
# arrays, which the TPU lowering refuses ("last two dimensions of your
# block shape [must be] divisible by 8 and 128 ... or equal the array's"),
# and behind that the body is XLA-shaped (4D reshape/swapaxes, two-batch-dim
# einsums, whole-cache `where`s) with one layer's weights plus KV planes far
# past VMEM at hidden 1024. Until it is rewritten for Mosaic, the compiled
# impl raises -- it never interprets or gives way to XLA at run time -- and
# `auto` is the XLA step in code. `pallas_interpret` keeps the parity tests.
MOSAIC_REFUSAL = (
    "decode_stack_step(impl='pallas') does not compile under Mosaic (block "
    "shapes (1, E) of (L, E) operands are refused; the layer body needs a "
    "Mosaic rewrite -- CHANGES.md PR 22). Use decode_step_impl=None/'xla' "
    "on the chip; 'pallas_interpret' runs the same code for parity tests."
)

_F32_MIN = float(jnp.finfo(jnp.float32).min)

# Stacked-weight dict keys -> the per-layer flax param path under
# encoder/h{i} (InnerBlock: attn.layer_norm + attn.attention.{q,k,v,out}
# + block layer_norm + mlp.{c_fc,c_proj}).
WEIGHT_NAMES = {
    "ln1_s": ("attn", "layer_norm", "scale"),
    "ln1_b": ("attn", "layer_norm", "bias"),
    "wq": ("attn", "attention", "q_proj", "kernel"),
    "wk": ("attn", "attention", "k_proj", "kernel"),
    "wv": ("attn", "attention", "v_proj", "kernel"),
    "wo": ("attn", "attention", "out_proj", "kernel"),
    "bo": ("attn", "attention", "out_proj", "bias"),
    "ln2_s": ("layer_norm", "scale"),
    "ln2_b": ("layer_norm", "bias"),
    "wfc": ("mlp", "c_fc", "kernel"),
    "bfc": ("mlp", "c_fc", "bias"),
    "wpr": ("mlp", "c_proj", "kernel"),
    "bpr": ("mlp", "c_proj", "bias"),
}


def stack_layer_weights(encoder_params, n_layers: int) -> dict:
    """Stacks the unrolled ``h{i}`` layer params into leading-``L`` arrays.

    Runs INSIDE the decode jit on the params argument, so hot-swap flips
    (which change the params pytree leaves, not the structure) restack for
    free and the stack itself fuses away into the kernel's operand feeds.
    """

    def pick(path):
        def leaf(i):
            node = encoder_params[f"h{i}"]
            for k in path:
                node = node[k]
            return node

        return jnp.stack([leaf(i) for i in range(n_layers)])

    return {name: pick(path) for name, path in WEIGHT_NAMES.items()}


def _flax_layer_norm(x, scale, bias, eps, cdt):
    """flax.linen.LayerNorm, mirrored to the operation: stats in (at
    least) fp32, ``var = max(0, E[x^2] - E[x]^2)``, and the reference
    multiply order ``(x - mean) * (rsqrt(var + eps) * scale) + bias``."""
    xs = x.astype(jnp.promote_types(jnp.float32, x.dtype))
    mean = jnp.mean(xs, axis=-1, keepdims=True)
    var = jnp.maximum(
        0.0, jnp.mean(xs * xs, axis=-1, keepdims=True) - mean * mean
    )
    mul = jax.lax.rsqrt(var + eps) * scale
    return ((x - mean) * mul + bias).astype(cdt)


def _dense(x, kernel, bias, cdt):
    """flax.linen.Dense: operands promoted to the compute dtype, last-axis
    contraction, broadcast bias add."""
    y = jnp.dot(x.astype(cdt), kernel.astype(cdt))
    if bias is not None:
        y = y + bias.astype(cdt)
    return y


def _layer_math(
    h,
    kc,
    vc,
    ks,
    vs,
    start,
    event_mask,
    new_mask,
    w,
    *,
    window,
    activation,
    eps,
    quantized,
):
    """One InnerBlock at S=1 against a per-row-cursor KV cache.

    Mirrors ``InnerSelfAttention``'s vector-length cache branch +
    ``InnerBlock``'s residual wiring + the CI transformer's between-layer
    event-mask zeroing, on squeezed shapes:

        h (B, E) · kc/vc (B, H, M, D) · ks/vs (B, H, M) fp32 | None
        start (B,) int32 · event_mask (B,) bool · new_mask (B, M) bool

    ``new_mask`` is the ALREADY-UPDATED full-buffer padding mask (this
    event's bit written at the cursor) — it is layer-independent, so the
    caller computes it once. ``window`` is an int32 (0 = global layer);
    the windowing term applies under a ``where`` so the formulation is
    identical whether the value is static (XLA path) or streamed from the
    per-layer operand block (kernel path). Returns
    ``(h', kc', vc', ks', vs')``.
    """
    B, E = h.shape
    H, M, D = kc.shape[1], kc.shape[2], kc.shape[3]
    cdt = h.dtype
    x = h[:, None, :]  # (B, 1, E): the model's S=1 layout

    n1 = _flax_layer_norm(x, w["ln1_s"], w["ln1_b"], eps, cdt)
    split = lambda t: t.reshape(B, 1, H, D).swapaxes(1, 2)  # noqa: E731
    q = split(_dense(n1, w["wq"], None, cdt))  # (B, H, 1, D)
    k = split(_dense(n1, w["wk"], None, cdt))
    v = split(_dense(n1, w["wv"], None, cdt))

    pos = jnp.arange(M)
    write = pos[None, :] == start[:, None]  # (B, M) one-hot at the cursor
    if quantized:
        k_q, k_s = quantize_kv(k, kc.dtype)
        v_q, v_s = quantize_kv(v, vc.dtype)
        new_kc = jnp.where(write[:, None, :, None], k_q, kc)
        new_vc = jnp.where(write[:, None, :, None], v_q, vc)
        new_ks = jnp.where(write[:, None, :], k_s, ks)
        new_vs = jnp.where(write[:, None, :], v_s, vs)
        key = dequantize_kv(new_kc, new_ks, cdt)
        value = dequantize_kv(new_vc, new_vs, cdt)
    else:
        new_kc = jnp.where(write[:, None, :, None], k.astype(kc.dtype), kc)
        new_vc = jnp.where(write[:, None, :, None], v.astype(vc.dtype), vc)
        new_ks = new_vs = None
        key, value = new_kc, new_vc

    # make_causal_mask on (B, 1) query positions: k <= q, and for local
    # layers additionally k > q - window. valid_k (pos < start + 1) is
    # subsumed by the causal term at S=1 but kept for op-parity.
    q_pos = start[:, None, None]  # (B, 1, 1)
    k_pos = pos[None, None, :]  # (1, 1, M)
    w32 = jnp.asarray(window, jnp.int32)
    causal = (k_pos <= q_pos) & jnp.where(w32 > 0, k_pos > q_pos - w32, True)
    mask = causal[:, None] & (pos[None, :] < start[:, None] + 1)[:, None, None, :]

    attn = jnp.einsum(
        "bhqd,bhkd->bhqk", q, key, preferred_element_type=jnp.float32
    )
    attn = jnp.where(mask, attn, _F32_MIN)
    attn = attn + jnp.where(new_mask[:, None, None, :], 0.0, _F32_MIN)
    attn = jnp.maximum(attn, _F32_MIN)
    attn = jax.nn.softmax(attn, axis=-1).astype(value.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", attn, value)
    out = out.swapaxes(-3, -2).reshape(B, 1, E)
    x = _dense(out, w["wo"], w["bo"], cdt) + x  # attn residual

    n2 = _flax_layer_norm(x, w["ln2_s"], w["ln2_b"], eps, cdt)
    m = _dense(n2, w["wfc"], w["bfc"], cdt)
    m = ACT2FN[activation](m)
    x = x + _dense(m, w["wpr"], w["bpr"], cdt)  # MLP residual

    # Between-layer event-mask zeroing (CI transformer loop parity).
    x = jnp.where(event_mask[:, None, None], x, 0.0)
    return x[:, 0, :], new_kc, new_vc, new_ks, new_vs


_W_ORDER = tuple(WEIGHT_NAMES)


def _stack_kernel(
    h0_ref,
    start_ref,
    em_ref,
    nmask_ref,
    win_ref,
    *rest,
    activation,
    eps,
    quantized,
):
    n_w = len(_W_ORDER)
    w_refs = rest[:n_w]
    kc_ref, vc_ref, ks_ref, vs_ref = rest[n_w : n_w + 4]
    h_ref, kco_ref, vco_ref, kso_ref, vso_ref = rest[n_w + 4 :]
    l = pl.program_id(0)

    @pl.when(l == 0)
    def _seed():
        h_ref[...] = h0_ref[...]

    h = h_ref[...]
    start = start_ref[...][:, 0]
    em = em_ref[...][:, 0] != 0
    nmask = nmask_ref[...] != 0
    window = win_ref[...][0, 0]
    w = {name: ref[...][0] for name, ref in zip(_W_ORDER, w_refs)}
    ks = ks_ref[...][0] if quantized else None
    vs = vs_ref[...][0] if quantized else None
    h2, nkc, nvc, nks, nvs = _layer_math(
        h,
        kc_ref[...][0],
        vc_ref[...][0],
        ks,
        vs,
        start,
        em,
        nmask,
        w,
        window=window,
        activation=activation,
        eps=eps,
        quantized=quantized,
    )
    h_ref[...] = h2
    kco_ref[...] = nkc[None]
    vco_ref[...] = nvc[None]
    if quantized:
        kso_ref[...] = nks[None]
        vso_ref[...] = nvs[None]
    else:  # dummy scale blocks: pin deterministic bytes
        kso_ref[...] = jnp.zeros(kso_ref.shape, kso_ref.dtype)
        vso_ref[...] = jnp.zeros(vso_ref.shape, vso_ref.dtype)


def _layer_spec(shape):
    """Leading-layer-axis operand: block (1, *rest) streamed per grid step."""
    nd = len(shape)
    return pl.BlockSpec(
        (1,) + tuple(shape[1:]), lambda l, _nd=nd: (l,) + (0,) * (_nd - 1)
    )


def _pinned_spec(shape):
    """Layer-independent operand: the full array, revisited every step."""
    nd = len(shape)
    return pl.BlockSpec(tuple(shape), lambda l, _nd=nd: (0,) * _nd)


def decode_stack_step(
    weights: dict,
    key_cache: jnp.ndarray,
    value_cache: jnp.ndarray,
    key_scale: jnp.ndarray | None,
    value_scale: jnp.ndarray | None,
    h0: jnp.ndarray,
    start: jnp.ndarray,
    event_mask: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    windows: tuple,
    activation: str,
    layer_norm_eps: float,
    impl: str | None = None,
):
    """One CI decode step through the whole layer stack, fused.

    Args:
        weights: `stack_layer_weights` dict — leading axis ``L`` per leaf.
        key_cache / value_cache: ``(L, B, H, M, D)`` stacked KV planes in
            the cache dtype (quantized int8/fp8 or float).
        key_scale / value_scale: ``(L, B, H, M)`` fp32 scale tables for
            quantized caches, else ``None`` (both or neither).
        h0: ``(B, E)`` input-layer embedding of the current event (already
            event-mask zeroed by the input layer).
        start: ``(B,)`` int32 per-row cache cursors.
        event_mask: ``(B,)`` bool — the decoded event's mask bit.
        mask: ``(B, M)`` bool full-buffer padding mask BEFORE this event.
        windows: per-layer int window sizes, 0 = global. Static.
        activation: config.activation_function (ACT2FN key). Static.
        layer_norm_eps: config.layer_norm_epsilon. Static.
        impl: ``None``/"auto"/"pallas"/"pallas_interpret"/"xla"
            (`ops.impl_select`; ``$ESGPT_PALLAS_IMPL`` overrides auto).

    Returns:
        ``(h, key_cache', value_cache', key_scale', value_scale', mask',
        length')`` — ``h`` is the post-stack hidden state BEFORE ``ln_f``;
        ``mask'``/``length'`` are the layer-shared cache-tracking updates
        (``length' = start + 1``).
    """
    if impl in (None, "auto") and not os.environ.get(ENV_VAR):
        impl = "xla"  # the compiled kernel does not lower (below)
    impl = resolve_impl(impl, "decode_stack_step")
    if impl == "pallas":
        raise NotImplementedError(MOSAIC_REFUSAL)
    L, B = key_cache.shape[0], key_cache.shape[1]
    quantized = key_scale is not None
    if (value_scale is not None) != quantized:
        raise ValueError("key_scale and value_scale must both be set or both None")
    if len(windows) != L:
        raise ValueError(f"windows must have one entry per layer ({L}), got {len(windows)}")
    em_b = event_mask.astype(bool)
    pos = jnp.arange(key_cache.shape[3])
    write = pos[None, :] == start[:, None]
    new_mask = jnp.where(write, em_b[:, None], mask)
    new_length = start + 1

    if impl == "xla":
        h = h0
        nkc, nvc, nks, nvs = [], [], [], []
        for l in range(L):
            wl = {name: weights[name][l] for name in _W_ORDER}
            h, a, b, c, d = _layer_math(
                h,
                key_cache[l],
                value_cache[l],
                key_scale[l] if quantized else None,
                value_scale[l] if quantized else None,
                start,
                em_b,
                new_mask,
                wl,
                window=int(windows[l]),
                activation=activation,
                eps=layer_norm_eps,
                quantized=quantized,
            )
            nkc.append(a)
            nvc.append(b)
            nks.append(c)
            nvs.append(d)
        out_kc, out_vc = jnp.stack(nkc), jnp.stack(nvc)
        out_ks = jnp.stack(nks) if quantized else None
        out_vs = jnp.stack(nvs) if quantized else None
        return h, out_kc, out_vc, out_ks, out_vs, new_mask, new_length

    # Kernel path: sequential grid over layers; h carried in a revisited
    # VMEM output block, weights/KV streamed through leading-axis blocks.
    ks_op = key_scale if quantized else jnp.zeros((L, 1, 1, 1), jnp.float32)
    vs_op = value_scale if quantized else jnp.zeros((L, 1, 1, 1), jnp.float32)
    win_op = jnp.asarray(windows, jnp.int32).reshape(L, 1)
    per_step = [
        h0,
        start.astype(jnp.int32)[:, None],
        em_b.astype(jnp.int32)[:, None],
        new_mask.astype(jnp.int32),
    ]
    per_layer = (
        [win_op]
        + [weights[name] for name in _W_ORDER]
        + [key_cache, value_cache, ks_op, vs_op]
    )
    in_specs = [_pinned_spec(a.shape) for a in per_step] + [
        _layer_spec(a.shape) for a in per_layer
    ]
    out_specs = [
        _pinned_spec(h0.shape),
        _layer_spec(key_cache.shape),
        _layer_spec(value_cache.shape),
        _layer_spec(ks_op.shape),
        _layer_spec(vs_op.shape),
    ]
    out_shape = [
        jax.ShapeDtypeStruct(h0.shape, h0.dtype),
        jax.ShapeDtypeStruct(key_cache.shape, key_cache.dtype),
        jax.ShapeDtypeStruct(value_cache.shape, value_cache.dtype),
        jax.ShapeDtypeStruct(ks_op.shape, ks_op.dtype),
        jax.ShapeDtypeStruct(vs_op.shape, vs_op.dtype),
    ]
    h, out_kc, out_vc, out_ks, out_vs = pl.pallas_call(
        functools.partial(
            _stack_kernel,
            activation=activation,
            eps=layer_norm_eps,
            quantized=quantized,
        ),
        grid=(L,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=impl == "pallas_interpret",
    )(*per_step, *per_layer)
    if not quantized:
        out_ks = out_vs = None
    return h, out_kc, out_vc, out_ks, out_vs, new_mask, new_length
