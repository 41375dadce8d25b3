"""`ops.pallas_flash` -- the global layers' flash attention op.

The three kernels run in Pallas' interpreter on the CPU (the same kernel
code the chip compiles; `tests/test_chip_compile.py` asks Mosaic) against a
plain float32 softmax over the keys ``seg[k] == seg[q] and k <= q``: output
and the three gradients, in float32 to 1e-5 and in bf16 to the tolerance
``chip_smoke.py`` holds the bf16 kernels to (2e-2 of the reference's scale).
The bounds are checked on their own: every pair the mask allows lies in a
visited chunk pair, whatever the segment layout.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eventstreamgpt_tpu.ops.pallas_flash import (
    FlashSizes,
    chunk_bounds,
    flash_attention,
    flash_block_sizes,
    lane_tile_groups,
    visited_share,
)

pytestmark = pytest.mark.pallas


def reference(q, k, v, seg, scale):
    """Plain float32 softmax attention over ``[B, S, H, d]``."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") * scale
    pos = jnp.arange(q.shape[1])
    mask = (pos[None, :] <= pos[:, None])[None, None] & (seg[:, None, :, None] == seg[:, None, None, :])
    probs = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision="highest")


def segments(layout: str, B: int, S: int, rng) -> np.ndarray:
    seg = np.zeros((B, S), np.int32)
    if layout == "one_segment":
        return seg
    if layout == "one_segment_padded":  # the padded cell's rows
        lengths = rng.integers(S // 8, S, B)
        return np.where(np.arange(S)[None, :] < lengths[:, None], 0, -1).astype(np.int32)
    for b in range(B):
        pos, ids = 0, []
        while pos < S:
            n = int(rng.integers(S // 16, S // 3))
            ids.append((pos, min(pos + n, S)))
            pos += n
        if layout == "packed_padded":  # ascending ids, the last stretch padding
            for s, (lo, hi) in enumerate(ids):
                seg[b, lo:hi] = s
            seg[b, ids[-1][0] :] = -1
        elif layout == "long_segment":  # one segment longer than two chunks, then short ones
            seg[b] = 1 + np.searchsorted(np.asarray([hi for _, hi in ids]), np.arange(S), side="right")
            seg[b, : min(S, 300)] = 0
        elif layout == "scrambled":  # ids neither contiguous nor ascending, padding in the middle
            labels = rng.permutation(len(ids) + 3)[: len(ids)] * 7 - 5
            for (lo, hi), s in zip(ids, labels):
                seg[b, lo:hi] = s
            seg[b, S // 2 : S // 2 + 9] = -1
            seg[b, rng.integers(0, S, 12)] = labels[0]  # strays of the first id all over the row
        else:
            raise ValueError(layout)
    return seg


def case(B, S, H, d, dtype, layout, seed):
    rng = np.random.default_rng(seed)
    # logits of order one at scale 1: the softmax is neither flat nor one-hot
    q, k = (jnp.asarray(rng.normal(size=(B, S, H, d)) * d**-0.25, jnp.float32).astype(dtype) for _ in range(2))
    v, w = (jnp.asarray(rng.normal(size=(B, S, H, d)), jnp.float32).astype(dtype) for _ in range(2))
    return q, k, v, w.astype(jnp.float32), jnp.asarray(segments(layout, B, S, rng))


CASES = [
    # B, S, H, d, scale is d**-0.5?, sizes, layout
    (2, 128, 2, 128, False, None, "one_segment_padded"),
    (4, 256, 1, 128, False, None, "one_segment_padded"),  # the padded cell's rows a step
    (2, 256, 2, 128, True, None, "packed_padded"),
    (1, 256, 1, 256, True, None, "scrambled"),
    (1, 256, 1, 256, False, FlashSizes(1, 1, 256, 128), "packed_padded"),
    (2, 512, 1, 128, False, FlashSizes(2, 1, 128, 256), "scrambled"),
    (1, 512, 2, 128, True, FlashSizes(1, 2, 256, 256), "long_segment"),
    (1, 1024, 2, 128, False, None, "packed_padded"),
    (1, 1024, 1, 128, True, FlashSizes(1, 1, 128, 128), "long_segment"),
    (2, 256, 4, 64, True, None, "packed_padded"),  # a head narrower than a lane tile
    (1, 1024, 1, 128, False, FlashSizes(1, 1, 512, 128), "one_segment"),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
@pytest.mark.parametrize("B,S,H,d,scaled,sizes,layout", CASES)
def test_output_and_gradients_match_a_plain_softmax(B, S, H, d, scaled, sizes, layout, dtype):
    q, k, v, w, seg = case(B, S, H, d, dtype, layout, seed=S + d)
    # unscaled logits of order one need smaller operands than scaled ones
    scale = d**-0.5 if scaled else 1.0
    if scaled:
        q = (q.astype(jnp.float32) * d**0.5).astype(dtype)

    def ours(q, k, v):
        return flash_attention(q, k, v, seg, sm_scale=scale, sizes=sizes, interpret=True)

    out, ref = ours(q, k, v), reference(q, k, v, seg, scale)
    assert out.dtype == dtype and out.shape == q.shape
    grads = jax.grad(lambda *a: (ours(*a).astype(jnp.float32) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    ref_grads = jax.grad(lambda *a: (reference(*a, seg, scale) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for name, a, b in zip(("out", "dq", "dk", "dv"), (out, *grads), (ref, *ref_grads)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all(), name
        assert np.abs(a - b).max() <= tol * max(1.0, np.abs(b).max()), (name, np.abs(a - b).max(), np.abs(b).max())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
@pytest.mark.parametrize("d,dv,H", [(192, 128, 2), (256, 128, 1), (128, 256, 2)], ids=["192-128", "256-128", "128-256"])
def test_a_key_width_beside_a_value_width(d, dv, H, dtype):
    """Latent attention at 128 + 64 / 128: q and k of one width, v and the
    output of another, on packed rows with a padding tail; two heads of 192
    are three lane tiles. The softmax's scale is an argument of its own
    (YaRN's ``192^-1/2 m^2``). Output and the three gradients against the
    plain softmax; dq and dk have the key width, dv the value width."""
    B, S = 2, 256
    q, k, _, _, seg = case(B, S, H, d, dtype, "packed_padded", seed=d + dv)
    _, _, v, w, _ = case(B, S, H, dv, dtype, "packed_padded", seed=d + dv + 1)
    scale = 192**-0.5 * (0.1 * np.log(64) + 1) ** 2
    q = (q.astype(jnp.float32) * d**0.5).astype(dtype)
    assert flash_block_sizes(B, S, H, d, 2, dv).heads * d % 128 == 0

    def ours(q, k, v):
        return flash_attention(q, k, v, seg, sm_scale=scale, interpret=True)

    out, ref = ours(q, k, v), reference(q, k, v, seg, scale)
    assert out.dtype == dtype and out.shape == v.shape
    grads = jax.grad(lambda *a: (ours(*a).astype(jnp.float32) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    ref_grads = jax.grad(lambda *a: (reference(*a, seg, scale) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    assert [g.shape for g in grads] == [q.shape, k.shape, v.shape]
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for name, a, b in zip(("out", "dq", "dk", "dv"), (out, *grads), (ref, *ref_grads)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all(), name
        assert np.abs(a - b).max() <= tol * max(1.0, np.abs(b).max()), (name, np.abs(a - b).max(), np.abs(b).max())


def test_equal_widths_take_the_sizes_they_took():
    """A value width given equal to the key width, or not given, changes nothing."""
    for shape in [(16, 1024, 8, 128), (64, 256, 8, 128), (16, 1024, 20, 256), (4, 256, 4, 64)]:
        assert flash_block_sizes(*shape, 2, shape[-1]) == flash_block_sizes(*shape)
    assert flash_block_sizes(8, 1024, 32, 192, 2, 128) == (1, 8, 128, 128)  # xing40_a4b_ep8's core
    # one rule: the groups `flash_block_sizes` picks from are the ones the kinds block's core asks for
    assert lane_tile_groups(32, 192, 128) == [2, 4, 8, 16, 32] and lane_tile_groups(20, 256) == [1, 2, 4, 5, 10, 20]
    assert lane_tile_groups(2, 64, 64) == [2] and lane_tile_groups(8, 32, 32) == [4, 8]
    assert not lane_tile_groups(3, 192, 128) and not lane_tile_groups(4, 12, 12) and not lane_tile_groups(2, 96, 64)
    assert flash_block_sizes(8, 1024, 8, 32).heads in lane_tile_groups(8, 32)


@pytest.mark.parametrize("chunk_q,chunk_k", [(128, 128), (256, 128), (128, 256), (512, 512)])
@pytest.mark.parametrize("layout", ["packed_padded", "scrambled", "long_segment", "one_segment_padded"])
def test_every_visible_pair_lies_in_a_visited_chunk_pair(layout, chunk_q, chunk_k):
    S = 1024
    seg = segments(layout, 6, S, np.random.default_rng(chunk_q + chunk_k))
    k_lo, k_hi, q_lo, q_hi = chunk_bounds(seg, chunk_q, chunk_k)
    n_q, n_k = S // chunk_q, S // chunk_k
    assert k_lo.shape == k_hi.shape == (6, n_q) and q_lo.shape == q_hi.shape == (6, n_k)
    pos = np.arange(S)
    visible = (seg[:, :, None] == seg[:, None, :]) & (pos[None, :] <= pos[:, None])[None]  # [B, q, k]
    holds = visible.reshape(6, n_q, chunk_q, n_k, chunk_k).any(axis=(2, 4))  # [B, n_q, n_k]
    i, j = np.arange(n_q)[None, :, None], np.arange(n_k)[None, None, :]
    by_query = (k_lo[:, :, None] <= j) & (j <= k_hi[:, :, None])
    by_key = (q_lo[:, None, :] <= i) & (i <= q_hi[:, None, :])
    assert not (holds & ~by_query).any() and not (holds & ~by_key).any()
    assert by_query.any(-1).all() and by_key.any(-2).all()  # a query sees itself: no walk is empty
    assert visited_share(seg, chunk_q, chunk_k) == by_query.sum() / by_query.size
    # the device's bounds are the host's
    for host, device in zip((k_lo, k_hi, q_lo, q_hi), jax.jit(chunk_bounds, static_argnums=(1, 2))(seg, chunk_q, chunk_k)):
        np.testing.assert_array_equal(host, np.asarray(device))


def test_contiguous_rows_skip_what_their_segments_allow():
    """Ascending ids with a padding tail (the packed cells' rows): a chunk
    pair is visited only between a query chunk's first segment's chunk and
    the diagonal; one segment a row degenerates to the causal half."""
    seg = np.repeat(np.arange(8), 128)[None, :].astype(np.int32)  # a segment a chunk
    assert visited_share(seg, 128) == 8 / 64
    seg[0, 1000:] = -1  # the padding tail does not reach back
    assert visited_share(seg, 128) == 8 / 64
    assert visited_share(np.zeros((1, 1024), np.int32), 128) == 36 / 64


def test_sizes_follow_the_static_shapes():
    for batch, seq_len, heads, head_dim in [
        (16, 1024, 8, 128), (64, 256, 8, 128), (16, 1024, 20, 256), (3, 128, 5, 128), (2, 2048, 1, 128)
    ]:
        rows, group, chunk_q, chunk_k = flash_block_sizes(batch, seq_len, heads, head_dim)
        assert batch % rows == 0 and heads % group == 0 and seq_len % chunk_q == 0 and seq_len % chunk_k == 0
        assert chunk_q % 128 == 0 and chunk_k % 128 == 0
    assert flash_block_sizes(64, 256, 8, 128).rows == 4  # short rows share a grid step


STEP = "jit(chunk_step)/while/body/closed_call/"
FWD = STEP + "jvp(CIPPTForGenerativeSequenceModeling)/encoder/"
BWD = STEP + "transpose(jvp(CIPPTForGenerativeSequenceModeling))/encoder/jvp(CIPPTForGenerativeSequenceModeling)/encoder/"


@pytest.mark.parametrize(
    "path, want",
    [
        (FWD + "h1/attn/es.attn_global/attention/es.attn_global/flash_attention/pallas_call", ("attn_global", "forward")),
        (BWD + "checkpoint/h1/attn/attention/es.attn_global/flash_mha_bwd_dkv/pallas_call", ("attn_global", "backward")),
        (BWD + "checkpoint/rematted_computation/h1/attn/attention/es.attn_global/flash_attention/pallas_call", ("attn_global", "recompute")),
    ],
)
def test_the_trace_reader_puts_the_ops_mosaic_calls_under_attn_global(path, want):
    """The paths a device trace shows for the three kernels, written by hand:
    the custom_vjp's rules enter the scope themselves, so the backward's calls
    carry it without the caller's name stack (compiled for the chip in
    `tests/test_chip_compile.py`)."""
    from benchmark.harness.scopes import scope_of

    assert scope_of(path) == want
