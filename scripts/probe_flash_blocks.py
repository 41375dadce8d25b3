"""Size sweep for the global layers' flash attention op (`ops/pallas_flash.py`).

Measures forward and forward+backward of one global-attention layer at the
benchmark cells' shapes, on segment ids drawn like the cells' (log-normal
history lengths, median 200, first-fit into packed rows; one history a row
with a padding tail for the padded cell), across the op's rows and heads a grid step and
its chunk widths; a timing is a window of back-to-back dispatches ended by
``jax.block_until_ready``, which waits on the chip. The winner
feeds `ops.pallas_flash.flash_block_sizes`; the table is in PERF.md section 6
(PR 29). ``--stock`` also times jax's stock kernel at the blocks the parent
commit gave it, for the same inputs (equal widths only). ``--only <text>``
keeps the shapes whose name holds the text: ``xing40`` is latent attention at
a key width of 192 beside a value width of 128 (the sweep against keys padded
to 256 with zero columns is in PERF.md section 6, PR 34; the padded shape is
gone with the padded path).

Run on the real chip:

    python scripts/probe_flash_blocks.py [--stock] [--only <text>]
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))  # repo root

from eventstreamgpt_tpu.ops.pallas_flash import (  # noqa: E402
    FlashSizes,
    flash_attention,
    flash_block_sizes,
    visited_share,
)

# name, B, H, S, d (queries and keys), dv (values), sm_scale, packed, longest history
SHAPES = [
    ("ci_w1024.pretrain_packed", 16, 8, 1024, 128, 128, 1.0, True, 512),
    ("ci_w1024.pretrain_padded", 64, 8, 256, 128, 128, 1.0, False, 256),
    ("glm47flash_ep8.pretrain_packed", 16, 20, 1024, 256, 256, 256**-0.5, True, 512),
    ("xing40_a4b_ep8.pretrain_packed", 8, 32, 1024, 192, 128, 192**-0.5, True, 512),
]
CHUNKS = [(128, 128), (256, 128), (256, 256), (512, 256)]


def cell_segment_ids(B: int, S: int, packed: bool, cap: int, seed: int = 0) -> np.ndarray:
    """``[B, S]`` segment ids as the cells' feeds make them, padding ``-1``."""
    rng = np.random.default_rng(seed)
    lengths = np.clip(rng.lognormal(np.log(200), 0.6, 64 * B).astype(int), 4, cap)
    if not packed:
        return np.where(np.arange(S)[None, :] < lengths[:B, None], 0, -1).astype(np.int32)
    rows: list[list[int]] = []
    for n in lengths:  # first fit
        for row in rows:
            if sum(row) + n <= S:
                row.append(n)
                break
        else:
            rows.append([n])
    seg = np.full((B, S), -1, np.int32)
    for b, row in enumerate(rows[:B]):
        seg[b, : sum(row)] = np.repeat(np.arange(len(row)), row)
    return seg


def cost_ms(fn, q, k, v, n_pipeline=30, repeats=3):
    """Sustained ms a call of ``fn(q, k, v) -> array(s)``: back-to-back
    dispatches (the device runs them in order), one wait at the end."""
    step = jax.jit(fn)
    jax.block_until_ready(step(q, k, v))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(n_pipeline):
            out = step(q, k, v)
        jax.block_until_ready(out)  # graftcheck: allow GC001 -- the end of a timed window
        best = min(best, 1000.0 * (time.perf_counter() - t0) / n_pipeline)
    return best


def with_gradient(fwd):
    """``fwd`` and the gradient of its sum in q, k and v."""

    def both(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    return fwd, both


def ours(seg, scale, sizes, H):
    def fwd(q, k, v):  # [B, S, H * d] as a projection leaves them
        heads = lambda x: x.reshape(*x.shape[:2], H, -1)  # noqa: E731
        return flash_attention(heads(q), heads(k), heads(v), seg, sm_scale=scale, sizes=sizes).reshape(v.shape)

    return with_gradient(fwd)


def stock(seg, scale, d, H):
    """The parent commit's call: heads-first, blocks of 1,024 (512 above width 128)."""
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes, SegmentIds, flash_attention

    def fwd(q, k, v):
        shape = q.shape
        q, k, v = (x.reshape(*x.shape[:2], H, -1) for x in (q, k, v))
        S = q.shape[1]
        bn = min(1024 if d == 128 else 512, S)
        blocks = BlockSizes(
            block_q=bn, block_k_major=bn, block_k=bn, block_b=1,
            block_q_major_dkv=bn, block_k_major_dkv=bn, block_k_dkv=bn, block_q_dkv=bn,
            block_k_major_dq=bn, block_k_dq=bn, block_q_dq=bn,
        )
        out = flash_attention(
            q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2), segment_ids=SegmentIds(q=seg, kv=seg),
            causal=True, sm_scale=scale, block_sizes=blocks,
        )
        return out.swapaxes(1, 2).reshape(shape)

    return with_gradient(fwd)


def main():
    args = sys.argv[1:]
    with_stock = "--stock" in args
    only = args[args.index("--only") + 1] if "--only" in args else ""
    for name, B, H, S, d, dv, scale, packed, cap in SHAPES:
        if only not in name:
            continue
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (B, S, H * w), jnp.bfloat16) for kk, w in zip(ks, (d, d, dv)))
        seg_np = cell_segment_ids(B, S, packed, cap)
        seg = jnp.asarray(seg_np)
        chosen = flash_block_sizes(B, S, H, d, 2, dv)
        print(f"== {name} B={B} H={H} S={S} d={d} dv={dv} real={float((seg_np >= 0).mean()):.3f} "
              f"chosen={tuple(chosen)}", flush=True)
        if with_stock and d == dv:
            fwd, both = stock(seg, scale, d, H)
            print(f"  {'stock':>20}: fwd {cost_ms(fwd, q, k, v):7.3f}  fwd+bwd {cost_ms(both, q, k, v):7.3f} ms/layer", flush=True)
        for cq, ck in CHUNKS:
            if cq > S or ck > S:
                continue
            groups = sorted({g for g in (1, 2, 4, 5, 8, 10, chosen.heads) if H % g == 0 and g * d % 128 == 0})
            for rows, group in [(1, 1)] * (chosen.rows > 1) + [(chosen.rows, g) for g in groups]:
                sizes = FlashSizes(rows, group, cq, ck)
                share = visited_share(seg_np, cq, ck)
                try:
                    fwd, both = ours(seg, scale, sizes, H)
                    f_ms, b_ms = cost_ms(fwd, q, k, v), cost_ms(both, q, k, v)
                except Exception as e:  # a size Mosaic refuses at this shape
                    print(f"  {str(tuple(sizes)):>20}: FAILED ({type(e).__name__}: {str(e)[:120]})", flush=True)
                    continue
                print(f"  {str(tuple(sizes)):>20}: fwd {f_ms:7.3f}  fwd+bwd {b_ms:7.3f} ms/layer  visited {share:.3f}", flush=True)


if __name__ == "__main__":
    main()
