"""Block-size tuning probe for the Pallas flash-attention kernel.

Measures fwd+bwd cost of one global-attention layer at the production-width
shapes (``scripts/probe_scale.py``'s sweep points) across kernel block
configurations, using the honest sustained-timing protocol
(``utils/benchmarking.py``, the readback-subtraction protocol). The winner feeds ``models/transformer.py``'s block-size choice.

Run on the real chip:

    python scripts/probe_flash_blocks.py
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))  # repo root

from eventstreamgpt_tpu.utils.benchmarking import (  # noqa: E402
    dispatch_echo_ms,
    drain,
    readback_echo_ms,
    wait_for_quiet,
)


def make_inputs(B, H, L, D, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, L, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, H, L, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, H, L, D), jnp.bfloat16)
    # Production packed batches carry segment ids; include them so the
    # measurement matches the training kernel invocation exactly.
    seg = jnp.zeros((B, L), jnp.int32).at[:, L // 2 :].set(1)
    return q, k, v, seg


def layer_cost_ms(q, k, v, seg, block_sizes, n_pipeline=20, repeats=2):
    from jax.experimental.pallas.ops.tpu.flash_attention import SegmentIds, flash_attention

    def fwd(q, k, v):
        out = flash_attention(
            q, k, v, segment_ids=SegmentIds(q=seg, kv=seg), causal=True,
            sm_scale=1.0, block_sizes=block_sizes,
        )
        return (out.astype(jnp.float32) ** 2).sum()

    grad_fn = jax.jit(jax.value_and_grad(fwd, argnums=(0, 1, 2)))

    # Warm/compile.
    loss, grads = grad_fn(q, k, v)
    drain(loss)

    best = float("inf")
    for _ in range(repeats):
        rtt = readback_echo_ms()
        qq = q
        t0 = time.perf_counter()
        for _ in range(n_pipeline):
            loss, (dq, dk, dv) = grad_fn(qq, k, v)
            qq = qq + 0.0 * dq  # chain steps so the device cannot overlap them
        drain(loss)
        window = 1000.0 * (time.perf_counter() - t0) - rtt
        best = min(best, max(window, 0.0) / n_pipeline)
    return best


def main():
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

    shapes = [
        ("h1024_hd128", 8, 8, 1024, 128),
        ("h1024_hd64", 8, 16, 1024, 64),
    ]
    configs = []
    for bn in (128, 256, 512, 1024):
        configs.append((f"sym{bn}", lambda L, bn=bn: BlockSizes(
            block_q=min(bn, L), block_k_major=min(bn, L), block_k=min(bn, L), block_b=1,
            block_q_major_dkv=min(bn, L), block_k_major_dkv=min(bn, L),
            block_k_dkv=min(bn, L), block_q_dkv=min(bn, L),
            block_k_major_dq=min(bn, L), block_k_dq=min(bn, L), block_q_dq=min(bn, L),
        )))
    # Asymmetric: wide k blocks, narrower q blocks (and vice versa).
    configs.append(("q256_k1024", lambda L: BlockSizes(
        block_q=256, block_k_major=min(1024, L), block_k=min(1024, L), block_b=1,
        block_q_major_dkv=256, block_k_major_dkv=min(1024, L),
        block_k_dkv=min(1024, L), block_q_dkv=256,
        block_k_major_dq=min(1024, L), block_k_dq=min(1024, L), block_q_dq=256,
    )))
    configs.append(("q1024_k256", lambda L: BlockSizes(
        block_q=min(1024, L), block_k_major=256, block_k=256, block_b=1,
        block_q_major_dkv=min(1024, L), block_k_major_dkv=256,
        block_k_dkv=256, block_q_dkv=min(1024, L),
        block_k_major_dq=256, block_k_dq=256, block_q_dq=min(1024, L),
    )))
    configs.append(("default", lambda L: None))

    for shape_name, B, H, L, D in shapes:
        q, k, v, seg = make_inputs(B, H, L, D)
        echo, contended = wait_for_quiet()
        print(f"== {shape_name} B={B} H={H} L={L} D={D} "
              f"(echo {echo:.2f} ms, contended={contended})")
        for name, mk in configs:
            bs = mk(L)
            try:
                ms = layer_cost_ms(q, k, v, seg, bs)
            except Exception as e:  # invalid block config for this shape
                print(f"  {name:>12}: FAILED ({type(e).__name__}: {str(e)[:80]})")
                continue
            # Useful FLOPs: causal halves the L^2 plane; fwd 2 matmuls,
            # bwd ~5 matmul-equivalents (dq, dk, dv + recompute).
            flops = 0.5 * (2 + 5) * 2 * B * H * L * L * D
            eff = flops / (ms / 1000.0) / 197e12
            print(f"  {name:>12}: {ms:7.3f} ms/layer fwd+bwd  (~{100*eff:.1f}% of peak)")


if __name__ == "__main__":
    main()
