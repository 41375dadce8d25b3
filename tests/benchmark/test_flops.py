"""The FLOP and ops/bytes functions against values worked by hand at one
small shape."""

from __future__ import annotations

import pytest

from benchmark.harness import flops

VOCAB = {
    "vocab_size": 48,
    "vocab_sizes": {"event_type": 5, "lab": 20, "med": 6, "demo": 16},
    "measurements_idxmap": {"event_type": 1, "lab": 2, "med": 3, "demo": 4},
    "single_label_classification": ["event_type"],
    "multi_label_classification": ["lab", "med"],
    "multivariate_regression": ["lab"],
}
MODEL = {
    "mode": "ci", "hidden_size": 8, "intermediate_size": 32, "num_hidden_layers": 2,
    "seq_attention_types": ["local", "global"], "tte_components": 3,
    "measurements_per_dep_graph_level": None,
}


def test_ci_forward_by_hand():
    # per layer: q,k,v,out = 4 matmuls of 8x8 -> 2*4*64 = 512; feed-forward 2*(2*8*32) = 1024
    # attention: 4*h*keys -> local layer 4*8*3 = 96, global layer 4*8*10 = 320
    # heads: 2*8*(48 + 40 + 9 + 4) = 1616
    want = (512 + 96) + (512 + 320) + 2 * 1024 + 1616
    assert flops.forward_flops_per_event(MODEL, VOCAB, global_keys=10, local_keys=3) == want
    assert flops.train_flops_per_event(MODEL, VOCAB, 10, 3) == 3 * want


def test_na_forward_by_hand():
    model = dict(MODEL, mode="na", measurements_per_dep_graph_level=[[], ["event_type"], ["lab", "med"]])
    # sequence attention as CI's: 608 + 832
    # dep-graph per layer: k,v on 4 positions and q,out on 3: 2*64*(8+6) = 1792;
    #   attention 4*8*(2+3+4) = 288; feed-forward on 3 levels: 3*1024 = 3072
    # heads: 2*8*((5+20+6) + 40 + 9 + 2*4) = 1408
    want = 608 + 832 + 2 * (1792 + 288 + 3072) + 1408
    assert flops.forward_flops_per_event(model, VOCAB, global_keys=10, local_keys=3) == want


def test_attention_needs_and_roofline_by_hand():
    need = flops.attention_needs(n_queries=100, keys_per_query=50, heads=2, head_dim=4, itemsize=2)
    one = 2 * 2 * 4 * 100 * 50  # one matmul: 2 flops * heads * head_dim * queries * keys
    assert need == {"fwd_flops": 2 * one, "bwd_flops": 4 * one, "fwd_bytes": 4 * 1600, "bwd_bytes": 8 * 1600}
    peak = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    share, bound = flops.roofline_share(flops=2e9, nbytes=1e6, seconds=0.004, peak=peak)
    assert bound == "compute" and share == pytest.approx(50.0)  # 2 ms of compute in 4 ms
    share, bound = flops.roofline_share(flops=1e6, nbytes=3e6, seconds=0.004, peak=peak)
    assert bound == "bytes" and share == pytest.approx(75.0)  # 3 ms of bytes in 4 ms
