"""The flash-attention kernels' share of their roofline in the train step.

Time: the kernels' events in the device trace as the chip names them:
``flash_attention`` (forward) and the two backward kernels
``flash_mha_bwd_dkv_...`` and ``flash_mha_bwd_dq_...``. Needs (benchmark/harness/flops.py `attention_needs`): causal
attention inside each packed segment over the real events of the global
layers, forward once and backward once. The forward kernel runs a second time
under the ``dots_no_batch`` remat policy; that time is in the denominator and
its operations are not in the numerator. At a few hundred keys a query the
bound is bytes (q, k, v, o and their gradients), not arithmetic.
"""

from benchmark.harness import trace
from benchmark.harness.device import peaks
from benchmark.harness.flops import attention_needs, roofline_share

LAYER = "encoder attention"
UNIT = "%"
MOVES = "train_events_per_s"
SOURCE = "device_trace"
KERNELS = ("flash_attention", "flash_mha_")


def read(record: dict):
    ns, n = trace.kernel_ns(record["trace"]["ops"], KERNELS)
    if not n:
        return None
    m, c = record["model_sizes"], record["counters"]
    types = m["seq_attention_types"]
    n_global = sum(types[i % len(types)] == "global" for i in range(m["num_hidden_layers"]))
    need = attention_needs(c["events"] * n_global, c["global_keys"], m["num_attention_heads"], m["head_dim"], 2)
    share, _bound = roofline_share(
        need["fwd_flops"] + need["bwd_flops"], need["fwd_bytes"] + need["bwd_bytes"],
        ns / 1e9, peaks(record["device_kind"]),
    )
    return share
