"""The whole train step's share of the chip's peak FLOP/s: operations the
forward and backward need per real event (benchmark/harness/flops.py;
recomputation not counted) times real events per second, over the peak."""

from benchmark.harness.device import peaks

LAYER = "whole step"
UNIT = "%"
MOVES = "train_events_per_s"
SOURCE = "host_clock"


def read(record: dict):
    rate = record["end_to_end"].get("train_events_per_s")
    if rate is None:
        return None
    peak = peaks(record["device_kind"])["flops_per_s"]
    return 100.0 * record["counters"]["flops_per_event"] * rate / peak
