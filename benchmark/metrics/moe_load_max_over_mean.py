"""Routing balance: the largest load of one held expert in a step over the
mean load of a held expert, averaged over the window's steps; 1 is even.
From the program's two counters (token-expert pairs computed here; largest
load of one held expert), read back after the window."""

LAYER = "encoder routed mlp"
UNIT = "x"
MOVES = "train_events_per_s"
SOURCE = "program_counter"


def read(record: dict):
    counters = record["counters"]
    if not counters.get("moe_pairs"):
        return None
    mean_load = counters["moe_pairs"] / (counters["moe_routed_layers"] * counters["moe_experts_held"])
    return counters["moe_load_max_sum"] / mean_load
