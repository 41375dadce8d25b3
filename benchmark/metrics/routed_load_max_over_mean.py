"""Routing balance of the ungated (relu^2) experts: the largest load of one
held expert in a step over the mean load of a held expert, averaged over the
window's steps; 1 is even. From the program's two counters (token-expert pairs
computed here; largest load of one held expert) as the job kind
``pretrain_hybrid`` records them (``relu2_*``), read back after the window."""

LAYER = "encoder routed mlp"
UNIT = "x"
MOVES = "train_events_per_s"
SOURCE = "program_counter"


def read(record: dict):
    counters = record["counters"]
    if not counters.get("relu2_pairs"):
        return None
    mean_load = counters["relu2_pairs"] / (counters["relu2_routed_layers"] * counters["relu2_experts_held"])
    return counters["relu2_load_max_sum"] / mean_load
