"""The fullest held expert's pairs over the mean held expert's in the
window's decode ticks, from the serve programs' per-expert counters,
averaged over the expert layers (1 is perfect balance). Layer: expert
layer (parallel/moe.py moe_dropless in a tick); moves
serve_tokens_per_s."""


def read(obs):
    return obs["counters"].get("moe_load_imbalance")
