"""The fullest held expert's tokens over the mean held expert's, from
the program's `layerN_moe_load` counters, averaged over the expert
layers and the followed steps (1 is perfect balance). Layer: expert
layer (parallel/moe.py moe_dropless); moves train_tokens_per_s."""


def read(obs):
    return obs["counters"].get("moe_load_imbalance")
