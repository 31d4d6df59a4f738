"""Programs JAX had to compile or fetch from its cache inside the
measured window (there should be none). Layer: executor; moves
train_tokens_per_s."""


def read(obs):
    return obs["counters"].get("compiles_in_window")
