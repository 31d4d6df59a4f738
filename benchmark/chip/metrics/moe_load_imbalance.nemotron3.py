"""The fullest held expert's pairs over the mean held expert's in the
window, from the serve programs' per-expert counters, averaged over
the five expert layers (1 is perfect balance; 22 of 512 chosen a token,
128 held). Layer: expert layer (parallel/moe.py moe_dropless in a tick
and in a prefill chunk); moves serve_tokens_per_s."""


def read(obs):
    return obs["counters"].get("moe_load_imbalance")
