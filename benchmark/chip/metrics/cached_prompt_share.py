"""Share of the prompt tokens of the window's admissions that the radix
tree already held (whole blocks mapped into the lane, not prefilled).
Layer: serving scheduler (inference/decoder_only.py); moves
serve_tokens_per_s (what is found cached is not prefilled)."""


def read(obs):
    n = obs["counters"]
    if not n.get("prompt_tokens"):
        return None
    return 100.0 * n["cached_prompt_tokens"] / n["prompt_tokens"]
