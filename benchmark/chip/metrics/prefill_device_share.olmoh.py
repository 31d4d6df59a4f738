"""Share of the traced window's device busy time that ran under
`olmoh.prefill_chunk`: the prompts' chunks (every turn's whole history
again: a bundle with lane state takes no prefix hit), which a dispatch
runs before its decode ticks. Layer: decode engine; moves
tpot_ms_p95."""
from benchmark.chip import scopes_olmoh


def read(obs):
    spent = scopes_olmoh.under(obs, scopes_olmoh.CHUNK)
    if not spent or not obs["trace"].get("busy_s"):
        return None
    return 100.0 * spent / obs["trace"]["busy_s"]
