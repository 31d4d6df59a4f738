"""Share of the traced window's device busy time that ran under
`nemotronh.prefill_chunk`: the prompts' chunks, which a dispatch runs
before its decode ticks. Layer: decode engine; moves tpot_ms_p95."""
from benchmark.chip import scopes_nemotron


def read(obs):
    spent = scopes_nemotron.under(obs, scopes_nemotron.CHUNK)
    if not spent or not obs["trace"].get("busy_s"):
        return None
    return 100.0 * spent / obs["trace"]["busy_s"]
