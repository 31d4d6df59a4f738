"""Share of the traced window's device busy time that ran under
`glm.prefill_chunk`: the prompts' chunks, which a dispatch runs before
its decode ticks. Layer: decode engine; moves tpot_ms_p95."""
from benchmark.chip import scopes_glm


def read(obs):
    spent = scopes_glm.under(obs, "glm.prefill_chunk")
    if not spent or not obs["trace"].get("busy_s"):
        return None
    return 100.0 * spent / obs["trace"]["busy_s"]
