"""Share of the decode ticks' device time that the expert layers take
(router, held experts, combine, shared expert): the operations traced
under `glm.moe`, over the traced busy time less what ran under a
prefill chunk. Layer: expert layer; moves tpot_ms_p95."""
from benchmark.chip import scopes_glm


def read(obs):
    spent, whole = scopes_glm.under(obs, "glm.moe"), \
        scopes_glm.tick_seconds(obs)
    return 100.0 * spent / whole if spent and whole else None
