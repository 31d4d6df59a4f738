"""Share of the decode ticks' device time that the linear-attention
mixers take: the operations traced under `olmoh.gdn`, over the traced
busy time less what ran under a prefill chunk. Layer: decode tick
kernels; moves tpot_ms_p95."""
from benchmark.chip import scopes_olmoh


def read(obs):
    spent, whole = scopes_olmoh.under(obs, "olmoh.gdn"), \
        scopes_olmoh.tick_seconds(obs)
    return 100.0 * spent / whole if spent and whole else None
