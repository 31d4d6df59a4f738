"""Share of the decode ticks' device time that the expert layers take
(router, latent projections, held experts, combine, shared expert):
the operations traced under `nemotronh.moe`, over the traced busy time
less what ran under a prefill chunk. Layer: expert layer; moves
tpot_ms_p95."""
from benchmark.chip import scopes_nemotron


def read(obs):
    spent, whole = scopes_nemotron.under(obs, "nemotronh.moe"), \
        scopes_nemotron.tick_seconds(obs)
    return 100.0 * spent / whole if spent and whole else None
