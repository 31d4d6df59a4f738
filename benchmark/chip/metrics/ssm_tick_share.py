"""Share of the decode ticks' device time that the state-space mixers
take: the operations traced under `nemotronh.ssm`, over the traced busy
time less what ran under a prefill chunk. Layer: decode tick kernels;
moves tpot_ms_p95."""
from benchmark.chip import scopes_nemotron


def read(obs):
    spent, whole = scopes_nemotron.under(obs, "nemotronh.ssm"), \
        scopes_nemotron.tick_seconds(obs)
    return 100.0 * spent / whole if spent and whole else None
