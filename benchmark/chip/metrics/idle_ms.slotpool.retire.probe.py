"""Device idle time a dispatch under the program's `slotpool.retire.probe`
span, inside `slotpool.retire`: a retiring lane's probe rows taken in
one compiled call and read back, the host blocked on the transfer
(inference/decoder_only.py `_keep_probe`). Layer: serving scheduler;
moves serve_tokens_per_s."""
from benchmark.chip import program_spans


def read(obs):
    return program_spans.idle_ms_per(obs, "slotpool.retire.probe",
                                     "dispatches")
