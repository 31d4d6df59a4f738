"""Share of the device's busy time that the expert layers take: the
device time of the operations traced under the `lfm2.moe.route`,
`.experts` and `.combine` scopes, forward and backward, over the traced
window's busy time (benchmark/chip/device_scopes.py). Layer: expert
layer; moves train_tokens_per_s."""
from benchmark.chip import device_scopes


def read(obs):
    return device_scopes.share_of_busy(obs, "lfm2.moe.")
