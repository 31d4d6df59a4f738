"""The layer-norm kernel's share of its roofline (bound by bytes): the
bytes its calls must move, from the shapes in each call's HLO text,
over the chip's memory bandwidth, against the device time of the
layer_norm events. The kernel is the forward pass; the backward is
XLA's. Layer: Pallas kernels (ops/pallas/layer_norm.py); moves
train_tokens_per_s."""
from benchmark.chip import shapes


def read(obs):
    if not obs["trace"]:
        return None
    return shapes.kernel_roofline_share(obs["trace"], "layer_norm",
                                        obs["peaks"])
