"""The fused cross-entropy kernels' share of their roofline: the least
time the chip could take for the bytes their calls must move (read from
the shapes in each call's HLO text; bound by bytes) over the device
time of the xent_forward and xent_backward events. Layer: Pallas
kernels (ops/pallas/xent.py); moves train_tokens_per_s."""
from benchmark.chip import shapes


def read(obs):
    if not obs["trace"]:
        return None
    return shapes.kernel_roofline_share(obs["trace"], "xent_",
                                        obs["peaks"])
