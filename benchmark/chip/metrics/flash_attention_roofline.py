"""The flash attention kernels' share of their roofline (bound by
operations): causal attention's operations of the traced steps, forward
and backward from shapes, over the chip's peak, against the device time
of the flash_attention_fwd, _dq and _dkv events (the backward
pass's carry a `jvp_` before the name). Layer: Pallas kernels
(ops/pallas/attention.py); moves train_tokens_per_s."""
from benchmark.chip import shapes_lfm2


def read(obs):
    tr, steps = obs["trace"], obs["counters"].get("traced_steps")
    if not tr or not steps:
        return None
    spent = sum(s for name, s in tr["op_s"].items()
                if "flash_attention_" in name)
    if not spent:
        return None
    c = obs["sizes"]
    flops = steps * shapes_lfm2.flash_attention_train_flops(
        c, c["seq_len"])
    return 100.0 * flops / obs["peaks"]["flops_per_s"] / spent
