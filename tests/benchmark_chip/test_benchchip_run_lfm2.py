"""The command, end to end, on the LFM2-MoE training cell at its
rehearsal sizes on the CPU, and the faults the cell can have, each
planted under the timed path."""
import pytest

from benchchip_util import RUN, cell_args, planted, python, result_line

CELL = "train_lfm2_ep8_s8k"
COUNTS = {"cache_hits_at_setup", "compiles_in_window.train",
          "moe_load_imbalance"}


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_end_to_end(trace):
    proc = python([RUN] + cell_args(CELL, trace) + ["--rehearse"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = result_line(proc.stdout)
    assert res is not None, proc.stdout[-2000:]
    assert res["correct"] is True, proc.stderr[-2000:]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    # counts only: no time, rate or share of a device from the CPU
    assert set(res["metrics"]) == (COUNTS if trace else set())
    names = [r["name"] for r in res["compared"]]
    assert names == ["loss_gap_step1", "loss_gap_step2", "loss_gap_step3",
                     "grad_norm_gap", "update_norm_gap",
                     "routing_flip_share", "routing_call_loss_gap",
                     "window_losses_finite"]
    tail = proc.stderr.strip().splitlines()[-len(names):]
    assert all(line.startswith("compared ") and "(limit " in line
               for line in tail)
    if trace:
        assert res["metrics"]["compiles_in_window.train"]["value"] == 0
        assert 1.0 <= res["metrics"]["moe_load_imbalance"]["value"] < 3.0


STATE_UNCHANGED = """
import numpy as np
from benchmark.chip.drivers import lfm2_train
_step = lfm2_train.Trainer.step
def step(self, feed, fetch=()):
    names = list(self.scope.local_var_names())
    before = {n: np.array(self.scope._get(n)) for n in names}
    out = _step(self, feed, fetch)
    for n, v in before.items():
        self.scope._set(n, v)
    return out
lfm2_train.Trainer.step = step
"""

EXPERTS_LEFT_OUT = """
from paddle_tpu.parallel import moe
_real = moe.moe_dropless
def left_out(*a, **k):
    out, idx, load, pairs = _real(*a, **k)
    return out * 0, idx, load, pairs
moe.moe_dropless = left_out
"""

SOFTMAX_ROUTER = """
import jax, jax.numpy as jnp
from paddle_tpu.parallel import moe
def softmax_router(x, wg, bias, top_k, norm_topk=True, scaling=1.0):
    s = jax.nn.softmax(x.astype(jnp.float32) @ wg.astype(jnp.float32))
    w, idx = jax.lax.top_k(s, top_k)
    return idx.astype(jnp.int32), w / (w.sum(-1, keepdims=True) + 1e-6)
moe.route_dropless = softmax_router
"""

CONV_LOOKS_AHEAD = """
import jax.numpy as jnp
from paddle_tpu.core import registry
_real = registry.get_op_info("short_conv").kernel
def ahead(ctx):
    out = _real(ctx)["Out"]
    return {"Out": jnp.concatenate([out[:, 1:], out[:, :1] * 0], axis=1)}
registry.get_op_info("short_conv").kernel = ahead
"""

FAULTS = {"state_unchanged": (STATE_UNCHANGED, "update_norm_gap"),
          "experts_left_out": (EXPERTS_LEFT_OUT, "grad_norm_gap"),
          "softmax_router": (SOFTMAX_ROUTER, "routing_flip_share"),
          "conv_looks_ahead": (CONV_LOOKS_AHEAD, "routing_flip_share")}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_planted_under_the_timed_path_reads_not_correct(fault):
    plant, fails = FAULTS[fault]
    proc = planted(plant, CELL)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = result_line(proc.stdout)
    assert res is not None and res["correct"] is False
    over = {r["name"] for r in res["compared"]
            if not r["value"] <= r["limit"]}
    assert fails in over, res["compared"]
