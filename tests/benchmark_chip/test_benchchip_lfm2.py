"""The LFM2-MoE training cell at its rehearsal sizes on the CPU: every
part of the program against the plain reference (forward and gradient),
the whole model's loss, gradients and update, the share test, the
control and the faults, and the arithmetic its per-layer metrics rest
on. (test_benchchip_run_lfm2.py drives the cell through run.py.)"""
import json

import numpy as np
import pytest

from benchmark.chip import (controls, controls_lfm2, device_scopes,
                            harness, shapes_lfm2, traffic)
from benchmark.chip.drivers import lfm2_train as D
from benchmark.chip.reference import lfm2_moe as R

CELL, CONFIG = "train_lfm2_ep8_s8k", "lfm2-24b-a2b-train-ep8"


def _sizes(**over):
    return {**controls._sizes(CONFIG, rehearse=True), **over}


def _published():
    with open(harness.ROOT + f"/benchmark/chip/configs/{CONFIG}.json") as f:
        return json.load(f)


# ---------------------------------------------------------------------
# part by part
# ---------------------------------------------------------------------
def _program_part(c, part):
    """A program of one part of layer `i` on x [B, T, D], with
    sum(out * w) differentiated; returns what to fetch."""
    import paddle_tpu as fluid
    from paddle_tpu import layers, unique_name
    from paddle_tpu.models import lfm2_moe as M

    d = c["d_model"]
    prog, start = fluid.Program(), fluid.Program()
    with unique_name.guard(), fluid.program_guard(prog, start):
        x = layers.data("x", shape=[c["seq_len"], d])
        w = layers.data("w", shape=[c["seq_len"], d])
        x.stop_gradient = False
        if part == "conv":
            out = M.conv_mixer(x, d, c["conv_taps"], "l0")
        elif part == "attention":
            out = M.attention_mixer(x, d, c["n_heads"], c["n_kv_heads"],
                                    c["rope_theta"], c["norm_eps"], "l1")
        elif part == "dense":
            out = M.dense_ff(x, d, c["d_dense"], "l0")
        else:
            out = layers.moe_dropless(
                x, c["n_experts"], c["d_expert"], c["top_k"],
                experts_held=(c["first_held"], c["experts_held"]),
                name="layer1_moe")[0]
        loss = layers.reduce_sum(layers.elementwise_mul(out, w))
        fluid.backward.append_backward(loss)
    return prog, start, out


def _reference_part(p, x, c, part):
    cfg = D.model_cfg(c)
    if part == "conv":
        return R.conv_mixer(p, "l0", x, cfg, "highest")
    if part == "attention":
        return R.attention_mixer(p, "l1", x, cfg, "highest")
    if part == "dense":
        return R.dense_ff(p, "l0", x, "highest")
    return R.moe_ff(p, "l1", x, cfg, "highest")[0]


@pytest.mark.parametrize("part", ["conv", "attention", "dense", "moe"])
def test_each_part_matches_the_reference_forward_and_gradient(part):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.core.scope import Scope

    c = _sizes(amp=False)
    params = R.make_params(5, D.model_cfg(c))
    leaves = {n: parts for n, parts in D.program_leaves(c).items()
              if n.startswith(("l0_", "l1_", "layer1_"))}
    prog, start, out = _program_part(c, part)
    used = {n: parts for n, parts in leaves.items()
            if prog.global_block.has_var(n)}
    assert used
    exe, scope = fluid.Executor(), Scope()
    exe.run(start, scope=scope)
    for name, value in D.to_program(params, used).items():
        scope._set(name, value)
    rng = np.random.default_rng(0)
    shape = (c["batch"], c["seq_len"], c["d_model"])
    x = rng.standard_normal(shape).astype("float32")
    w = rng.standard_normal(shape).astype("float32")
    trained = sorted(n for n in used if not n.endswith("_bias"))
    got = exe.run(prog, feed={"x": x, "w": w}, scope=scope,
                  fetch_list=[out, "x@GRAD"]
                  + [f"{n}@GRAD" for n in trained])

    def scalar(p, x):
        return jnp.sum(_reference_part(p, x, c, part) * w)
    want_out = _reference_part(params, jnp.asarray(x), c, part)
    g_p, g_x = jax.grad(scalar, (0, 1))(params, jnp.asarray(x))
    want = [want_out, g_x] + list(D.to_program(
        g_p, {n: used[n] for n in trained}).values())
    for a, b in zip(got, want):
        assert a.shape == b.shape
        assert np.abs(a - b).max() <= 2e-5 * max(1.0, np.abs(b).max())


def test_whole_model_follows_the_reference_in_float32():
    c = _sizes(amp=False)
    spec = {**traffic.load("fresh_batches"), "pool_batches": 3}
    feeds = traffic.train_batches(11, spec, c, D.START_ID)
    trainer = D.Trainer(c, seed=11)
    followed = [D.feed_of(f) for f in feeds]
    routing = trainer.routing_on_seed_state(followed[0])
    got = trainer.first_steps(followed)
    trainer.free()
    # the counters' call left the seed's state behind it
    assert routing["loss"] == pytest.approx(got["losses"][0], rel=1e-6)
    got["chosen"], load = routing["chosen"], routing["load"]
    want = D.reference_readings(c, 11, feeds)
    rows = {r["name"]: r["value"] for r in D.compare_readings(
        got, want, c["limits"]).rows}
    assert max(rows[f"loss_gap_step{i}"] for i in (1, 2, 3)) < 1e-5
    assert rows["grad_norm_gap"] < 1e-4
    assert rows["update_norm_gap"] < 1e-3
    assert rows["routing_flip_share"] == 0.0
    # every trained leaf is covered and moved; the biases are buffers
    assert set(got["grad_norms"]) == set(want["grad_norms"]) \
        == set(D.trained_leaves(c))
    assert not any(n.endswith("_bias") for n in got["grad_norms"])
    assert min(want["change_norms"].values()) > 0
    # the counters: every pair of a held expert, nothing dropped
    n_moe = c["n_layers"] - c["n_dense_layers"]
    assert load.shape == (n_moe, c["experts_held"])
    assert np.array_equal(routing["pairs_here"], load.sum(-1))
    held = range(c["first_held"], c["first_held"] + c["experts_held"])
    for layer, picks in want["chosen"].items():
        counts = np.bincount(picks.ravel(), minlength=c["n_experts"])
        assert np.array_equal(load[layer - c["n_dense_layers"]],
                              counts[list(held)])


def test_shares_of_an_expert_layer_add_up_to_the_uncut_reference():
    """The guide's share test: every rank's share of one expert layer
    (4 of 16 experts each, through the program's op) adds up to what
    the reference gives for the whole layer."""
    import jax.numpy as jnp

    from paddle_tpu.parallel import moe

    c = _sizes()
    n, held = c["n_experts"], c["experts_held"]
    whole = D.model_cfg({**c, "experts_held": n, "first_held": 0})
    p = R.make_params(9, whole)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (c["batch"], c["seq_len"], c["d_model"])), jnp.float32)
    want, chosen = R.moe_ff(p, "l1", x, whole, "highest")
    w13 = jnp.concatenate([p["l1.moe.w1"], p["l1.moe.w3"]], -1)
    total, pairs = 0.0, 0
    for first in range(0, n, held):
        out, idx, _, here = moe.moe_dropless(
            x.reshape(-1, c["d_model"]), p["l1.moe.wg"], p["l1.moe.b"],
            w13[first:first + held], p["l1.moe.w2"][first:first + held],
            first, c["top_k"])
        assert np.array_equal(idx, chosen)
        total, pairs = total + out, pairs + int(here[0])
    assert pairs == chosen.size
    assert np.abs(total.reshape(want.shape) - want).max() < 1e-5


# ---------------------------------------------------------------------
# the control and the faults
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def control_readings():
    c = _sizes()
    return [controls_lfm2.train_controls(c, seed,
                                         traffic.load("fresh_batches"))
            for seed in (2 ** 31 + 1, 12, 13)]


@pytest.mark.parametrize("what", sorted(controls_lfm2.VARIANTS))
def test_control_and_faults_come_out_not_correct(control_readings, what):
    """The reference at fp8, and with each fault, through
    `compare_readings` and `Compared.correct`, on every seed."""
    for reading in control_readings:
        assert reading[what]["correct"] is False, reading[what]


def test_host_ledger_lays_a_long_step_at_a_door():
    """A step that waits shows as long, under the span it waited in,
    with next to no processor time."""
    import time

    from paddle_tpu.observability import tracing

    with D.HostLedger() as ledger:
        for ms in (5, 5, 100, 5, 5):
            with tracing.span("exe.fetch"):
                time.sleep(ms / 1e3)
            ledger.mark()
    steps = ledger.steps()
    assert len(steps) == 5
    # (a busy machine may oversleep a short step into the list too)
    late = max(D.long_steps(steps), key=lambda r: r["ms"])
    assert late["step"] == 2 and late["exe.fetch"] >= 99
    assert late["process_cpu_ms"] < 50 and late["gc_ms"] == 0
    assert tracing.ambient_traces() == []


def test_routing_flip_share_counts_sets_of_experts():
    a = {1: np.array([[0, 3], [2, 5], [7, 1]])}
    same = {1: np.array([[3, 0], [5, 2], [1, 7]])}     # order is free
    one = {1: np.array([[3, 0], [5, 4], [1, 7]])}
    assert D.routing_flip_share(a, same) == 0.0
    assert D.routing_flip_share(a, one) == pytest.approx(1 / 3)


# ---------------------------------------------------------------------
# the configuration and the metrics' arithmetic
# ---------------------------------------------------------------------
def test_published_keys_are_the_sources_and_widths_are_uncut():
    cfg = _published()
    sizes = cfg["sizes"]
    assert sizes["d_model"] == cfg["hidden_size"] == 2048
    assert sizes["d_dense"] == cfg["intermediate_size"] == 11776
    assert sizes["d_expert"] == cfg["moe_intermediate_size"] == 1536
    assert sizes["n_heads"] == cfg["num_attention_heads"] == 32
    assert sizes["n_kv_heads"] == cfg["num_key_value_heads"] == 8
    assert sizes["n_experts"] == cfg["num_experts"] == 64
    assert sizes["top_k"] == cfg["num_experts_per_tok"] == 4
    assert sizes["conv_taps"] == cfg["conv_L_cache"] == 3
    assert sizes["rope_theta"] == cfg["rope_parameters"]["rope_theta"]
    assert sizes["norm_eps"] == cfg["norm_eps"]
    assert cfg["num_hidden_layers"] == 40 and cfg["vocab_size"] == 65536
    # the cut: one dense layer and one whole period, an eighth of the
    # experts and of the vocabulary
    kinds = R.layer_types(D.model_cfg(sizes))
    assert kinds == ["conv", "attention", "conv", "conv", "conv"]
    published = ["attention" if k == "full_attention" else k
                 for k in cfg["layer_types"]]
    assert published[1:6] == kinds and published[:2] == ["conv", "conv"]
    assert sizes["experts_held"] * 8 == sizes["n_experts"]
    assert sizes["vocab"] * 8 == cfg["vocab_size"]
    assert set(cfg["reduced_why"]) == {"n_layers", "n_dense_layers",
                                       "experts_held", "vocab"}


def test_parameters_and_operations_are_the_issues_arithmetic():
    c = _published()["sizes"]
    cfg = D.model_cfg(c)
    shapes = dict(R.top_shapes(cfg))
    for i in range(cfg["n_layers"]):
        shapes.update(R.layer_shapes(cfg, i))
    n_params = sum(int(np.prod(s)) for s, _ in shapes.values())
    assert round(n_params / 1e6) == 469
    even = c["seq_len"] * c["top_k"] * c["experts_held"] / c["n_experts"]
    fwd = shapes_lfm2.forward_flops_per_token(c, c["seq_len"],
                                              even / c["seq_len"])
    assert fwd == pytest.approx(405.5e6, rel=0.002)   # the issue's 405 M
    step = shapes_lfm2.train_flops_per_token(c, c["seq_len"], 4 * even) \
        * c["seq_len"]
    assert step == pytest.approx(9.95e12, rel=0.01)
    assert shapes_lfm2.expert_flops_per_pair(c) == 6 * 2048 * 1536
    assert shapes_lfm2.flash_attention_train_flops(c, 8192) \
        == pytest.approx(7 * 8192 * 8193 * 64 * 32, rel=1e-9)
    cost = shapes_lfm2.moe_experts_cost(c, 4 * even)
    assert cost["bytes"] == 3 * 4 * 8 * 3 * 2048 * 1536 * 2


HLO = '''
ENTRY %main {
  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/lfm2.moe.experts/mul" source_file="x.py"}
  ROOT %gmm.3 = bf16[8,8]{1,0} custom-call(%a), metadata={op_name="jit(step)/transpose(jvp(lfm2.moe.experts))/lfm2.moe.experts/gmm"}
  %copy.1 = f32[8]{0} copy(%p), metadata={op_name="jit(step)/lfm2.conv/mul"}
  %fusion.82 = bf16[8,8]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/transpose(jvp(lfm2.moe.combine))/gather"}
  %gmm.8 = bf16[8,8]{1,0} custom-call(%a), metadata={op_name="jit(step)/jvp(lfm2.moe.experts)/jit(gmm)/pallas_call"}
  %add.2 = f32[8]{0} add(%p, %p), metadata={op_name="jit(step)/add"}
}
'''


def test_scopes_are_read_off_the_compiled_text():
    assert device_scopes.scopes_of(HLO, "lfm2.") == {
        "fusion.7": "lfm2.moe.experts", "gmm.3": "lfm2.moe.experts",
        "copy.1": "lfm2.conv", "fusion.82": "lfm2.moe.combine",
        "gmm.8": "lfm2.moe.experts"}
    assert device_scopes.scopes_of(HLO, "lfm2.moe.") == {
        "fusion.7": "lfm2.moe.experts", "gmm.3": "lfm2.moe.experts",
        "fusion.82": "lfm2.moe.combine", "gmm.8": "lfm2.moe.experts"}
    assert device_scopes.share_of_busy({"trace": None}, "lfm2.") is None


@pytest.mark.parametrize("name", ["train_mfu.lfm2", "moe_step_share",
                                  "moe_experts_roofline",
                                  "flash_attention_roofline"])
def test_device_readers_read_nothing_without_a_chip(name):
    """As on the CPU and on a program without the counters: the line
    leaves the metric out and nothing raises."""
    obs = {"on_chip": False, "trace": None, "counters": {}, "sizes": {},
           "end_to_end": {}, "observed": {}, "peaks": None}
    assert harness.load_reader(name).read(obs) is None


def test_rooflines_from_a_reduced_trace():
    c = _published()["sizes"]
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    flash = shapes_lfm2.flash_attention_train_flops(c, 8192) / 197e12
    obs = {"on_chip": True, "sizes": c, "peaks": peaks,
           "counters": {"traced_steps": 10, "moe_pairs_per_step": 16384.0},
           "trace": {"op_s": {
               "flash_attention_fwd_bf16_32_8192_64": 4 * flash,
               "jvp_flash_attention_dkv__bf16_8_8192_64": 16 * flash,
               "gmm_bf16_8192_3072": 0.03, "tgmm_bf16_8_2048_3072": 0.01,
               "fusion_bf16_8192_2048": 1.0}}}
    assert harness.load_reader("flash_attention_roofline").read(obs) \
        == pytest.approx(50.0)
    cost = shapes_lfm2.moe_experts_cost(c, 16384.0)
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    assert harness.load_reader("moe_experts_roofline").read(obs) \
        == pytest.approx(100 * 10 * least / 0.04)
