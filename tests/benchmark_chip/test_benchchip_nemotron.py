"""The Nemotron-3-Super serving cell at its rehearsal sizes on the CPU:
the command end to end, the controls and the planted faults through the
run's own comparison, a tree that lacks the builder, the traffic mix,
the configuration file against the catalog row it copies, and the
arithmetic its per-layer metrics rest on. (tests/test_nemotron_h.py
holds the program to the reference part by part.)"""
import json
import os

import numpy as np
import pytest

from benchchip_util import REPO, RUN, cell_args, python, result_line
from benchmark.chip import controls, controls_nemotron, harness, \
    scopes_nemotron, shapes, shapes_nemotron as S, traffic
from benchmark.chip.drivers import nemotron_serve as D
from benchmark.chip.reference import nemotron_h as R

CELL, CONFIG = "serve_nemotron3_chat_128", "nemotron-3-super-serve-ep4"
COUNTS = {"cache_hits_at_setup", "compiles_in_window.serve",
          "tokens_per_dispatch", "moe_load_imbalance.nemotron3"}
COMPARED = ["served_logit_gap", "served_wide_gap_share",
            "served_logit_error", "stream_equals_row",
            "routing_flip_share", "state_bf16_share",
            "no_request_failed", "no_prefix_hit_with_lane_state"]
NEW_METRICS = {"serve_mfu.nemotron3", "decode_hbm_roofline.nemotron3",
               "ssm_step_roofline", "ssm_scan_roofline", "ssm_tick_share",
               "moe_tick_share.nemotron3",
               "prefill_device_share.nemotron3",
               "moe_load_imbalance.nemotron3"}
SEED = 2 ** 31 + 17
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _published():
    with open(os.path.join(harness.HERE, "configs",
                           f"{CONFIG}.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------------
# the command, end to end
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def runs():
    """One rehearsal of the cell untraced and one traced, and the
    sample the first left behind."""
    out = {}
    for trace in (0, 1):
        proc = python([RUN] + cell_args(CELL, trace, seed=SEED)
                      + ["--rehearse"])
        out[trace] = (proc, result_line(proc.stdout))
    out["sample"] = os.path.join(
        REPO, "chiprun_out", "benchchip",
        f"{CELL}.seed{SEED}.trace0.sample.npz")
    return out


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_end_to_end(runs, trace):
    proc, res = runs[trace]
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert res is not None, proc.stdout[-2000:]
    assert res["correct"] is True, proc.stderr[-2000:]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"] == {"platform": "cpu", "kind": "cpu",
                             "count": 1, "memory_peak_bytes": None}
    assert set(res["metrics"]) == (COUNTS if trace else set())
    assert [r["name"] for r in res["compared"]] == COMPARED
    assert list(res)[-1] == "compared"


def test_the_window_compiles_nothing_and_finds_nothing_cached(runs):
    proc, res = runs[1]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["compiles_in_window.serve"] == 0
    assert m["tokens_per_dispatch"] > 1
    counters = json.loads(proc.stdout.strip().splitlines()[-2])["counters"]
    assert counters["cached_prompt_tokens"] == 0
    assert counters["prefix_reuse_skipped"] == counters["state_resets"] > 0
    assert counters["state_lanes"] == 4 and counters["state_bytes"] > 0
    assert counters["moe_load_imbalance"] >= 1


def test_the_run_says_where_it_got_to(runs):
    err = runs[0][0].stderr
    for phase in ("server built", "both serve programs warm",
                  "window closed", "sample held to the reference"):
        assert f"[nemotron_serve] {phase}" in err


@pytest.fixture(scope="module")
def readings(runs):
    assert runs[0][1] is not None, runs[0][0].stderr[-2000:]
    c = controls._sizes(CONFIG, rehearse=True)
    seed, sample = D.load_sample(runs["sample"])
    assert seed == SEED
    # a request of each prompt length, and the two reset probes with
    # the scan state their lanes kept
    assert sorted(len(s["prompt"]) for s in sample) \
        == [2, 5] + [6] * 4 + [20] * 4 + [70] * 4
    assert [len(s["prompt"]) for s in sample if "state" in s] == [2, 5]
    assert sample[-1]["state"].shape == (
        c["layers"].count("M"), c["ssm_heads"],
        c["ssm_head_dim"], c["ssm_state"])
    return controls_nemotron.serve_controls(c, seed, sample)


def test_the_saved_sample_reads_as_the_run_read_it(runs, readings):
    mine = {r["name"]: r["value"] for r in runs[0][1]["compared"]}
    for name, value in readings["program"].items():
        if name not in ("correct", "state_gap"):
            assert value == pytest.approx(mine[name], abs=1e-6)
    assert readings["program"]["correct"] is True


STATE_ALONE = "control_state_bf16"
FAULTS = sorted(set(controls_nemotron.VARIANTS) - {STATE_ALONE})


@pytest.mark.parametrize("what", sorted(controls_nemotron.VARIANTS))
def test_controls_and_faults_read_not_correct(readings, what):
    assert readings[what]["correct"] is False, readings[what]


def test_a_bfloat16_scan_state_shows_in_the_state_itself(readings):
    """Beside bfloat16 weights the logits hide a scan state kept in
    bfloat16 (PERF.md section 2); the state the probes' lanes kept does
    not: every number of it is then a bfloat16 number, of the float32
    state the configuration states about one in 65,536."""
    limit = controls._sizes(CONFIG, rehearse=True)["limits"][
        "state_bf16_share"]
    assert readings["program"]["state_bf16_share"] < limit / 2
    for what in (STATE_ALONE, "control_low"):
        assert readings[what]["state_bf16_share"] == 1.0
    for what in FAULTS:
        if what != "control_low":
            assert readings[what]["state_bf16_share"] < limit / 2, what


def test_the_faults_fail_by_the_logit_of_the_served_token(readings):
    """A fault moves the logits of the tokens a request was served,
    in the reset probes above all; a precision shows in the tokens and
    the routing first."""
    limits = controls._sizes(CONFIG, rehearse=True)["limits"]
    for what in FAULTS:
        got = readings[what]
        if what.startswith("fault_"):
            assert got["served_logit_error"] \
                > 2 * limits["served_logit_error"], what
        else:
            assert got["served_wide_gap_share"] \
                > limits["served_wide_gap_share"], what
            assert got["routing_flip_share"] \
                > limits["routing_flip_share"], what


# what float32 weights read: the program has no rounding of its own, so
# a limit can lie below what a bfloat16 scan state does to a logit
EXACT_LIMITS = {"served_logit_gap": 1e-3, "served_wide_gap_share": 0.0,
                "served_logit_error": 2e-4, "routing_flip_share": 0.0,
                "state_bf16_share": 0.01}


@pytest.fixture(scope="module")
def exact():
    """The rehearsal sizes with float32 weights, served in this
    process: a request of each prompt length, its probes, and the
    readings of the program and of every control and fault."""
    c = {**controls._sizes(CONFIG, rehearse=True),
         "weight_dtype": "float32", "limits": EXACT_LIMITS}
    srv, _exe, _scope = D.build_server(c, 9)
    rng = np.random.default_rng(9)
    try:
        sample = []
        for n, new in ((6, 8), (20, 16), (70, 16)):
            prompt = rng.integers(3, c["vocab"], n)
            reply = srv.submit(prompt, max_new_tokens=new)
            row = np.asarray(reply.result(timeout=600))
            sample.append({"prompt": prompt, "row": row,
                           "streamed": list(D.served_of(row)),
                           "probe": reply.probe,
                           "state": D.lane_states(
                               srv, _scope, [reply.probe["lane"]])[0]})
    finally:
        srv.close()
    return controls_nemotron.serve_controls(c, 9, sample)


def test_float32_weights_read_the_reference(exact):
    """Beside float32 weights the program's logits and the scan state
    its lanes keep are the reference's but for the order of the sums,
    and a scan state kept in bfloat16 lies a thousand times farther
    from the reference's than the program's does."""
    assert exact["program"]["correct"] is True, exact["program"]
    assert exact["program"]["served_logit_error"] < 5e-5
    assert max(exact["program"]["state_gap"]) < 5e-6
    assert min(exact["control_state_bf16"]["state_gap"]) > 1e-3


@pytest.mark.parametrize("what", sorted(controls_nemotron.VARIANTS))
def test_every_planted_fault_reads_not_correct_beside_float32(exact, what):
    """State not reset on admission, scan state kept in bfloat16,
    shared expert left out, latent up-projection left out, padded
    positions advancing the state, and operands in 8 bits: each moves
    the served tokens' logits by more than the limit."""
    assert exact[what]["correct"] is False, exact[what]
    assert exact[what]["served_logit_error"] \
        > EXACT_LIMITS["served_logit_error"], exact[what]


def test_a_tree_without_the_builder_fails_at_once():
    """What the parent does with this cell once the benchmark's files
    are laid over it: the driver is there, the builder it imports is
    not; the run ends with another code than 0 and no result line."""
    code = ("import sys; sys.path.insert(0, %r)\n"
            "sys.modules['paddle_tpu.models.nemotron_h'] = None\n"
            "from benchmark.chip import run\n"
            "sys.exit(run.main(%r))"
            % (REPO, cell_args(CELL, 0, seed=5) + ["--rehearse"]))
    proc = python(["-c", code], timeout=300)
    assert proc.returncode != 0
    assert result_line(proc.stdout) is None
    assert "nemotron_h" in proc.stderr


# ---------------------------------------------------------------------
# the traffic mix
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def spec():
    return traffic.load("chat_cold_mix")


def test_the_mix_is_a_data_file_of_a_known_kind(spec):
    assert spec["kind"] == "closed_loop" and spec["callers"] == 128
    assert spec["prompt_pool"] == 0 and spec["ramp_s"] == 10
    lengths, shares = zip(*spec["prompt_tokens"])
    assert lengths == (128, 512, 2048) and shares == (0.40, 0.35, 0.25)
    assert round(sum(n * p for n, p in spec["prompt_tokens"])) == 742
    assert sum(n * p for n, p in spec["max_new_tokens"]) == 288
    c = _published()["sizes"]
    assert max(lengths) + max(n for n, _ in spec["max_new_tokens"]) \
        == c["context"] and c["max_new_tokens"] == 512


def test_every_stretch_holds_every_choice_in_its_number(spec):
    mix = D.ChatMix(3, {**spec, "max_requests": 1280}, {"vocab": 32768})
    for a in range(0, 1280, 128):
        p = mix.p_len[a:a + 128]
        assert abs((p == 128).sum() - 51) <= 1
        assert abs((p == 512).sum() - 45) <= 1
        assert (p == 2048).sum() == 32
        new = mix.max_new[a:a + 128]
        assert (new == 256).sum() == 64 and (new == 128).sum() == 32


def test_the_seed_says_what_is_said_and_not_how_long(spec):
    small = {**spec, "max_requests": 200}
    a, b = (D.ChatMix(s, small, {"vocab": 32768})
            for s in (1, 2 ** 31 + 5))
    assert (a.p_len == b.p_len).all() and (a.max_new == b.max_new).all()
    (pa, na), (pb, nb) = a.next_request(), b.next_request()
    assert (len(pa), na) == (len(pb), nb) and not (pa == pb).all()
    assert pa.min() >= 3 and pa.max() < 32768
    with pytest.raises(ValueError, match="prompt_pool"):
        D.ChatMix(1, {**small, "prompt_pool": 4}, {"vocab": 32768})


def test_the_padding_of_every_chunk_is_where_the_planner_puts_it():
    c = {"chunk_sizes": [8, 32]}
    prompt, served = np.arange(100, 171), np.array([7, 8, 9])
    toks, ghost, want = D.ghosted(c, prompt, served)
    # 70 to prefill: 32, 32 and 6 padded to 8; then what the lane is
    # fed: the prompt's last token and every served one but the last
    assert len(toks) == 32 + 32 + 8 + 1 + 2 and ghost.sum() == 2
    assert list(np.flatnonzero(ghost)) == [70, 71]
    assert list(toks[~ghost]) == list(prompt) + list(served[:-1])
    assert list(want) == [72, 73, 74] and toks[72] == prompt[-1]
    # a rest that fills its chunks has no padding at all
    toks, ghost, want = D.ghosted(c, np.arange(41), served)
    assert not ghost.any() and list(want) == [40, 41, 42]


# ---------------------------------------------------------------------
# the configuration file
# ---------------------------------------------------------------------
def test_the_file_carries_the_catalogs_config_unchanged():
    row = None
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guides here")
    with open(path) as f:
        for line in f:
            if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in line:
                row = json.loads(line)
    cfg = _published()
    for key, value in row["config"].items():
        assert cfg[key] == value, key
    assert cfg["source_url"] == row["source_url"]


def test_every_width_is_the_published_one():
    cfg = _published()
    s = cfg["sizes"]
    for mine, theirs in (
            ("d_model", "hidden_size"), ("ssm_heads", "mamba_num_heads"),
            ("ssm_head_dim", "mamba_head_dim"), ("ssm_groups", "n_groups"),
            ("ssm_state", "ssm_state_size"), ("conv_kernel", "conv_kernel"),
            ("scan_block", "chunk_size"),
            ("n_heads", "num_attention_heads"),
            ("n_kv_heads", "num_key_value_heads"),
            ("head_dim", "head_dim"), ("n_experts", "n_routed_experts"),
            ("top_k", "num_experts_per_tok"),
            ("d_expert", "moe_intermediate_size"),
            ("d_latent", "moe_latent_size"),
            ("d_shared", "moe_shared_expert_intermediate_size"),
            ("routed_scaling", "routed_scaling_factor"),
            ("norm_eps", "norm_eps"), ("norm_topk", "norm_topk_prob"),
            ("time_step_min", "time_step_min"),
            ("time_step_max", "time_step_max"),
            ("time_step_floor", "time_step_floor")):
        assert s[mine] == cfg[theirs], mine
    assert s["ssm_heads"] * s["ssm_head_dim"] \
        == cfg["expand"] * cfg["hidden_size"]
    # published layers 0 to 10: one whole period of the pattern
    assert s["layers"] == cfg["hybrid_override_pattern"][:11]
    assert s["n_layers"] == len(s["layers"]) == 11
    assert (s["layers"].count("M"), s["layers"].count("E"),
            s["layers"].count("*")) == (5, 5, 1)
    assert set(cfg["reduced_why"]) == {"n_layers", "experts_held", "vocab"}
    assert s["vocab"] * 4 == cfg["vocab_size"]
    assert s["experts_held"] * 4 == cfg["n_routed_experts"]
    assert s["first_held"] == 128 and s["n_slots"] == 128
    assert {"rotary_embedding", "latent_projections", "state_dtype",
            "init"} <= set(cfg["assumed"])
    assert "deployment" in cfg and "prediction" in cfg["reduced_why"][
        "n_layers"]
    assert set(s["limits"]) == set(cfg["rehearsal"]["limits"]) \
        == set(cfg["limits_why"]) - {"rehearsal"} == {
        "served_logit_gap", "served_wide_gap_share", "served_logit_error",
        "routing_flip_share", "state_bf16_share"}


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(harness.HERE, "reference", "nemotron_h.py")
    with open(path) as f:
        text = f.read()
    assert "import paddle_tpu" not in text
    assert "from paddle_tpu" not in text and "from ..." not in text
    assert "lax.scan(step" in text      # a sequential recurrence


# ---------------------------------------------------------------------
# the arithmetic of the per-layer metrics
# ---------------------------------------------------------------------
def test_the_issues_count_of_parameters_state_and_cache():
    c = _published()["sizes"]
    assert round(S.ssm_weights(c) / 1e6, 1) == 109.6
    assert round(S.attention_weights(c) / 1e6, 1) == 35.7
    assert round(S.expert_layer_fixed_weights(c) / 1e6, 1) == 54.5
    assert S.expert_weights(c) == 5505024
    assert S.kv_position_bytes(c) == 1024
    assert round(S.kv_position_bytes(c) * c["n_blocks"] * c["block_size"]
                 / 1e9, 2) == 0.34
    assert S.lane_state_bytes(c) == 128 * 64 * 128 * 4 + 3 * 10240 * 2
    assert round(5 * S.lane_state_bytes(c) / 1e6, 1) == 21.3
    assert round(5 * S.lane_state_bytes(c) * 129 / 1e9, 2) == 2.74
    # every parameter the reference makes, in bfloat16
    n = sum(int(np.prod(shape)) for i in range(c["n_layers"])
            for shape, _ in R.layer_shapes(c, i).values())
    n += sum(int(np.prod(shape)) for shape, _ in R.top_shapes(c).values())
    assert round(n / 1e9, 2) == 4.65 and round(n * 2 / 1e9, 1) == 9.3


def test_one_tick_by_hand():
    """128 live lanes at a mean context of 1,000, every held expert
    hit: the weights once, the state read and written, the cache
    read."""
    c = _published()["sizes"]
    weights = (5 * 109627392 + 35651584
               + 5 * (54525952 + 128 * 5505024) + 4096 * 32768) * 2
    state = 5 * 2 * 128 * 4255744
    cache = 128 * 1001 * 1024
    assert S.tick_weight_bytes(c, 128) == weights
    assert S.decode_tick_min_bytes(c, 128, 1000, 128) \
        == weights + state + cache
    assert 14.0e9 < weights + state + cache < 15.0e9
    cost = S.ssm_tick_cost(c, 128)
    assert cost["bytes"] == 5 * 109627392 * 2 + state
    assert cost["flops"] == 5 * 128 * (2 * 109627392 + 6 * 128 * 64 * 128)
    # bound by bytes: 6.5 GB against 0.14 TFLOP
    assert shapes.roofline_seconds(cost, PEAKS) == cost["bytes"] / 819e9
    # a served token: 2.1 GFLOP with 5.5 pairs an expert layer
    flops = S.token_flops(c, 1000, 5.5)
    assert flops == 2 * 4096 * 32768 \
        + 5 * (2 * 109627392 + 6 * 128 * 64 * 128) \
        + 2 * 35651584 + 4 * 32 * 128 * 1000 \
        + 5 * (2 * 54525952 + 5.5 * 2 * 5505024)
    assert 2.0e9 < flops < 2.4e9


def test_one_chunk_by_hand():
    """A chunk of 2,048 real positions through the five state-space
    layers: the projections and the chunked scan at blocks of 128."""
    c = _published()["sizes"]
    scan = 2 * 8 * 128 * 128 + 2 * 128 * 128 * 64 + 4 * 128 * 64 * 128
    assert S.scan_flops(c, True) == scan == 6553600
    cost = S.ssm_chunk_cost(c, 2048, 1)
    assert cost["flops"] == 5 * 2048 * (2 * 109627392 + scan)
    rows = 2 * 4096 + 2 * 8192 + 10240 + 128
    assert cost["bytes"] == 5 * (109627392 * 2 + 2 * 4255744
                                 + 2048 * rows * 2)
    # bound by operations
    assert shapes.roofline_seconds(cost, PEAKS) \
        == cost["flops"] / 197e12
    # a prefilled token has no head and is routed as any token is
    assert S.token_flops(c, 1000, 5.5, chunked=True, head=False) \
        == S.token_flops(c, 1000, 5.5) - 2 * 4096 * 32768 \
        + 5 * (scan - 6 * 128 * 64 * 128)


def test_nested_scopes_count_under_each_once():
    tick = '''
  %fusion.3 = f32[129,8192]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/while/body/nemotronh.ssm/ssm.step/mul"}
  %fusion.4 = bf16[129,4096]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/while/body/nemotronh.moe/nemotronh.moe.route/top_k"}
  %copy.1 = bf16[8,8]{1,0} copy(%p), metadata={op_name="jit(step)/while/body/rms_norm"}
'''
    chunk = '''
  %fusion.3 = f32[512,8192]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/while/body/nemotronh.prefill_chunk/nemotronh.ssm_scan/ssm.scan/mul"}
  %fusion.5 = bf16[512,4096]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/while/body/nemotronh.prefill_chunk/nemotronh.prefill_chunk.moe.experts/dot"}
  %fusion.4 = bf16[129,4096]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/while/body/nemotronh.attn/gather"}
'''
    scopes = scopes_nemotron.scopes_of([tick, chunk])
    assert scopes["fusion.3|fusion_f32_129_8192"] == "nemotronh.ssm"
    assert scopes["fusion.3|fusion_f32_512_8192"] \
        == "nemotronh.prefill_chunk nemotronh.ssm_scan"
    assert scopes["fusion.5|fusion_bf16_512_4096"] \
        == "nemotronh.prefill_chunk nemotronh.prefill_chunk.moe.experts"
    assert scopes["fusion.4|fusion_bf16_129_4096"] == "?"
    assert not any(k.startswith("copy.1") for k in scopes)


def test_seconds_under_a_scope_do_not_take_its_namesakes(monkeypatch):
    seconds = {"nemotronh.ssm": 1.0,
               "nemotronh.prefill_chunk nemotronh.ssm_scan": 2.0,
               "nemotronh.prefill_chunk "
               "nemotronh.prefill_chunk.moe.experts": 4.0,
               "nemotronh.moe nemotronh.moe.route": 8.0, "?": 16.0}
    monkeypatch.setattr(scopes_nemotron, "scope_seconds",
                        lambda obs: seconds)
    under = scopes_nemotron.under
    assert under({}, "nemotronh.ssm") == 1.0
    assert under({}, "nemotronh.ssm_scan") == 2.0
    assert under({}, "nemotronh.prefill_chunk") == 6.0
    assert under({}, "nemotronh.moe") == 8.0
    obs = {"trace": {"busy_s": 40.0}}
    assert scopes_nemotron.tick_seconds(obs) == 34.0


def test_readers_read_nothing_without_a_trace_or_counters():
    obs = {"counters": {}, "trace": None, "sizes": _published()["sizes"],
           "on_chip": False, "end_to_end": {}, "observed": {},
           "peaks": None}
    manifest = harness.load_manifest()
    mine = {m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]}
    assert mine == NEW_METRICS
    for name in mine:
        assert harness.load_reader(name).read(obs) is None, name


def test_the_rooflines_read_one_hundred_at_the_least_time(monkeypatch):
    """A traced window whose ticks take exactly the least bytes' time,
    and whose chunks exactly their operations' time, reads 100%."""
    c = _published()["sizes"]
    counters = {"traced_ticks": 10, "mean_live_lanes": 100.0,
                "mean_context": 900.0, "held_experts_hit_per_tick": 120.0,
                "prefill_tokens": 5000, "prefill_chunks": 9}
    tick = S.decode_tick_min_bytes(c, 100.0, 900.0, 120.0) / 819e9
    step = shapes.roofline_seconds(S.ssm_tick_cost(c, 100.0), PEAKS)
    scan = shapes.roofline_seconds(S.ssm_chunk_cost(c, 5000, 9), PEAKS)
    seconds = {"nemotronh.ssm": 10 * step,
               "nemotronh.prefill_chunk nemotronh.ssm_scan": scan,
               "nemotronh.prefill_chunk": 1.0}
    monkeypatch.setattr(scopes_nemotron, "scope_seconds",
                        lambda obs: seconds)
    obs = {"counters": counters, "sizes": c, "peaks": PEAKS,
           "on_chip": True,
           "trace": {"busy_s": 10 * tick + scan + 1.0}}
    read = {name: harness.load_reader(name).read(obs)
            for name in NEW_METRICS - {"serve_mfu.nemotron3"}}
    assert read["decode_hbm_roofline.nemotron3"] == pytest.approx(100.0)
    assert read["ssm_step_roofline"] == pytest.approx(100.0)
    assert read["ssm_scan_roofline"] == pytest.approx(100.0)
    assert read["ssm_tick_share"] == pytest.approx(100 * step / tick)
    assert read["moe_tick_share.nemotron3"] is None
    assert read["prefill_device_share.nemotron3"] == pytest.approx(
        100 * (scan + 1.0) / (10 * tick + scan + 1.0))
