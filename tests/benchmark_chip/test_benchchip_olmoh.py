"""The Olmo-Hybrid serving cell at its rehearsal sizes on the CPU: the
command end to end, the controls and the planted faults through the
run's own comparison, a tree that lacks the builder, the session
traffic, the configuration file against the catalog row it copies, the
manifest's entries, and the arithmetic its per-layer metrics rest on.
(tests/test_olmo_hybrid.py holds the program to the reference part by
part.)"""
import json
import os

import numpy as np
import pytest

from benchchip_util import REPO, RUN, cell_args, python, result_line
from benchmark.chip import controls, controls_olmoh, harness, \
    scopes_olmoh, shapes, shapes_olmoh as S, traffic
from benchmark.chip.drivers import olmoh_serve as D
from benchmark.chip.reference import olmo_hybrid as R

CELL, CONFIG = "serve_olmoh_sessions_32", "olmo-hybrid-7b-serve-pp4"
COUNTS = {"cache_hits_at_setup", "compiles_in_window.serve",
          "tokens_per_dispatch"}
COMPARED = ["served_logit_gap", "served_wide_gap_share",
            "served_logit_error", "stream_equals_row", "state_bf16_share",
            "no_request_failed", "no_prefix_hit_with_lane_state"]
NEW_METRICS = {"gdn_step_roofline", "gdn_chunk_roofline",
               "gdn_tick_share", "prefill_device_share.olmoh",
               "decode_hbm_roofline.olmoh", "serve_mfu.olmoh"}
SEED = 2 ** 31 + 29
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


def test_the_window_repeats_history_and_finds_nothing_cached(runs):
    proc, res = runs[1]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["compiles_in_window.serve"] == 0
    assert m["tokens_per_dispatch"] > 1
    lines = proc.stdout.strip().splitlines()
    counters = json.loads(lines[-2])["counters"]
    notes = json.loads(lines[-2])["notes"]
    assert counters["cached_prompt_tokens"] == 0
    assert counters["prefix_reuse_skipped"] == counters["state_resets"] > 0
    assert counters["state_lanes"] == 4 and counters["state_bytes"] > 0
    # most of a later turn's prompt is the turn before it, prefilled
    # again: at rehearsal lengths about three quarters
    assert 0.6 < counters["repeated_prompt_share"] < 0.9
    assert all(n > 0 for n in notes["by_turn"])


def test_the_run_says_where_it_got_to(runs):
    err = runs[0][0].stderr
    for phase in ("server built", "both serve programs warm",
                  "window closed", "sample held to the reference"):
        assert f"[olmoh_serve] {phase}" in err


@pytest.fixture(scope="module")
def readings(runs):
    assert runs[0][1] is not None, runs[0][0].stderr[-2000:]
    c = controls._sizes(CONFIG, rehearse=True)
    seed, sample = D.load_sample(runs["sample"])
    assert seed == SEED
    # a request of each turn, perhaps the longest beside them, and the
    # two reset probes with the state their lanes kept
    assert len(sample) in (6, 7)
    assert [len(s["prompt"]) for s in sample if "state" in s] == [2, 5]
    assert sample[-1]["state"].shape[0] == c["layers"].count("L")
    assert sample[-1]["state"].shape[2:] == (c["lin_key_dim"],
                                             c["lin_value_dim"])
    return controls_olmoh.serve_controls(c, seed, sample)


def test_the_saved_sample_reads_as_the_run_read_it(runs, readings):
    mine = {r["name"]: r["value"] for r in runs[0][1]["compared"]}
    for name, value in readings["program"].items():
        if name not in ("correct", "state_gap"):
            assert value == pytest.approx(mine[name], abs=1e-6)
    assert readings["program"]["correct"] is True


STATE_ALONE = "control_state_bf16"


@pytest.mark.parametrize("what", sorted(controls_olmoh.VARIANTS))
def test_controls_and_faults_read_not_correct(readings, what):
    assert readings[what]["correct"] is False, readings[what]


def test_a_bfloat16_state_shows_in_the_state_itself(readings):
    """Every number of a state kept in bfloat16 is a bfloat16 number;
    of the float32 state the configuration states about one in 65,536
    is."""
    limit = controls._sizes(CONFIG, rehearse=True)["limits"][
        "state_bf16_share"]
    assert readings["program"]["state_bf16_share"] < limit / 2
    for what in (STATE_ALONE, "control_low"):
        assert readings[what]["state_bf16_share"] == 1.0


@pytest.mark.parametrize("what", sorted(
    w for w in controls_olmoh.VARIANTS if w != STATE_ALONE))
def test_a_fault_or_a_precision_fails_by_the_served_tokens(readings, what):
    """A fault of the delta rule or of the lane's state, and operands a
    precision down, put first a token whose logit lies below the one
    served by more than the limit (at rehearsal widths a bfloat16
    stream moves one short probe's logits by as much as a fault moves
    them: the configuration's `limits_why`)."""
    limits = controls._sizes(CONFIG, rehearse=True)["limits"]
    assert readings[what]["served_logit_gap"] \
        > limits["served_logit_gap"], readings[what]


def test_a_tree_without_the_builder_fails_at_once():
    """What the parent does with this cell once the benchmark's files
    are laid over it: the driver is there, the builder it imports is
    not; the run ends with another code than 0 and no result line."""
    code = ("import sys; sys.path.insert(0, %r)\n"
            "sys.modules['paddle_tpu.models.olmo_hybrid'] = None\n"
            "from benchmark.chip import run\n"
            "sys.exit(run.main(%r))"
            % (REPO, cell_args(CELL, 0, seed=5) + ["--rehearse"]))
    proc = python(["-c", code], timeout=300)
    assert proc.returncode != 0
    assert result_line(proc.stdout) is None
    assert "olmo_hybrid" in proc.stderr


# ---------------------------------------------------------------------
# the traffic
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def spec():
    return traffic.load("sessions_grow_9k")


def _mean(table):
    return sum(n * p for n, p in table)


def test_the_mix_is_a_data_file_of_a_known_kind(spec):
    assert spec["kind"] == "closed_loop" and spec["callers"] == 32
    assert spec["session_turns"] == 4 and spec["ramp_s"] == 10
    assert [n for n, _ in spec["first_prompt_tokens"]] == [1024, 2048,
                                                           4096]
    assert round(_mean(spec["first_prompt_tokens"]), 1) == 2150.4
    assert round(_mean(spec["message_tokens"]), 1) == 422.4
    assert _mean(spec["max_new_tokens"]) == 288
    c = _published()["sizes"]
    longest = 4096 + 3 * (512 + 1024)
    assert longest + 512 == c["context"] == 9216
    assert c["max_new_tokens"] == 512


def test_three_quarters_of_the_prompt_tokens_repeat_the_last_turn(spec):
    """By arithmetic: turn k's mean prompt is the first turn's plus k-1
    times a reply and a message, and all of a later turn's prompt but
    its message repeats what the lane was fed one turn before."""
    first, msg = _mean(spec["first_prompt_tokens"]), \
        _mean(spec["message_tokens"])
    reply = _mean(spec["max_new_tokens"])
    prompts = [first + k * (reply + msg) for k in range(4)]
    assert [round(p) for p in prompts] == [2150, 2861, 3571, 4282]
    assert round(sum(prompts) / 4) == 3216
    repeated = sum(p + reply for p in prompts[:3])
    assert round(repeated / sum(prompts), 3) == 0.734


def test_a_session_resends_its_history(spec):
    mix = D.Sessions(3, {**spec, "max_requests": 400}, {"vocab": 100352})
    openers = [mix.opening(c) for c in range(8)]
    assert [r.turn for r in openers] == [1, 2, 3, 4] * 2
    req = openers[1]
    req.tokens = list(range(5, 5 + req.max_new))
    nxt = mix.following(req)
    assert nxt.turn == 3 and nxt.caller == 1
    n = len(req.prompt)
    assert (nxt.prompt[:n] == req.prompt).all()
    assert list(nxt.prompt[n:n + req.max_new]) == req.tokens
    assert len(nxt.prompt) - n - req.max_new in (128, 512, 1024)
    last = openers[3]
    last.tokens = [7] * last.max_new
    again = mix.following(last)
    assert again.turn == 1 and len(again.prompt) in (1024, 2048, 4096)
    assert max(len(r.prompt) for r in openers) + 512 <= 9216


def test_the_seed_says_what_is_said_and_not_how_long(spec):
    small = {**spec, "max_requests": 200}
    a, b = (D.Sessions(s, small, {"vocab": 100352})
            for s in (1, 2 ** 31 + 5))
    assert (a.first_len == b.first_len).all()
    assert (a.max_new == b.max_new).all() and (a.msg_len == b.msg_len).all()
    ra, rb = a.opening(2), b.opening(2)
    assert (len(ra.prompt), ra.max_new) == (len(rb.prompt), rb.max_new)
    assert not (ra.prompt == rb.prompt).all()
    assert ra.prompt.min() >= 3 and ra.prompt.max() < 100352


def test_a_callers_lengths_do_not_hang_on_the_others_order(spec):
    """Two runs whose callers' replies end in another order send each
    caller the same lengths."""
    def lengths(order):
        mix = D.Sessions(3, {**spec, "max_requests": 400},
                         {"vocab": 100352})
        sent = {c: [mix.opening(c)] for c in range(4)}
        for c in order:
            req = sent[c][-1]
            req.tokens = [5] * req.max_new
            sent[c].append(mix.following(req))
        return {c: [(len(r.prompt), r.max_new) for r in reqs]
                for c, reqs in sent.items()}

    assert lengths([0, 1, 2, 3, 0, 1]) == lengths([3, 1, 1, 0, 2, 0])


def test_every_stretch_holds_every_choice_in_its_number(spec):
    mix = D.Sessions(3, {**spec, "max_requests": 200}, {"vocab": 100352})
    for a in range(0, 200, 20):
        first = mix.first_len[a:a + 20]
        assert [(first == n).sum() for n in (1024, 2048, 4096)] \
            == [8, 7, 5]
        msg = mix.msg_len[a:a + 20]
        assert [(msg == n).sum() for n in (128, 512, 1024)] == [10, 6, 4]
        new = mix.max_new[a:a + 20]
        assert [(new == n).sum() for n in (128, 256, 512)] == [5, 10, 5]


# ---------------------------------------------------------------------
# the configuration file and the manifest
# ---------------------------------------------------------------------
def test_the_file_carries_the_catalogs_config_unchanged():
    row = None
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guides here")
    with open(path) as f:
        for line in f:
            if '"Olmo-Hybrid-7B"' in line:
                row = json.loads(line)
    cfg = _published()
    for key, value in row["config"].items():
        assert cfg[key] == value, key
    assert cfg["source_url"] == row["source_url"]


def test_every_width_is_the_published_one():
    cfg = _published()
    s = cfg["sizes"]
    for mine, theirs in (
            ("d_model", "hidden_size"),
            ("lin_heads", "linear_num_value_heads"),
            ("lin_heads", "linear_num_key_heads"),
            ("lin_key_dim", "linear_key_head_dim"),
            ("lin_value_dim", "linear_value_head_dim"),
            ("conv_kernel", "linear_conv_kernel_dim"),
            ("allow_neg_eigval", "linear_allow_neg_eigval"),
            ("n_heads", "num_attention_heads"),
            ("n_kv_heads", "num_key_value_heads"),
            ("d_ff", "intermediate_size"), ("vocab", "vocab_size"),
            ("norm_eps", "rms_norm_eps")):
        assert s[mine] == cfg[theirs], mine
    assert s["head_dim"] * s["n_heads"] == cfg["hidden_size"]
    # published layers 0 to 7: two whole periods of the pattern
    kinds = {"linear_attention": "L", "full_attention": "F"}
    assert s["layers"] == "".join(kinds[t] for t in cfg["layer_types"][:8])
    assert s["n_layers"] == len(s["layers"]) == 8
    assert (s["layers"].count("L"), s["layers"].count("F")) == (6, 2)
    assert set(cfg["reduced_why"]) == {"n_layers"}
    assert s["n_slots"] == 32 and s["context"] == 9216
    assert {"post_norm", "rope_theta", "conv", "gated_norm",
            "decay", "state_dtype", "silent_ids", "init"} \
        <= set(cfg["assumed"])
    assert "deployment" in cfg
    assert set(s["limits"]) == set(cfg["rehearsal"]["limits"]) == {
        "served_logit_gap", "served_wide_gap_share", "served_logit_error",
        "state_bf16_share"}


def test_the_manifest_adds_the_cell_and_its_metrics():
    manifest = harness.load_manifest()
    cfg = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert len(cfg) == 1 and cfg[0]["reduced"] == ["n_layers"]
    assert cfg[0]["source"] == _published()["source_url"]
    cell = harness.find_cell(manifest, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "sessions_grow_9k"
    assert len(cell["why"]) <= 200
    e2e = {m["name"] for m in harness.cell_metrics(manifest, cell,
                                                   "end_to_end")}
    assert e2e == {"serve_tokens_per_s", "tpot_ms_p95", "setup_s"}
    mine = {m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]}
    assert mine == NEW_METRICS
    # the cross-cell metrics the other decoder-only cell reports
    nem = {m["name"] for m in manifest["per_layer"]
           if "serve_nemotron3_chat_128" in m.get("workloads", ())
           and len(m["workloads"]) > 1}
    ours = {m["name"] for m in harness.cell_metrics(manifest, cell,
                                                    "per_layer")}
    assert ours == nem | NEW_METRICS
    for m in manifest["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["unit"] == "%" and m["moves"] in e2e


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(harness.HERE, "reference", "olmo_hybrid.py")
    with open(path) as f:
        text = f.read()
    assert "import paddle_tpu" not in text
    assert "from paddle_tpu" not in text and "from ..." not in text
    assert "lax.scan(step" in text      # the recurrent form


# ---------------------------------------------------------------------
# the arithmetic of the per-layer metrics
# ---------------------------------------------------------------------
def test_the_issues_count_of_parameters_state_and_cache():
    c = _published()["sizes"]
    assert round(S.layer_params(c, "L") / 1e6, 2) == 215.57
    assert round(S.layer_params(c, "F") / 1e6, 2) == 185.81
    assert round(S.top_params(c) / 1e6, 1) == 770.7
    assert round(S.state_elements(c) * 4 / 1e6, 2) == 2.21
    assert (c["conv_kernel"] - 1) * S.conv_width(c) * 2 == 69120
    assert round(S.lane_state_bytes(c) * 6 / 1e6, 2) == 13.69
    assert S.kv_position_bytes(c) * 2 == 30720
    assert round(S.kv_position_bytes(c) * 2 * 64 * c["n_blocks"]
                 / 1e9, 2) == 7.05
    # every parameter the reference makes, in bfloat16
    n = sum(int(np.prod(shape)) for i in range(c["n_layers"])
            for shape, _ in R.layer_shapes(c, i).values())
    n += sum(int(np.prod(shape)) for shape, _ in R.top_shapes(c).values())
    assert n == 6 * S.layer_params(c, "L") + 2 * S.layer_params(c, "F") \
        + S.top_params(c)
    assert round(n / 1e6, 1) == 2435.7 and round(n * 2 / 1e9, 2) == 4.87


def test_one_tick_by_hand():
    """32 live lanes at a mean context of 3,216: the weights once, the
    state and tails read and written, the cache read."""
    c = _published()["sizes"]
    gdn = 3840 * 17340 + 5760 * 3840 + 11520 * 4
    attn = 3840 * 11520 + 3840 * 3840
    mlp = 3 * 3840 * 11008
    weights = (6 * gdn + 2 * attn + 8 * mlp + 3840 * 100352) * 2
    assert S.gdn_weights(c) == gdn and S.attention_weights(c) == attn
    assert S.tick_weight_bytes(c) == weights
    state = 6 * 2 * 32 * (30 * 96 * 192 * 4 + 3 * 11520 * 2)
    cache = 2 * 32 * 3217 * 15360
    assert S.decode_tick_min_bytes(c, 32, 3216) == weights + state + cache
    # the issue's reckoning: weights 4.1 GB, cache 3.3 GB, state 0.85
    assert round(weights / 1e9, 1) == 4.1
    assert round(cache / 1e9, 1) == 3.2 and round(state / 1e9, 2) == 0.88
    cost = S.gdn_tick_cost(c, 32)
    assert cost["bytes"] == 6 * gdn * 2 + state
    # bound by bytes
    assert shapes.roofline_seconds(cost, PEAKS) == cost["bytes"] / 819e9
    # a served token 4.2 GFLOP, 0.77 of them the head's; a prefilled
    # one, without the head, the issue's 3.33 and the attention's
    flops = S.token_flops(c, 3216)
    assert 4.1e9 < flops < 4.3e9
    prefilled = S.token_flops(c, 3216, chunked=True, head=False)
    assert prefilled == flops - 2 * 3840 * 100352 + 6 * (
        S.delta_flops(c, True) - S.delta_flops(c, False))
    assert 3.33e9 < prefilled < 3.5e9


def test_one_chunk_by_hand():
    """A chunk of 2,048 real positions through the six linear layers:
    the projections and the chunked delta rule at blocks of 64."""
    c = _published()["sizes"]
    delta = 2 * 30 * (2 * 64 * 96 + 64 * 288 // 2 + 64 * 192
                      + 3 * 96 * 192)
    assert S.delta_flops(c, True) == delta
    cost = S.gdn_chunk_cost(c, 2048, 1)
    assert cost["flops"] == 6 * 2048 * (2 * S.gdn_weights(c) + delta)
    # bound by operations
    assert shapes.roofline_seconds(cost, PEAKS) == cost["flops"] / 197e12


def test_nested_scopes_count_under_each_once():
    tick = '''
  %fusion.3 = f32[33,5760]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/while/body/olmoh.gdn/gdn.step/mul"}
  %fusion.4 = bf16[33,3840]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/while/body/olmoh.mlp/dot"}
  %copy.1 = bf16[8,8]{1,0} copy(%p), metadata={op_name="jit(step)/while/body/rms_norm"}
'''
    chunk = '''
  %fusion.3 = f32[2048,5760]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/while/body/olmoh.prefill_chunk/olmoh.gdn_chunk/gdn.chunk/mul"}
  %fusion.4 = bf16[33,3840]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/while/body/olmoh.attn/gather"}
'''
    scopes = scopes_olmoh.scopes_of([tick, chunk])
    assert scopes["fusion.3|fusion_f32_33_5760"] == "olmoh.gdn"
    assert scopes["fusion.3|fusion_f32_2048_5760"] \
        == "olmoh.prefill_chunk olmoh.gdn_chunk"
    assert scopes["fusion.4|fusion_bf16_33_3840"] == "?"
    assert not any(k.startswith("copy.1") for k in scopes)


def test_seconds_under_a_scope_do_not_take_its_namesakes(monkeypatch):
    seconds = {"olmoh.gdn": 1.0,
               "olmoh.prefill_chunk olmoh.gdn_chunk": 2.0,
               "olmoh.prefill_chunk": 4.0, "olmoh.mlp": 8.0, "?": 16.0}
    monkeypatch.setattr(scopes_olmoh, "scope_seconds", lambda obs: seconds)
    under = scopes_olmoh.under
    assert under({}, "olmoh.gdn") == 1.0
    assert under({}, "olmoh.gdn_chunk") == 2.0
    assert under({}, "olmoh.prefill_chunk") == 6.0
    obs = {"trace": {"busy_s": 40.0}}
    assert scopes_olmoh.tick_seconds(obs) == 34.0


def test_readers_read_nothing_without_a_trace_or_counters():
    obs = {"counters": {}, "trace": None, "sizes": _published()["sizes"],
           "on_chip": False, "end_to_end": {}, "observed": {},
           "peaks": None}
    for name in NEW_METRICS:
        assert harness.load_reader(name).read(obs) is None, name


def test_the_rooflines_read_one_hundred_at_the_least_time(monkeypatch):
    """A traced window whose ticks take exactly the least bytes' time,
    and whose chunks exactly their least time, reads 100%."""
    c = _published()["sizes"]
    counters = {"traced_ticks": 10, "mean_live_lanes": 30.0,
                "mean_context": 3000.0, "prefill_tokens": 9000,
                "prefill_chunks": 7}
    tick = S.decode_tick_min_bytes(c, 30.0, 3000.0) / 819e9
    step = shapes.roofline_seconds(S.gdn_tick_cost(c, 30.0), PEAKS)
    chunk = shapes.roofline_seconds(S.gdn_chunk_cost(c, 9000, 7), PEAKS)
    seconds = {"olmoh.gdn": 10 * step,
               "olmoh.prefill_chunk olmoh.gdn_chunk": chunk,
               "olmoh.prefill_chunk": 1.0}
    monkeypatch.setattr(scopes_olmoh, "scope_seconds", lambda obs: seconds)
    obs = {"counters": counters, "sizes": c, "peaks": PEAKS,
           "on_chip": True,
           "trace": {"busy_s": 10 * tick + chunk + 1.0}}
    read = {name: harness.load_reader(name).read(obs)
            for name in NEW_METRICS - {"serve_mfu.olmoh"}}
    assert read["decode_hbm_roofline.olmoh"] == pytest.approx(100.0)
    assert read["gdn_step_roofline"] == pytest.approx(100.0)
    assert read["gdn_chunk_roofline"] == pytest.approx(100.0)
    assert read["gdn_tick_share"] == pytest.approx(100 * step / tick)
    assert read["prefill_device_share.olmoh"] == pytest.approx(
        100 * (chunk + 1.0) / (10 * tick + chunk + 1.0))
