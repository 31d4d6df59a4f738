"""The GLM-5.2 serving cell at its rehearsal sizes on the CPU: the
command end to end, the control and the faults through the run's own
comparison, a served token altered where it is produced, the traffic
mix, the configuration file against the catalog row it copies, and the
arithmetic its per-layer metrics rest on. (tests/test_glm_moe_dsa.py
holds the program to the reference part by part.)"""
import glob
import json
import os

import numpy as np
import pytest

from benchchip_util import REPO, RUN, cell_args, python, result_line
from benchmark.chip import controls, controls_glm, harness, scopes_glm, \
    shapes, shapes_glm, traffic
from benchmark.chip.drivers import glm_serve as D
from benchmark.chip.drivers import glm_traffic
from benchmark.chip.reference import glm_moe_dsa as R

CELL, CONFIG = "serve_glm52_docs_32k", "glm-5.2-serve-ep16"
COUNTS = {"cache_hits_at_setup", "compiles_in_window.serve",
          "tokens_per_dispatch", "cached_prompt_share",
          "moe_load_imbalance.serve"}
COMPARED = ["served_logit_gap", "served_wide_gap_share",
            "stream_equals_row", "selection_flip_share",
            "routing_flip_share", "no_request_failed"]
SEED = 2 ** 31 + 17


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


def test_the_window_compiles_nothing_and_finds_the_documents(runs):
    m = {k: v["value"] for k, v in runs[1][1]["metrics"].items()}
    assert m["compiles_in_window.serve"] == 0
    # every request's document is resident: most prompt tokens are
    assert m["cached_prompt_share"] > 60
    assert m["moe_load_imbalance.serve"] >= 1
    assert m["tokens_per_dispatch"] > 1


def test_the_run_says_where_it_got_to(runs):
    err = runs[0][0].stderr
    for phase in ("server built", "both serve programs warm",
                  "documents resident", "window closed",
                  "sample held to the reference"):
        assert f"[glm_serve] {phase}" in err or phase in err


@pytest.fixture(scope="module")
def readings(runs):
    assert runs[0][1] is not None, runs[0][0].stderr[-2000:]
    c = controls._sizes(CONFIG, rehearse=True)
    seed, sample = D.load_sample(runs["sample"])
    assert seed == SEED
    return controls_glm.serve_controls(c, seed, sample)


def test_the_saved_sample_reads_as_the_run_read_it(runs, readings):
    mine = {r["name"]: r["value"] for r in runs[0][1]["compared"]}
    for name, value in readings["program"].items():
        if name != "correct":
            assert value == pytest.approx(mine[name], abs=1e-6)
    assert readings["program"]["correct"] is True


@pytest.mark.parametrize("what", sorted(controls_glm.VARIANTS))
def test_control_and_faults_read_not_correct(readings, what):
    assert readings[what]["correct"] is False, readings[what]


def test_each_fault_fails_by_the_number_that_is_for_it(readings):
    c = controls._sizes(CONFIG, rehearse=True)["limits"]
    assert readings["fault_selection_left_out"][
        "selection_flip_share"] > 5 * c["selection_flip_share"]
    assert readings["fault_shared_selects_itself"][
        "selection_flip_share"] > c["selection_flip_share"]
    assert readings["fault_shared_expert_left_out"][
        "served_wide_gap_share"] > 5 * c["served_wide_gap_share"]
    assert readings["control_fp8"]["routing_flip_share"] \
        > c["routing_flip_share"]


TOKEN_ALTERED = """
import numpy as np
from benchmark.chip.drivers import glm_serve
_build = glm_serve.build_server
def build(c, seed):
    srv, exe, scope = _build(c, seed)
    for prepared in srv._serves.values():
        run = prepared.run
        def altered(feed, return_numpy=True, run=run):
            outs = list(run(feed, return_numpy=return_numpy))
            tok = np.array(outs[0])
            tok[:, 3] = np.where(tok[:, 3] > 0, (tok[:, 3] + 1) % 256,
                                 tok[:, 3])
            outs[0] = tok
            return outs
        prepared.run = altered
    return srv, exe, scope
glm_serve.build_server = build
"""


def test_altered_token_reads_not_correct():
    code = ("import sys; sys.path.insert(0, %r)\n"
            "from benchmark.chip import run\n%s\n"
            "sys.exit(run.main(%r))"
            % (REPO, TOKEN_ALTERED,
               cell_args(CELL, 0, seed=5) + ["--rehearse"]))
    proc = python(["-c", code])
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = result_line(proc.stdout)
    assert res is not None and res["correct"] is False
    over = {r["name"] for r in res["compared"]
            if not r["value"] <= r["limit"]}
    assert over & {"served_wide_gap_share", "served_logit_gap"}, \
        res["compared"]


def test_the_parent_has_no_such_cell():
    """What a tree without this cell does with its name: it ends at
    once with another code than 0 and no result line."""
    proc = python([RUN] + cell_args("serve_glm52_docs_64k", 0))
    assert proc.returncode != 0
    assert result_line(proc.stdout) is None
    assert "no workload" in proc.stderr


# ---------------------------------------------------------------------
# the traffic mix
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def spec():
    return traffic.load("shared_docs_zipf")


def test_the_mix_is_a_data_file_of_a_known_kind(spec):
    assert spec["kind"] == "closed_loop" and spec["callers"] == 32
    assert sum(n * k for n, k in spec["documents"]) == 294912
    assert [n for n, _ in spec["question_tokens"]] == [64, 256, 1024]


def test_every_stretch_holds_every_choice_in_its_number(spec):
    c = {"vocab": 19360}
    small = {**spec, "documents": [[64, 4], [96, 4], [128, 4]],
             "max_requests": 960}
    mix = glm_traffic.SharedDocs(3, small, c)
    for a in range(0, 960, 96):
        q = mix.q_len[a:a + 96]
        assert abs((q == 64).sum() - 48) <= 1
        assert abs((q == 256).sum() - 29) <= 1
        assert abs((q == 1024).sum() - 19) <= 1
        new = mix.max_new[a:a + 96]
        assert (new == 128).sum() == 48 and (new == 64).sum() == 24
        counts = np.bincount(mix.doc_of[a:a + 96], minlength=12)
        assert counts.max() >= 30 and counts.min() >= 2


def test_the_seed_says_what_is_said_and_not_which_or_how_long(spec):
    c = {"vocab": 19360}
    small = {**spec, "documents": [[64, 4], [96, 4], [128, 4]],
             "max_requests": 200}
    a, b = (glm_traffic.SharedDocs(s, small, c) for s in (1, 2 ** 31 + 5))
    assert (a.doc_of == b.doc_of).all() and (a.q_len == b.q_len).all()
    assert (a.max_new == b.max_new).all()
    assert [len(d) for d in a.docs] == [len(d) for d in b.docs]
    assert not (a.docs[0] == b.docs[0]).all()
    pa, na, ca, da = a.next_request()
    pb, nb, cb, db = b.next_request()
    assert (len(pa), na, ca, da) == (len(pb), nb, cb, db)
    assert (pa[:ca] == a.docs[da]).all() and not (pa == pb).all()
    assert pa.min() >= 3 and pa.max() < 19360


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
            if '"GLM-5.2"' in line:
                row = json.loads(line)
    cfg = _published()
    for key, value in row["config"].items():
        assert cfg[key] == value, key
    assert cfg["source_url"] == row["source_url"]


def test_every_width_is_the_published_one():
    cfg = _published()
    s = cfg["sizes"]
    for mine, theirs in (
            ("d_model", "hidden_size"), ("n_heads", "num_attention_heads"),
            ("q_lora_rank", "q_lora_rank"),
            ("kv_lora_rank", "kv_lora_rank"),
            ("qk_nope_head_dim", "qk_nope_head_dim"),
            ("qk_rope_head_dim", "qk_rope_head_dim"),
            ("v_head_dim", "v_head_dim"),
            ("index_n_heads", "index_n_heads"),
            ("index_head_dim", "index_head_dim"),
            ("index_topk", "index_topk"), ("d_dense", "intermediate_size"),
            ("d_expert", "moe_intermediate_size"),
            ("n_experts", "n_routed_experts"),
            ("top_k", "num_experts_per_tok"),
            ("n_shared_experts", "n_shared_experts"),
            ("routed_scaling", "routed_scaling_factor"),
            ("norm_eps", "rms_norm_eps")):
        assert s[mine] == cfg[theirs], mine
    assert s["rope_theta"] == cfg["rope_parameters"]["rope_theta"]
    # published layers 2 to 6: one dense layer, a whole indexer period
    assert s["indexer_types"] == cfg["indexer_types"][2:7]
    assert cfg["mlp_layer_types"][2:7] == ["dense"] + ["sparse"] * 4
    assert set(cfg["reduced_why"]) == {"n_layers", "n_dense_layers",
                                       "experts_held", "vocab"}
    assert s["vocab"] * 8 == cfg["vocab_size"]
    assert s["experts_held"] * 16 == cfg["n_routed_experts"]
    assert len(cfg["assumed"]) >= 3 and "deployment" in cfg
    assert set(s["limits"]) == set(cfg["rehearsal"]["limits"]) == {
        "served_logit_gap", "served_wide_gap_share",
        "selection_flip_share", "routing_flip_share"}


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(harness.HERE, "reference", "glm_moe_dsa.py")
    with open(path) as f:
        text = f.read()
    assert "import paddle_tpu" not in text
    assert "from paddle_tpu" not in text and "from ..." not in text


# ---------------------------------------------------------------------
# the arithmetic of the per-layer metrics
# ---------------------------------------------------------------------
def test_the_issues_count_of_parameters_and_cache():
    c = _published()["sizes"]
    assert round(shapes_glm.attention_weights(c) / 1e6, 1) == 165.0
    assert round(shapes_glm.indexer_weights(c) / 1e6, 1) == 9.4
    assert round(shapes_glm.gated_weights(c["d_model"],
                                          c["d_expert"]) / 1e6, 1) == 37.7
    assert shapes_glm.cell_bytes(c) == 6272
    assert round(shapes_glm.cell_bytes(c) * c["n_blocks"]
                 * c["block_size"] / 1e9, 2) == 3.29
    # every parameter the reference makes, in bfloat16
    n = sum(int(np.prod(shape)) for i in range(c["n_layers"])
            for shape, _ in R.layer_shapes(c, i).values())
    n += sum(int(np.prod(shape)) for shape, _ in R.top_shapes(c).values())
    assert round(n * 2 / 1e9, 1) == 7.8


def test_a_ticks_least_bytes_are_mostly_weights():
    c = _published()["sizes"]
    all_hit = shapes_glm.tick_weight_bytes(c, c["experts_held"])
    none_hit = shapes_glm.tick_weight_bytes(c, 0)
    assert 7.0e9 < all_hit < 8.0e9 and 2.5e9 < none_hit < 3.5e9
    need = shapes_glm.decode_tick_min_bytes(c, 32, 24576, 2048, 10)
    keys = shapes_glm.indexer_tick_cost(c, 32, 24576)["bytes"]
    rows = shapes_glm.sparse_attention_tick_cost(c, 32, 2048)["bytes"]
    assert 0.38e9 < keys < 0.46e9 and 0.37e9 < rows < 0.39e9
    assert need > shapes_glm.tick_weight_bytes(c, 10) + rows
    # attending the whole context would read twelve times the rows
    whole = shapes_glm.sparse_attention_tick_cost(c, 32, 24576)["bytes"]
    assert whole == 12 * rows


def test_a_tokens_operations_grow_with_context_and_selection():
    c = _published()["sizes"]
    short = shapes_glm.token_flops(c, 1024, 1024, 0.5)
    deep = shapes_glm.token_flops(c, 32768, 2048, 0.5)
    assert 2.0e9 < short < deep < 6.0e9
    assert deep - short == pytest.approx(
        2 * shapes_glm.indexer_score_flops(c, 32768 - 1024)
        + 5 * shapes_glm.attention_flops(c, 1024))


def test_scopes_are_joined_by_name_and_shape_over_modules():
    tick = '''
  %fusion.3 = bf16[33,6144]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/while/body/glm.indexer/dot_general"}
  %fusion.4 = f32[33,2048]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/while/body/glm.moe/glm.moe.route/top_k"}
  %copy.1 = bf16[8,8]{1,0} copy(%p), metadata={op_name="jit(step)/while/body/rms_norm"}
'''
    chunk = '''
  %fusion.3 = bf16[64,6144]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/while/body/glm.prefill_chunk/glm.indexer/dot_general"}
  %fusion.4 = f32[33,2048]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/while/body/glm.sparse_attn/gather"}
'''
    scopes = scopes_glm.scopes_of([tick, chunk])
    assert scopes["fusion.3|fusion_bf16_33_6144"] == "glm.indexer"
    assert scopes["fusion.3|fusion_bf16_64_6144"] == "glm.prefill_chunk"
    # one name, one shape, two scopes: nobody's
    assert scopes["fusion.4|fusion_f32_33_2048"] == "?"
    assert not any(k.startswith("copy.1") for k in scopes)


def test_readers_read_nothing_without_a_trace_or_counters():
    obs = {"counters": {}, "trace": None, "sizes": _published()["sizes"],
           "on_chip": False, "end_to_end": {}, "observed": {},
           "peaks": None}
    manifest = harness.load_manifest()
    mine = [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert len(mine) == 8
    for name in mine:
        assert harness.load_reader(name).read(obs) is None, name


def test_the_rooflines_stay_under_one_hundred_at_the_least_time():
    """A traced second that is all the least bytes reads 100%."""
    c = _published()["sizes"]
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    cost = shapes_glm.sparse_attention_tick_cost(c, 32, 2048)
    assert shapes.roofline_seconds(cost, peaks) \
        == cost["bytes"] / 819e9
    cost = shapes_glm.indexer_tick_cost(c, 32, 24576)
    assert shapes.roofline_seconds(cost, peaks) \
        == cost["bytes"] / 819e9
