"""The command, end to end, on the serving cells at their rehearsal
sizes on the CPU, and a served token altered where it is produced."""
import pytest

from benchchip_util import RUN, cell_args, planted, python, result_line

COUNTS = {"cache_hits_at_setup", "compiles_in_window.serve",
          "tokens_per_dispatch", "prefix_hit_share", "radix_admit_share"}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", ["serve_big_repeat",
                                      "serve_big_cold"])
def test_cell_rehearses_end_to_end(workload, trace):
    proc = python([RUN] + cell_args(workload, trace) + ["--rehearse"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = result_line(proc.stdout)
    assert res is not None, proc.stdout[-2000:]
    assert res["correct"] is True, proc.stderr[-2000:]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"] == {"platform": "cpu", "kind": "cpu",
                             "count": 1, "memory_peak_bytes": None}
    assert set(res["metrics"]) == (COUNTS if trace else set())
    assert "breakdown" not in res
    names = [r["name"] for r in res["compared"]]
    assert names == ["served_logit_gap", "served_wide_gap_share",
                     "stream_equals_row", "no_request_failed"]
    assert list(res)[-1] == "compared"
    if trace:
        m = {k: v["value"] for k, v in res["metrics"].items()}
        assert m["compiles_in_window.serve"] == 0
        if workload == "serve_big_cold":
            assert m["prefix_hit_share"] == 0
            assert m["radix_admit_share"] == 0
        else:
            # repeated prompts find the table, and some the radix memo
            assert m["prefix_hit_share"] > 20
            assert 0 < m["radix_admit_share"] <= m["prefix_hit_share"]


TOKEN_ALTERED = """
import numpy as np
from paddle_tpu.inference import serving
_deliver = serving.ContinuousGenerationServer._deliver_stream
_cycle = serving.ContinuousGenerationServer._cycle
# the token buffer the device hands back, altered before the scheduler
# reads it: every lane's third position takes its neighbour's id
def run_altered(prepared):
    run = prepared.run
    def altered(feed, return_numpy=True):
        outs = list(run(feed, return_numpy=return_numpy))
        tok = np.array(outs[0])
        tok[:, 3] = np.where(tok[:, 3] > 0, (tok[:, 3] + 1) % 256, tok[:, 3])
        outs[0] = tok
        return outs
    prepared.run = altered
_build = serve.build_server
def build(c, seed):
    srv, exe, scope = _build(c, seed)
    for prepared in srv._serves.values():
        run_altered(prepared)
    return srv, exe, scope
serve.build_server = build
"""


def test_altered_token_reads_not_correct():
    proc = planted(TOKEN_ALTERED, "serve_big_cold")
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = result_line(proc.stdout)
    assert res is not None and res["correct"] is False
    over = {r["name"] for r in res["compared"]
            if not r["value"] <= r["limit"]}
    assert "served_logit_gap" in over, res["compared"]
