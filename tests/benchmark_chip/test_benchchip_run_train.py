"""The command, end to end, on the training cells at their rehearsal
sizes on the CPU; the chip path without a chip; the faults a training
cell can have, each planted under the timed path."""
import os
import shutil

import pytest

from benchchip_util import (REPO, RUN, cell_args, planted, python,
                            result_line)

COUNTS = {"cache_hits_at_setup", "compiles_in_window.train"}


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_end_to_end(trace):
    workload, chips = "train_base_s256", 1
    proc = python([RUN] + cell_args(workload, trace) + ["--rehearse"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = result_line(proc.stdout)
    assert res is not None, proc.stdout[-2000:]
    assert res["correct"] is True, proc.stderr[-2000:]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == chips
    # counts only: no time, rate or share of a device from the CPU
    assert set(res["metrics"]) == (COUNTS if trace else set())
    assert "busy_s" not in res["device"] and "breakdown" not in res
    names = [r["name"] for r in res["compared"]]
    assert names[:3] == ["loss_gap_step1", "loss_gap_step2",
                         "loss_gap_step3"]
    assert {"grad_norm_gap", "update_norm_gap"} <= set(names)
    # each number compared is printed beside its limit, last on stderr
    tail = proc.stderr.strip().splitlines()[-len(names):]
    assert all(line.startswith("compared ") and "(limit " in line
               for line in tail)
    assert list(res)[-1] == "compared"
    if trace:
        assert res["metrics"]["compiles_in_window.train"]["value"] == 0


def test_chip_path_fails_without_a_tpu():
    proc = python([RUN] + cell_args("train_base_s256", 0))
    assert proc.returncode != 0
    assert "not 'tpu'" in proc.stderr
    assert result_line(proc.stdout) is None


def test_unknown_workload_fails():
    proc = python([RUN] + cell_args("no_such_cell", 0))
    assert proc.returncode != 0 and result_line(proc.stdout) is None


def test_bare_directory_prints_no_result(tmp_path):
    """Only BENCHMARK.json and the files under `paths`: no program."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in ("benchmark/chip", "tests/benchmark_chip"):
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = python([str(tmp_path / "benchmark/chip/run.py")]
                  + cell_args("train_base_s256", 0), cwd=str(tmp_path))
    assert proc.returncode != 0
    assert result_line(proc.stdout) is None


STATE_UNCHANGED = """
import numpy as np
_step = train.Trainer.step
def step(self, feed):
    names = list(self.scope.local_var_names())
    # copies on the host: the step donates the buffers it updates
    before = {n: np.array(self.scope._get(n)) for n in names}
    loss = _step(self, feed)
    for n, v in before.items():
        self.scope._set(n, v)
    return loss
train.Trainer.step = step
"""

HALF_BATCH = """
_step = train.Trainer.step
def step(self, feed):
    half = len(feed["label"]) // 2
    return _step(self, {k: v[:half] for k, v in feed.items()})
train.Trainer.step = step
"""

FAULTS = {
    "state_unchanged": (STATE_UNCHANGED, "train_base_s256",
                        "update_norm_gap"),
    "half_batch": (HALF_BATCH, "train_base_s256", "grad_norm_gap"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_reads_not_correct(fault):
    plant, workload, fails = FAULTS[fault]
    proc = planted(plant, workload)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = result_line(proc.stdout)
    assert res is not None and res["correct"] is False
    over = {r["name"] for r in res["compared"]
            if not r["value"] <= r["limit"]}
    assert fails in over, res["compared"]
