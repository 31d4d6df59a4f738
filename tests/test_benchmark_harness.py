"""Benchmark harness tests (parity model: reference benchmark/fluid/
fluid_benchmark.py CLI semantics — per-pass examples/sec)."""
import numpy as np

from benchmark.fluid_benchmark import MODELS, parse_args, run_benchmark


def _args(**kw):
    argv = []
    for k, v in kw.items():
        if isinstance(v, bool):
            if v:
                argv.append(f"--{k}")
        else:
            argv += [f"--{k}", str(v)]
    args = parse_args(argv)
    if "batch_size" not in kw:
        args.batch_size = 8
    if "skip_batch_num" not in kw:
        args.skip_batch_num = 1
    if "iterations" not in kw:
        args.iterations = 2
    return args


class TestBenchmarkHarness:
    def test_model_registry_complete(self):
        # the reference benchmark model set must all be present
        for name in ("mnist", "resnet", "vgg", "se_resnext",
                     "stacked_dynamic_lstm", "machine_translation",
                     "transformer"):
            assert name in MODELS

    def test_mnist_speed_positive(self):
        res = run_benchmark(_args(model="mnist"))
        assert len(res) == 1
        assert res[0]["speed"] > 0
        assert res[0]["unit"] == "examples/sec"
        assert np.isfinite(res[0]["loss"])

    def test_lstm_counts_tokens(self):
        res = run_benchmark(_args(model="stacked_dynamic_lstm",
                                  batch_size=4))
        assert res[0]["unit"] == "tokens/sec"
        assert res[0]["speed"] > 0

    def test_parallel_mode_runs(self):
        res = run_benchmark(_args(model="mnist", parallel=True,
                                  batch_size=16))
        assert res[0]["speed"] > 0

    def test_multi_pass(self):
        res = run_benchmark(_args(model="word2vec", pass_num=2))
        assert len(res) == 2


    def test_zero_iterations_rejected(self):
        import pytest

        with pytest.raises(ValueError):
            run_benchmark(_args(model="word2vec", iterations=0))


class TestMeasurementHarness:
    """benchmark/harness.py: the interleaved best-of-N / fail-fast /
    telemetry scaffolding the seven bench configs share (extracted
    from their ad-hoc copies; no measured-number changes — these
    tests pin the selection semantics the configs relied on)."""

    def test_interleave_rounds_preserves_leg_order(self):
        from benchmark.harness import interleave_rounds

        calls = []
        legs = [("a", lambda: calls.append("a") or {"wall_s": 1.0}),
                ("b", lambda: calls.append("b") or {"wall_s": 2.0})]
        rounds = interleave_rounds(legs, rounds=3)
        # INTERLEAVED: a,b,a,b,a,b — never a,a,a,b,b,b (sequential
        # best-of-N lands whole legs in different throttle windows)
        assert calls == ["a", "b"] * 3
        assert len(rounds) == 3 and all(
            set(r) == {"a", "b"} for r in rounds)

    def test_best_leg_and_paired_ratio(self):
        from benchmark.harness import (best_leg, interleave_rounds,
                                       paired_ratio_max)

        data = iter([
            {"wall_s": 4.0, "tok_s": 100.0},   # a round 1
            {"wall_s": 1.0, "tok_s": 50.0},    # b round 1
            {"wall_s": 2.0, "tok_s": 400.0},   # a round 2
            {"wall_s": 3.0, "tok_s": 100.0},   # b round 2
        ])
        rounds = interleave_rounds(
            [("a", lambda: next(data)), ("b", lambda: next(data))],
            rounds=2)
        assert best_leg(rounds, "a")["wall_s"] == 2.0
        # PAIRED ratios: round1 100/50=2, round2 400/100=4 — the max
        # is 4, NOT best(a)/best(b) = 400/50 = 8 (window luck)
        assert paired_ratio_max(rounds, "a", "b") == 4.0

    def test_best_of_scalar(self):
        from benchmark.harness import best_of

        vals = iter([3.0, 9.0, 5.0])
        assert best_of(lambda: next(vals), 3) == 9.0

    def test_paired_median_ab_alternates_and_medians(self):
        from benchmark.harness import paired_median_ab

        modes_seen = []
        vals = {"a": iter([10.0, 20.0, 30.0]),
                "b": iter([10.0, 10.0, 10.0])}

        def run_leg():
            return next(vals[modes_seen[-1]]), None

        med, ratios, legs = paired_median_ab(
            run_leg, modes_seen.append, "a", "b", 3)
        # back-to-back pairs with alternating order per rep
        assert modes_seen == ["a", "b", "b", "a", "a", "b"]
        assert ratios == [1.0, 2.0, 3.0] and med == 2.0
        assert len(legs["a"]) == len(legs["b"]) == 3

    def test_write_bench_self_guards_schema(self, tmp_path,
                                            monkeypatch):
        import json

        import pytest

        from benchmark import harness

        monkeypatch.setattr(harness, "BENCH_DIR", str(tmp_path))
        res = harness.write_bench_self(
            "BENCH_SELF_t.json", {"metric": "m", "value": 1})
        assert "telemetry" in res  # r12 contract: every record
        on_disk = json.loads(
            (tmp_path / "BENCH_SELF_t.json").read_text())
        assert set(on_disk) == {"metric", "value", "telemetry"}
        # same schema: rewrites fine
        harness.write_bench_self("BENCH_SELF_t.json",
                                 {"metric": "m", "value": 2})
        # dropped field: the refactor-thins-the-record failure mode
        with pytest.raises(AssertionError, match="schema drifted"):
            harness.write_bench_self("BENCH_SELF_t.json",
                                     {"metric": "m"})
        # intentional evolution: explicit opt-in
        harness.write_bench_self("BENCH_SELF_t.json", {"metric": "m"},
                                 allow_schema_change=True)

    def test_bench_py_routes_through_harness(self):
        # the seven configs' scaffolding is the ONE implementation:
        # bench.py's module-level helpers must BE the harness's
        import bench
        from benchmark import harness

        assert bench._telemetry_snapshot is harness.telemetry_snapshot
        assert bench._write_bench_self is harness.write_bench_self
        # one process per chip: no child probes the backend
        assert not hasattr(harness, "probe_backend")

    def test_committed_records_parse_with_schema_keys(self):
        # every committed BENCH_SELF record the configs would diff
        # against parses and carries the r12 telemetry key (the
        # schema guard compares against these files)
        import glob
        import json
        import os

        from benchmark.harness import BENCH_DIR

        # r12 introduced the telemetry key; every LATER record must
        # carry it (r11 and earlier are pre-contract history — listed
        # explicitly so records from r20 on are never silently
        # excluded from the check)
        pre_contract = {f"BENCH_SELF_r{n:02d}.json"
                        for n in range(0, 12)}
        recent = [p for p in glob.glob(
            os.path.join(BENCH_DIR, "BENCH_SELF_r*.json"))
            if os.path.basename(p) not in pre_contract]
        assert recent, "committed BENCH_SELF records missing"
        for p in recent:
            with open(p) as f:
                rec = json.load(f)
            assert "telemetry" in rec, p


class TestTrendSentinel:
    """benchmark/trend.py: the perf-trend drift gate over the
    committed BENCH_SELF history (the analysis_baseline.json
    discipline applied to the measured record). The fast lane runs
    the REAL gate in-process: the committed bench_trend.json must be
    current, and a synthetically regressed headline must fail."""

    def test_committed_store_is_current(self):
        # the tier-1-adjacent assertion: `python bench.py trend` on
        # this checkout is green — the store matches the files
        from benchmark import trend

        records = trend.build_records()
        store = trend.load_store()
        assert store is not None, \
            "bench_trend.json missing; run bench.py trend --write-trend"
        regressions, stale = trend.diff_against_store(records, store)
        assert not regressions, regressions
        assert not stale, stale

    def _tmp_history(self, tmp_path):
        import json
        import os
        import shutil

        from benchmark import trend
        from benchmark.harness import BENCH_DIR

        for f in os.listdir(BENCH_DIR):
            if f.startswith("BENCH_SELF_r") and f.endswith(".json"):
                shutil.copy(os.path.join(BENCH_DIR, f), tmp_path)
        store_path = str(tmp_path / "bench_trend.json")
        trend.write_store(path=store_path, bench_dir=str(tmp_path))
        return trend, json, store_path

    def test_synthetic_headline_regression_fails_loudly(self, tmp_path):
        trend, json, store_path = self._tmp_history(tmp_path)
        p = tmp_path / "BENCH_SELF_r13.json"
        rec = json.loads(p.read_text())
        rec["value"] = rec["value"] * 0.1  # collapse the headline
        p.write_text(json.dumps(rec))
        regs, stale = trend.diff_against_store(
            trend.build_records(str(tmp_path)),
            trend.load_store(store_path))
        assert any("REGRESSED" in r for r in regs), (regs, stale)
        assert trend.check(path=store_path,
                           bench_dir=str(tmp_path)) == 2

    def test_lost_parity_flag_is_a_regression(self, tmp_path):
        trend, json, store_path = self._tmp_history(tmp_path)
        p = tmp_path / "BENCH_SELF_r14.json"
        rec = json.loads(p.read_text())
        rec["token_parity_vs_whole_loop"] = False
        p.write_text(json.dumps(rec))
        regs, _ = trend.diff_against_store(
            trend.build_records(str(tmp_path)),
            trend.load_store(store_path))
        assert any("parity" in r for r in regs), regs

    def test_steady_state_compiles_appearing_is_a_regression(
            self, tmp_path):
        trend, json, store_path = self._tmp_history(tmp_path)
        p = tmp_path / "BENCH_SELF_r13.json"
        rec = json.loads(p.read_text())
        rec["steady_state_compiles"] = 3
        p.write_text(json.dumps(rec))
        regs, _ = trend.diff_against_store(
            trend.build_records(str(tmp_path)),
            trend.load_store(store_path))
        assert any("steady-state" in r for r in regs), regs

    def test_new_record_is_stale_until_appended(self, tmp_path):
        trend, json, store_path = self._tmp_history(tmp_path)
        src = json.loads((tmp_path / "BENCH_SELF_r14.json").read_text())
        (tmp_path / "BENCH_SELF_r99.json").write_text(json.dumps(src))
        regs, stale = trend.diff_against_store(
            trend.build_records(str(tmp_path)),
            trend.load_store(store_path))
        assert not regs
        assert any("BENCH_SELF_r99" in s and "--write-trend" in s
                   for s in stale), stale
        # the refresh appends it and goes green
        trend.write_store(path=store_path, bench_dir=str(tmp_path))
        assert trend.check(path=store_path,
                           bench_dir=str(tmp_path)) == 0

    def test_schema_drift_is_stale(self, tmp_path):
        trend, json, store_path = self._tmp_history(tmp_path)
        p = tmp_path / "BENCH_SELF_r12.json"
        rec = json.loads(p.read_text())
        rec.pop("observability_overhead")
        p.write_text(json.dumps(rec))
        _, stale = trend.diff_against_store(
            trend.build_records(str(tmp_path)),
            trend.load_store(store_path))
        assert any("schema drifted" in s for s in stale), stale

    def test_store_schema_version_guard(self, tmp_path):
        import pytest as _pytest

        trend, json, store_path = self._tmp_history(tmp_path)
        store = json.loads(open(store_path).read())
        store["schema_version"] = 99
        open(store_path, "w").write(json.dumps(store))
        with _pytest.raises(ValueError, match="schema_version"):
            trend.load_store(store_path)
        assert trend.check(path=store_path,
                           bench_dir=str(tmp_path)) == 2

    def test_headline_extraction_covers_every_era(self):
        # r10 nested dict, r11+ flat — each era's committed records
        # must yield at least one headline (the r02/r05/r06 records
        # of the retired rig were deleted in PR 21)
        from benchmark import trend

        by_round = {r["round"]: r for r in trend.build_records()}
        for rnd in (7, 9, 10, 11, 12, 13, 14):
            assert by_round[rnd]["headlines"], rnd
        # parity flags surfaced from both nesting styles
        assert any("parity" in k
                   for k in by_round[13]["parity"])
        assert any(k.endswith("steady_state_compiles")
                   for k in by_round[13]["parity"])
