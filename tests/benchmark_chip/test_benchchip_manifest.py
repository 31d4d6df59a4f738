"""BENCHMARK.json against the contract it was written to, and the rule
that every cell, configuration and metric is found by its name."""
import json
import os
import re

import pytest

from benchmark.chip import harness, traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|"
                   r"_rank$|head_size|d_model|d_inner|expansion|"
                   r"experts_per)")


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds",
                             "configs", "workloads", "end_to_end",
                             "per_layer"}
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    n_cells = 24    # what later PRs may grow the benchmark to
    budget = (2 + 14 * n_cells) * (manifest["run_seconds"] + 60) \
        + n_cells * 2 * 90 + 1200
    assert budget <= 43200
    assert 1 <= len(manifest["command"]) <= 32
    assert all(_line(w) for w in manifest["command"])
    assert 1 <= len(manifest["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in manifest["paths"])
    path = os.path.join(harness.ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024


def test_command_names_only_files_under_paths(manifest):
    for word in manifest["command"][1:]:
        if os.path.exists(os.path.join(harness.ROOT, word)):
            assert any(word.startswith(p + "/")
                       for p in manifest["paths"]), word


def test_every_name_and_unit_uses_allowed_characters(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"),
                          entry["name"]))
    for cell in manifest["workloads"]:
        assert NAME.match(cell["config"]) and NAME.match(cell["traffic"])
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    metric_names = [n for is_metric, n in names if is_metric]
    assert len(metric_names) == len(set(metric_names))
    for group in ("configs", "workloads"):
        got = [e["name"] for e in manifest[group]]
        assert len(got) == len(set(got))


def test_entries_have_exactly_the_contracts_keys(manifest):
    for cfg in manifest["configs"]:
        assert set(cfg) == {"name", "source", "file", "reduced", "why"}
        assert _line(cfg["source"]) and _line(cfg["why"])
        assert len(cfg["reduced"]) <= 16
        for key in cfg["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
    for cell in manifest["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert cell["chips"] in (1, 4) and _line(cell["why"])
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert _line(m["layer"])


def test_cells_and_four_chip_share(manifest):
    cells = manifest["workloads"]
    assert 1 <= len(cells) <= 24
    pairs = [(c["config"], c["traffic"]) for c in cells]
    assert len(pairs) == len(set(pairs))
    four = sum(c["chips"] == 4 for c in cells)
    assert four <= max(1, len(cells) // 4)
    used = {c["config"] for c in cells}
    assert used == {c["name"] for c in manifest["configs"]}


def test_setup_s_is_reported_everywhere(manifest):
    setup = [m for m in manifest["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup[0]["bound"] <= 0.1


def test_every_file_is_found_by_its_name(manifest):
    files = set()
    for cfg in manifest["configs"]:
        assert any(cfg["file"].startswith(p + "/")
                   for p in manifest["paths"])
        assert cfg["file"] not in files
        files.add(cfg["file"])
        loaded = harness.load_config(manifest, cfg["name"])
        assert loaded["name"] == cfg["name"]
        for key in cfg["reduced"]:
            assert key in loaded["sizes"], key
            assert key in loaded["reduced_why"], key
        driver = os.path.join(harness.HERE, "drivers",
                              loaded["driver"] + ".py")
        reference = os.path.join(harness.HERE, "reference",
                                 loaded["reference"] + ".py")
        assert os.path.exists(driver) and os.path.exists(reference)
    for cell in manifest["workloads"]:
        assert traffic.load(cell["traffic"])["kind"]
    for m in manifest["per_layer"]:
        assert callable(harness.load_reader(m["name"]).read)


def test_files_under_paths_are_named_from_a_names_characters(manifest):
    for p in manifest["paths"]:
        for base, dirs, names in os.walk(os.path.join(harness.ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for n in names:
                if n.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(base, n),
                                      harness.ROOT)
                assert PATH.match(rel), rel


def test_each_cell_reports_what_the_contract_asks(manifest):
    e2e_names = {m["name"] for m in manifest["end_to_end"]}
    layers = {}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e_names, m
        layers.setdefault(m["layer"], []).append(m["name"])
    for cell in manifest["workloads"]:
        e2e = {m["name"] for m in
               harness.cell_metrics(manifest, cell, "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2, cell["name"]
        per = harness.cell_metrics(manifest, cell, "per_layer")
        assert per, cell["name"]
        for m in per:
            assert m["moves"] in e2e, (cell["name"], m["name"])
        assert any("mfu" in m["name"] for m in per), cell["name"]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        for w in m.get("workloads", []):
            assert w in {c["name"] for c in manifest["workloads"]}, w


def test_no_end_to_end_metric_is_a_median_of_pieces(manifest):
    for m in manifest["end_to_end"]:
        assert "p50" not in m["name"] and "gap" not in m["name"]


def test_limits_are_stated_in_the_configurations(manifest):
    for cfg in manifest["configs"]:
        loaded = harness.load_config(manifest, cfg["name"])
        limits = loaded["sizes"]["limits"]
        assert limits and all(
            isinstance(v, float) and 0 < v < 10 for v in limits.values())
        assert json.dumps(loaded["rehearsal"])
