"""BENCHMARK.json against the contract's letter and against the files it
names: a later PR adds cells, configurations, traffic mixes and per-layer
metrics as files and entries only, and this is what holds them to that."""
import json
import os
import re

import pytest

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
from benchmark import harness

ROOT = harness.ROOT
MAN = harness.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in MAN["workloads"]]
E2E = {m["name"]: m for m in MAN["end_to_end"]}
LAYER_METRICS = [m["name"] for m in MAN["per_layer"]]


def _e2e_of(cell):
    return {m["name"] for m in harness.end_to_end_of(MAN, cell)}


def test_top_level_keys_and_limits():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(MAN["paths"]) <= 16 and len(MAN["command"]) <= 32
    for path in MAN["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", path)
        assert not path.startswith("/") and ".." not in path.split("/")
        assert os.path.isdir(os.path.join(ROOT, path))
    assert any(MAN["command"][1].startswith(p + "/") for p in MAN["paths"])
    assert 1 <= len(MAN["configs"]) <= 24 and 1 <= len(MAN["workloads"]) <= 24
    assert 1 <= len(MAN["end_to_end"]) <= 16
    assert 1 <= len(MAN["per_layer"]) <= 128


def test_a_full_check_fits_its_time():
    seconds = MAN["run_seconds"]
    assert isinstance(seconds, int) and 1 <= seconds <= 51
    assert (2 + 14 * 24) * (seconds + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_are_unique_and_spelled_from_the_allowed_characters():
    for section in ("configs", "workloads"):
        names = [x["name"] for x in MAN[section]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names), names
    metrics = [m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    assert all(NAME.match(n) for n in metrics), metrics
    for w in MAN["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(set(pairs)) == len(pairs)


@pytest.mark.parametrize("metric", MAN["end_to_end"] + MAN["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(metric):
    e2e = metric["name"] in E2E
    keys = {"name", "unit", "better", "source"} | \
        ({"bound"} if e2e else {"layer", "moves"})
    assert set(metric) - {"workloads"} == keys
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    if e2e:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0 < metric["bound"] <= 0.1
    else:
        assert 1 <= len(metric["layer"]) <= 200 and "\n" not in metric["layer"]
    for cell in metric.get("workloads", []):
        assert cell in CELLS


def test_setup_s_is_an_end_to_end_metric_of_every_cell():
    assert "setup_s" in E2E and "workloads" not in E2E["setup_s"]


@pytest.mark.parametrize("config", MAN["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert 1 <= len(config["why"]) <= 200 and 1 <= len(config["source"]) <= 200
    assert any(config["file"].startswith(p + "/") for p in MAN["paths"])
    assert len(config["reduced"]) <= 16
    assert all(NAME.match(k) for k in config["reduced"])
    widths = re.compile(r"((hidden|intermediate|latent|state|proj\w*)_size|"
                        r"_dim$|_rank$|head_size|expan|per_tok|n_embd|n_inner|"
                        r"d_model|d_ff)")
    assert not [k for k in config["reduced"] if widths.search(k)]
    with open(os.path.join(ROOT, config["file"])) as f:
        body = json.load(f)
    assert body["source"] == config["source"]
    assert sorted(body["changed"]) == sorted(config["reduced"])
    assert body["reduced"] == config["reduced"]
    assert all(k in body for k in config["reduced"])
    assert {"family", "assumed", "recipe", "deployment"} <= set(body)
    assert any(w["config"] == config["name"] for w in MAN["workloads"])
    files = [c["file"] for c in MAN["configs"]]
    assert files.count(config["file"]) == 1
    # its family and the plain reference beside it resolve by name
    family = harness.load_module("families", body["family"])
    assert callable(family.build) and callable(family.step_flops)
    assert callable(family.reference.param_specs)


@pytest.mark.parametrize("name", CELLS)
def test_cell_entry_and_files_resolve_by_name(name):
    entry = next(w for w in MAN["workloads"] if w["name"] == name)
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert entry["chips"] in (1, 4) and 1 <= len(entry["why"]) <= 200
    cell = harness.load_cell(name, MAN)   # config, traffic and cell files
    assert callable(harness.load_module("loops", cell["loop"]).run)
    objective = harness.load_module("traffic", cell["traffic"]["objective"])
    assert callable(objective.rows)
    mesh_chips = 1
    for size in (cell["mesh"] or {}).values():
        mesh_chips *= size
    assert mesh_chips == entry["chips"]
    assert cell["traffic"]["batch"] % entry["chips"] == 0
    # a cell file holds what BENCHMARK.json has no key for, and no knob
    assert set(cell) - set(entry) <= {"loop", "mesh", "kernels", "limits",
                                      "sizing"}
    # ``kernels``: prefixes of the program's pallas_call names the compiled
    # step must hold; a name of the contract's characters, none twice
    kernels = cell.get("kernels", [])
    assert all(NAME.match(k) for k in kernels)
    assert len(set(kernels)) == len(kernels)
    assert set(cell["limits"]) == {"loss_gap_1", "loss_gap_2", "loss_gap_3",
                                   "grad_norm_gap", "grad_rel_err",
                                   "delta_norm_gap"}
    # every cell reports setup_s, another end-to-end metric, a per-layer one
    e2e = _e2e_of(name)
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.layer_readers(MAN, cell)


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = [w for w in MAN["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("name", LAYER_METRICS)
def test_per_layer_metric_has_a_reader_that_agrees_with_the_manifest(name):
    entry = next(m for m in MAN["per_layer"] if m["name"] == name)
    reader = harness.load_module("layer_metrics", name)
    assert callable(reader.read)
    assert (reader.LAYER, reader.UNIT, reader.MOVES) == \
        (entry["layer"], entry["unit"], entry["moves"])
    assert entry["moves"] in E2E
    # which cells report it is the reader's rule on the cell's own fields;
    # the entry's ``workloads`` key, where there is one, repeats the outcome
    by_rule = [c for c in CELLS if name in {
        m["name"] for m, _ in harness.layer_readers(
            MAN, harness.load_cell(c, MAN))}]
    assert by_rule
    if "workloads" in entry:
        assert sorted(entry["workloads"]) == sorted(by_rule)
    else:
        assert by_rule == [c for c in CELLS if entry["moves"] in _e2e_of(c)]


def test_every_file_under_paths_is_named_from_the_allowed_characters():
    for path in MAN["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                if f.endswith(".pyc"):
                    continue
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), (folder, f)
