"""BENCHMARK.json against the benchmark's rules: names, units, keys,
and that every entry finds its files by name."""

import json
import os
import re

import pytest

from benchmark import reference, run

with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
E2E = {m["name"]: m for m in SPEC["end_to_end"]}
CELLS = {w["name"]: w for w in SPEC["workloads"]}


def line_ok(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(SPEC["command"]) <= 32 and all(map(line_ok, SPEC["command"]))
    assert SPEC["paths"] == ["benchmark"]
    assert os.path.getsize(os.path.join(run.ROOT, "BENCHMARK.json")) < 65536


def test_names_units_and_entry_keys():
    names = []
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line_ok(c["source"])
        assert line_ok(c["why"]) and c["file"].startswith("benchmark/")
        assert all(NAME.match(k) for k in c["reduced"])
        assert os.path.exists(os.path.join(run.ROOT, c["file"]))
        names.append(c["name"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] == 1 and line_ok(w["why"])
        names.append(w["name"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                           "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                           "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert line_ok(m["layer"])
        if "_roofline" in m["name"] or m["name"].startswith("device_idle"):
            assert m["unit"] == "%"
    assert len(names) == len(set(names))
    assert E2E["setup_s"]["bound"] <= 0.25


def _reports(cell, metric):
    return cell in metric.get("workloads", CELLS)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_every_cell_finds_its_files_and_metrics(cell):
    w = CELLS[cell]
    assert w["config"] in {c["name"] for c in SPEC["configs"]}
    assert os.path.exists(os.path.join(run.HERE, "traffic",
                                       w["traffic"] + ".json"))
    loaded = run.load_cell(cell)
    assert os.path.exists(os.path.join(run.HERE, "drivers",
                                       loaded.traffic["driver"] + ".py"))
    if loaded.traffic["driver"] == "rados":
        assert "osd_device" in loaded.config["cluster"]
    reference.Code(loaded.config["profile"])
    e2e = [m for m in SPEC["end_to_end"] if _reports(cell, m)]
    layer = [m for m in SPEC["per_layer"] if _reports(cell, m)]
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert layer
    for m in e2e + layer:
            assert os.path.exists(os.path.join(run.HERE, "metrics",
                                           m["name"] + ".py"))


def test_moves_is_reported_where_the_metric_is():
    for m in SPEC["per_layer"]:
        assert m["moves"] in E2E and m["moves"] != "setup_s"
        for cell in m["workloads"]:
            assert cell in CELLS and _reports(cell, E2E[m["moves"]])


def test_every_config_is_used_and_one_layer_name_per_layer():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    layers = {m["layer"] for m in SPEC["per_layer"]}
    with open(os.path.join(run.ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert "| %s |" % layer in perf


def test_runner_names_no_cell():
    with open(os.path.join(run.HERE, "run.py")) as f:
        src = f.read()
    for word in list(CELLS) + [c["name"] for c in SPEC["configs"]] + \
            [w["traffic"] for w in SPEC["workloads"]] + \
            [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]:
        assert not re.search(r"(?<![\w.])%s(?![\w])" % re.escape(word),
                             src), word


def test_check_fits_the_time_limit_at_24_cells():
    per_run = SPEC["run_seconds"] + 60
    assert (2 + 14 * 24) * per_run + 24 * 2 * 90 + 1200 <= 43200
