"""BENCHMARK.json against the contract's limits and against the files it
names: every cell, configuration, mix, metric and reader resolves, so a new
one is a new file plus an entry."""

import json
import re

import pytest

from chipbench import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
#: what ``reduced`` may never name: a width of the model
WIDTH = re.compile(r"(_dim|_rank|hidden_size|intermediate_size|head_size"
                   r"|n_embd|experts_per_tok)$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCH = cells.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check of 24 cells must fit into 43200 s
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(BENCH)) < 64 * 1024
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


def test_names_units_and_keys():
    for group, extra in (("end_to_end", {"bound"}),
                         ("per_layer", {"layer", "moves"})):
        for m in BENCH[group]:
            assert set(m) - {"workloads"} == {
                "name", "unit", "better", "source"} | extra, m
            assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
            assert m["source"] in SOURCES
            assert set(m.get("workloads", CELLS)) <= set(CELLS)
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert c["file"].startswith("chipbench/")
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_files(name):
    cell = cells.resolve(BENCH, name)
    # a cell states its cuts once: the file's list is the entry's, and a
    # width is never among them
    entry = next(c for c in BENCH["configs"] if c["name"] == cell.config_name)
    assert cell.config["reduced"] == entry["reduced"]
    assert len(entry["reduced"]) <= 16
    assert not any(WIDTH.search(key) for key in entry["reduced"]), entry
    # a kind of traffic is a driver of that name, whichever PR brought it
    assert (cells.HERE / "drivers" / f"{cell.traffic['kind']}.py").is_file()
    assert cells.load_driver(cell.traffic["kind"]).run
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for metric in cell.per_layer:
        read, args = cells.load_reader(metric["name"])
        assert callable(read) and isinstance(args, dict)
        # a per-layer metric moves an end-to-end metric of every cell that
        # reports it
        assert metric["moves"] in e2e, (metric["name"], name)
    if cell.traffic["kind"] == "train":
        assert cell.traffic["rate_metric"] in e2e


def test_layers_are_spelled_one_way():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all("\n" not in layer and len(layer) <= 200 for layer in layers)
    by_stem = {}
    for m in BENCH["per_layer"]:
        by_stem.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_stem.values()), by_stem


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        cells.resolve(BENCH, "no-such.cell")


def test_a_new_cell_is_files_plus_entries(tmp_path):
    """A later PR adds a configuration, a mix and a per-layer metric without
    touching a file that is there."""
    here = tmp_path / "chipbench"
    for sub in ("configs", "traffic", "metrics"):
        (here / sub).mkdir(parents=True)
    (here / "configs" / "m.json").write_text('{"family": "gpt2", "reduced": []}')
    (here / "traffic" / "t.json").write_text('{"kind": "train"}')
    (here / "metrics" / "x_ms.json").write_text(
        '{"reader": "span_percentile", "args": {"span": "s", "percentile": 50}}')
    bench = {
        "configs": [{"name": "m", "file": "chipbench/configs/m.json"}],
        "workloads": [{"name": "m.t", "config": "m", "traffic": "t",
                       "chips": 1}],
        "end_to_end": [{"name": "setup_s"}],
        "per_layer": [{"name": "x_ms", "workloads": ["m.t"]},
                      {"name": "other", "workloads": ["elsewhere"]}],
    }
    cell = cells.resolve(bench, "m.t", here=here)
    assert cell.traffic == {"kind": "train"}
    assert [m["name"] for m in cell.per_layer] == ["x_ms"]
    read, args = cells.load_reader("x_ms", here=here)
    assert args == {"span": "s", "percentile": 50}
