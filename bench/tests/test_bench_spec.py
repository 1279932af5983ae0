"""Discovery by name, and BENCHMARK.json against the benchmark's contract."""
import json
import re
import shutil
from types import SimpleNamespace

import pytest

from bench.harness import spec
from bench.harness.device import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_every_cell_is_found_with_its_files(bench):
    for w in bench["workloads"]:
        cell = spec.find_cell(w["name"], bench)
        assert cell.config["name"] == w["config"]
        assert cell.traffic["kind"] in ("train", "serve_open")
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names


def test_every_reader_states_its_unit_and_the_metric_it_moves(bench):
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert set(entries) <= {p.stem for p in (spec.BENCH_DIR / "metrics")
                            .glob("*.py")}
    for path in sorted((spec.BENCH_DIR / "metrics").glob("*.py")):
        reader = spec.metric_reader(path.stem)
        if path.stem in entries:
            assert reader.UNIT == entries[path.stem]["unit"]
            assert reader.MOVES == entries[path.stem]["moves"]
        # a reader that finds nothing to read returns nothing
        assert reader.read(SimpleNamespace()) is None


def test_benchmark_json_keeps_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert all(not p.startswith("/") and ".." not in p for p in bench["paths"])
    assert 1 <= bench["run_seconds"] <= 51
    cells = bench["workloads"]
    assert 2 + 14 * 24 <= 43200 and (
        (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200
        <= 43200)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    used = {w["config"] for w in cells}
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and c["name"] in used
        assert c["file"].startswith("bench/")
        with open(ROOT / c["file"]) as f:
            assert json.load(f)["name"] == c["name"]
    for w in cells:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["moves"] in e2e and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        layers.setdefault(m["layer"], []).append(m["name"])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert len(json.dumps(bench)) < 64 * 1024


def test_a_cell_added_as_new_files_only(tmp_path, bench):
    """A later cell brings a config, a mix and a metric as new files and new
    entries; nothing that is already there is edited."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    new = dict(bench)
    config = json.loads((root / "bench/configs/fcf-lastfm.json").read_text())
    config["name"] = "fcf-movielens"
    (root / "bench/configs/fcf-movielens.json").write_text(json.dumps(config))
    mix = {"kind": "train", "training": {"strategy": "bts",
                                         "keep_fraction": 0.25},
           "max_rounds": 100, "trace_chunks": 1}
    (root / "bench/traffic/train.bts25.json").write_text(json.dumps(mix))
    (root / "bench/metrics/rounds_seen.py").write_text(
        'UNIT = "rounds"\nMOVES = "rounds_per_s"\n\n\n'
        "def read(ctx):\n"
        "    return getattr(ctx, 'traced_rounds', None)\n")
    new["configs"] = bench["configs"] + [{
        "name": "fcf-movielens", "source": "https://doi.org/10.1145/3460231.3474254",
        "file": "bench/configs/fcf-movielens.json", "reduced": [],
        "why": "a third Table-2 dataset"}]
    new["workloads"] = bench["workloads"] + [{
        "name": "movielens.train.bts25", "config": "fcf-movielens",
        "traffic": "train.bts25", "chips": 1, "why": "added by files"}]
    new["end_to_end"] = [dict(m, workloads=m["workloads"]
                              + ["movielens.train.bts25"])
                         if m["name"] == "rounds_per_s" else m
                         for m in bench["end_to_end"]]
    new["per_layer"] = bench["per_layer"] + [{
        "name": "rounds_seen", "unit": "rounds", "better": "higher",
        "source": "host_clock", "layer": "round loop (federated/simulation)",
        "moves": "rounds_per_s", "workloads": ["movielens.train.bts25"]}]
    (root / "BENCHMARK.json").write_text(json.dumps(new))

    cell = spec.find_cell("movielens.train.bts25", root=root)
    assert cell.config["name"] == "fcf-movielens"
    assert cell.traffic["training"]["keep_fraction"] == 0.25
    assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                    "rounds_per_s"]
    got = spec.read_per_layer(cell, SimpleNamespace(traced_rounds=200),
                              root=root)
    assert got == {"rounds_seen": {"value": 200.0, "unit": "rounds"}}
    # the old cells are untouched by the addition
    old = spec.find_cell("lastfm.train.bts", root=root)
    assert "rounds_seen" not in {m["name"] for m in old.per_layer}


def test_an_unknown_cell_is_named_in_the_error(bench):
    with pytest.raises(KeyError, match="lastfm.train.bts"):
        spec.find_cell("no.such.cell", bench)
