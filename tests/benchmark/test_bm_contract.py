"""BENCHMARK.json against the contract's limits that can be checked
without a chip, and against the files it names."""

import json
import os
import re
import shutil

import pytest

from bm_paths import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|"
                   r"_rank$|head_dim|expansion|experts_per_tok")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    B = json.load(f)
CELLS = [w["name"] for w in B["workloads"]]
METRICS = B["end_to_end"] + B["per_layer"]


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_command():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(B["command"]) <= 32 and all(map(_line, B["command"]))
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    assert 1 <= len(B["paths"]) <= 16
    for p in B["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(ROOT, p))
    script = B["command"][1]
    assert any(script.startswith(p + "/") for p in B["paths"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_every_file_under_paths_is_named_from_name_characters():
    for p in B["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel


@pytest.mark.parametrize("cfg", B["configs"], ids=lambda c: c["name"])
def test_config_entry(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and _line(cfg["source"]) \
        and _line(cfg["why"])
    assert any(cfg["file"].startswith(p + "/") for p in B["paths"])
    assert len(cfg["reduced"]) <= 16
    for key in cfg["reduced"]:
        assert NAME.match(key) and not WIDTH.search(key), key
    with open(os.path.join(ROOT, cfg["file"])) as f:
        run = json.load(f)
    about_path = os.path.join(os.path.dirname(
        os.path.join(ROOT, cfg["file"])), "about.json")
    with open(about_path) as f:
        about = json.load(f)
    assert about["source"] == cfg["source"]
    assert sorted(about["reduced"]) == sorted(cfg["reduced"])
    # every published value is kept but the reduced ones, and those
    # really differ
    for key, pub in about["published"].items():
        if key in cfg["reduced"]:
            assert run[key] != pub, key
        else:
            assert run[key] == pub, key
    assert any(w["config"] == cfg["name"] for w in B["workloads"])
    assert len({c["file"] for c in B["configs"]}) == len(B["configs"])
    # the configuration's own reference: a file under paths that has
    # reference_logits; its weight scales: numbers or "zeros"
    from benchmark.harness import cells

    cell = next(w["name"] for w in B["workloads"]
                if w["config"] == cfg["name"])
    loaded = cells.load_cell(cell)
    ref = os.path.relpath(loaded["reference_file"], ROOT)
    assert ref == about["reference"]
    assert any(ref.startswith(p + "/") for p in B["paths"])
    assert callable(cells.load_reference(loaded).reference_logits)
    assert loaded["weight_scales"] == about.get("weight_scales", {})


@pytest.mark.parametrize("about, said", [
    ({}, '"reference" key'),
    ({"reference": None}, '"reference" key'),
    ({"reference": "benchmark/configs/none/reference.py"}, "not a file"),
    ({"reference": "benchmark/../bench.py"}, "not a file under benchmark/"),
    ({"reference": "benchmark/reference.py",
      "weight_scales": {"w_router": "big"}}, '"weight_scales"'),
    (None, "about.json"),
])
def test_a_configuration_that_names_no_reference_is_refused(tmp_path, about,
                                                            said):
    """At load_cell, before anything is built, by a message that names
    the key."""
    from benchmark.harness import cells

    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copy(os.path.join(ROOT, "bench.py"), tmp_path)
    cfg = B["configs"][0]
    path = tmp_path / os.path.dirname(cfg["file"]) / "about.json"
    cell = next(w["name"] for w in B["workloads"]
                if w["config"] == cfg["name"])
    assert cells.load_cell(cell, str(tmp_path))["reference_file"] == str(
        tmp_path / "benchmark" / "reference.py")
    if about is None:
        path.unlink()
    else:
        path.write_text(json.dumps(about))
    with pytest.raises(SystemExit, match=said):
        cells.load_cell(cell, str(tmp_path))


@pytest.mark.parametrize("cell", B["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_files(cell):
    from benchmark.harness import cells

    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key]), cell[key]
    assert cell["chips"] in (1, 4) and _line(cell["why"])
    assert cell["config"] in {c["name"] for c in B["configs"]}
    loaded = cells.load_cell(cell["name"])
    assert os.path.isfile(os.path.join(loaded["model_path"], "config.json"))
    assert loaded["traffic_params"]["loop"] in ("open", "closed")
    e2e = cells.metrics_for(cell["name"], "end_to_end")
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    assert cells.metrics_for(cell["name"], "per_layer")


def test_cells_are_unique_and_few_take_four_chips():
    assert len(set(CELLS)) == len(CELLS) <= 24
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in B["workloads"])
    assert four <= max(len(CELLS) // 4, 1)


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entry_and_reader(m):
    e2e = m in B["end_to_end"]
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"bound"} if e2e else {"layer", "moves"})
    assert set(m) <= allowed and allowed - {"workloads"} <= set(m)
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for w in m.get("workloads", []):
        assert w in CELLS
    if e2e:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    else:
        assert _line(m["layer"])
        moved = next(x for x in B["end_to_end"] if x["name"] == m["moves"])
        assert set(m.get("workloads", CELLS)) <= set(
            moved.get("workloads", CELLS))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    from benchmark.harness import cells

    # a variant <quantity>.<variant> is read by the quantity's file
    assert os.path.isfile(cells.reader_path(m["name"]))
    assert callable(cells.load_reader(m["name"]))


def test_metric_names_unique_and_setup_present():
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)
    assert 1 <= len(B["end_to_end"]) <= 16 and 1 <= len(B["per_layer"]) <= 128
    setup = next(m for m in B["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] <= 0.1 and "workloads" not in setup


def test_layers_are_perf_md_layers():
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for m in B["per_layer"]:
        assert f"| {m['layer']} |" in perf, m["layer"]


def test_variant_is_read_by_its_quantity_unless_it_has_a_file(tmp_path):
    """``<quantity>.<variant>`` (a quantity listed again for cells where
    it moves another end-to-end metric) needs no file of its own."""
    from benchmark.harness import cells

    mdir = tmp_path / "benchmark" / "metrics"
    mdir.mkdir(parents=True)
    (mdir / "wait_ms.py").write_text("def read(raw):\n    return 1.0\n")
    root = str(tmp_path)
    assert cells.load_reader("wait_ms.some-cell", root)({}) == 1.0
    (mdir / "wait_ms.some-cell.py").write_text(
        "def read(raw):\n    return 2.0\n")
    assert cells.load_reader("wait_ms.some-cell", root)({}) == 2.0
    assert cells.load_reader("wait_ms", root)({}) == 1.0
    with pytest.raises(FileNotFoundError):
        cells.load_reader("nothing.some-cell", root)
