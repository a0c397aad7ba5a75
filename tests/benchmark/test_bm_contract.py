"""BENCHMARK.json against the contract's limits that can be checked
without a chip, and against the files it names."""

import copy
import importlib
import json
import os
import re
import shutil

import pytest

from bm_paths import FIXTURES, ROOT, copy_benchmark

from benchmark.harness import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|"
                   r"_rank$|head_dim|expansion|experts_per_tok")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    B = json.load(f)
CELLS = [w["name"] for w in B["workloads"]]
METRICS = B["end_to_end"] + B["per_layer"]
# what a run reports: a metric in a cell. A metric without a
# ``workloads`` list is every cell's.
PAIRS = [(m, c) for m in METRICS for c in m.get("workloads", CELLS)]


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
    copy_benchmark(tmp_path)
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


def _entry_holds(bench, m):
    """One metric's entry, of a loaded dict and without a file opened."""
    cells_ = [w["name"] for w in bench["workloads"]]
    e2e = m in bench["end_to_end"]
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"bound"} if e2e else {"layer", "moves"})
    assert set(m) <= allowed and allowed - {"workloads"} <= set(m), m
    assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m["name"]
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    # a list, where there is one, names cells, each once, and is not
    # empty: an entry no cell reports is checked by nothing else
    listed = m.get("workloads", cells_)
    assert listed and set(listed) <= set(cells_), m["name"]
    assert len(set(listed)) == len(listed), m["name"]
    if e2e:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        return
    assert _line(m["layer"])
    if m["name"].endswith("_roofline"):
        assert m["unit"] == "%"
    # every cell that reports the entry reports the end-to-end metric
    # it moves
    for cell in listed:
        assert m["moves"] in [x["name"] for x in cells.metrics_in(
            bench, cell, "end_to_end")], (m["name"], cell)


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entry_and_reader(m):
    """One case an entry, whatever its list holds."""
    _entry_holds(B, m)
    # a variant <quantity>.<variant> is read by the quantity's file
    assert os.path.isfile(cells.reader_path(m["name"]))
    assert callable(cells.load_reader(m["name"]))


@pytest.mark.parametrize("m, cell", PAIRS,
                         ids=[f"{m['name']}@{c}" for m, c in PAIRS])
def test_metric_in_its_cell(m, cell):
    """One case a (metric, cell) a run reports: a quantity listed once
    for seven cells counts as seven."""
    kind = "end_to_end" if m in B["end_to_end"] else "per_layer"
    assert cell in CELLS
    assert m in cells.metrics_for(cell, kind)
    if kind == "per_layer":
        assert m["moves"] in [x["name"] for x in cells.metrics_for(
            cell, "end_to_end")]


def _quantity(name):
    """The name up to its last ``.``."""
    return name.rsplit(".", 1)[0]


def _listed_twice(per_layer, root=ROOT):
    """Entries that list a quantity again for the end-to-end metric an
    earlier entry of that quantity already moves, without a reader file
    of their own: a copy, where the earlier entry's ``workloads`` list
    takes the cell."""
    seen, twice = set(), []
    for m in per_layer:
        key = (_quantity(m["name"]), m["moves"])
        own = os.path.isfile(os.path.join(root, "benchmark", "metrics",
                                          m["name"] + ".py"))
        if key in seen and not (own and "." in m["name"]):
            twice.append(m["name"])
        seen.add(key)
    return twice


def lists_hold(bench):
    """Every rule of this file that is about BENCHMARK.json's lists
    alone, of a loaded dict: the repo's file, and a copy to which a
    later configuration is appended. No count of today's entries, cells
    or pairs is among them: the lists are there to grow."""
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    cells_ = [w["name"] for w in bench["workloads"]]
    assert len(set(cells_)) == len(cells_) <= 24
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(len(cells_) // 4, 1)
    configs = [c["name"] for c in bench["configs"]]
    assert len(set(configs)) == len(configs) <= 24
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    assert len({c["file"] for c in bench["configs"]}) == len(configs)
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    assert 1 <= len(bench["end_to_end"]) <= 16
    assert 1 <= len(bench["per_layer"]) <= 128
    setup = next(m for m in bench["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] <= 0.1 and "workloads" not in setup
    for m in metrics:
        _entry_holds(bench, m)
    for cell in cells_:
        assert len(cells.metrics_in(bench, cell, "end_to_end")) >= 2, cell
        assert cells.metrics_in(bench, cell, "per_layer"), cell
    # the rule that keeps the list short: ``<quantity>.<variant>`` stands
    # beside ``<quantity>`` only where it moves another end-to-end metric
    # (the TTFT side, ``.tpot``) or has a reader file of its own
    # (``paged_attn_roofline.hybrid`` / ``.agent-loop``)
    assert _listed_twice(bench["per_layer"]) == []


def _with_a_later_configuration(bench):
    """A copy with what the next ``model_config`` PR appends: an eighth
    configuration, a ninth cell, two per-layer entries of the cell's
    own; and the cell's name at the end of the ``workloads`` lists of
    the accepted quantities it shares (a model with experts and GQA)."""
    b = copy.deepcopy(bench)
    cell = "next-config.long-decode"
    b["configs"].append({
        "name": "next-config", "source": "test", "reduced": [],
        "why": "test", "file": "benchmark/configs/next-config/config.json"})
    b["workloads"].append({"name": cell, "config": "next-config",
                           "traffic": "long-decode", "chips": 1,
                           "why": "test"})
    for name in ("window_attn_busy_share", "window_pool_fill_share"):
        b["per_layer"].append({
            "name": name, "unit": "%", "better": "lower",
            "source": "device_trace", "layer": "kernels",
            "moves": "tpot_p50_ms", "workloads": [cell]})
    for m in b["per_layer"]:
        if m["name"] in ("moe_busy_share", "paged_attn_busy_share",
                         "output_tok_s.tpot"):
            m["workloads"].append(cell)
    return b


LATER = _with_a_later_configuration(B)


@pytest.mark.parametrize("bench", [B, LATER],
                         ids=["as-committed", "a-later-configuration"])
def test_the_lists_hold(bench):
    """For the repo's file and for a copy with the next configuration's
    cell, configuration and entries appended: a ``model_config`` PR may
    not edit this file, so a rule here that today's counts were written
    into would refuse whatever it adds."""
    lists_hold(bench)
    if bench is LATER:
        assert LATER["workloads"][-1]["name"] not in CELLS
        assert len(LATER["per_layer"]) == len(B["per_layer"]) + 2


@pytest.mark.parametrize("fault, said", [
    (lambda b: b["per_layer"].append(
        dict(b["per_layer"][0], name="moe_busy_share.next",
             moves="tpot_p50_ms", workloads=[CELLS[-1]])), "moe_busy"),
    (lambda b: b["per_layer"][-1].update(workloads=[]), "assert"),
    (lambda b: b["per_layer"][-1].update(workloads=["no-such.cell"]),
     "assert"),
    (lambda b: b["per_layer"][-1].update(moves="ttft_mean_ms"),
     "ttft_mean_ms"),
    (lambda b: b["workloads"].append(dict(b["workloads"][0],
                                          name="mixtral-8x7b.alone")),
     "assert"),
    (lambda b: b["per_layer"].extend(
        dict(b["per_layer"][-1], name=f"filler_{i}")
        for i in range(129 - len(b["per_layer"]))), "128"),
], ids=["a-copy-of-a-quantity", "an-empty-list", "a-mistyped-cell",
        "moves-what-the-cell-does-not-report", "a-pair-twice",
        "past-the-cap"])
def test_the_lists_rules_refuse(fault, said):
    b = copy.deepcopy(B)
    fault(b)
    with pytest.raises(AssertionError, match=said):
        lists_hold(b)


def test_a_variant_that_moves_another_metric_is_no_copy():
    copy_ = {"name": "moe_busy_share.next", "unit": "%", "better": "lower",
             "source": "device_trace", "layer": "step programs",
             "moves": "tpot_p50_ms", "workloads": [CELLS[-1]]}
    assert _listed_twice(B["per_layer"] + [copy_]) == [copy_["name"]]
    assert _listed_twice(B["per_layer"] + [
        dict(copy_, moves="setup_s")]) == []


def _reported(per_layer, cells_):
    """{(reader file, cell, moves)} of a per_layer list."""
    return {(os.path.basename(cells.reader_path(m["name"]))[:-3], c,
             m["moves"])
            for m in per_layer for c in m.get("workloads", cells_)}


def test_every_reading_of_pr_44_is_still_reported():
    """fixtures/reported_pr44.csv: the (reader file, cell, end-to-end
    metric moved) triples of the list as PR 44 had it, 128 entries for
    54 quantities, before PR 45 made each quantity one entry. The lists
    of today report each of them, and may report more. A benchmark PR
    that retires a reading takes its line out, and says so in PERF.md."""
    with open(os.path.join(FIXTURES, "reported_pr44.csv")) as f:
        old = {tuple(ln.strip().split(",")) for ln in f.readlines()[1:]}
    assert len(old) > 200
    assert old <= _reported(B["per_layer"], CELLS)
    assert old <= _reported(LATER["per_layer"], CELLS)


def test_layers_are_perf_md_layers():
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for m in B["per_layer"]:
        assert f"| {m['layer']} |" in perf, m["layer"]


def test_variant_is_read_by_its_quantity_unless_it_has_a_file(tmp_path):
    """``<quantity>.<variant>`` (a quantity listed again for cells where
    it moves another end-to-end metric) needs no file of its own."""
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


# ------------------------------ the configurations' own test files

HERE = os.path.dirname(os.path.abspath(__file__))
# a configuration's test file names the accepted cell its tiny cell
# stands for as ``LIKE``; found by that, never from a list kept here
CONFIG_TESTS = sorted(
    f[:-3] for f in os.listdir(HERE)
    if re.fullmatch(r"test_bm_\w+\.py", f)
    and re.search(r"^LIKE = ", open(os.path.join(HERE, f)).read(), re.M))


@pytest.mark.parametrize("module", CONFIG_TESTS)
def test_a_later_configuration_breaks_no_configurations_test(module):
    """Every assertion a ``test_bm_<configuration>.py`` makes about the
    benchmark's lists is kept in its ``benchmark_lists_hold(bench)``:
    it holds for the repo's file (that file's own test) and for a copy
    to which the next configuration is appended (here). A test that
    pinned the END of a list (PR 40's, PR 33's) failed the second. A
    file that names a ``LIKE`` cell and has no such function fails
    here, with the name of what it lacks."""
    mod = importlib.import_module(module)
    assert callable(getattr(mod, "benchmark_lists_hold", None)), (
        f"{module}.py names a LIKE cell and defines no "
        f"benchmark_lists_hold(bench)")
    assert mod.LIKE in CELLS
    mod.benchmark_lists_hold(B)
    mod.benchmark_lists_hold(LATER)


def test_the_accepted_configurations_test_files_are_found():
    """At least those of today; a later one is found the same way."""
    assert {"test_bm_jamba", "test_bm_kanana", "test_bm_lfm2",
            "test_bm_sdar", "test_bm_granite"} <= set(CONFIG_TESTS)
