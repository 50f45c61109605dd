"""``BENCHMARK.json`` and the data files against the limits of the
benchmark's contract that can be read off the files: names, units, lengths,
which metric moves which, and that every file a cell needs is there."""
import importlib
import inspect
import json
import os
import re

import pytest

from perfbench import run

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
_SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
#: what every configuration holds, whatever its runner; the runner's own
#: ``CONFIG_KEYS`` come on top
_COMMON_KEYS = ("factory", "recipe", "precision", "fwd_macs_per_sample",
                "reference", "reduced", "assumed")
_RUN_PARAMETERS = ["cell", "platform", "seed", "seconds", "trace", "t_start",
                   "counter"]
_WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|head_size|"
                    r"experts_per_tok")


def _load(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


_ROOTS = [pytest.param(_ROOT, id="repo"), pytest.param(_DATA, id="testdata")]


@pytest.mark.parametrize("root", _ROOTS)
def test_keys_names_units_and_lengths(root):
    b = _load(root)
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(b["command"]) <= 32 and 1 <= len(b["paths"]) <= 16
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    for key, allowed in (
            ("configs", {"name", "source", "file", "reduced", "why"}),
            ("workloads", {"name", "config", "traffic", "chips", "why"}),
            ("end_to_end", {"name", "unit", "better", "bound", "source",
                            "workloads"}),
            ("per_layer", {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"})):
        names = [e["name"] for e in b[key]]
        assert len(names) == len(set(names)), names
        for e in b[key]:
            assert set(e) <= allowed and allowed - {"workloads"} <= set(e), e
            assert _NAME.match(e["name"]), e["name"]
            for text in ("why", "layer", "source"):
                if text in e:
                    assert 1 <= len(e[text]) <= 200 and "\n" not in e[text] \
                        and "\t" not in e[text], e
    for m in b["end_to_end"] + b["per_layer"]:
        assert _UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in _SOURCES
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert "setup_s" in [m["name"] for m in b["end_to_end"]]
    assert len(json.dumps(b)) < 64 * 1024


@pytest.mark.parametrize("root", _ROOTS)
def test_cells_configs_and_chips(root):
    b = _load(root)
    configs = {c["name"]: c for c in b["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in b["workloads"]} == set(configs)
    files = [c["file"] for c in b["configs"]]
    assert len(files) == len(set(files))
    for c in b["configs"]:
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        assert len(c["reduced"]) <= 16
        assert not [k for k in c["reduced"] if _WIDTH.search(k)]
    for w in b["workloads"]:
        assert w["chips"] in (1, 4) and _NAME.match(w["traffic"])
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 4)


@pytest.mark.parametrize("root", _ROOTS)
def test_every_cell_reports_what_its_layer_metrics_move(root):
    b = _load(root)
    cells = [w["name"] for w in b["workloads"]]

    def cells_of(m):
        assert set(m.get("workloads", cells)) <= set(cells), m
        return set(m.get("workloads", cells))

    e2e = {m["name"]: cells_of(m) for m in b["end_to_end"]}
    layers = {}
    for m in b["per_layer"]:
        assert m["moves"] in e2e, m
        assert cells_of(m) <= e2e[m["moves"]], m
        layers.setdefault(m["layer"], []).append(m["name"])
    for cell in cells:
        reported = [n for n, c in e2e.items() if cell in c]
        assert "setup_s" in reported and len(reported) >= 2, cell
        assert any(cell in cells_of(m) for m in b["per_layer"]), cell
    for layer in layers:
        assert 1 <= len(layer) <= 200 and "\n" not in layer


@pytest.mark.parametrize("root", _ROOTS)
def test_every_cell_loads_from_its_files_by_name(root, toy_tokens_runner):
    b = _load(root)
    for w in b["workloads"]:
        cell = run.load_cell(root, w["name"])
        # a runner is a module of perfbench/runners/ (the test data's second
        # runner is installed there by the fixture), checked by what it
        # declares and not against a list of the runners there are
        assert _NAME.match(cell["traffic"]["runner"])
        runner = importlib.import_module(
            "perfbench.runners." + cell["traffic"]["runner"])
        assert list(inspect.signature(runner.run).parameters) == \
            _RUN_PARAMETERS, w["name"]
        assert callable(runner.abstract_sample)
        assert isinstance(runner.CONFIG_KEYS, tuple) and runner.CONFIG_KEYS
        for key in _COMMON_KEYS + runner.CONFIG_KEYS:
            assert key in cell["config"], (w["name"], key)
        assert [m["name"] for m in cell["end_to_end"]].count("setup_s") == 1
        assert all("reader" in m["spec"] for m in cell["per_layer"])
    with pytest.raises(KeyError, match="no workload"):
        run.load_cell(root, "no_such_cell")


def test_the_test_data_has_a_runner_that_is_not_train_and_counts_tokens(
        toy_tokens_runner):
    cells = [run.load_cell(_DATA, w["name"])
             for w in _load(_DATA)["workloads"]]
    runners = {c["name"]: c["traffic"]["runner"] for c in cells}
    assert runners == {"tiny_train": "train", "tiny_train_dp4": "train",
                       "tiny_tokens": "toy_tokens"}
    config = cells[2]["config"]
    assert "image_size" not in config and "channels" not in config
    sample = toy_tokens_runner.abstract_sample(config)
    assert sample.shape == (1, config["seq_len"]) and sample.dtype == "int32"
    # and no toy is left among the benchmark's own runners
    assert not os.path.exists(os.path.join(_ROOT, "perfbench", "runners",
                                           "toy_tokens.py"))


def test_files_under_paths_are_named_from_the_characters_of_a_name():
    b = _load(_ROOT)
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in b["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", path)
        for dirpath, dirnames, filenames in os.walk(os.path.join(_ROOT, path)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in filenames:
                rel = os.path.relpath(os.path.join(dirpath, f), _ROOT)
                assert ok.match(rel), rel
    assert b["command"] == ["python3", "perfbench/run.py"]


def test_peaks_table_refuses_a_kind_it_does_not_list():
    from perfbench import checks

    assert checks.peaks("TPU v5 lite") == {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9}
    for kind in ("TPU v4", "cpu", "_source"):
        with pytest.raises(KeyError):
            checks.peaks(kind)
