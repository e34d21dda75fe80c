"""BENCHMARK.json and the files it names: the contract's shape, files found
by name, and a cell added as new files only."""
import json
import os
import re
import shutil
import sys
import time

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_command(spec):
    assert set(spec) == TOP_KEYS
    assert spec["command"] == ["python3", "bench/run.py"]
    assert spec["paths"] == ["bench", "tests/bench"]
    assert 1 <= spec["run_seconds"] <= 51 and isinstance(spec["run_seconds"], int)
    # A full check of 24 cells has to fit its time budget.
    assert (2 + 14 * 24) * (spec["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_entry_keys(spec):
    names = []
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16
        names += [c["name"]] + c["reduced"]
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["config"], w["traffic"]]
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        names.append(m["name"])
    assert all(NAME.match(n) for n in names)
    metric_names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    assert len({w["name"] for w in spec["workloads"]}) == len(spec["workloads"])
    pairs = {(w["config"], w["traffic"]) for w in spec["workloads"]}
    assert len(pairs) == len(spec["workloads"])


def test_bounds_and_sources(spec):
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert {"spgemm_ms", "setup_s"} <= set(e2e)
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end", "per_layer"])
def test_every_named_file_exists(spec, kind):
    for entry in spec[kind]:
        if kind == "configs":
            assert os.path.isfile(os.path.join(ROOT, entry["file"]))
            with open(os.path.join(ROOT, entry["file"])) as f:
                cfg = json.load(f)
            assert cfg["source"] == entry["source"] and cfg["reduced"] == entry["reduced"]
            assert set(cfg["reduced"]) <= set(cfg)
        elif kind == "workloads":
            cell = harness.resolve(entry["name"])
            assert cell.chips == entry["chips"]
            assert callable(cell.path.run) and callable(cell.path.warm)
            assert cell.end_to_end and cell.per_layer
        else:
            mod = harness.load_module("metrics", entry["name"])
            assert mod.UNIT == entry["unit"] and callable(mod.read)


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        harness.resolve("no-such-cell")
    with pytest.raises(FileNotFoundError):
        harness.load_module("metrics", "no_such_metric")


def test_a_cell_is_added_by_new_files_only(tmp_path, spec):
    """A new configuration with its own matrix, a mix, an entry-point module
    and a metric, each a new file beside the committed ones, are found by
    the names in BENCHMARK.json; no committed file changes."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    b = tmp_path / "bench"
    (b / "matrices" / "hpcg27_again.py").write_text(
        (b / "matrices" / "hpcg27.py").read_text())
    cfg = json.loads((b / "configs" / "hpcg40-A2.json").read_text())
    (b / "configs" / "hpcg-tile64.json").write_text(
        json.dumps(dict(cfg, tile=64, matrix="hpcg27_again")))
    (b / "traffic" / "single_again.json").write_text(
        json.dumps({"path": "single_again", "ring": 2, "check_samples": 1}))
    (b / "paths" / "single_again.py").write_text(
        (b / "paths" / "single.py").read_text())
    (b / "metrics" / "products.py").write_text(
        'UNIT = "1"\n\ndef read(ctx):\n    return ctx["completed"]\n')
    new = json.loads(json.dumps(spec))
    new["configs"].append(dict(spec["configs"][0], name="hpcg-tile64",
                               file="bench/configs/hpcg-tile64.json"))
    new["workloads"].append({"name": "hpcg-tile64.single_again",
                             "config": "hpcg-tile64", "traffic": "single_again",
                             "chips": 1, "why": "test"})
    new["end_to_end"].append({"name": "products", "unit": "1", "better": "higher",
                              "bound": 0.01, "source": "host_clock"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    cell = harness.resolve("hpcg-tile64.single_again", root=str(tmp_path))
    assert cell.config["tile"] == 64 and cell.traffic["ring"] == 2
    assert cell.path.__file__ == str(b / "paths" / "single_again.py")
    assert "products" in cell.end_to_end
    result = harness.run_cell(
        cell, 5, 0.3, False, t0=time.perf_counter(),
        overrides={"grid": [8, 8, 16], "backend": "pallas_interpret",
                   "plan_dir": str(tmp_path / "plans")})
    assert result["correct"] is True
    assert result["metrics"]["products"]["value"] == result["attempted"] > 0
    (b / "matrices" / "hpcg27_again.py").unlink()  # the matrix is the new file's
    with pytest.raises(FileNotFoundError, match="hpcg27_again"):
        harness.run_cell(cell, 5, 0.1, False, t0=time.perf_counter(),
                         overrides={"grid": [2, 2, 2], "backend": "pallas_interpret"})
