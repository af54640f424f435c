"""BENCHMARK.json and the files the harness finds by name, and the refusal
to run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import tiny_cell
import harness
import run_cell
import yardstick

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks/chip"]
    assert bench["command"][1].startswith(bench["paths"][0] + "/")
    assert 1 <= bench["run_seconds"] <= 51


def test_configs_and_cells_are_found_by_name(bench):
    names = {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        assert NAME.match(c["name"])
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        cfg = harness.load_config(bench, c["name"])
        assert cfg["name"] == c["name"]
        assert set(cfg["reduced"]) == set(c["reduced"])
        assert hasattr(harness.reference(cfg["arch"]), "train")
    used = set()
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert w["config"] in names and w["chips"] in (1, 4)
        traffic = harness.load_traffic(w["traffic"])
        assert traffic["mode"] in ("full", "cpr-mfu", "cpr-ssu")
        used.add(w["config"])
    assert used == names
    with pytest.raises(KeyError):
        harness.find(bench["workloads"], "no-such-cell", "workload")


def test_metrics_have_readers(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    empty = {"trace": None, "peaks": None, "cfg": {}, "traffic": {},
             "yardstick": yardstick,
             "counters": {"window_steps": 0, "window_saves": 0,
                          "window_save_bytes": 0, "batch": 1,
                          "selection_calls_per_save": []}}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        reader = harness.metric_reader(m["name"])
        # a reader with nothing to read returns nothing, never 0
        assert reader.read(empty) is None
    only = harness.metrics_for(bench, "another-cell", "per_layer")
    assert "tracker_select_roofline_pct" not in {m["name"] for m in only}


def test_refuses_without_tpu(capsys):
    code = run_cell.main(["--workload", "kaggle-mfu", "--seed", "1",
                          "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert code != 0 and out.out == ""
    assert "TPU" in out.err


def test_fails_in_a_checkout_of_the_benchmark_alone(tmp_path):
    root = os.path.dirname(os.path.dirname(tiny_cell.BENCH))
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
    shutil.copytree(tiny_cell.BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run_cell.py", "--workload",
         "kaggle-mfu", "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
