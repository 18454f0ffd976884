"""The harness finds cells, configurations, drivers and per-layer metrics
by name, as files alone: a later change adds a file and edits none."""
import json
import shutil

import pytest

from bench.harness import core
from bench.harness.readings import Readings


@pytest.fixture
def bench_copy(tmp_path, monkeypatch):
    dst = tmp_path / "bench"
    shutil.copytree(core.BENCH, dst,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    monkeypatch.setattr(core, "BENCH", dst)
    return dst


def test_a_cell_added_as_a_file_is_found(bench_copy):
    cell = core.workload("mnist-cnn.train.multi_region")
    cell["name"] = "mnist-cnn.train.wide"
    cell["population"]["n_devices"] = 200
    (bench_copy / "workloads" / "mnist-cnn.train.wide.json").write_text(
        json.dumps(cell))
    found = core.workload("mnist-cnn.train.wide")
    assert found["population"]["n_devices"] == 200
    assert core.config(found["config"])["dataset"] == "mnist"
    assert hasattr(core.driver(found["driver"]), "run")


def test_a_config_and_a_metric_added_as_files_are_found(bench_copy):
    cfg = core.config("mnist-cnn")
    cfg["name"] = "fmnist-cnn"
    (bench_copy / "configs" / "fmnist-cnn.json").write_text(json.dumps(cfg))
    assert core.config("fmnist-cnn")["name"] == "fmnist-cnn"
    (bench_copy / "metrics" / "rounds_seen.py").write_text(
        'LAYER = "engine"\nUNIT = "rounds"\nMOVES = "round_s"\n\n'
        'def read(r):\n    return r.counts.get("rounds")\n')
    mods = core.metric_modules()
    assert "rounds_seen" in mods and "train_mfu" in mods
    r = Readings(kind="train", chips=1, config=cfg, peaks={}, trace=None,
                 counts={"rounds": 3})
    assert mods["rounds_seen"].read(r) == 3


def test_every_metric_file_declares_its_layer_unit_and_end_to_end_metric():
    bench = json.loads((core.ROOT / "BENCHMARK.json").read_text())
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name, mod in core.metric_modules().items():
        assert mod.LAYER and mod.UNIT and mod.MOVES
        if name in listed:
            assert listed[name]["unit"] == mod.UNIT
            assert listed[name]["layer"] == mod.LAYER
            assert listed[name]["moves"] == mod.MOVES


def test_a_reader_with_nothing_to_read_returns_none():
    cfg = core.config("vgg11-cifar10")
    empty = Readings(kind="serve", chips=1, config=cfg, peaks={},
                     trace=None)
    for name, mod in core.metric_modules().items():
        if name.endswith(".serve") or name in (
                "serve_mfu", "predict_ms", "gateway_host_share"):
            continue
        assert mod.read(empty) is None, name


def test_an_unknown_name_is_an_error_that_names_what_exists():
    with pytest.raises(core.BenchError, match="vgg11.train.multi_region"):
        core.workload("no-such-cell")
    with pytest.raises(core.BenchError, match="not in bench/peaks.json"):
        core.peaks("TPU v9 imaginary")
