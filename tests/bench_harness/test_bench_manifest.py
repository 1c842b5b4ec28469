"""BENCHMARK.json and its data files: the loader's checks, and that every
name resolves to a file."""

import importlib
import json
import os

import pytest

from chipbench import manifest, run

MAN = manifest.Manifest()
ROOT = manifest.ROOT


@pytest.mark.parametrize("name", ["a b", "a,b", "a/b", "", "x" * 65,
                                  "-lead", "café", "μs"])
def test_bad_name_rejected(name):
    with pytest.raises(manifest.ManifestError):
        manifest.check_name(name)


@pytest.mark.parametrize("name", ["resnet50-gluon-train-bs128", "a", "_x.1-b",
                                  "9lives", "x" * 64])
def test_good_name_accepted(name):
    assert manifest.check_name(name) == name


@pytest.mark.parametrize("unit", ["tokens per s", "μs", "", "x" * 17,
                                  "ms,s", "a\tb"])
def test_bad_unit_rejected(unit):
    with pytest.raises(manifest.ManifestError):
        manifest.check_unit(unit)


@pytest.mark.parametrize("unit", ["tokens/s", "img/s", "%", "ms", "count",
                                  "GB/s", "us"])
def test_good_unit_accepted(unit):
    assert manifest.check_unit(unit) == unit


def test_top_level_keys_are_the_contracts():
    assert set(MAN.data) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert 1 <= MAN.data["run_seconds"] <= 51
    # 2 + 14 x 24 runs of run_seconds + 60, 2 x 90 a cell, 1200 spare
    full = (2 + 14 * 24) * (MAN.data["run_seconds"] + 60) + 24 * 180 + 1200
    assert full <= 43200


def test_four_chip_cells_within_the_quarter():
    four = [w for w in MAN.data["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(MAN.data["workloads"]) // 4)


@pytest.mark.parametrize("cell", sorted(MAN.cells))
def test_cell_files_found_by_name(cell):
    c = MAN.cell(cell)
    config, traffic = MAN.config_of(c), MAN.traffic_of(c)
    assert run.kind_of(traffic)
    assert importlib.util.find_spec("chipbench.runners." + config["runner"])
    assert os.path.exists(os.path.join(ROOT, config["reference"]))
    limits = manifest.load_limits(cell)
    numbers = [v["limit"] if isinstance(v, dict) else v
               for k, v in limits.items() if k not in ("control", "readings")]
    assert numbers and all(v > 0 for v in numbers)
    from chipbench.reference.common import OPERANDS
    assert limits["control"] in OPERANDS and limits["readings"]
    end = [m["name"] for m in MAN.metrics_of(c, "end_to_end")]
    assert "setup_s" in end and len(end) >= 2
    assert set(traffic["end_to_end"]) == set(end) - {"setup_s"}
    assert MAN.metrics_of(c, "per_layer")


@pytest.mark.parametrize("metric", sorted(MAN.per_layer))
def test_layer_metric_has_a_data_file_and_a_reader(metric):
    spec = manifest.load_layer_metric(metric)
    reader = importlib.import_module("chipbench.readers." + spec["reader"])
    # a reader that finds nothing to read returns nothing
    assert reader.read({"config": {}, "traffic": {}, "device": {}},
                       spec.get("args", {})) is None
    m = MAN.per_layer[metric]
    assert m["moves"] in MAN.end_to_end
    movers = MAN.end_to_end[m["moves"]].get("workloads")
    assert movers is None or set(m["workloads"]) <= set(movers)


@pytest.mark.parametrize("config", sorted(MAN.configs))
def test_reduced_names_no_width(config):
    widths = ("n_embd", "n_inner", "n_head", "hidden", "intermediate",
              "channels")
    for key in MAN.configs[config]["reduced"]:
        assert key not in widths and not key.endswith(("_dim", "_rank"))


def test_unknown_workload_is_an_error():
    with pytest.raises(manifest.ManifestError):
        MAN.cell("no-such-cell")
