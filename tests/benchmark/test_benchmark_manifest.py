"""BENCHMARK.json against the contract, and the files it names."""

import json
import os

import pytest

from benchmarks.harness import manifest


def _manifest(root):
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def test_manifest_meets_the_contract(repo_root):
    assert manifest.check_manifest(repo_root) == []


def test_every_cell_has_its_files(repo_root):
    for w in _manifest(repo_root)["workloads"]:
        cell = manifest.load_cell(repo_root, w["name"])
        assert cell.chips == w["chips"]
        assert cell.global_batch == cell.batch_per_chip * cell.chips
        assert cell.n_train >= cell.global_batch
        note = os.path.join(repo_root, "benchmarks", "workloads", w["name"] + ".json")
        assert os.path.exists(note), note
        # the reference and its operation count are found by name
        model = manifest.load_module(repo_root, "models", cell.config["reference"])
        assert model.train_flops_per_sample(cell.config["arch"]) > 0
        # the merge yields a TrainConfig
        from tpu_dist.config.config import TrainConfig

        TrainConfig(**manifest.train_config_fields(cell, seed=1))


def test_every_layer_metric_has_a_reader_that_agrees(repo_root):
    for m in _manifest(repo_root)["per_layer"]:
        reader = manifest.load_module(repo_root, "layer_metrics", m["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (m["layer"], m["unit"], m["moves"])
        assert callable(reader.read)


def test_four_chip_cells_are_at_most_a_quarter(repo_root):
    cells = _manifest(repo_root)["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)


@pytest.mark.parametrize("breach", ["name", "unit", "moves", "orphan_config", "bound"])
def test_check_manifest_sees_a_breach(tiny_root, breach):
    man = _manifest(tiny_root)
    assert manifest.check_manifest(tiny_root) == []
    if breach == "name":
        man["workloads"][0]["name"] = "has space"
    elif breach == "unit":
        man["end_to_end"][0]["unit"] = "samples per second"
    elif breach == "moves":
        man["per_layer"][0]["moves"] = "peak_hbm_gib"
        man["end_to_end"][2]["workloads"] = ["tiny.fused"]
    elif breach == "orphan_config":
        man["configs"].append(dict(man["configs"][0], name="nobody",
                                   file="benchmarks/configs/nobody.json"))
    elif breach == "bound":
        man["end_to_end"][0]["bound"] = 0.5
    with open(os.path.join(tiny_root, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump(man, f)
    assert manifest.check_manifest(tiny_root) != []


@pytest.mark.parametrize("where", ["config", "traffic"])
def test_merge_rejects_an_unknown_field(tiny_root, where):
    path = os.path.join(tiny_root, "benchmarks", *(
        ("configs", "vit_tiny_test.json") if where == "config" else ("traffic", "stream.json")))
    with open(path, encoding="utf-8") as f:
        obj = json.load(f)
    obj["train_config"]["no_such_field"] = 1
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f)
    with pytest.raises(manifest.ManifestError, match="no_such_field"):
        manifest.train_config_fields(manifest.load_cell(tiny_root, "tiny.stream"), seed=0)


def test_merge_rejects_a_field_the_harness_owns(tiny_root):
    path = os.path.join(tiny_root, "benchmarks", "traffic", "stream.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"chips": 1, "loader": "stream", "train_config": {"batch_size": 8}}, f)
    with pytest.raises(manifest.ManifestError, match="batch_size"):
        manifest.train_config_fields(manifest.load_cell(tiny_root, "tiny.stream"), seed=0)


def test_traffic_layout_fields_reach_the_config(tiny_root):
    path = os.path.join(tiny_root, "benchmarks", "traffic", "stream.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"chips": 1, "loader": "stream", "batch_per_chip": 8,
                   "train_config": {"grad_accu_steps": 2}}, f)
    fields = manifest.train_config_fields(manifest.load_cell(tiny_root, "tiny.stream"), seed=7)
    assert (fields["batch_size"], fields["grad_accu_steps"], fields["seed"]) == (8, 2, 7)


def test_peaks_v5e_row_and_unknown_kind(repo_root):
    row = manifest.load_peaks(repo_root, "TPU v5 lite")
    assert row == {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                   "hbm_bytes": 16e9, "ici_bits_per_s": 1600e9}
    for kind in ("TPU v5e", "cpu", "_source"):
        with pytest.raises(manifest.RefusedError):
            manifest.load_peaks(repo_root, kind)


def test_unknown_workload_names_the_cells(repo_root):
    with pytest.raises(manifest.ManifestError, match="resnet18_cifar100.stream"):
        manifest.load_cell(repo_root, "no.such.cell")
