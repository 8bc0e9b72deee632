"""Fixtures of the benchmark's own tests: the repository's root, and a tiny
test-only root (its own BENCHMARK.json, peaks table, configuration, traffic
mixes and cells beside the repository's models and layer-metric readers),
which is also the dry addition: a cell made of nothing but new data files."""

import copy
import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY_CONFIG = {
    "name": "vit_tiny_test",
    "arch": {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
             "intermediate_size": 256, "patch_size": 4, "image_size": 32,
             "num_channels": 3, "num_labels": 10},
    "reference": "vit",
    "train_config": {"model": "vit_tiny", "num_classes": 10, "optimizer": "adamw",
                     "lr": 1e-3, "weight_decay": 0.05, "log_every": 5},
    "data": {"image_size": 32, "num_classes": 10, "epoch_steps": 6, "distinct": 32,
             "batch_per_chip": 16},
    "reference_check": {"samples_per_chip": None, "chunk": 8, "loss_rel_tol": 1e-4,
                        "sign_floor_rms": 0.5, "sign_agreement_min": 0.99,
                        "reason": "float32 on both sides here"},
}


@pytest.fixture(scope="session")
def repo_root():
    return REPO


def _write(path, obj):
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f)


@pytest.fixture
def tiny_root(tmp_path):
    root = str(tmp_path / "root")
    bench = os.path.join(root, "benchmarks")
    for d in ("configs", "traffic", "workloads"):
        os.makedirs(os.path.join(bench, d))
    for d in ("models", "layer_metrics"):
        shutil.copytree(os.path.join(REPO, "benchmarks", d), os.path.join(bench, d))
    _write(os.path.join(bench, "peaks.json"), {"cpu": {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9,
        "ici_bits_per_s": 1e9}})
    _write(os.path.join(bench, "configs", "vit_tiny_test.json"), TINY_CONFIG)
    _write(os.path.join(bench, "traffic", "stream.json"),
           {"chips": 1, "loader": "stream", "warmup_steps": 2, "trace_steps": 3,
            "train_config": {}})
    _write(os.path.join(bench, "traffic", "fused.json"),
           {"chips": 1, "loader": "fused", "train_config": {}})
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        man = copy.deepcopy(json.load(f))
    man["configs"] = [{"name": "vit_tiny_test", "source": "test",
                       "file": "benchmarks/configs/vit_tiny_test.json",
                       "reduced": [], "why": "test"}]
    man["workloads"] = [
        {"name": "tiny.stream", "config": "vit_tiny_test", "traffic": "stream",
         "chips": 1, "why": "test"},
        {"name": "tiny.fused", "config": "vit_tiny_test", "traffic": "fused",
         "chips": 1, "why": "test"},
    ]
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.stream"]
    _write(os.path.join(root, "BENCHMARK.json"), man)
    return root
