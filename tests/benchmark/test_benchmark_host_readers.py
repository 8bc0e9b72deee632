"""The readers of the program's own input-path and epoch-boundary counters
(``producer_gather_share``, ``producer_h2d_share``, ``epoch_boundary_ms``):
each on a hand-made window, in the manifest, and in a traced run of the tiny
cell, where the program under test really increments what they read."""

import json
import os
import types

import pytest

from benchmarks.harness import manifest, run_cell
from benchmarks.harness import trace as trace_lib

STREAMED = ["resnet18_cifar100.stream", "vit_b16_imagenet.stream", "vit_b16_imagenet.dp4"]
COUNTERS = {
    "loader.gather_s": 6.0, "loader.h2d_s": 3.0, "loader.h2d_bytes": 12e9,
    "loader.producer_wait_s": 0.5, "train.epochs": 4,
    "train.epoch_head_s": 0.004, "train.epoch_refill_s": 0.2,
    "train.epoch_tail_s": 0.036, "train.epoch_drain_s": 0.4,
}
WANT = {"producer_gather_share": 60.0, "producer_h2d_share": 30.0, "epoch_boundary_ms": 60.0}


def _window(counters, fused=False):
    said = []
    return {"cell": types.SimpleNamespace(fused=fused), "wall_s": 10.0, "steps": 52,
            "epochs": [], "counters": counters, "say": said.append}, said


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_a_hand_made_window(repo_root, name):
    reader = manifest.load_module(repo_root, "layer_metrics", name)
    window, said = _window(dict(COUNTERS))
    assert reader.read(window) == pytest.approx(WANT[name])
    if name == "producer_h2d_share":
        assert "4.00 GB/s" in said[0]
    if name == "epoch_boundary_ms":
        assert "head 1.00 ms, refill 50.00 ms, tail 9.00 ms, drain 100.00 ms" in said[0]
    # a fused cell has no loader and no step loop
    assert reader.read(_window(dict(COUNTERS), fused=True)[0]) is None
    # the parent program lacks these counters: nothing is read, nothing raises
    old = {"loader.producer_wait_s": 0.5, "train.epochs": 4}
    assert reader.read(_window(old)[0]) is None


def test_manifest_adds_the_three_readers_and_nothing_else(repo_root):
    with open(os.path.join(repo_root, "BENCHMARK.json"), encoding="utf-8") as f:
        per_layer = json.load(f)["per_layer"]
    assert [m["name"] for m in per_layer[-3:]] == [
        "producer_gather_share", "producer_h2d_share", "epoch_boundary_ms"]
    for m in per_layer[-3:]:
        assert m["source"] == "program_counter" and m["moves"] == "samples_per_s"
        assert m["workloads"] == STREAMED
    for name in STREAMED:
        mine = {m["name"] for m in manifest.load_cell(repo_root, name).per_layer}
        assert mine >= set(WANT)
    fused = manifest.load_cell(repo_root, "resnet18_cifar100.fused").per_layer
    assert set(WANT).isdisjoint(m["name"] for m in fused)


def test_traced_tiny_window_reports_the_producer_split_and_the_boundary(
        tiny_root, repo_root, monkeypatch):
    recorded = os.path.join(repo_root, "benchmarks", "harness", "testdata",
                            "v5e_resnet18_fused.json.gz")

    def recorded_capture(path):  # the CPU writes no device plane
        capture = trace_lib.load_trace(recorded)
        del capture["host"]
        return capture

    monkeypatch.setattr(trace_lib, "load_xplane", recorded_capture)
    result = run_cell(tiny_root, "tiny.stream", seed=5, seconds=0.6, trace=True, on_chip=False)
    assert result["correct"] is True
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(WANT) <= set(got)
    assert got["epoch_boundary_ms"] > 0
    # gather, h2d and the full queue are the producer thread's whole life, and
    # the thread lives inside the window's epochs
    split = got["producer_gather_share"] + got["producer_h2d_share"] + got["producer_idle_share"]
    assert 0 < got["producer_gather_share"] and 0 < got["producer_h2d_share"]
    assert split <= 101.0
