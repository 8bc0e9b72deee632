"""The window loop on a tiny test-only configuration, through the harness's
Python entry; the command itself refuses without a TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.harness import manifest, run_cell
from benchmarks.harness import trace as trace_lib

E2E = {"samples_per_s", "mfu", "peak_hbm_gib", "setup_s"}


def _check_line(result, cell):
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    for name, m in result["metrics"].items():
        assert isinstance(m["value"], float) and m["unit"], name
    json.dumps(result)


def test_stream_window_reports_the_end_to_end_metrics(tiny_root):
    result = run_cell(tiny_root, "tiny.stream", seed=3, seconds=1.0, trace=False, on_chip=False)
    _check_line(result, "tiny.stream")
    assert set(result["metrics"]) == E2E
    assert result["metrics"]["samples_per_s"]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0
    assert "breakdown" not in result


def test_fused_window_ends_on_an_epoch_boundary(tiny_root):
    result = run_cell(tiny_root, "tiny.fused", seed=3, seconds=0.5, trace=False, on_chip=False)
    _check_line(result, "tiny.fused")
    assert set(result["metrics"]) == E2E - {"peak_hbm_gib"}
    assert result["attempted"] % 6 == 0  # whole 6-step epochs only


def test_traced_window_reads_the_layer_metrics(tiny_root, repo_root, monkeypatch):
    """The CPU writes no device plane, so the recorded v5e trace stands in for
    the capture; everything else (spans, alignment, laps, counters, readers) is real."""
    recorded = os.path.join(repo_root, "benchmarks", "harness", "testdata",
                            "v5e_resnet18_fused.json.gz")

    def recorded_capture(path):
        capture = trace_lib.load_trace(recorded)
        del capture["host"]  # this run's own records take its place
        return capture

    monkeypatch.setattr(trace_lib, "load_xplane", recorded_capture)
    # a scratch layer metric: a new reader file and one manifest entry, no edit
    with open(os.path.join(tiny_root, "benchmarks", "layer_metrics", "epochs_in_window.py"),
              "w", encoding="utf-8") as f:
        f.write('LAYER = "host loop"\nUNIT = "epochs"\nMOVES = "samples_per_s"\n\n\n'
                "def read(window):\n    return float(len(window['epochs']))\n")
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        man = json.load(f)
    man["per_layer"].append({"name": "epochs_in_window", "unit": "epochs", "better": "higher",
                             "source": "host_clock", "layer": "host loop",
                             "moves": "samples_per_s", "workloads": ["tiny.stream"]})
    with open(path, "w", encoding="utf-8") as f:
        json.dump(man, f)
    assert manifest.check_manifest(tiny_root) == []

    result = run_cell(tiny_root, "tiny.stream", seed=3, seconds=1.5, trace=True, on_chip=False)
    _check_line(result, "tiny.stream")
    got = set(result["metrics"])
    assert got.isdisjoint(E2E)
    assert got >= {"data_wait_share", "producer_idle_share", "dispatch_ms",
                   "device_step_ms", "device_idle_share", "epochs_in_window"}
    # what needs a number of steps is there when this machine was fast enough
    assert ("loss_at_100" in got) == (result["attempted"] > 100)
    assert "step_ms_p99" in got or result["attempted"] < 40
    assert result["metrics"]["epochs_in_window"]["value"] >= 2
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
    bd = result["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    # the capture itself happened, and the program beside it says what it was given
    out = os.path.join(tiny_root, "chiprun_out", "trace", "tiny.stream")
    assert trace_lib.find_xplane(out) is not None
    with open(os.path.join(out, "program.json"), encoding="utf-8") as f:
        assert "matmul_computations" in json.load(f)


def test_same_seed_same_inputs_and_losses(tiny_root):
    from benchmarks.harness.adapter import Adapter
    import jax
    import numpy as np

    cell = manifest.load_cell(tiny_root, "tiny.stream")
    batches = []
    for seed in (4, 4, 5):
        ad = Adapter(cell, seed, jax.devices())
        assert ad.full_epoch_steps == 6
        x, y = ad.first_batch()
        batches.append((np.asarray(x), np.asarray(y)))
    assert (batches[0][0] == batches[1][0]).all() and (batches[0][1] == batches[1][1]).all()
    assert not (batches[0][0] == batches[2][0]).all()
    cut = Adapter(cell, 4, jax.devices()).first_batch(8)
    assert cut[1].shape == (8,) and (np.asarray(cut[0]) == batches[0][0][:8]).all()


def _run_command(root, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"), "--workload",
         "resnet18_cifar100.stream", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def test_command_refuses_without_a_tpu(repo_root):
    done = _run_command(repo_root, repo_root)
    assert done.returncode != 0
    assert "not on a TPU" in done.stderr
    assert not any(line.startswith("{") for line in done.stdout.splitlines())


def test_command_fails_where_only_the_benchmark_is(repo_root, tmp_path):
    bare = str(tmp_path / "bare")
    os.makedirs(bare)
    shutil.copy(os.path.join(repo_root, "BENCHMARK.json"), bare)
    shutil.copytree(os.path.join(repo_root, "benchmarks"), os.path.join(bare, "benchmarks"))
    done = _run_command(bare, bare)
    assert done.returncode != 0
    assert not any(line.startswith("{") for line in done.stdout.splitlines())


def test_unknown_cell_is_an_error(tiny_root):
    with pytest.raises(manifest.ManifestError):
        run_cell(tiny_root, "no.such", seed=0, seconds=1.0, trace=False, on_chip=False)
