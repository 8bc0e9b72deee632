"""Bench harness config integrity (no heavy compute — registry drift guard)."""

import os
import subprocess
import sys

import pytest

import bench


def test_all_configs_have_resolvable_models():
    from tpu_dist.nn import resnet18, resnet34, resnet50
    from tpu_dist.nn.resnet import resnet50_imagenet
    from tpu_dist.nn.vit import vit_b16

    known = {"resnet18", "resnet34", "resnet50", "resnet50_imagenet", "vit_b16"}
    for name, cfg in bench.CONFIGS.items():
        assert cfg.model in known, (name, cfg.model)
        assert cfg.global_batch % cfg.grad_accum == 0
        assert cfg.epoch_images > 0


def test_config_names_match_keys():
    for name, cfg in bench.CONFIGS.items():
        assert cfg.name == name


def test_bench_help_runs():
    out = subprocess.run(
        [sys.executable, "bench.py", "--help"],
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu",
             "PATH": "/usr/bin:/bin:/usr/local/bin", "PYTHONPATH": "."},
        cwd=".",
    )
    assert out.returncode == 0
    assert "--scaling" in out.stdout and "--all" in out.stdout


@pytest.mark.parametrize("script", ["bench.py", "chip_smoke.py"])
def test_measurement_entry_points_refuse_without_a_chip(script):
    """No TPU (here: an inherited JAX_PLATFORMS=cpu) -> non-zero exit, the
    platform named in the message, and no metric/result line on stdout."""
    out = subprocess.run(
        [sys.executable, script], capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode != 0
    assert "cpu" in out.stdout + out.stderr
    assert not [l for l in out.stdout.splitlines() if l.lstrip().startswith("{")]


def test_attn_microbench_smoke():
    """run_attn JSON contract at a tiny length (interpret mode on CPU)."""
    out = bench.run_attn(64, steps=1, warmup=0, batch=1)
    assert out["seq_len"] == 64
    assert out["unit"] == "tokens/sec"
    assert out["heads"] == 8 and out["head_dim"] == 128
    # flash ran (value present) — xla too on these tiny shapes
    assert out["flash_ms"] and out["xla_ms"]
    assert out["value"] and out["vs_baseline"]
