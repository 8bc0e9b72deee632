"""CLI layer: flag parsing, presets, trainer wiring (SURVEY §1 L4)."""

import pytest

from tpu_dist.cli import (
    dataparallel,
    dataparallel_apex,
    distributed,
    distributed_apex,
    distributed_gradient_accumulation,
    distributed_mp,
    train,
)


def test_train_cli_constructs_trainer_and_runs_zero_epochs(capsys):
    import jax

    from tpu_dist import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        # epochs=0: full CLI -> config -> Trainer init path without jit compiles
        trainer = train.main(
            ["--epochs", "0", "--dataset", "synthetic", "--batch_size", "64"]
        )
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    out = capsys.readouterr().out
    assert "model=resnet18" in out and "devices=8" in out
    # a run that landed on the CPU says so
    assert "platform=cpu" in out and "device_kind='cpu'" in out
    # the entry point asks for the in-checkout compile cache by default
    assert trainer.cfg.compile_cache_dir == compile_cache.DEFAULT_DIR


def test_presets_set_their_flags(monkeypatch):
    seen = {}

    def fake_main(argv=None, **preset):
        seen["argv"] = list(argv or [])
        seen["preset"] = preset

    for mod, expect_preset, expect_argv in [
        (dataparallel, {}, []),
        (dataparallel_apex, {"bf16": True}, []),
        (distributed, {}, []),
        (distributed_mp, {}, ["--seed", "1"]),
        (distributed_apex, {"bf16": True}, ["--seed", "1"]),
        (
            distributed_gradient_accumulation,
            {"drop_last": True},
            ["--grad_accu_steps", "4"],
        ),
    ]:
        monkeypatch.setattr(mod, "_main", fake_main)
        mod.main([])
        assert seen["preset"] == expect_preset, mod.__name__
        assert seen["argv"] == expect_argv, mod.__name__


def test_seed_flag_not_overridden_by_preset(monkeypatch):
    seen = {}
    monkeypatch.setattr(distributed_mp, "_main", lambda argv=None, **p: seen.update(argv=argv))
    distributed_mp.main(["--seed", "7"])
    assert seen["argv"] == ["--seed", "7"]


def test_unknown_flag_fails_loud():
    with pytest.raises(SystemExit):
        train.main(["--definitely_not_a_flag"])


def test_backend_flag_xla_only():
    """BASELINE north star names `--backend=xla`; nccl/gloo get a pointed
    refusal, not a silent ignore."""
    import argparse

    import pytest

    from tpu_dist.config import add_reference_flags, config_from_args

    p = add_reference_flags(argparse.ArgumentParser())
    cfg = config_from_args(p.parse_args(["--backend", "xla"]))
    assert cfg is not None
    with pytest.raises(SystemExit, match="nccl"):
        config_from_args(p.parse_args(["--backend", "nccl"]))
