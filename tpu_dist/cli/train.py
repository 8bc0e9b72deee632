"""Unified training CLI (SURVEY §1 L4 replacement).

The reference ships six near-identical scripts × three launch modes
(``torch.distributed.launch``, ``mp.spawn``, in-process); on TPU one
process drives all local chips, so there is ONE entry point and the
reference scripts become flag presets (see the sibling modules named after
them). All reference flags are accepted (``distributed.py:18-25``).

Usage::

    python -m tpu_dist.cli.train --batch_size 256 --epochs 200 --lr 0.1
    python -m tpu_dist.cli.train --bf16 --grad_accu_steps 4
    # multi-host (one invocation per host):
    python -m tpu_dist.cli.train --num_processes 4 --process_id $RANK \
        --ip <coordinator> --port 23456
"""

from __future__ import annotations

import argparse
from typing import Optional, Sequence

from tpu_dist.config import add_reference_flags, config_from_args
from tpu_dist.metrics.logging import rank0_print


def main(argv: Optional[Sequence[str]] = None, **preset):
    """Parse flags, build the :class:`Trainer`, ``fit()``; returns the
    trainer so an in-process caller can inspect what the run left behind."""
    parser = argparse.ArgumentParser(
        description="tpu_dist trainer (TPU-native DDP-equivalent)"
    )
    add_reference_flags(parser)
    args = parser.parse_args(argv)
    cfg = config_from_args(args, **preset)
    if cfg.compile_cache_dir is None:
        # an entry point, so reruns should load compiled programs: the
        # Trainer turns the cache on only when the config names a directory
        from tpu_dist import compile_cache  # noqa: PLC0415

        cfg = cfg.replace(compile_cache_dir=compile_cache.DEFAULT_DIR)

    from tpu_dist.resilience.preemption import (  # noqa: PLC0415
        PREEMPTION_EXIT_CODE,
        PreemptedError,
    )
    from tpu_dist.train.trainer import Trainer  # lazy: jax init after parse

    trainer = Trainer(cfg)
    dev0 = trainer.mesh.devices.flat[0]
    rank0_print(
        f"tpu_dist: model={cfg.model} devices={trainer.n_devices} "
        f"platform={dev0.platform} device_kind={dev0.device_kind!r} "
        f"global_batch={cfg.batch_size} bf16={cfg.bf16} sync_bn={cfg.sync_bn} "
        f"grad_accu_steps={cfg.grad_accu_steps}"
    )
    try:
        trainer.fit()
    except PreemptedError as e:
        # graceful preemption: the emergency snapshot discipline already ran
        # inside fit(); exit with the distinct requeue-me code instead of
        # dying on the signal (launch.py propagates it)
        rank0_print(f"=> preempted: {e}; exiting {PREEMPTION_EXIT_CODE}")
        raise SystemExit(PREEMPTION_EXIT_CODE) from None
    return trainer


if __name__ == "__main__":
    main()
