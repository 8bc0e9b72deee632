"""The elastic drill — ``make elastic-drill`` / ``python -m
tpu_dist.elastic.drill``.

A self-contained local proof of the elastic contract
(docs/resilience.md "Elastic training"), on CPU-emulated devices:

1. **Golden** — an uninterrupted run at ``--devices`` emulated devices
   (ZeRO-1 + error-feedback state, so the dp-dependent layouts are real).
2. **Preempt** — the same run with a deterministic
   ``sigterm@epoch=E:step=S`` fault: the trainer finishes the in-flight
   step, writes the exact mid-epoch emergency snapshot, and exits 75.
3. **Shrink + resume** — the same command relaunched at ``--shrink_to``
   devices with ``--resume``: the restore ladder remaps the checkpoint
   onto the smaller dp extent (ZeRO-1 flat vectors and EF residuals
   re-laid) and training continues mid-epoch.
4. **Verify** — exit codes (75 then 0), the ``resume`` record's
   ``resharded`` flag in the JSONL, and the continued loss trajectory
   against the golden run within the golden-trajectory tolerance.

Each phase is a subprocess with its own
``--xla_force_host_platform_device_count``, because a process cannot
change its device count after the backend initializes. The bit-identity
half of the proof (restored state vs emergency save) lives in
``tests/test_elastic.py``, where the restored arrays are inspectable
in-process.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
from typing import List, Optional, Sequence

from tpu_dist.resilience.preemption import PREEMPTION_EXIT_CODE

#: Relative loss tolerance — the golden-trajectory bound the test suite
#: uses (tests/test_golden_trajectory.py): the shrunk run reduces over a
#: different device count, so float reduction order differs while the
#: math is the same.
LOSS_RTOL = 2e-3


def _say(msg: str) -> None:
    # tpu-dist: ignore[TD002,TD007] — single-process CLI; stdout is the report
    print(f"elastic-drill: {msg}", flush=True)


def _run_phase(
    name: str, devices: int, train_args: List[str], extra_env: dict
) -> int:
    import re  # noqa: PLC0415

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # replace (not append) any inherited device-count flag: each phase
    # owns its own emulated device count
    inherited = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        env.get("XLA_FLAGS", ""),
    )
    env["XLA_FLAGS"] = (
        inherited + f" --xla_force_host_platform_device_count={devices}"
    ).strip()
    env.update(extra_env)
    cmd = [sys.executable, "-m", "tpu_dist.cli.train"] + train_args
    _say(f"phase {name}: {devices} device(s): {' '.join(train_args)}")
    rc = subprocess.call(cmd, env=env)
    _say(f"phase {name}: exit {rc}")
    return rc


def _load(log_path: str) -> List[dict]:
    from tpu_dist.obs.summarize import load_records  # one JSONL reader

    records, _bad = load_records(log_path)
    return records


def _epoch_losses(records: List[dict]) -> dict:
    return {
        rec.get("epoch"): rec["loss"]  # last segment wins
        for rec in records
        if rec.get("kind") == "train_epoch"
        and isinstance(rec.get("loss"), (int, float))
    }


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m tpu_dist.elastic.drill",
        description="preempt-at-step-k -> shrink -> parity drill (CPU)",
    )
    p.add_argument("--workdir", required=True, help="scratch dir for ckpts/logs")
    p.add_argument("--devices", type=int, default=8)
    p.add_argument("--shrink_to", type=int, default=4)
    p.add_argument("--model", default="vit_tiny")
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--steps_per_epoch", type=int, default=3)
    p.add_argument("--batch_size", type=int, default=64)
    p.add_argument("--kill_epoch", type=int, default=1)
    p.add_argument("--kill_step", type=int, default=1)
    p.add_argument(
        "--grad_compression", default="none",
        choices=("none", "bf16", "int8", "int8_ef"),
        help="wire format for the drilled run; 'none' (default) keeps the "
             "shrunk trajectory inside the tight golden tolerance (the "
             "int8 modes re-chunk quantization at the new extent — "
             "parity, but noisier); int8_ef additionally drills the EF "
             "residual remap, which tests/test_elastic.py covers "
             "bit-exactly in-process",
    )
    args = p.parse_args(argv)

    os.makedirs(args.workdir, exist_ok=True)
    golden_log = os.path.join(args.workdir, "golden.jsonl")
    elastic_log = os.path.join(args.workdir, "elastic.jsonl")
    base = [
        "--dataset", "synthetic", "--model", args.model,
        "--num_classes", "10", "--synthetic_n", "256",
        "--batch_size", str(args.batch_size),
        "--epochs", str(args.epochs),
        "--steps_per_epoch", str(args.steps_per_epoch),
        "--eval_every", "0", "--save_every", "1", "--log_every", "50",
        "--seed", "0", "--shard_weight_update",
        "--grad_compression", args.grad_compression,
    ]

    rc = _run_phase(
        "golden", args.devices,
        base + ["--ckpt_dir", os.path.join(args.workdir, "ck_golden"),
                "--log_file", golden_log],
        {},
    )
    if rc != 0:
        _say(f"FAIL: golden run exited {rc}")
        return 1

    elastic_ck = os.path.join(args.workdir, "ck_elastic")
    rc = _run_phase(
        "preempt", args.devices,
        base + ["--ckpt_dir", elastic_ck, "--log_file", elastic_log,
                "--fault_plan",
                f"sigterm@epoch={args.kill_epoch}:step={args.kill_step}"],
        {},
    )
    if rc != PREEMPTION_EXIT_CODE:
        _say(f"FAIL: preempted run exited {rc}, wanted {PREEMPTION_EXIT_CODE}")
        return 1

    rc = _run_phase(
        "shrink-resume", args.shrink_to,
        base + ["--ckpt_dir", elastic_ck, "--log_file", elastic_log,
                "--resume"],
        {"TPU_DIST_ELASTIC_RESTARTS": "1"},
    )
    if rc != 0:
        _say(f"FAIL: shrunk resume exited {rc}")
        return 1

    elastic_recs = _load(elastic_log)
    resumes = [r for r in elastic_recs if r.get("kind") == "resume"]
    if not resumes:
        _say("FAIL: no 'resume' record in the elastic log")
        return 1
    last = resumes[-1]
    if not last.get("resharded"):
        _say(f"FAIL: resume record not resharded: {last}")
        return 1
    _say(
        f"resume record: epoch {last.get('epoch')} dp {last.get('prev_dp')}"
        f" -> {last.get('dp')}, resharded"
    )

    golden = _epoch_losses(_load(golden_log))
    elastic = _epoch_losses(elastic_recs)
    for epoch, want in sorted(golden.items()):
        got = elastic.get(epoch)
        if got is None:
            _say(f"FAIL: elastic run has no epoch {epoch}")
            return 1
        rel = abs(got - want) / max(abs(want), 1e-12)
        _say(
            f"epoch {epoch}: golden loss {want:.6f}, elastic {got:.6f} "
            f"(rel {rel:.2e})"
        )
        if rel > LOSS_RTOL:
            _say(f"FAIL: loss diverged past rtol {LOSS_RTOL}")
            return 1
    _say(
        f"PASS: preempted at epoch {args.kill_epoch} step {args.kill_step} "
        f"on {args.devices} devices, resumed on {args.shrink_to}, state "
        "resharded, trajectory within golden tolerance"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
