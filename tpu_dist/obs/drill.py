"""The postmortem drill — ``make postmortem-drill`` / ``python -m
tpu_dist.obs.drill``.

The end-to-end proof of the crash-forensics chain
(docs/observability.md "Crash forensics"), self-contained on
CPU-emulated devices:

1. **Wedge** — a REAL trainer (``vit_tiny``, synthetic data) runs under
   the REAL launcher with the full forensic kit injected
   (``--heartbeat_dir`` + ``--metrics_dir`` + ``--crash_dir`` +
   watchdog flags) and a deterministic ``hang@epoch=E:step=S`` fault:
   at that step the rank stops beating but stays alive — the failure
   mode no exit code ever reports.
2. **Detect + capture** — the launcher watchdog notices the frozen beat
   counter, sends ``SIGUSR1`` (the rank's registered faulthandler dump
   fires, naming the hang site), waits for the dump, THEN escalates
   SIGTERM→SIGKILL — and auto-invokes the postmortem assembler.
3. **Verify** — the launcher exited nonzero-and-not-75 (a wedge is a
   crash, never a requeue), its stderr names the wedged worker AND the
   stuck frame, the bundle's decoded flight ring ends exactly at the
   wedged step, the stack dump's current thread sits in the hang loop,
   and the ``postmortem`` record (history schema v9) landed in the
   run's JSONL where ``obs tail``/``summarize``/``pod`` render it.

One subprocess round, one wedged rank — the multi-rank wedge semantics
(healthy ranks torn down by the fail-fast SIGTERM) are covered by the
launcher watchdog tests; this drill proves the forensic CHAIN.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from typing import List, Optional, Sequence

from tpu_dist.obs import flight as flight_lib
from tpu_dist.obs import postmortem as postmortem_lib


def _say(msg: str) -> None:
    # tpu-dist: ignore[TD002,TD007] — single-process CLI; stdout is the report
    print(f"postmortem-drill: {msg}", flush=True)


def _fail(msg: str) -> int:
    _say(f"FAIL: {msg}")
    return 1


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m tpu_dist.obs.drill",
        description="hang -> watchdog -> SIGUSR1 dump -> postmortem drill "
                    "(CPU)",
    )
    p.add_argument("--workdir", required=True, help="scratch dir")
    p.add_argument("--devices", type=int, default=4)
    p.add_argument("--model", default="vit_tiny")
    p.add_argument("--steps_per_epoch", type=int, default=6)
    p.add_argument("--batch_size", type=int, default=64)
    p.add_argument("--hang_epoch", type=int, default=0)
    p.add_argument("--hang_step", type=int, default=3)
    p.add_argument(
        "--watchdog_timeout", type=float, default=10.0,
        help="must exceed the cold-compile stall of --model on this host "
             "(vit_tiny compiles in ~2s on CPU; raise for bigger models)",
    )
    p.add_argument("--watchdog_dump_grace", type=float, default=6.0)
    p.add_argument("--watchdog_grace", type=float, default=3.0)
    p.add_argument(
        "--round_timeout", type=float, default=600.0,
        help="hard cap on the whole launcher round — the drill must "
             "never itself wedge the CI job that runs it",
    )
    args = p.parse_args(argv)

    os.makedirs(args.workdir, exist_ok=True)
    log = os.path.join(args.workdir, "run.jsonl")
    fault = f"hang@epoch={args.hang_epoch}:step={args.hang_step}"
    launch_cmd = [
        sys.executable, "-m", "tpu_dist.cli.launch",
        "--nproc", "1", "--devices_per_proc", str(args.devices),
        "--heartbeat_dir", args.workdir,
        "--metrics_dir", args.workdir,
        "--crash_dir", args.workdir,
        "--watchdog_timeout", str(args.watchdog_timeout),
        "--watchdog_dump_grace", str(args.watchdog_dump_grace),
        "--watchdog_grace", str(args.watchdog_grace),
        "--",
        sys.executable, "-m", "tpu_dist.cli.train",
        "--dataset", "synthetic", "--model", args.model,
        "--num_classes", "10",
        "--batch_size", str(args.batch_size),
        "--epochs", "2", "--steps_per_epoch", str(args.steps_per_epoch),
        "--synthetic_n", str(4 * args.batch_size),
        "--seed", "0", "--eval_every", "0", "--log_every", "2",
        "--log_file", log,
        "--fault_plan", fault,
    ]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    _say(f"wedging a real {args.model} run with {fault!r} under the "
         f"watchdog (timeout {args.watchdog_timeout:.0f}s)")
    try:
        proc = subprocess.run(
            launch_cmd, env=env, timeout=args.round_timeout,
            capture_output=True, text=True,
        )
    except subprocess.TimeoutExpired:
        return _fail(
            f"launcher round exceeded {args.round_timeout:.0f}s — the "
            "watchdog never fired (is --watchdog_timeout sized right?)"
        )
    sys.stderr.write(proc.stderr)
    _say(f"launcher exit {proc.returncode}")

    failures: List[str] = []
    if proc.returncode in (0, 75):
        failures.append(
            f"launcher exited {proc.returncode} — a wedge must be a "
            "crash, never clean / requeue-75"
        )
    if "WATCHDOG: worker 0 wedged" not in proc.stderr:
        failures.append("watchdog never reported the wedged worker")
    if "stack dump: stuck in" not in proc.stderr:
        failures.append(
            "watchdog did not name the stuck frame from the SIGUSR1 dump"
        )
    if "postmortem bundle written" not in proc.stderr:
        failures.append("watchdog did not auto-invoke the postmortem")

    bundle_path = os.path.join(args.workdir, postmortem_lib.BUNDLE_NAME)
    if not os.path.exists(bundle_path):
        failures.append(f"no bundle at {bundle_path}")
    else:
        with open(bundle_path) as f:
            bundle = json.load(f)
        rank0 = next(
            (r for r in bundle.get("ranks", []) if r.get("rank") == 0), None
        )
        if rank0 is None:
            failures.append("bundle holds no rank-0 report")
        else:
            if rank0.get("verdict") != "no-clean-exit":
                failures.append(
                    f"rank-0 verdict {rank0.get('verdict')!r}, expected "
                    "'no-clean-exit' (the hard-kill signature)"
                )
            ls = (rank0.get("flight") or {}).get("last_step") or {}
            if (ls.get("epoch"), ls.get("step")) != (
                args.hang_epoch, args.hang_step
            ):
                failures.append(
                    f"flight ring ends at epoch {ls.get('epoch')} step "
                    f"{ls.get('step')}, expected the wedged step "
                    f"({args.hang_epoch}, {args.hang_step})"
                )
            else:
                _say(
                    f"flight ring ends at the wedged step (epoch "
                    f"{ls.get('epoch')}, step {ls.get('step')}) ✓"
                )
            stuck = (rank0.get("stack") or {}).get("stuck_frame") or ""
            if "_hang" not in stuck and "on_step" not in stuck:
                failures.append(
                    f"stack dump names {stuck!r}, expected the hang site "
                    "(faults._hang / faults.on_step)"
                )
            else:
                _say(f"stack dump names the hang site: {stuck} ✓")

    # the crash must be renderable from the run's own log (schema v9)
    pm_recs = []
    try:
        with open(log) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue  # the dead writer's torn tail — expected here
                if isinstance(rec, dict) and rec.get("kind") == "postmortem":
                    pm_recs.append(rec)
    except OSError:
        failures.append(f"run log {log} unreadable")
    if not pm_recs:
        failures.append(
            "no 'postmortem' record in the run's JSONL — the watchdog's "
            "annotate step did not land"
        )
    else:
        _say("postmortem record landed in the run's JSONL ✓")

    # and the ring must decode directly too (the CLI path)
    ring = os.path.join(args.workdir, flight_lib.RING_NAME)
    try:
        dec = flight_lib.decode(ring)
        _say(
            f"ring decodes: {len(dec['records'])} record(s), "
            f"{dec['torn_slots']} torn slot(s)"
        )
    except OSError as e:
        failures.append(f"flight ring unreadable: {e}")

    if failures:
        for msg in failures:
            _say(f"FAIL: {msg}")
        return 1
    _say("PASS: wedge detected, stack captured, bundle assembled — the "
         "whole forensic chain holds")
    return 0


if __name__ == "__main__":
    sys.exit(main())
