"""The fleet drill — ``make fleet-drill`` / ``python -m tpu_dist.fleet.drill``.

The end-to-end proof of elastic scale-up + fleet arbitration
(docs/resilience.md "Scale-up & fleet scheduling"), self-contained on
CPU-emulated devices. Two phases:

**Phase grow** — the full elastic round trip, driven by the REAL
supervisor loop (``elastic/supervisor.py::supervise`` + a real
``CapacityProbe`` over a real allocation file):

1. **Golden** — an uninterrupted run at ``--devices`` (ZeRO-1 state so
   the dp-dependent layouts are real).
2. **Preempt** — round 0 with a deterministic ``sigterm@epoch=E:step=S``
   fault exits 75; the drill marks the preempted chips gone (allocation
   file → ``--shrink_to``), and the supervisor's failure relaunch is
   CAPPED BY THE CENSUS: it resumes at ``--shrink_to`` devices, state
   remapped onto the smaller extent.
3. **Grow** — when the shrunken world finishes an epoch, the drill
   returns the chips (allocation file → ``--devices``); the probe
   notices, the round checkpoints itself (SIGTERM → 75), and the
   supervisor relaunches at full size — the restore ladder grows the
   state back (TD112's remap path).
4. **Verify** — exit codes (75, 75, 0), a shrink resume record
   (``prev_dp=devices → dp=shrink_to``) AND a grow resume record
   (``prev_dp=shrink_to → dp=devices``) in the JSONL, the
   ``elastic.grows`` counter, and every epoch's loss within the
   golden-trajectory tolerance of the uninterrupted run.

Each round is a subprocess with its own
``--xla_force_host_platform_device_count`` (a process cannot change its
device count after the backend initializes), so "world size" here is
the emulated device count — the same state-remap path a multi-host
resize takes, without needing cross-process collectives on CPU.

**Phase fleet** — two REAL supervised launcher runs (stub children, no
jax) share one chip pool; the scheduler scrapes each run's OpenMetrics
textfile, decides the stalled run donates to the compute-bound one,
writes the allocation files — and both launchers act on it through
their capacity probes (donor: SIGTERM → 75 → relaunch smaller;
recipient: probe → grow). Verified: the auditable ``fleet`` decision
record (with its scraped inputs) and each run's observed world-size
sequence.
"""

from __future__ import annotations

import argparse
import os
import re
import signal
import subprocess
import sys
import time
from typing import List, Optional, Sequence

from tpu_dist.elastic.supervisor import CapacityProbe, RoundResult, supervise
from tpu_dist.fleet import capacity as capacity_lib
from tpu_dist.fleet.scheduler import (
    FleetPolicy,
    FleetScheduler,
    RunSpec,
    read_signals,
)
from tpu_dist.obs import export as export_lib
from tpu_dist.resilience.preemption import PREEMPTION_EXIT_CODE

#: Same golden-trajectory bound the elastic drill gates at: resumed
#: segments reduce over different device counts, so float order differs
#: while the math is the same.
LOSS_RTOL = 2e-3


def _say(msg: str) -> None:
    # tpu-dist: ignore[TD002,TD007] — single-process CLI; stdout is the report
    print(f"fleet-drill: {msg}", flush=True)


def _train_env(devices: int) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    inherited = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        env.get("XLA_FLAGS", ""),
    )
    env["XLA_FLAGS"] = (
        inherited + f" --xla_force_host_platform_device_count={devices}"
    ).strip()
    return env


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


# -- phase grow --------------------------------------------------------------


def run_grow_phase(args) -> int:
    golden_log = os.path.join(args.workdir, "golden.jsonl")
    elastic_log = os.path.join(args.workdir, "elastic.jsonl")
    cap_file = os.path.join(args.workdir, "allocation")
    base = [
        "--dataset", "synthetic", "--model", args.model,
        "--num_classes", "10", "--synthetic_n", "256",
        "--batch_size", str(args.batch_size),
        "--epochs", str(args.epochs),
        "--steps_per_epoch", str(args.steps_per_epoch),
        "--eval_every", "0", "--save_every", "1", "--log_every", "50",
        "--seed", "0", "--shard_weight_update",
    ]

    _say(f"phase golden: {args.devices} device(s), uninterrupted")
    rc = subprocess.call(
        [sys.executable, "-m", "tpu_dist.cli.train"] + base
        + ["--ckpt_dir", os.path.join(args.workdir, "ck_golden"),
           "--log_file", golden_log],
        env=_train_env(args.devices),
    )
    if rc != 0:
        _say(f"FAIL: golden run exited {rc}")
        return 1

    # the elastic run, driven by the REAL supervisor + capacity probe:
    # the allocation file starts at full capacity; the preemption takes
    # chips away, finishing an epoch at the shrunken size brings them back
    capacity_lib.write_allocation(cap_file, args.devices)
    probe = CapacityProbe(
        capacity_lib.make_census(cap_file),
        original=args.devices,
        min_procs=args.shrink_to,
        interval=0.3,
    )
    elastic_ck = os.path.join(args.workdir, "ck_elastic")
    capacity_returned = [False]
    seen_size = [0]  # re-parse the log only when it actually grew

    def shrunk_finished_an_epoch() -> bool:
        try:
            size = os.path.getsize(elastic_log)
        except OSError:
            return False
        if size == seen_size[0]:
            return False  # nothing new — don't re-parse the whole file
        seen_size[0] = size
        return any(
            r.get("kind") == "train_epoch"
            and r.get("epoch") == args.kill_epoch
            for r in _load(elastic_log)
        )

    def round_fn(n: int, round_idx: int) -> RoundResult:
        child = [sys.executable, "-m", "tpu_dist.cli.train"] + base + [
            "--ckpt_dir", elastic_ck, "--log_file", elastic_log,
        ]
        if round_idx == 0:
            child += [
                "--fault_plan",
                f"sigterm@epoch={args.kill_epoch}:step={args.kill_step}",
            ]
        else:
            child += ["--resume"]
        env = _train_env(n)
        env["TPU_DIST_ELASTIC_RESTARTS"] = str(round_idx)
        _say(f"round {round_idx}: {n} device(s)")
        proc = subprocess.Popen(child, env=env)
        probe.reset_timer()
        resize: Optional[int] = None
        while proc.poll() is None:
            time.sleep(0.2)
            if (
                not capacity_returned[0]
                and n == args.shrink_to
                and shrunk_finished_an_epoch()
            ):
                # the preempted chips came back — exactly the scale-up
                # trigger the probe exists to notice
                _say(f"capacity returns: allocation -> {args.devices}")
                capacity_lib.write_allocation(cap_file, args.devices)
                capacity_returned[0] = True
            if resize is None:
                target = probe.poll(n)
                if target is not None and target != n:
                    _say(
                        f"probe: census wants {target} (running {n}) — "
                        "checkpointing this round for the resize"
                    )
                    resize = target
                    proc.send_signal(signal.SIGTERM)
        rc = proc.returncode
        _say(f"round {round_idx}: exit {rc}")
        if round_idx == 0 and rc == PREEMPTION_EXIT_CODE:
            # the preemption took the chips with it: the supervisor's
            # failure relaunch must be capped by the census
            capacity_lib.write_allocation(cap_file, args.shrink_to)
        return RoundResult(rc, {0: rc}, resize)

    rc = supervise(
        round_fn,
        nproc=args.devices,
        min_procs=args.shrink_to,
        max_restarts=3,
        backoff_base=0.01,
        announce=lambda m: _say(f"supervisor: {m}"),
        probe=probe,
    )
    if rc != 0:
        _say(f"FAIL: supervised elastic run exited {rc}")
        return 1

    recs = _load(elastic_log)
    resumes = [r for r in recs if r.get("kind") == "resume"]
    shrinks = [
        r for r in resumes
        if r.get("prev_dp") == args.devices and r.get("dp") == args.shrink_to
    ]
    grows = [
        r for r in resumes
        if r.get("prev_dp") == args.shrink_to and r.get("dp") == args.devices
    ]
    if not shrinks:
        _say(f"FAIL: no shrink resume record ({args.devices}->{args.shrink_to})")
        return 1
    if not grows:
        _say(f"FAIL: no grow resume record ({args.shrink_to}->{args.devices})")
        return 1
    _say(
        f"resume records: shrank dp {args.devices}->{args.shrink_to}, "
        f"grew dp {args.shrink_to}->{args.devices}"
    )
    counters = [
        r.get("counters") for r in recs
        if isinstance(r.get("counters"), dict)
    ]
    if not any(c.get("elastic.grows") for c in counters):
        _say("FAIL: elastic.grows counter never observed in the history")
        return 1

    golden = _epoch_losses(_load(golden_log))
    elastic = _epoch_losses(recs)
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
        f"PASS grow: preempt-shrink {args.devices}->{args.shrink_to}, "
        f"probe-grow back to {args.devices}, trajectory within golden "
        "tolerance"
    )
    return 0


# -- phase fleet -------------------------------------------------------------

_STUB_CHILD = """
import os, signal, sys, time
argv = sys.argv
n = int(argv[argv.index('--num_processes') + 1])
rank = int(argv[argv.index('--process_id') + 1])
if rank == 0:
    with open(os.environ['DRILL_MARKER'], 'a') as f:
        f.write(f"{n} resume={'--resume' in argv}\\n")
signal.signal(signal.SIGTERM, lambda *a: sys.exit(75))
time.sleep(120)
"""


def _await(deadline: float, what: str, cond) -> bool:
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.2)
    _say(f"FAIL: timed out waiting for {what}")
    return False


def _worlds(marker: str) -> List[int]:
    if not os.path.exists(marker):
        return []
    return [int(ln.split()[0]) for ln in open(marker) if ln.strip()]


def run_fleet_phase(args, timeout_s: float = 90.0) -> int:
    """Two supervised stub runs on one pool; the scheduler moves chips
    from the deliberately stalled one to the compute-bound one based on
    genuinely scraped OpenMetrics textfiles."""
    fleet_dir = os.path.join(args.workdir, "fleet")
    scheduler = FleetScheduler(
        [RunSpec("stalled", 4, min_procs=1),
         RunSpec("compute", 4, min_procs=1)],
        policy=FleetPolicy(),
        fleet_dir=fleet_dir,
        allocations={"stalled": 4, "compute": 2},
        total_chips=6,
    )
    launchers = {}
    markers = {}
    try:
        for run in ("stalled", "compute"):
            marker = os.path.join(fleet_dir, run, "worlds.txt")
            markers[run] = marker
            env = dict(os.environ)
            env["DRILL_MARKER"] = marker
            launchers[run] = subprocess.Popen(
                [
                    sys.executable, "-m", "tpu_dist.cli.launch",
                    "--nproc", "4", "--elastic_min_procs", "1",
                    "--elastic_max_restarts", "3",
                    "--elastic_backoff", "0.01",
                    "--elastic_probe_interval", "0.3",
                    "--elastic_capacity_file",
                    scheduler.allocation_path(run),
                    "--", sys.executable, "-c", _STUB_CHILD,
                ],
                env=env,
            )
        deadline = time.monotonic() + timeout_s
        # both runs settle at their scheduler-granted allocations first
        # ("compute" launches at 4 and is shrunk to its allocation of 2 by
        # the census — the allocation file is authoritative from birth)
        if not _await(
            deadline, "runs to settle at allocations (4, 2)",
            lambda: _worlds(markers["stalled"])[-1:] == [4]
            and _worlds(markers["compute"])[-1:] == [2],
        ):
            return 1
        _say("both runs settled: stalled@4, compute@2")

        # each run's exporter textfile — written here the way the trainer
        # writes them, then GENUINELY scraped back by the scheduler
        sig = {}
        for run, stall, goodput, mfu in (
            ("stalled", 0.62, 0.35, 0.08),
            ("compute", 0.02, 0.93, 0.52),
        ):
            prom = os.path.join(fleet_dir, run, "metrics.prom")
            with open(prom, "w") as f:
                f.write(export_lib.render({
                    "train.data_stall_frac": stall,
                    "goodput.goodput_frac": goodput,
                    "train.mfu": mfu,
                    "train.epoch": 1,
                }))
            sig[run] = read_signals(run, prom)
            if sig[run].data_stall_frac != stall:
                _say(f"FAIL: scrape of {prom} did not round-trip")
                return 1
        # tick 0: the pool is dry, so the stalled run DONATES — its chips
        # bank as pending (the donor needs its checkpoint/relaunch window
        # to vacate them; granting now would oversubscribe the pool)
        decisions = scheduler.step(0, sig, ts=time.time())
        if not decisions or decisions[0].get("action") != "donate":
            _say(f"FAIL: expected a donation at tick 0, got {decisions}")
            return 1
        d = decisions[0]
        _say(f"decision: {d['reason']} — alloc {d['alloc_before']} -> "
             f"{d['alloc_after']}")
        if d["donor"] != "stalled" or d.get("for_run") != "compute":
            _say(f"FAIL: wrong donation {d}")
            return 1
        if not _await(
            deadline, "the donor to vacate (stalled->2)",
            lambda: _worlds(markers["stalled"])[-1:] == [2],
        ):
            return 1
        # tick 1: the banked chips mature into the free pool and the
        # compute-bound run is granted them
        decisions = scheduler.step(1, sig, ts=time.time())
        if not decisions or decisions[0].get("action") != "grant":
            _say(f"FAIL: expected a grant at tick 1, got {decisions}")
            return 1
        g = decisions[0]
        _say(f"decision: {g['reason']} — alloc {g['alloc_before']} -> "
             f"{g['alloc_after']}")
        if g["recipient"] != "compute" or g["donor"] is not None:
            _say(f"FAIL: wrong grant {g}")
            return 1
        if not _await(
            deadline, "the recipient to grow (compute->4)",
            lambda: _worlds(markers["compute"])[-1:] == [4],
        ):
            return 1
        hist = _load(scheduler.history_path())
        audited = [
            r for r in hist if r.get("kind") == "fleet" and r.get("inputs")
        ]
        if len(audited) != 2:
            _say(f"FAIL: expected 2 auditable fleet records, got {len(audited)}")
            return 1
        _say(
            "PASS fleet: stalled run donated 2 chips (worlds "
            f"{_worlds(markers['stalled'])}), compute-bound run was "
            f"granted them one tick later (worlds "
            f"{_worlds(markers['compute'])}); both decisions audited "
            "with their scraped inputs"
        )
        return 0
    finally:
        for proc in launchers.values():
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        for proc in launchers.values():
            try:
                proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                proc.kill()


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m tpu_dist.fleet.drill",
        description="preempt-shrink -> probe-grow -> fleet arbitration "
                    "drill (CPU)",
    )
    p.add_argument("--workdir", required=True, help="scratch dir for ckpts/logs")
    p.add_argument("--devices", type=int, default=8)
    p.add_argument("--shrink_to", type=int, default=4)
    p.add_argument("--model", default="vit_tiny")
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--steps_per_epoch", type=int, default=3)
    p.add_argument("--batch_size", type=int, default=32)
    p.add_argument("--kill_epoch", type=int, default=1)
    p.add_argument("--kill_step", type=int, default=1)
    p.add_argument(
        "--phase", choices=("all", "grow", "fleet"), default="all",
        help="'grow' = golden + preempt-shrink + probe-grow parity (jax "
             "subprocesses, slow); 'fleet' = the two-run arbitration "
             "drill (stub children, fast); 'all' = both",
    )
    args = p.parse_args(argv)
    os.makedirs(args.workdir, exist_ok=True)
    if args.phase in ("all", "grow"):
        rc = run_grow_phase(args)
        if rc != 0:
            return rc
    if args.phase in ("all", "fleet"):
        rc = run_fleet_phase(args)
        if rc != 0:
            return rc
    _say("PASS: all requested phases")
    return 0


if __name__ == "__main__":
    sys.exit(main())
