"""Local multi-process launcher — ``torch.distributed.launch`` / ``torchrun``
equivalent (SURVEY §2.2 N8).

On real TPU pods you normally run ONE process per host and the TPU runtime
does slice discovery, so this launcher exists for two cases the reference's
launchers cover:

* spinning up a multi-process run on one machine (CPU emulation of
  multi-host — each process gets its own device set via
  ``--xla_force_host_platform_device_count``),
* explicitly-coordinated multi-host setups where you want rank/env control
  (`--node_rank`-style splits).

Usage::

    python -m tpu_dist.cli.launch --nproc 2 --devices_per_proc 4 -- \
        python -m tpu_dist.cli.train --dataset synthetic --epochs 1

Injects ``--num_processes/--process_id/--ip/--port`` into the child command
line (the reference injects ``--local_rank``, ``distributed.py:18-25``) and
propagates failures: first child to die non-zero kills the rest.

Preemption contract (docs/resilience.md): a SIGTERM to the launcher is
FORWARDED to every child — each trainer finishes its in-flight step, runs
the emergency snapshot, and exits ``PREEMPTION_EXIT_CODE`` — and the
launcher then exits with that same distinct code (75, EX_TEMPFAIL) so the
orchestrator can requeue instead of treating preemption as a crash. A
child that exits with the preemption code on its own (e.g. a per-host
SIGTERM) propagates it the same way.

Watchdog contract (docs/observability.md): ``--heartbeat_dir`` injects
ONE base ``--heartbeat_file`` into every child; each process derives its
per-rank file from it (rank 0 keeps the bare path, rank k appends
``.h<k>``) and the launcher reads the same scheme back
(``heartbeat.read``). With ``--watchdog_timeout`` set, a child
whose beat counter stops advancing for that long while the process is
still alive is WEDGED — a deadlocked collective or dead loader, which no
exit code will ever report — and the launcher says which host stalled, in
which phase and at which position, counts the stall as goodput loss, and
terminates it (SIGTERM, then SIGKILL after ``--watchdog_grace``) instead
of waiting forever. With ``--metrics_dir`` the launcher additionally
injects one base ``--metrics_file`` into every child (per-rank derived
paths, the heartbeat scheme) and the watchdog SCRAPES the wedged
worker's last OpenMetrics exposition on the way to killing it — so the
report says not just that the heartbeat froze but WHY the worker was
sick: last epoch, data-stall fraction, MFU, goodput fraction, and which
alert rules were active (docs/observability.md "Live export"). A
watchdog kill is a failure, not a preemption: the
launcher exits nonzero even if the dying child manages its graceful
exit-75, because requeueing a deterministic wedge would loop the
orchestrator on it forever. Size the timeout above the worst cold-compile
stall — the watchdog cannot tell a wedged step from one that never beat.
Once a preemption shutdown begins the watchdog stands down: children beat
once ('preempted') then go silent in the emergency save by design, and
reclassifying that as a wedge would turn the requeue-75 exit into a crash.

Crash-forensics contract (docs/observability.md "Crash forensics"):
``--crash_dir`` injects ``--crash_dir`` into every child — each rank
writes a SIGKILL-surviving flight-recorder ring and arms a faulthandler
stack-capture file (``tpu_dist/obs/flight.py``). The watchdog then
upgrades its kill sequence for a live-but-frozen rank: it first sends
``SIGUSR1`` (the registered all-threads dump), waits up to
``--watchdog_dump_grace`` for the dump to land, and names the STUCK
FRAME (loader ``get``, collective dispatch, ckpt write, ...) in the
wedge report — only then does it escalate SIGTERM→SIGKILL. After a
wedged round ends, the launcher auto-invokes the postmortem assembler
(``python -m tpu_dist.obs postmortem``) over the forensics dirs: one
bundle per incident, plus a ``postmortem`` history record appended to
the run's JSONL so ``obs tail``/``summarize``/``pod`` render the crash.
At every round spawn the launcher also sweeps per-rank files of ranks
OUTSIDE the new world (``heartbeat.sweep_stale_ranks``) — after an
elastic shrink, a departed rank's lingering heartbeat/metrics/forensics
files must not read as a dead worker.

Elastic contract (docs/resilience.md "Elastic training"): with
``--elastic_min_procs`` set, the launcher becomes its own orchestrator for
the shrink case. A round that ends preempted (exit 75) or with dead ranks
is not the end of the run: the supervisor (``tpu_dist/elastic/
supervisor.py``) counts which ranks survived (clean / 75 / forwarded-
SIGTERM exits), picks the largest feasible reduced world size (a divisor
of the original ``--nproc``, at least the floor), waits the deterministic
backoff, and relaunches the command with ``--resume`` injected and
``TPU_DIST_ELASTIC_RESTARTS`` in the environment — the trainer's elastic
restore ladder remaps the checkpoint onto the new dp extent and the
sampler re-partitions the remaining examples. Bounded by
``--elastic_max_restarts``. A SIGTERM to the LAUNCHER itself still means
"the orchestrator wants the job gone": elastic stands down and the
distinct requeue-75 code propagates as before.

Scale-up contract (docs/resilience.md "Scale-up & fleet scheduling"):
with ``--elastic_probe_interval`` set, a shrunken run does not stay
small forever. The running round polls a capacity census — the
``--elastic_capacity_file`` allocation file when given (the channel the
fleet scheduler writes), else ``TPU_DIST_AVAILABLE_PROCS``, else the
original ``--nproc`` (a dedicated host's chips "return" as soon as the
preemption ends) — at the probe interval, with a deterministic
``resilience/retry.py`` cooldown between grow decisions so a flapping
census cannot thrash the run. When the census staffs a larger feasible
divisor (bounded by ``--elastic_max_procs``), the round gracefully
SIGTERMs its own world — every rank checkpoints and exits 75 — and the
supervisor relaunches ``--resume`` at the new size; the elastic restore
ladder grows the state back bit-exactly (TD112). The same probe carries
scheduler-initiated donations (the allocation file dropped below the
current size) and caps failure relaunches (never respawn onto chips the
scheduler took away). Resizes consume no restart budget. A SIGTERM to
the launcher stands the WHOLE policy down, probe included.
"""

from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence

from tpu_dist.elastic.supervisor import RoundResult, supervise
from tpu_dist.resilience.preemption import PREEMPTION_EXIT_CODE


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(description="tpu_dist multi-process launcher")
    p.add_argument("--nproc", type=int, required=True, help="processes to spawn")
    p.add_argument("--ip", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0, help="0 = pick a free port")
    p.add_argument(
        "--devices_per_proc", type=int, default=0,
        help=">0: give each process N emulated CPU devices (testing mode)",
    )
    p.add_argument(
        "--elastic_min_procs", type=int, default=0, metavar="N",
        help="enable the elastic supervisor: when a round ends preempted "
             "(exit 75) or with dead ranks, relaunch --resume at the "
             "largest feasible reduced world size (a divisor of --nproc) "
             "instead of failing the run, never below N; 0 (default) "
             "disables — one round, exit codes as before",
    )
    p.add_argument(
        "--elastic_max_restarts", type=int, default=3, metavar="K",
        help="elastic relaunch budget: give up (surfacing the real exit "
             "code) after K relaunches — a deterministic crash loop must "
             "not cycle forever",
    )
    p.add_argument(
        "--elastic_backoff", type=float, default=0.5, metavar="S",
        help="base of the deterministic exponential backoff between "
             "elastic relaunches (resilience/retry.py schedule: "
             "S * 2^restart, capped at 30s)",
    )
    p.add_argument(
        "--elastic_probe_interval", type=float, default=0.0, metavar="S",
        help="with the elastic supervisor on: poll the capacity census "
             "every S seconds while a round runs; when it staffs a "
             "larger feasible divisor the round checkpoints (graceful "
             "SIGTERM -> exit 75) and relaunches --resume at the bigger "
             "size — a shrunken run grows back when chips return. A "
             "census below the current size is a scheduler donation: "
             "same path, smaller relaunch. 0 (default) disables probing",
    )
    p.add_argument(
        "--elastic_max_procs", type=int, default=0, metavar="N",
        help="ceiling for probe-driven grows (never above --nproc); "
             "0 (default) = --nproc",
    )
    p.add_argument(
        "--elastic_capacity_file", default=None, metavar="PATH",
        help="allocation file the capacity census reads (one integer, "
             "atomically written — the fleet scheduler's channel, "
             "tpu_dist/fleet/capacity.py); without it the census falls "
             "back to TPU_DIST_AVAILABLE_PROCS, then to --nproc",
    )
    p.add_argument(
        "--elastic_same_size_retries", type=int, default=2, metavar="K",
        help="consecutive whole-pod-loss retries at the SAME world size "
             "before the supervisor steps down one divisor (floor "
             "permitting) — one flaky round doesn't shrink the run, a "
             "persistently preempted size doesn't burn the whole budget",
    )
    p.add_argument(
        "--heartbeat_dir", default=None,
        help="inject --heartbeat_file <dir>/hb.json into every child "
             "(each process beats its own derived file: rank 0 the bare "
             "path, rank k .h<k>) and watch the files for liveness",
    )
    p.add_argument(
        "--metrics_dir", default=None,
        help="inject --metrics_file <dir>/metrics.prom into every child "
             "(per-rank derived paths, like the heartbeat) so the "
             "watchdog can scrape a wedged worker's last exposition and "
             "report WHY it was sick, not just that its beat froze",
    )
    p.add_argument(
        "--crash_dir", default=None,
        help="inject --crash_dir <dir> into every child (per-rank "
             "flight-recorder ring + faulthandler stack file, "
             "tpu_dist/obs/flight.py); the watchdog then SIGUSR1s a "
             "wedged rank for an all-threads stack dump and names the "
             "stuck frame before killing it, and a wedged round is "
             "auto-assembled into a postmortem bundle "
             "(docs/observability.md 'Crash forensics')",
    )
    p.add_argument(
        "--watchdog_dump_grace", type=float, default=5.0, metavar="S",
        help="with --crash_dir: seconds the watchdog waits for a wedged "
             "rank's SIGUSR1 stack dump to land before escalating to "
             "SIGTERM (a truly dead interpreter never answers the dump "
             "signal — the escalation must not wait on it forever)",
    )
    p.add_argument(
        "--watchdog_timeout", type=float, default=0.0, metavar="S",
        help="with --heartbeat_dir: a child whose heartbeat counter "
             "stops advancing for S seconds while the process lives is "
             "wedged — report which host/phase and terminate it instead "
             "of waiting forever; 0 disables. Must exceed the worst "
             "compile stall",
    )
    p.add_argument(
        "--watchdog_grace", type=float, default=10.0, metavar="S",
        help="seconds between the watchdog's SIGTERM and its SIGKILL",
    )
    p.add_argument("cmd", nargs=argparse.REMAINDER, help="-- command to run")
    args = p.parse_args(argv)

    cmd = list(args.cmd)
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        p.error("missing command (after --)")
    if args.watchdog_timeout > 0 and not args.heartbeat_dir:
        p.error("--watchdog_timeout needs --heartbeat_dir (the liveness "
                "signal it watches)")
    if args.elastic_min_procs > args.nproc:
        p.error(f"--elastic_min_procs {args.elastic_min_procs} exceeds "
                f"--nproc {args.nproc}")

    hb_base = None
    if args.heartbeat_dir:
        os.makedirs(args.heartbeat_dir, exist_ok=True)
        # one BASE path injected into every child; the trainer derives its
        # per-rank file from it (heartbeat.per_rank_path — rank 0 = bare
        # path, rank k = .h<k>), and the watchdog reads the same scheme
        hb_base = os.path.join(args.heartbeat_dir, "hb.json")
    metrics_base = None
    if args.metrics_dir:
        os.makedirs(args.metrics_dir, exist_ok=True)
        # same per-rank scheme as the heartbeat: the trainer derives
        # .h<k> textfiles and the watchdog scrapes them back
        metrics_base = os.path.join(args.metrics_dir, "metrics.prom")
    if args.crash_dir:
        # the dir itself is the injected flag: each rank derives its own
        # ring/stacks files inside it (obs/flight.py naming)
        os.makedirs(args.crash_dir, exist_ok=True)

    live: List[subprocess.Popen] = []  # the CURRENT round's children
    launcher_sig = [False]  # SIGTERM delivered to the LAUNCHER itself

    def _forward_sigterm(signum, frame):  # noqa: ARG001
        # graceful fan-out: children run their own SIGTERM discipline
        # (emergency snapshot + distinct exit code); we keep waiting for
        # them below instead of dying and orphaning the job. This is also
        # the elastic stand-down signal: the orchestrator preempting the
        # whole job outranks any local relaunch policy.
        launcher_sig[0] = True
        for pr in list(live):
            try:
                pr.send_signal(signal.SIGTERM)
            except OSError:  # tpu-dist: ignore[TD006] — child already gone
                pass

    try:
        prev_term = signal.signal(signal.SIGTERM, _forward_sigterm)
    except ValueError:  # not the main thread (embedded use) — skip
        prev_term = None
    try:
        def say(msg: str) -> None:
            # tpu-dist: ignore[TD002,TD007] — the launcher IS the single
            # parent process and stderr is its orchestrator contract
            print(f"launch: {msg}", file=sys.stderr, flush=True)

        probe = None
        start_procs = None
        if args.elastic_min_procs > 0 and args.elastic_probe_interval > 0:
            from tpu_dist.elastic.supervisor import (  # noqa: PLC0415
                CapacityProbe,
                next_world_size,
            )
            from tpu_dist.fleet import capacity as capacity_lib  # noqa: PLC0415

            probe = CapacityProbe(
                capacity_lib.make_census(
                    args.elastic_capacity_file, default=args.nproc
                ),
                original=args.nproc,
                min_procs=args.elastic_min_procs,
                max_procs=args.elastic_max_procs,
                interval=args.elastic_probe_interval,
            )
            # the census is authoritative from BIRTH: a run whose chips
            # are currently granted elsewhere (the fleet scheduler wrote
            # a smaller allocation before launch) must not spawn round 0
            # on top of another run and then shrink — start at the
            # granted feasible size; the probe grows it back later
            avail = probe.available()
            if avail is not None and avail < args.nproc:
                granted = next_world_size(
                    args.nproc, int(avail), args.elastic_min_procs
                )
                if granted is None:
                    say(
                        f"elastic: capacity census grants only {avail} "
                        f"proc(s) — below min_procs="
                        f"{args.elastic_min_procs}; refusing to start"
                    )
                    return 1
                say(
                    f"elastic: capacity census grants {granted} of "
                    f"{args.nproc} proc(s) at launch"
                )
                start_procs = granted

        def round_fn(nproc: int, restart: int) -> RoundResult:
            return _run_round(
                args, cmd, nproc, restart, hb_base, metrics_base,
                live, launcher_sig, probe=probe, say=say,
            )

        if args.elastic_min_procs <= 0:
            return round_fn(args.nproc, 0).rc

        return supervise(
            round_fn,
            nproc=args.nproc,
            min_procs=args.elastic_min_procs,
            max_restarts=args.elastic_max_restarts,
            backoff_base=args.elastic_backoff,
            announce=say,
            should_continue=lambda: not launcher_sig[0],
            probe=probe,
            same_size_retries=args.elastic_same_size_retries,
            start_procs=start_procs,
        )
    finally:
        if prev_term is not None:
            signal.signal(signal.SIGTERM, prev_term)
        for pr in live:
            pr.kill()


def _run_round(
    args,
    cmd: List[str],
    nproc: int,
    restart: int,
    hb_base: Optional[str],
    metrics_base: Optional[str],
    live: List[subprocess.Popen],
    launcher_sig: List[bool],
    probe=None,
    say=None,
) -> RoundResult:
    """Spawn and supervise ONE world: ``nproc`` children at a fresh
    coordinator port, fail-fast + watchdog + preemption semantics exactly
    as the single-round launcher always had. Returns the aggregate exit
    code plus every rank's raw exit status — the elastic supervisor's
    survivor census. ``live`` is the launcher-level registry the SIGTERM
    handler forwards to (children of the current round only).

    ``probe`` (a ``CapacityProbe``) arms the resize path: the wait loop
    polls it, and a census that staffs a different feasible size makes
    this round stand its own world down gracefully (SIGTERM -> every
    rank checkpoints and exits 75) and report ``resize_to`` — the
    supervisor relaunches ``--resume`` at the new size."""
    port = args.port or _free_port()
    procs: List[subprocess.Popen] = []
    ranks: Dict[subprocess.Popen, int] = {}
    exits: Dict[int, int] = {}
    preempted = [launcher_sig[0]]  # a child's exit-75 also sets this
    resize_to: List[Optional[int]] = [None]  # probe-requested new size
    announce = say if say is not None else (lambda _msg: None)
    if probe is not None:
        # a freshly spawned world always gets one full probe interval to
        # settle before the census may bounce it again
        probe.reset_timer()

    # elastic-resize hygiene: per-rank files of ranks OUTSIDE this
    # round's world (heartbeats/metrics/forensics a departed rank left
    # behind after a shrink) must be swept BEFORE spawning — a lingering
    # rank-6 heartbeat in a 4-wide world would read as a dead worker to
    # the watchdog and to `obs pod`
    from tpu_dist.obs import heartbeat as heartbeat_lib  # noqa: PLC0415

    stale_bases = [b for b in (hb_base, metrics_base) if b]
    if args.crash_dir:
        from tpu_dist.obs import flight as flight_lib  # noqa: PLC0415

        stale_bases += [
            os.path.join(args.crash_dir, flight_lib.RING_NAME),
            os.path.join(args.crash_dir, flight_lib.STACKS_NAME),
        ]
    swept = sum(
        heartbeat_lib.sweep_stale_ranks(base, nproc) for base in stale_bases
    )
    if swept:
        announce(
            f"swept {swept} stale per-rank file(s) from ranks outside "
            f"the new world of {nproc}"
        )

    # causal arbitration tracing: when this (re)launch is the actuation
    # of a fleet decision, the allocation file carries the scheduler's
    # decision_id/cause tokens — read ONCE per round and stamped into
    # every child so the trainer's resume record, flight-ring slot, and
    # goodput window can name the arbitration (stale values from the
    # launcher's own env are cleared by the stamp helper)
    from tpu_dist.elastic.supervisor import (  # noqa: PLC0415
        DECISION_CAUSE_ENV,
        DECISION_ID_ENV,
        read_decision,
    )

    meta = read_decision(getattr(args, "elastic_capacity_file", None))
    if restart > 0 and meta["decision_id"] is not None:
        announce(
            f"relaunch actuates fleet decision {meta['decision_id']}"
            + (f" ({meta['cause']})" if meta["cause"] else "")
        )

    try:
        for rank in range(nproc):
            env = dict(os.environ)
            if args.devices_per_proc > 0:
                env["JAX_PLATFORMS"] = "cpu"  # CPU emulation mode
                env["XLA_FLAGS"] = (
                    env.get("XLA_FLAGS", "")
                    + f" --xla_force_host_platform_device_count={args.devices_per_proc}"
                ).strip()
            # relaunched rounds tell the trainer which restart they are
            # (elastic.restarts gauge); round 0 stamps 0 so a child's env
            # never inherits a stale value from the launcher's own env
            env["TPU_DIST_ELASTIC_RESTARTS"] = str(restart)
            # one meta read per ROUND (above), applied to every rank —
            # a mid-loop allocation rewrite must not split the world
            # across two decision ids
            for key, val in (
                (DECISION_ID_ENV, meta["decision_id"]),
                (DECISION_CAUSE_ENV, meta["cause"]),
            ):
                if val is not None:
                    env[key] = str(val)
                else:
                    env.pop(key, None)
            child = cmd + [
                "--num_processes", str(nproc),
                "--process_id", str(rank),
                "--ip", args.ip,
                "--port", str(port),
            ]
            if restart > 0 and "--resume" not in cmd:
                # the relaunched world must continue the run, not restart
                # it — the trainer's elastic restore ladder picks up the
                # emergency/periodic checkpoint and remaps onto the new
                # dp extent
                child.append("--resume")
            if hb_base is not None:
                child += ["--heartbeat_file", hb_base]
            if metrics_base is not None:
                child += ["--metrics_file", metrics_base]
            if args.crash_dir is not None:
                child += ["--crash_dir", args.crash_dir]
            pr = subprocess.Popen(child, env=env)
            procs.append(pr)
            live.append(pr)
            ranks[pr] = rank

        rc = 0
        crash_rc = 0  # first exit that is neither clean, preemption, nor
        # death-by-our-own-SIGTERM — a REAL failure that must never be
        # reported as "requeue me"
        # watchdog state per rank: last seen beat counter, when it last
        # advanced (spawn counts as the first advance — a child that never
        # beats at all is as wedged as one that stopped), and the SIGKILL
        # deadline once the watchdog fired
        now = time.monotonic()
        wd_seen: Dict[int, tuple] = {ranks[pr]: (None, now) for pr in procs}
        wd_kill_at: Dict[int, float] = {}
        # stack-capture state (--crash_dir): rank -> [dump deadline,
        # stack-file size before SIGUSR1, size at the last poll] — the
        # watchdog waits for the dump to land AND settle before it
        # parses the appended bytes and escalates
        wd_dump: Dict[int, list] = {}
        wedged: List[int] = []  # ranks the watchdog declared wedged
        watchdog = args.watchdog_timeout > 0

        def _stack_path(rank: int) -> Optional[str]:
            if not args.crash_dir:
                return None
            from tpu_dist.obs import flight as flight_lib  # noqa: PLC0415
            from tpu_dist.obs import heartbeat as heartbeat_lib  # noqa: PLC0415

            return heartbeat_lib.per_rank_path(
                os.path.join(args.crash_dir, flight_lib.STACKS_NAME), rank
            )

        def _stack_size(rank: int) -> int:
            path = _stack_path(rank)
            try:
                return os.path.getsize(path) if path else 0
            except OSError:
                return 0

        def _sick_report(rank: int) -> str:
            """WHY the wedged worker was sick: its last OpenMetrics
            exposition (the exporter leaves the textfile behind exactly
            for this read). Empty string when nothing is scrapeable —
            the watchdog's heartbeat-only report still stands."""
            if metrics_base is None:
                return ""
            from tpu_dist.obs import export as export_lib  # noqa: PLC0415
            from tpu_dist.obs import heartbeat as heartbeat_lib  # noqa: PLC0415

            vals = export_lib.scrape(
                textfile=heartbeat_lib.per_rank_path(metrics_base, rank)
            )
            if not vals:
                return ""
            # ONE gauge set shared with the postmortem assembler
            # (export.KEY_GAUGES) — the two reads can never drift
            parts = [
                f"{label} {v}"
                for label, v in export_lib.key_gauges(vals).items()
            ]
            active = export_lib.active_labels(vals)
            if active:
                parts.append(f"active alerts: {', '.join(active)}")
            return (
                f"; last exposition: {', '.join(parts)}" if parts else ""
            )

        def _watch(pr) -> None:
            nonlocal crash_rc
            from tpu_dist.obs import heartbeat as heartbeat_lib  # noqa: PLC0415

            if preempted[0] or launcher_sig[0] or resize_to[0] is not None:
                # preemption/resize shutdown: each child beats once
                # ('preempted') then goes silent in its emergency save BY
                # DESIGN — a frozen counter here is not a wedge, and
                # reclassifying it would turn the requeue-75 exit into a
                # crash. A truly stuck shutdown is bounded by the
                # platform's own SIGKILL deadline, not by us.
                return
            rank = ranks[pr]
            t = time.monotonic()
            if rank in wd_kill_at:
                if t >= wd_kill_at[rank]:
                    pr.kill()  # SIGTERM grace expired — it really is stuck
                return
            if rank in wd_dump:
                # stack capture in flight: wait for the SIGUSR1 dump to
                # land and settle (two same-size polls), bounded by the
                # dump grace — a dead interpreter never answers
                deadline, size0, last_size = wd_dump[rank]
                size = _stack_size(rank)
                if t < deadline and (size <= size0 or size != last_size):
                    wd_dump[rank][2] = size
                    return
                from tpu_dist.obs import flight as flight_lib  # noqa: PLC0415

                parsed = (
                    flight_lib.read_stack_dump(_stack_path(rank), offset=size0)
                    if size > size0 else None
                )
                frame = flight_lib.stuck_frame(parsed) if parsed else None
                # tpu-dist: ignore[TD002,TD007] — the launcher IS the
                # single parent process; stderr is its orchestrator
                # contract (same as the wedge report above)
                print(
                    f"launch: WATCHDOG: worker {rank} stack dump: "
                    + (
                        f"stuck in {frame} "
                        f"({len(parsed['threads'])} thread(s) dumped)"
                        if frame else
                        "no dump captured (interpreter not answering "
                        "SIGUSR1 — likely stuck in native code)"
                    ),
                    file=sys.stderr, flush=True,
                )
                del wd_dump[rank]
                wd_kill_at[rank] = t + args.watchdog_grace
                try:
                    pr.send_signal(signal.SIGTERM)
                except OSError:  # tpu-dist: ignore[TD006] — child gone
                    pass
                return
            rec = heartbeat_lib.read(heartbeat_lib.per_rank_path(hb_base, rank))
            counter = rec.get("counter") if rec else None
            last_counter, last_adv = wd_seen[rank]
            if counter != last_counter:
                wd_seen[rank] = (counter, t)
                return
            stalled = t - last_adv
            if stalled < args.watchdog_timeout:
                return
            # wedged: alive but silent — no exit code would ever tell us
            where = (
                f"epoch {rec.get('epoch')} step {rec.get('step')} phase "
                f"{rec.get('phase')!r}" if rec else "before its first beat"
            )
            # tpu-dist: ignore[TD002,TD007] — the launcher IS the single
            # parent process (no ranks to guard), and stderr is its
            # contract with the orchestrator, same as the exit codes
            print(
                f"launch: WATCHDOG: worker {rank} wedged — heartbeat "
                f"stalled {stalled:.0f}s at {where}; terminating "
                f"(~{stalled:.0f}s goodput loss on this host)"
                + _sick_report(rank),
                file=sys.stderr, flush=True,
            )
            if crash_rc == 0:
                crash_rc = 1  # a wedge is a failure, never a requeue-75
            wedged.append(rank)
            if args.crash_dir:
                # stack capture FIRST: ask the frozen-but-live interpreter
                # WHERE it is (the rank's faulthandler registered SIGUSR1
                # as an all-threads dump) — the kill escalation waits for
                # the answer, bounded by --watchdog_dump_grace
                size0 = _stack_size(rank)
                try:
                    pr.send_signal(signal.SIGUSR1)
                except OSError:  # tpu-dist: ignore[TD006] — child gone
                    pass
                wd_dump[rank] = [t + args.watchdog_dump_grace, size0, size0]
                # tpu-dist: ignore[TD002,TD007] — launcher stderr contract
                print(
                    f"launch: WATCHDOG: requesting all-threads stack dump "
                    f"from worker {rank} (SIGUSR1), waiting up to "
                    f"{args.watchdog_dump_grace:.0f}s before escalating",
                    file=sys.stderr, flush=True,
                )
                return
            wd_kill_at[rank] = t + args.watchdog_grace
            try:
                pr.send_signal(signal.SIGTERM)
            except OSError:  # tpu-dist: ignore[TD006] — child already gone
                pass

        pending = list(procs)
        while pending:
            if (
                probe is not None and resize_to[0] is None
                and not preempted[0] and not launcher_sig[0]
                and crash_rc == 0
            ):
                target = probe.poll(nproc)
                if target is not None and target != nproc:
                    # capacity changed: stand this world down gracefully —
                    # every rank checkpoints (emergency save) and exits 75,
                    # and the supervisor relaunches --resume at the target
                    resize_to[0] = target
                    announce(
                        "elastic: capacity census wants world size "
                        f"{target} (running {nproc}) — checkpointing this "
                        "round for the resize"
                    )
                    for pr in list(pending):
                        try:
                            pr.send_signal(signal.SIGTERM)
                        except OSError:  # tpu-dist: ignore[TD006] — child gone
                            pass
            for pr in list(pending):
                ret = pr.poll()
                if ret is None:
                    if watchdog:
                        _watch(pr)
                    continue
                pending.remove(pr)
                exits[ranks[pr]] = ret
                if ret == PREEMPTION_EXIT_CODE:
                    preempted[0] = True
                elif ret not in (0, -signal.SIGTERM) and crash_rc == 0:
                    crash_rc = ret
                if ret != 0 and rc == 0:
                    rc = ret
                    for other in pending:  # fail fast like torchrun — which,
                        # with the trainer's cooperative handler installed,
                        # is a GRACEFUL shutdown request, not a kill
                        other.send_signal(signal.SIGTERM)
            if pending:
                try:
                    pending[0].wait(timeout=1)
                except subprocess.TimeoutExpired:
                    pass
        if wedged and args.crash_dir:
            # the forensic epilogue: assemble everything the dead world
            # left behind into ONE bundle + a `postmortem` history record
            # (obs tail/summarize/pod render it). Never raises — a broken
            # postmortem must not change the exit-code contract.
            _auto_postmortem(args, announce, wedged)
        if crash_rc:
            # a crash/wedge outranks a concurrent preemption AND a resize
            # request (the supervisor's failure path must see the real
            # census, not a voluntary-looking resize)
            return RoundResult(crash_rc, exits)
        if (
            preempted[0] or launcher_sig[0]
            or (resize_to[0] is not None and rc != 0)
        ) and rc in (0, PREEMPTION_EXIT_CODE, -signal.SIGTERM):
            # the whole job was preempted (not crashed): surface the
            # distinct requeue-me code even if some child died on the raw
            # signal before its handler was installed. A probe-driven
            # resize rides this same path (graceful 75s) and carries its
            # target so the supervisor relaunches instead of retrying.
            return RoundResult(PREEMPTION_EXIT_CODE, exits, resize_to[0])
        return RoundResult(rc, exits)
    finally:
        for pr in procs:
            pr.kill()  # no-op on already-reaped children
            if pr in live:
                live.remove(pr)


def _auto_postmortem(args, say, wedged: List[int]) -> None:
    """Watchdog epilogue: run the postmortem assembler over every
    forensics dir this launcher injected, write the bundle, annotate the
    run's history (when one is discoverable), and summarize the wedged
    ranks on stderr. Best-effort by contract."""
    from tpu_dist.obs import postmortem as postmortem_lib  # noqa: PLC0415

    dirs = [
        d for d in (args.crash_dir, args.heartbeat_dir, args.metrics_dir)
        if d
    ]
    try:
        report, bundle = postmortem_lib.run_postmortem(dirs, annotate=True)
    except Exception as e:
        say(f"postmortem assembly failed: {e}")
        return
    if bundle is None:
        say("postmortem: no forensic artifacts found")
        return
    say(f"postmortem bundle written to {bundle}")
    for r in report["ranks"]:
        if r["rank"] not in wedged:
            continue
        stuck = (r.get("stack") or {}).get("stuck_frame")
        ls = (r.get("flight") or {}).get("last_step")
        say(
            f"postmortem: rank {r['rank']} verdict {r['verdict']}"
            + (f", stuck in {stuck}" if stuck else "")
            + (
                f", flight ring ends at epoch {ls.get('epoch')} step "
                f"{ls.get('step')}" if ls else ""
            )
        )


if __name__ == "__main__":
    sys.exit(main())
