"""JSONL metrics history — file-based observability the reference reserves
but never builds (``.gitignore:3`` ignores ``/log``; tensorboard knob dead
in ``utils/config.py:8``). One JSON object per line, append-only, rank-0
only; consumable by pandas/jq/tensorboard-importers and by
``python -m tpu_dist.obs summarize`` (docs/observability.md).

Schema (version 6): every record carries

* ``ts`` — wall clock (epoch seconds; for humans and cross-run joins),
* ``rel_s`` — monotonic seconds since this history opened (immune to NTP
  steps; what offline latency math should use),
* ``schema_version`` and, when the owner passed one, ``run_id`` (config
  hash + start time stamped ONCE at construction — not re-derived per
  record, so every line of a run agrees),
* ``kind`` plus the caller's fields,
* ``counters`` — a snapshot of the process-global telemetry registry
  (``tpu_dist.obs.counters``), when non-empty; the summarize CLI turns
  successive snapshots into per-epoch deltas.

Version history: v2 added ``rel_s``/``run_id``/``counters``; v3 added the
device-health layer — ``device_stats`` and ``anomaly`` record kinds and
the ``mfu`` field on ``train_epoch``; v4 added the fleet layer —
``goodput`` (per-window wall-clock buckets + a run-end ``final`` totals
record) and ``profile`` (triggered device-capture events) kinds; v5
added the live layer — the ``alert`` kind (a declarative threshold rule
fired: rule/metric/value/threshold/sustained, ``obs/alerts.py``); v6
added the analytics layer — the ``profile_analysis`` kind (per-capture
device-time attribution read back from the trace by ``obs/xprof.py``:
category seconds, collectives by kind, comm/compute overlap fraction,
infeed stall, top ops, cost-model ``calibration`` gauges); v7 added the
elastic layer — the ``resume`` segment-boundary kind; v8 added the fleet
layer — the ``fleet`` kind (a scheduler chip-move decision with the
allocations before/after and the scraped signals that justified it); v9
added the forensics layer — the ``postmortem`` kind (a crash bundle
assembled from a dead run's leftover files: per-rank verdicts, stuck
frames, last flight-ring steps — ``obs/postmortem.py``, appended by the
watchdog's auto-invoke rather than by the dying run itself); v10 added
the serving layer — the ``serve`` kind (one SLO observation window per
record: latency percentile bounds, requests/s, availability, batch
occupancy, per-phase latency sums, a compact latency histogram —
``tpu_dist/serve``, docs/serving.md); v11 added the memory layer — the
``memory`` kind (the HBM ledger captured at first dispatch: static
per-leaf accounting from avals+shardings, the ``memory_analysis()``
waterfall, a live-buffer census reconciled against the allocator so
attributed + unattributed == bytes_in_use exactly; ``event: "oom"``
records carry a parsed RESOURCE_EXHAUSTED report plus the ledger
snapshot live at the crash — ``obs/memory.py``, docs/observability.md
"HBM ledger & OOM forensics"); v12 and v13 added the ``plan`` and
``tune`` kinds, the announcements of a static sharding planner and a
collective-schedule tuner that are gone — both kinds are RETIRED:
nothing writes them, and an old log that holds them reads like any log
with kinds the reader does not know (skipped, with a count)
(docs/observability.md). Consumers (``obs summarize``/``compare``) read
all versions: every addition is a new kind or optional field, never a
changed one, and readers skip-with-count kinds they don't know — so a
v4 reader tolerates a v5 log the same way a v5 reader tolerates a v6
one.

The file handle is opened once, line-buffered, and reused — the previous
open-per-``log()`` implementation paid a file open/close every record and
could interleave badly with slow filesystems.
"""

from __future__ import annotations

import json
import os
import time
from typing import Optional

import jax

from tpu_dist.obs import counters as counters_lib

SCHEMA_VERSION = 15  # v15 (additive): causal arbitration tracing —
#                      'resume'/'fleet'/'tenancy' records carry the
#                      scheduler's monotonic decision_id (+
#                      decision_cause on resumes), and the goodput
#                      ledger splits preempt_for_serve_s out of
#                      recovery_s off that cause
#                      (tpu_dist/fleet/scheduler.py, obs/goodput.py);
#                      v14 added 'tenancy' records — the fleet
#                      scheduler's per-tick chip-accounting snapshots
#                      (alloc/free/pending; tpu_dist/fleet/scheduler.py)
#                      whose sums make chip-second conservation exact;
#                      v12 'plan' and v13 'tune' are retired
#                      kinds (no writer; readers skip them); v11 'memory'
#                      HBM-ledger records (tpu_dist/obs/memory.py);
#                      v10 'serve' serving-SLO windows; v9 'postmortem'
#                      crash bundles; v8 'fleet' scheduler decisions;
#                      v7 'resume' segment boundaries


class MetricsHistory:
    def __init__(
        self,
        path: Optional[str],
        run_id: Optional[str] = None,
        t0: Optional[float] = None,
        all_processes: bool = False,
    ):
        """``path=None`` disables (and any non-primary process is a no-op
        unless ``all_processes`` — the Trainer's ``--per_host_log``, where
        every process writes its own rank-suffixed file for ``obs pod``
        aggregation; the caller owns making the paths distinct).
        ``run_id`` identifies the run in every record; the Trainer passes
        its config-hash + start-time stamp. ``t0`` (a ``time.monotonic()``
        reading) overrides the ``rel_s`` origin — the Trainer passes its
        construction instant, the SAME origin its span recorder zeroes at,
        so exported epoch bars and host spans share one timeline."""
        self.path = path if (
            path and (all_processes or jax.process_index() == 0)
        ) else None
        self.run_id = run_id
        self._f = None
        self._t0 = t0 if t0 is not None else time.monotonic()
        if self.path:
            os.makedirs(os.path.dirname(os.path.abspath(self.path)), exist_ok=True)
            # tpu-dist: ignore[TD002] — self.path is None off rank 0 (guard
            # in __init__) unless the caller opted into per-process files
            # (all_processes, distinct rank-suffixed paths), so this handle
            # never contends cross-process.
            # buffering=1: line-buffered — each record is flushed whole, so
            # tail -f / a concurrent summarize sees complete lines only.
            self._f = open(self.path, "a", buffering=1)

    def log(self, kind: str, **fields) -> None:
        if self._f is None:
            return
        rec = {
            "ts": round(time.time(), 3),
            "rel_s": round(time.monotonic() - self._t0, 3),
            "schema_version": SCHEMA_VERSION,
            "kind": kind,
        }
        if self.run_id:
            rec["run_id"] = self.run_id
        rec.update({k: (float(v) if hasattr(v, "item") else v) for k, v in fields.items()})
        if "counters" not in rec:
            snap = counters_lib.snapshot()
            if snap:
                rec["counters"] = snap
        self._f.write(json.dumps(rec) + "\n")

    def close(self) -> None:
        if self._f is not None:
            f, self._f = self._f, None
            f.close()

    def __enter__(self) -> "MetricsHistory":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):  # belt-and-braces: the Trainer close()s explicitly
        try:
            self.close()
        except Exception:  # tpu-dist: ignore[TD006] — __del__ runs at
            pass  # interpreter teardown where raising is forbidden anyway
