"""Rule registry for the distributed-training lint (Layer 1) and jaxpr
audit (Layer 2).

Each rule ports one correctness/perf discipline that the reference repo
states only as prose (rank-0 logging, ``no_sync`` accumulation, SyncBN
placement — SURVEY §2-3) or that the TPU literature identifies as a silent
killer (sharding-annotation and host-sync mistakes: Xu et al.
arXiv:2004.13336, Kumar et al. arXiv:2011.03641). The linter walks the
package with ``ast``; the audit traces registered step builders and
inspects the closed jaxpr. Both report :class:`Violation` records keyed by
these IDs.

Suppression: append ``# tpu-dist: ignore[TDxxx]`` (with a reason) to the
flagged line — or the line directly above — or record the finding in the
checked-in baseline (see ``tpu_dist/analysis/baseline.py``). Every rule is
documented in ``docs/analysis.md``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

# --------------------------------------------------------------------------
# Rule table. TD0xx = AST lint (Layer 1); TD1xx = jaxpr audit (Layer 2).
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Rule:
    id: str
    name: str
    summary: str


RULES: dict[str, Rule] = {
    r.id: r
    for r in [
        Rule(
            "TD001",
            "host-sync-in-traced-fn",
            "host-synchronizing call (.item()/float()/np.asarray/"
            "jax.device_get/.block_until_ready()) inside a traced/jitted "
            "function — forces a device round-trip every step",
        ),
        Rule(
            "TD002",
            "unguarded-nonrank0-io",
            "print/log/file-write not guarded by process_index() == 0 — "
            "every host duplicates the I/O (reference rank-0 discipline, "
            "tutorials/2 §3)",
        ),
        Rule(
            "TD003",
            "jit-missing-donate",
            "jax.jit on a hot-path step/epoch builder without "
            "donate_argnums — doubles peak HBM by keeping the old "
            "TrainState alive across the update",
        ),
        Rule(
            "TD004",
            "version-fragile-jax-import",
            "direct import of a JAX API that moved between releases "
            "(shard_map/pjit) — must route through tpu_dist.comm.compat",
        ),
        Rule(
            "TD005",
            "nondeterminism-in-traced-fn",
            "np.random/time.time()/stdlib random inside a traced function "
            "— baked in as a trace-time constant, NOT fresh per step",
        ),
        Rule(
            "TD006",
            "silent-exception-swallow",
            "`except ...: pass` (outside the benign allowlist) or bare "
            "`except:` silently swallows failures — in a multi-process job "
            "this hides the first fault until a collective deadlocks; "
            "re-raise, log, or narrow the type",
        ),
        Rule(
            "TD007",
            "bare-print-outside-logging-layer",
            "bare `print(` outside the metrics/logging allowlist — even "
            "rank-0-guarded prints bypass the one grep-able output layer "
            "(rank0_print / get_logger / ProgressMeter); route through it "
            "or inline-ignore with the audit reason",
        ),
        Rule(
            "TD008",
            "rank-guarded-collective",
            "a collective call site reachable only under rank-/process-"
            "dependent control flow — the guarded ranks enter the "
            "collective, the rest never do, and the job dies as a "
            "cross-host deadlock minutes later; hoist the collective out "
            "of the guard (compute on every rank, act on one)",
        ),
        Rule(
            "TD101",
            "collective-budget-mismatch",
            "jaxpr collective count differs from the parallelism config's "
            "budget — an accidental extra (or missing) cross-replica "
            "reduce in the compiled step",
        ),
        Rule(
            "TD102",
            "unexpected-transfer-op",
            "device_put / host transfer op inside the compiled step jaxpr "
            "— host↔device traffic on the hot path",
        ),
        Rule(
            "TD103",
            "bf16-promotion-over-budget",
            "more bf16→f32 convert_element_type ops than the mixed-"
            "precision path declares — an implicit promotion is silently "
            "doing f32 math",
        ),
        Rule(
            "TD105",
            "fault-injection-not-noop",
            "the traced train step differs between fault injection OFF and "
            "an armed --fault_plan — injection points must be host-side "
            "no-ops that never enter the compiled program "
            "(resilience/faults.py contract)",
        ),
        Rule(
            "TD106",
            "telemetry-not-noop",
            "the traced train step differs between telemetry OFF and "
            "armed spans/counters/heartbeat — run telemetry must be "
            "host-side only and add no per-step device work "
            "(tpu_dist.obs contract, docs/observability.md)",
        ),
        Rule(
            "TD107",
            "device-metrics-cost-leak",
            "the --device_metrics contract broke: flag OFF must leave the "
            "traced train step byte-identical, flag ON must add zero "
            "collectives and zero transfer ops on the pure-DP path (the "
            "health scalars ride the post-pmean gradients and the "
            "existing single per-step fetch — obs/device_stats.py)",
        ),
        Rule(
            "TD108",
            "profile-trigger-not-noop",
            "the traced train step differs between no profiler and an "
            "armed/capturing triggered profiler — capture control must "
            "stay host-side (arm flags, jax.profiler start/stop around "
            "the unmodified step; obs/profile.py contract)",
        ),
        Rule(
            "TD109",
            "live-export-not-noop",
            "the traced train step differs between live telemetry OFF and "
            "an armed OpenMetrics exporter + alert engine (exposition "
            "published, /metrics scraped, threshold rules fired) — live "
            "export and alerting must stay host-side (obs/export.py + "
            "obs/alerts.py contract)",
        ),
        Rule(
            "TD110",
            "xprof-hook-not-noop",
            "the traced train step differs between no profiler and a "
            "triggered profiler whose AUTO-ANALYZE hook is armed — across "
            "arm, capture-open, and capture-closed-and-analyzed states "
            "(obs/xprof.py read-back + cost-model calibration must stay "
            "host-side file crunching; obs/profile.py contract)",
        ),
        Rule(
            "TD111",
            "elastic-resume-not-noop",
            "the traced train step of an elastic-resumed trainer (state "
            "restored from a checkpoint written at a DIFFERENT dp extent "
            "and remapped) differs from a fresh-start trainer at the same "
            "new world size — the remap must be restore-time host work "
            "that reproduces exactly the shapes/dtypes a fresh "
            "construction gets (tpu_dist/elastic/remap.py contract)",
        ),
        Rule(
            "TD112",
            "elastic-grow-not-noop",
            "the traced train step of a GROW-resumed trainer (state "
            "restored from a checkpoint written at a SMALLER dp extent "
            "and remapped up onto more devices) differs from a "
            "fresh-start trainer at the same larger world size — the "
            "scale-up remap must be restore-time host work that "
            "reproduces exactly the shapes/dtypes a fresh construction "
            "gets (the grow mirror of TD111; tpu_dist/elastic/remap.py "
            "contract)",
        ),
        Rule(
            "TD113",
            "flight-recorder-not-noop",
            "the traced train step differs between crash forensics OFF "
            "and an armed flight recorder + faulthandler (ring slots "
            "written, excepthooks wrapped, span-open listener tapped, "
            "SIGUSR1 all-threads dump registered and fired) — crash "
            "forensics must stay host-side file I/O on the step "
            "boundary (obs/flight.py contract, docs/observability.md "
            "'Crash forensics')",
        ),
        Rule(
            "TD114",
            "serving-slo-not-noop",
            "the traced serving forward step differs between bare "
            "inference and the full serve telemetry/SLO kit armed "
            "(streaming latency histograms observing, queue/occupancy "
            "gauges published, SLO alert engine fired, histogram "
            "exposition rendered and parsed back, span recorder "
            "tapped) — serving observability must stay host-side "
            "arithmetic around the unmodified compiled step "
            "(tpu_dist/serve contract, docs/serving.md)",
        ),
        Rule(
            "TD115",
            "memory-ledger-not-noop",
            "the traced train step differs between the HBM ledger OFF "
            "and the full memory kit armed (static per-leaf ledger over "
            "a real sharded state, live-buffer census, allocator stats "
            "read, census/allocator reconciliation, mem.* gauges "
            "published, pre-flight feasibility check, memory_analysis "
            "waterfall of an AOT probe, RESOURCE_EXHAUSTED parser "
            "exercised) — memory observability must stay host-side "
            "metadata arithmetic (obs/memory.py contract, "
            "docs/observability.md 'HBM ledger & OOM forensics')",
        ),
        Rule(
            "TD116",
            "compiled-collectives-match-predicted",
            "the optimized HLO's collective wire accounting disagrees "
            "with the jaxpr-level TD104 ring model (elements exact; "
            "integer/quantized legs byte-exact; float legs exact modulo "
            "the backend's declared bf16->f32 normalization) — one of the "
            "two accountings is lying about what the step moves "
            "(tpu_dist/analysis/shardlint.py, docs/shard_report.md)",
        ),
        Rule(
            "TD117",
            "unintended-reshard-in-compiled-step",
            "the optimized HLO contains a collective the jaxpr-level "
            "inventory did not predict (an unpredicted op kind, or "
            "per-kind wire bytes beyond the prediction) — GSPMD inserted "
            "an implicit reshard, usually a bad in_shardings/out_shardings "
            "gathering state the step expected resident "
            "(tpu_dist/analysis/shardlint.py)",
        ),
        Rule(
            "TD120",
            "async-ckpt-semantics-preserved",
            "the async sharded checkpoint path (--sharded_ckpt + "
            "--async_ckpt) must leave the traced train step byte-identical "
            "to synchronous saves AND restore bit-exact to the synchronous "
            "sharded format; the injected EIO and SIGTERM fault probes "
            "must surface through the drain path — an uncaught probe "
            "means the detector is dead (CLI exit 2) "
            "(tpu_dist/ckpt/checkpoint.py, docs/checkpointing.md)",
        ),
        Rule(
            "TD122",
            "tenancy-arbitration-control-plane-only",
            "the traced train step or the jitted serving forward CHANGED "
            "when the multi-tenant arbitration kit was armed (serve-gauge "
            "scrape through read_signals, kind-aware fleet policy driven "
            "to a genuinely fired SLO preemption, the cooperative SIGTERM "
            "flag raised, load-shedding admission refusing work) — "
            "train/serve co-scheduling must stay host-side control-plane "
            "arithmetic around the unmodified compiled programs, and a "
            "probe where the preemption never fires is vacuous "
            "(tpu_dist/fleet/scheduler.py, tpu_dist/serve/engine.py, "
            "docs/resilience.md 'Multi-tenant pod')",
        ),
        Rule(
            "TD123",
            "pod-telemetry-control-plane-only",
            "the traced train step or the jitted serving forward CHANGED "
            "when the pod telemetry plane was armed (two-run federated "
            "hub scrape mid-audit, the arbiter fed from the hub snapshot, "
            "a donate→grant pair chained under ONE decision_id propagated "
            "through allocation file → relaunch env → resume record, the "
            "serve-preempt gap charged to preempt_for_serve_s with the "
            "bucket partition exact) — federation and causal tracing must "
            "stay host-side file arithmetic, and a probe that aggregates "
            "zero runs or loses the id mid-chain is vacuous "
            "(tpu_dist/obs/hub.py, tpu_dist/fleet/scheduler.py, "
            "docs/observability.md 'Pod telemetry hub')",
        ),
        Rule(
            "TD124",
            "archive-gate-not-vacuous",
            "the longitudinal archive's regression machinery went dead or "
            "device-side: an injected past-band candidate must come back "
            "REGRESSED through the MAD-band gate, an injected improvement "
            "must come back clean, an injected changepoint must be "
            "localized by --blame to the exact archived record, ingest "
            "must be idempotent by fingerprint with stale re-emissions "
            "flagged and excluded from the band — and arming the full "
            "ingest+gate+trend kit must leave the traced train step "
            "byte-identical (tpu_dist/obs/archive.py, "
            "docs/observability.md 'Longitudinal archive & trend gating')",
        ),
        Rule(
            "TD104",
            "quantized-wire-bytes-over-budget",
            "gradient-collective payload bytes of a quantized wire format "
            "exceed the declared ratio of its reference mode (int8 must "
            "stay ≤0.5× bf16 / ≤0.25× f32) — a wire leg silently "
            "decompressed",
        ),
    ]
}


@dataclasses.dataclass
class Violation:
    rule: str
    path: str  # repo-relative file, or "<jaxpr:case>" for Layer 2
    line: int
    message: str
    col: int = 0
    snippet: str = ""

    def format_text(self) -> str:
        loc = f"{self.path}:{self.line}:{self.col}"
        return f"{loc}: {self.rule} {self.message}"

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    def baseline_key(self) -> tuple:
        """Line numbers drift; baseline entries match on the line's text."""
        return (self.rule, self.path, self.snippet.strip())


# --------------------------------------------------------------------------
# Lint configuration (Layer 1 knobs, one place).
# --------------------------------------------------------------------------

# Entry points whose function arguments run under trace (TD001/TD005 scope).
TRACE_ENTRY_CALLS = {
    "jax.jit",
    "jax.pmap",
    "jax.vmap",
    "jax.grad",
    "jax.value_and_grad",
    "jax.checkpoint",
    "jax.remat",
    "jax.lax.scan",
    "jax.lax.map",
    "jax.lax.cond",
    "jax.lax.while_loop",
    "jax.lax.fori_loop",
    "jax.lax.associative_scan",
    "jax.experimental.shard_map.shard_map",
    "jax.shard_map",
    "tpu_dist.comm.compat.shard_map",
}

# Fully-resolved call targets that force a host sync (TD001).
HOST_SYNC_CALLS = {
    "jax.device_get",
    "jax.block_until_ready",
    "numpy.asarray",
    "numpy.array",
    "numpy.asanyarray",
    "numpy.ascontiguousarray",
}
# Method names that force a host sync on any receiver (TD001).
HOST_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
# Builtins that force a sync when applied to a traced value (TD001).
HOST_SYNC_BUILTINS = {"float", "int", "bool"}

# Nondeterministic-at-trace-time call prefixes (TD005).
NONDETERMINISM_PREFIXES = ("numpy.random.", "random.")
NONDETERMINISM_CALLS = {
    "time.time",
    "time.perf_counter",
    "time.monotonic",
    "time.time_ns",
}

# Logger-ish method names for TD002 (receiver name must look like a logger).
LOG_METHODS = {"debug", "info", "warning", "error", "critical", "exception", "log"}
LOGGERISH_NAMES = ("log", "logger")

# Rank-0 guard spellings TD002 recognizes in `if` tests.
RANK_CALL_SUFFIXES = ("process_index", "is_primary", "get_rank")
RANK_VAR_NAMES = {"rank", "local_rank", "process_id", "proc_id", "process_index", "pid"}

# Modules exempt from TD002: host-side tooling that never runs inside a
# multi-process training job (the analysis and obs CLIs' report output —
# `obs memory`'s ledger/OOM reports included, the fleet controller — the
# scheduler/drill/capacity census run in the single arbiter/launcher
# process, whose FILES are the control channel the runs' probes read —
# and the serve CLI/drill, which run in the single serving/operator
# process). obs/memory.py itself is NOT exempt: its in-job artifact
# writes (oom.json) carry inline ignores with the per-rank-path
# justification instead.
TD002_EXEMPT_PARTS = (
    "tpu_dist/analysis/", "tpu_dist/obs/__main__.py", "tpu_dist/fleet/",
    "tpu_dist/serve/__main__.py", "tpu_dist/serve/drill.py",
)

# TD007 allowlist: the designated output layer (rank0_print/get_logger and
# the ProgressMeter display sink, which carries the rank-0 guard itself)
# plus pure-CLI report modules whose stdout IS the product — the `obs`
# subcommands (summarize/compare/pod/xprof/postmortem/memory) all print
# through obs/__main__.py. Everything else must route prints through the
# logging layer — the statically-enforced version of the rank-0
# discipline the reference only documents.
TD007_ALLOWED_PARTS = (
    "tpu_dist/metrics/logging.py",
    "tpu_dist/metrics/meters.py",
    "tpu_dist/analysis/",
    "tpu_dist/obs/__main__.py",
    "tpu_dist/serve/__main__.py",
    "tpu_dist/serve/drill.py",
)

# TD003 scope: jit calls inside these factory-name patterns are "hot path".
HOT_FACTORY_REGEX = r"^(make|build)_.*(step|epoch|train|update)"

# TD008: call targets that are (or transitively drive) a cross-process
# collective, matched on the LAST dotted segment — the jax.lax primitives,
# the tpu_dist.comm.collectives wrappers (reduce_mean/barrier/...), the
# quantized two-stage reduce, and the multihost_utils host-level syncs.
# Any of these reachable only under a rank-dependent `if` is the classic
# deadlock shape: the guarded ranks enter the collective, the rest never
# do. `broadcast_from` IS rank-aware internally (every rank calls it) —
# what TD008 flags is a rank-guarded CALL SITE, where some rank skips the
# call entirely.
COLLECTIVE_CALLS = {
    # jax.lax primitives
    "psum", "pmean", "pmin", "pmax", "all_gather", "all_to_all",
    "ppermute", "pshuffle", "psum_scatter", "pgather",
    # tpu_dist.comm.collectives / quantize wrappers
    "reduce_mean", "reduce_sum", "broadcast_from", "barrier",
    "host_allreduce_mean", "quantized_pmean_flat",
    # jax.experimental.multihost_utils host-level syncs
    "broadcast_one_to_all", "process_allgather", "sync_global_devices",
    "reached_preemption_sync_point",
}
# ...except these receivers/modules, where a same-named method is host
# bookkeeping, not a collective (e.g. ``Counter``-style .barrier attrs).
# Matched on the resolved dotted prefix when resolution succeeds.
COLLECTIVE_CALL_NONMODULES = ("threading.", "multiprocessing.")

# TD006: exception types a `pass`-only handler may swallow without comment —
# probe/cleanup idioms where absence IS the answer. Matched on the LAST
# dotted segment (so `queue.Empty` and a bare `Empty` both pass). Anything
# else (OSError and friends above all) needs a logged handler or an inline
# `# tpu-dist: ignore[TD006]` with the audit reason.
TD006_ALLOWED_SILENT = {
    "FileNotFoundError",
    "ImportError",
    "ModuleNotFoundError",
    "StopIteration",
    "Empty",           # queue.Empty poll loops
    "TimeoutExpired",  # subprocess poll-wait loops
    "TimeoutError",
}

# Version-fragile imports (TD004): module → names that must come from compat.
FRAGILE_IMPORTS = {
    "jax": {"shard_map"},
    "jax.experimental": {"shard_map", "pjit"},
    "jax.experimental.shard_map": {"*"},
    "jax.experimental.pjit": {"*"},
}
# The one module allowed to perform those imports.
COMPAT_MODULE_SUFFIX = "tpu_dist/comm/compat.py"


def describe(rule_id: str) -> str:
    r = RULES.get(rule_id)
    return f"{r.id} ({r.name}): {r.summary}" if r else rule_id
