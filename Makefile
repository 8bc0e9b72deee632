# Convenience targets; CI / the driver call the underlying commands directly.

.PHONY: test quick bench csrc clean lint shard-report ckpt-bench pod-report monitor profile-report elastic-drill fleet-drill postmortem-drill serve-drill tenancy-drill hub-drill serve-report memory-report trend-report

csrc:
	$(MAKE) -C tpu_dist/csrc

test:
	python -m pytest tests/ -x -q

# Static lint (TD0xx) + jaxpr audit (TD1xx) against the checked-in baseline;
# non-zero exit on any new violation (docs/analysis.md)
lint:
	python -m tpu_dist.analysis --format json

# Layer 3 — the static HLO sharding & collective audit: lower+compile
# every config family, parse the OPTIMIZED HLO (what GSPMD actually
# emitted), gate TD116/TD117 (incl. the injected bad-in_shardings probe
# that must be caught), and write the schema-pinned shard_report.json
# (docs/shard_report.md):
#   make shard-report [OUT=shard_report.json]
shard-report:
	python -m tpu_dist.analysis shard --inject-reshard --out $(or $(OUT),shard_report.json)

# The async-checkpoint cost, on a TPU host (bench.py refuses without
# one): measure step-loop blocking per sharded save for the synchronous
# barrier path vs the snapshot-then-write background path on the same
# model, print the ratio, and keep the TD120 injected-EIO probe honest —
# a probe that comes back clean is a dead detector: exit 2
# (docs/checkpointing.md "The cost"):
#   make ckpt-bench
ckpt-bench:
	python bench.py --ckpt sweep --config resnet18_cifar100_fp32 --batch_size 64 --warmup 1

# <5-min cross-component slice (see tests/conftest.py for the curated set)
quick:
	python -m pytest tests/ -m quick -q

bench:
	python bench.py

# Cross-host pod report over per-host --log_file histories:
#   make pod-report LOGS="run.jsonl run.jsonl.h1" [TRACE=pod_trace.json]
# (docs/observability.md — per-host goodput ledgers, skew attribution,
# and optionally one merged Perfetto timeline)
pod-report:
	python -m tpu_dist.obs pod $(LOGS) $(if $(TRACE),--trace-out $(TRACE))

# Device-time attribution of a jax.profiler capture:
#   make profile-report CAPTURE=prof_dir/capture_0_s12_anomaly [TOP=10]
# (docs/observability.md "Trace analytics" — per-category device seconds,
# collectives by kind, comm/compute overlap, top ops)
profile-report:
	python -m tpu_dist.obs xprof $(CAPTURE) $(if $(TOP),--top $(TOP))

# The elastic proof, locally: preempt an 8-device ZeRO-1 run at step k
# (deterministic sigterm fault), resume at 4 devices (checkpoint remapped
# onto the new dp extent), assert the continued loss trajectory matches
# the uninterrupted golden run (docs/resilience.md "Elastic training"):
#   make elastic-drill [WORKDIR=/tmp/elastic_drill]
elastic-drill:
	python -m tpu_dist.elastic.drill --workdir $(or $(WORKDIR),/tmp/elastic_drill)

# The scale-up + fleet proof, locally: preempt an 8-device run (census
# caps the relaunch at 4), return the chips (the probe grows it back to
# 8 with golden-tolerance loss parity), then a 2-run arbitration — the
# scheduler scrapes real OpenMetrics textfiles and moves chips from the
# stalled run to the compute-bound one through the live supervised
# launchers (docs/resilience.md "Scale-up & fleet scheduling"):
#   make fleet-drill [WORKDIR=/tmp/fleet_drill] [PHASE=all|grow|fleet]
fleet-drill:
	python -m tpu_dist.fleet.drill --workdir $(or $(WORKDIR),/tmp/fleet_drill) --phase $(or $(PHASE),all)

# The crash-forensics proof, locally: a real run deliberately wedged at
# a step (deterministic hang fault), the launcher watchdog detects the
# frozen heartbeat, SIGUSR1s the rank for an all-threads stack dump
# (naming the hang site), escalates SIGTERM->SIGKILL, and auto-assembles
# the postmortem bundle — whose decoded flight ring must end exactly at
# the wedged step (docs/observability.md "Crash forensics"):
#   make postmortem-drill [WORKDIR=/tmp/postmortem_drill]
postmortem-drill:
	python -m tpu_dist.obs.drill --workdir $(or $(WORKDIR),/tmp/postmortem_drill)

# The serving proof, locally: deterministic request-trace replay through
# the continuous-batching engine — checkpoint loaded through the elastic
# Remapper, zero post-warmup retraces (CompileWatcher), histogram
# sum==count invariants, and the `obs compare --slo` exit contract (an
# injected latency regression exits 1, an improvement exits 0)
# (docs/serving.md):
#   make serve-drill [WORKDIR=/tmp/serve_drill]
serve-drill:
	python -m tpu_dist.serve drill --workdir $(or $(WORKDIR),/tmp/serve_drill)

# The co-scheduling proof, locally: one scheduler arbitrates a real
# training run and a supervised serving replica on the same chip budget
# through a deterministic diurnal cycle — a traffic spike breaches the
# serving SLO, training is preempted within the bounded tick count
# (SIGTERM -> emergency save -> exit 75 -> elastic relaunch on fewer
# chips, golden-loss parity), availability recovers, and off-peak the
# trainer reclaims the chips; the replica phase SIGKILLs the serving
# process and proves crash detection, postmortem bundling, and a
# bit-exact relaunch; chip-second conservation is audited exactly
# (docs/resilience.md "Multi-tenant pod"):
#   make tenancy-drill [WORKDIR=/tmp/tenancy_drill] [PHASE=all|policy|cycle|replica]
tenancy-drill:
	python -m tpu_dist.fleet.tenancy_drill --workdir $(or $(WORKDIR),/tmp/tenancy_drill) --phase $(or $(PHASE),all)

# The pod telemetry plane proof (docs/observability.md "Pod telemetry
# hub"): the diurnal replay arbitrated off ONE TelemetryHub fan-in
# (federated page round-trips with per-run labels + pod rollups), then
# the real-trainer cycle asserting the full causal chain — one
# decision_id spanning scheduler ledger -> allocation file/relaunch
# env -> resume record -> donor flight ring -> hub exposition, with
# the serve-preempt gap charged to preempt_for_serve_s and the goodput
# bucket partition exact:
#   make hub-drill [WORKDIR=/tmp/hub_drill]
hub-drill:
	python -m tpu_dist.fleet.tenancy_drill --workdir $(or $(WORKDIR),/tmp/hub_drill) --phase hub

# Offline serving SLO report over a run's serve records:
#   make serve-report LOG=serve.jsonl
# (docs/serving.md — per-window requests/s, latency p50/p99 bounds,
# availability, occupancy, fired SLO alerts)
serve-report:
	python -m tpu_dist.serve report $(LOG)

# Offline HBM report over a run's memory records + mem.* gauge series:
#   make memory-report LOG=run.jsonl
# (docs/observability.md "HBM ledger & OOM forensics" — the per-leaf
# static ledger, the memory_analysis waterfall, the census/allocator
# reconciliation, OOM events, and the peak-HBM compare-gate scalar)
memory-report:
	python -m tpu_dist.obs memory $(LOG)

# Trend + changepoint-blame report over a longitudinal archive, then the
# TD124 inject-regression self-test against it: a just-outside-band
# injection must be CAUGHT per band, an improvement must pass, and the
# synthetic changepoint must be localized — a dead detector exits 2
# (docs/observability.md "Longitudinal archive & trend gating"):
#   make trend-report ARCHIVE=bench_archive.jsonl
trend-report:
	python -m tpu_dist.obs trend $(ARCHIVE) --blame
	python -m tpu_dist.obs trend $(ARCHIVE) --inject-regression

# Follow a LIVE run from another terminal:
#   make monitor LOG=run.jsonl [HB=hb.json]
# (docs/observability.md "obs tail" — rolling epoch table, live alert/
# anomaly/straggler lines, heartbeat staleness)
monitor:
	python -m tpu_dist.obs tail $(LOG) $(if $(HB),--heartbeat $(HB))

clean:
	$(MAKE) -C tpu_dist/csrc clean
	find . -name __pycache__ -type d -exec rm -rf {} +
