# Test/verification entry points. The suite runs on 8 virtual CPU devices
# (conftest.py pins the platform), so no TPU is needed for any target here.

PYTHON ?= python

.PHONY: test test-all dryrun bench smoke aot real-data lint \
	trace-demo health-demo zero-demo compress-demo analyze-demo \
	lint-demo monitor-demo profile-demo goodput-demo registry-demo \
	tune-demo mem-demo curves-demo chaos-demo comms-demo data-demo \
	kernels-demo zero3-demo diagnose-demo bench-compare

# Fast default loop (round-3 verdict item 5): skips the `slow`-marked
# multi-process / end-to-end-CLI / AOT tests. CI and pre-commit should run
# `make test-all` at least once; `make test` is the between-commits loop.
test:
	$(PYTHON) -m pytest tests/ -x -q -m "not slow"

test-all:
	$(PYTHON) -m pytest tests/ -x -q

# Multi-device validation: compiles + runs every parallelism family's
# full train step on an 8-virtual-device CPU mesh.
dryrun:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	  $(PYTHON) -m tpu_ddp.tools.dryrun 8

bench:
	$(PYTHON) bench.py

# Deviceless AOT evidence: compiles all flagship programs with the real
# XLA:TPU + Mosaic toolchain (no chip needed); exits nonzero on any
# compile regression and rewrites benchmarks/aot_v5e.json.
aot:
	$(PYTHON) benchmarks/aot_v5e.py

# The 93% north star, unattended (BASELINE.md "The 93% pathway"):
# download -> MD5-verify -> extract real CIFAR-10, train the documented
# ResNet-18 recipe on TPU, gate on final test accuracy >= 0.93. In THIS
# build environment (zero egress) it fails fast with an explicit
# "no network egress" message; run it where egress exists.
real-data:
	$(PYTHON) -m tpu_ddp.tools.real_data

# Static checks (config in pyproject.toml [tool.ruff]; version pinned in
# the dev extra). A REAL gate in CI: missing ruff fails there instead of
# skipping. Locally (no $CI) it still skips with a notice when the
# container doesn't ship ruff.
lint:
	@if $(PYTHON) -m ruff --version >/dev/null 2>&1; then \
	  $(PYTHON) -m ruff check tpu_ddp tests; \
	elif command -v ruff >/dev/null 2>&1; then \
	  ruff check tpu_ddp tests; \
	elif [ -n "$$CI" ]; then \
	  echo "lint: ruff is required in CI (pip install the pinned version"; \
	  echo "lint: from pyproject [project.optional-dependencies].lint)"; \
	  exit 1; \
	else \
	  echo "lint: ruff not installed (pip install ruff); skipping"; \
	fi

# Telemetry smoke test for the whole pipeline: a 5-step CPU training run
# with the JSONL + Chrome sinks + watchdog enabled, then the trace
# summarized back into per-phase percentiles. The Chrome trace
# (trace-p0.trace.json) loads in https://ui.perfetto.dev.
TRACE_DEMO_DIR ?= /tmp/tpu_ddp_trace_demo
trace-demo:
	rm -rf $(TRACE_DEMO_DIR)
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	  $(PYTHON) -m tpu_ddp.cli.train --device cpu --synthetic-data \
	  --synthetic-size 1280 --epochs 1 --log-every-epochs 1 \
	  --telemetry-dir $(TRACE_DEMO_DIR) --watchdog-deadline 300
	JAX_PLATFORMS=cpu $(PYTHON) -m tpu_ddp.cli.main trace summarize \
	  $(TRACE_DEMO_DIR)

# Numerics flight-recorder acceptance: a short CPU run with one injected
# all-NaN batch under --health on / --health-policy skip_step. The demo
# exits non-zero unless the NaN step was detected, the anomaly dump
# (stats + history + offending batch) was written, the poisoned update
# was discarded, and training recovered with finite params — then the
# run dir renders through `tpu-ddp health`.
HEALTH_DEMO_DIR ?= /tmp/tpu_ddp_health_demo
health-demo:
	rm -rf $(HEALTH_DEMO_DIR)
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	  $(PYTHON) -m tpu_ddp.tools.health_demo --dir $(HEALTH_DEMO_DIR)
	$(PYTHON) -m tpu_ddp.cli.main health $(HEALTH_DEMO_DIR)

# ZeRO-1 acceptance: train the same config replicated and with --zero1 on
# 4 virtual CPU devices; exits non-zero unless the loss trajectories and
# final params match AND the optimizer state is physically scattered 1/N
# per device (tpu_ddp/tools/zero_demo.py).
zero-demo:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=4" \
	  $(PYTHON) -m tpu_ddp.tools.zero_demo --devices 4

# Gradient-compression acceptance: (1) the f32-mode ppermute ring must
# match lax.psum_scatter/lax.pmean (bit-identical on exact-arithmetic
# inputs, ULPs on gaussians); (2) a ~20-step int8 (+error-feedback) run's
# loss trajectory must stay within tolerance of the uncompressed run.
# Exits non-zero on drift (tpu_ddp/tools/compress_demo.py).
compress-demo:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=4" \
	  $(PYTHON) -m tpu_ddp.tools.compress_demo --devices 4

# Step-time anatomy acceptance (docs/analysis.md): a short CPU run with
# telemetry, then `tpu-ddp analyze <run_dir>` must rebuild the exact
# program from the run-metadata header, classify the roofline bound
# (attributed against the v5e chip spec), render the collective
# inventory, and join the measured phases; every strategy's compiled
# step must match its pinned collective fingerprint; and the
# `bench compare` gate must flag injected inventory drift. Exits
# non-zero on any miss (tpu_ddp/tools/analyze_demo.py).
ANALYZE_DEMO_DIR ?= /tmp/tpu_ddp_analyze_demo
analyze-demo:
	rm -rf $(ANALYZE_DEMO_DIR)
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=4" \
	  $(PYTHON) -m tpu_ddp.tools.analyze_demo --dir $(ANALYZE_DEMO_DIR)

# Graph-lint acceptance (docs/lint.md): `tpu-ddp lint --strategy all`
# must pass clean on the 4-virtual-device CPU mesh (all nine strategy
# programs + the RCP001 AST tier), two injected violations (stripped
# donation, planted host callback) must exit nonzero with exactly their
# rule ids (DON001 / XFR001), and a new finding count in the committed
# lint artifact must fail `tpu-ddp bench compare`.
LINT_DEMO_DIR ?= /tmp/tpu_ddp_lint_demo
lint-demo:
	rm -rf $(LINT_DEMO_DIR)
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=4" \
	  $(PYTHON) -m tpu_ddp.tools.lint_demo --dir $(LINT_DEMO_DIR)

# Live fleet-monitor acceptance (docs/monitoring.md): a short 4-device
# CPU run with the monitor exporter on an ephemeral port — /metrics must
# serve OpenMetrics text with the run-meta labels MID-RUN and /healthz
# must track the watchdog heartbeat; then `tpu-ddp watch --once --json`
# over the run dir (clean: no alerts), and synthetic 4-host fleets with
# an injected straggler / lost host / NaN spike that must raise exactly
# STR001 / FLT001 / NUM002 (and a clean fleet that raises none). Exits
# nonzero on any miss (tpu_ddp/tools/monitor_demo.py).
MONITOR_DEMO_DIR ?= /tmp/tpu_ddp_monitor_demo
monitor-demo:
	rm -rf $(MONITOR_DEMO_DIR)
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=4" \
	  $(PYTHON) -m tpu_ddp.tools.monitor_demo --dir $(MONITOR_DEMO_DIR)

# Anomaly-profiler acceptance (docs/profiling.md): a 4-device CPU run
# with an injected slow input pipeline — DWT001 must fire in a watch-side
# alert engine, the capture_profile action must auto-arm a capture over
# POST /profile, the bundle's host top stacks must contain the injected
# stall frame, and `tpu-ddp profile` must render it and point at the run's
# program map (jax.profiler absence degrades to a note). Exits nonzero on
# any miss
# (tpu_ddp/tools/profile_demo.py).
PROFILE_DEMO_DIR ?= /tmp/tpu_ddp_profile_demo
profile-demo:
	rm -rf $(PROFILE_DEMO_DIR)
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=4" \
	  $(PYTHON) -m tpu_ddp.tools.profile_demo --dir $(PROFILE_DEMO_DIR)

# Goodput-ledger acceptance (docs/goodput.md): a 4-device CPU run with
# step-cadence checkpoints is hard-killed past its last checkpoint (no
# run_end — a simulated SIGKILL), resumed to completion as incarnation 1
# (the dead life's trace survives as its own file), with the live
# goodput/fraction gauge scraped from /metrics MID-RUN; then `tpu-ddp
# goodput` must report exactly 2 incarnations, nonzero restart-gap and
# replayed-steps badput (replayed == steps since the last checkpoint),
# categories summing to elapsed wall-clock within 2%, and a Young–Daly
# checkpoint-interval recommendation; and `bench compare` must flag the
# incident ledger against a clean baseline. Exits nonzero on any miss
# (tpu_ddp/tools/goodput_demo.py).
GOODPUT_DEMO_DIR ?= /tmp/tpu_ddp_goodput_demo
goodput-demo:
	rm -rf $(GOODPUT_DEMO_DIR)
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=4" \
	  $(PYTHON) -m tpu_ddp.tools.goodput_demo --dir $(GOODPUT_DEMO_DIR)

# Perf-registry acceptance (docs/registry.md): a real 4-device CPU run's
# analyze/goodput/trace-summary artifacts must record into a fresh
# registry workspace provenance-stamped (git commit + the run's
# deterministic config digest); synthetic multi-commit history with an
# injected 10% throughput drift must trip `registry trend` with exactly
# REG001 while an equally long clean history stays quiet; and
# `bench compare --against <registry>` must auto-select its baseline
# (pass vs the candidate's own entry, fail vs a poisoned entry with one
# collective dropped, refuse with a named reason on a digest mismatch).
# Exits nonzero on any miss (tpu_ddp/tools/registry_demo.py).
REGISTRY_DEMO_DIR ?= /tmp/tpu_ddp_registry_demo
registry-demo:
	rm -rf $(REGISTRY_DEMO_DIR)
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=4" \
	  $(PYTHON) -m tpu_ddp.tools.registry_demo --dir $(REGISTRY_DEMO_DIR)

# Auto-tuner acceptance (docs/tuning.md): `tpu-ddp tune --chip v5e` on
# the 4-virtual-device CPU mesh must rank a non-trivial grid (>= 30
# candidates across the dp overlays + fsdp/tp/fsdp_tp meshes), every
# ranked candidate lint-clean and under the v5e HBM cap; an injected
# over-HBM candidate (per-shard 65536) must be excluded BY NAME with
# the over_hbm status; a re-run of the same grid must compile 0 new
# programs (the shared compile cache); the --json artifact must archive
# through `registry record` as a tune-kind entry and a doctored
# slower-winner copy must fail `bench compare`; and the emitted winner
# TrainConfig must validate with its CLI line. Exits nonzero on any
# miss (tpu_ddp/tools/tune_demo.py).
TUNE_DEMO_DIR ?= /tmp/tpu_ddp_tune_demo
tune-demo:
	rm -rf $(TUNE_DEMO_DIR)
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=4" \
	  $(PYTHON) -m tpu_ddp.tools.tune_demo --dir $(TUNE_DEMO_DIR)

# Memory truth-loop acceptance (docs/memory.md): a real 4-device CPU
# run must serve per-device memory/* gauges from the LIVE /metrics and
# leave a mem-p0.jsonl record; `tpu-ddp mem` must join the measured
# high-water against the recorded program's rebuilt static peak (with
# the documented CPU live-array degradation note); a synthetic
# near-limit fleet must raise exactly MEM001 (clean fleet none); an
# injected RESOURCE_EXHAUSTED must yield a postmortem bundle (samples +
# config + run_meta + report-time top-buffer plan), a goodput ledger
# exit of 'oom', and `tpu-ddp mem` exit 1; and the --json artifact must
# `registry record` as a mem-kind entry. Exits nonzero on any miss
# (tpu_ddp/tools/mem_demo.py).
MEM_DEMO_DIR ?= /tmp/tpu_ddp_mem_demo
mem-demo:
	rm -rf $(MEM_DEMO_DIR)
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=4" \
	  $(PYTHON) -m tpu_ddp.tools.mem_demo --dir $(MEM_DEMO_DIR)

# Convergence-observatory acceptance (docs/curves.md): three seeded CPU
# runs of one recipe must extract through `tpu-ddp curves --json` and
# archive as kind-"curves" registry entries sharing ONE seed-invariant
# quality digest; an injected lr x10 candidate must fail `tpu-ddp
# curves --against` naming exactly CRV001 + CRV002 while a clean fresh
# seed passes; the judged artifacts must gate through `bench compare`
# on the CRV counts exactly (and auto-baseline via --against); a dp vs
# dp+int8 pair must pass `tpu-ddp curves diff` within the documented
# tolerance (the oracle compress-demo shares); and `registry trend`
# must flag an injected CRV count as REG003. Exits nonzero on any miss
# (tpu_ddp/tools/curves_demo.py).
CURVES_DEMO_DIR ?= /tmp/tpu_ddp_curves_demo
curves-demo:
	rm -rf $(CURVES_DEMO_DIR)
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=4" \
	  $(PYTHON) -m tpu_ddp.tools.curves_demo --dir $(CURVES_DEMO_DIR)

# Elastic-runtime acceptance (docs/resilience.md): a supervised
# (`tpu-ddp elastic train`) run on the 8-virtual-device CPU mesh with
# three injected faults — save-io-flake x2 at the step-3 checkpoint
# (retried with backoff), checkpoint-corrupt of the newest save (step
# 6, bit-flipped after its checksum manifest lands), kill-host at step
# 8 with 4 survivors — must recover WITHOUT human input: classify
# `killed`, re-mesh 8->4 at the same global batch, REFUSE the corrupt
# step by name, resume from the older verified step, finish clean. The
# goodput ledger must show exactly 2 incarnations with 5 replayed
# steps, categories summing to elapsed within 2%, and the elastic
# decision join; `tpu-ddp curves --against` a 3-seed band recorded on
# 4 devices must pass the recovered run (the band is mesh-invariant by
# construction). Exits nonzero on any miss (tpu_ddp/tools/chaos_demo.py).
CHAOS_DEMO_DIR ?= /tmp/tpu_ddp_chaos_demo
chaos-demo:
	rm -rf $(CHAOS_DEMO_DIR)
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	  $(PYTHON) -m tpu_ddp.tools.chaos_demo --dir $(CHAOS_DEMO_DIR)

# Comms-observatory acceptance (docs/comms.md): on a 4-virtual-device
# CPU mesh, `tpu-ddp comms bench` must time the real XLA all-reduce and
# the hand-rolled f32/int8 rings, fit monotone per-link alpha-beta
# models, and show the int8 ring moving fewer bytes on the wire than
# f32 at equal payload; the artifact must `registry record` as kind
# "comms"; `tpu-ddp tune --comms-from` must price dp vs grad-compress
# DIFFERENTLY from the measured lines (and refuse the unpriceable cpu
# chip without it); a live --comms-monitor run under a chaos comm_stall
# must raise exactly COM001 against the calibrated baseline; `comms
# exposure` + `trace summarize` must join the measured exposed-comm
# share beside the accounted one; and a ring wedged past the watchdog
# deadline must exit 113 with a forensics bundle whose
# suspect_collective matches the program-order schedule, classify as
# "hang", and carry the suspect into the goodput ledger's notes. Exits
# nonzero on any miss (tpu_ddp/tools/comms_demo.py).
COMMS_DEMO_DIR ?= /tmp/tpu_ddp_comms_demo
comms-demo:
	rm -rf $(COMMS_DEMO_DIR)
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=4" \
	  $(PYTHON) -m tpu_ddp.tools.comms_demo --dir $(COMMS_DEMO_DIR)

# Data-path observatory acceptance (docs/data.md): `tpu-ddp data bench`
# must measure every loader stage and `registry record` as kind "data";
# a live staged-pipeline run under a chaos per-stage data_stall must
# raise exactly DAT001 naming the stalled stage against the benched
# busy-rate baseline, and `tpu-ddp data report` must call that stage
# dominant; a supervised kill -> 8-to-4 re-mesh resume must leave
# replayed digests `tpu-ddp data audit` verifies bit-identical (a
# mutated digest fails closed by step); `tpu-ddp tune --data-from` must
# price the measured input floor and exclude unfeedable candidates
# input_bound by name; and the artifact must self-compare clean. Exits
# nonzero on any miss (tpu_ddp/tools/data_demo.py).
DATA_DEMO_DIR ?= /tmp/tpu_ddp_data_demo
data-demo:
	rm -rf $(DATA_DEMO_DIR)
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	  $(PYTHON) -m tpu_ddp.tools.data_demo --dir $(DATA_DEMO_DIR)

# Fused-kernel tier acceptance (docs/kernels.md): interpret-mode `ops
# bench` must measure every strategy kernel bit-identical to its jnp
# reference and registry-record as kind `ops`; `tune --ops-from` must
# price the kernel switch by its SIGNED measured saving (negative in
# interpret mode — kernel-off outranks every +krn twin); a full
# zero1 + int8-ring + error-feedback training run with --kernels must
# match the XLA path bit for bit (params, moments + EMA, EF
# residuals); and a deliberately corrupted kernel must fail the
# parity gate by name with exit 1. Exits nonzero on any miss
# (tpu_ddp/tools/kernels_demo.py).
KERNELS_DEMO_DIR ?= /tmp/tpu_ddp_kernels_demo
kernels-demo:
	rm -rf $(KERNELS_DEMO_DIR)
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=4" \
	  $(PYTHON) -m tpu_ddp.tools.kernels_demo --dir $(KERNELS_DEMO_DIR)

# ZeRO-3 parameter-streaming acceptance (docs/PERF.md "Parameter
# streaming"): a full --zero3 Trainer run must land on the same final
# params as the in-tree GSPMD fsdp strategy (the ZeRO-3 oracle); the
# partition's static accounting must show ~1/N per-device param bytes
# with the prefetch high-water bounded, reconciled against the live
# mem sampler; a supervised chaos kill at step 8 (8 -> 4 survivors)
# must resume from the de-sharded checkpoint across the device-count
# change with `tpu-ddp data audit` verifying bit-identical replayed
# batches; and an injected serialized-gather program must trip COL001
# by id while the product program lints clean. Exits nonzero on any
# miss (tpu_ddp/tools/zero3_demo.py).
ZERO3_DEMO_DIR ?= /tmp/tpu_ddp_zero3_demo
zero3-demo:
	rm -rf $(ZERO3_DEMO_DIR)
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	  $(PYTHON) -m tpu_ddp.tools.zero3_demo --dir $(ZERO3_DEMO_DIR)

# Root-cause engine acceptance (docs/diagnose.md): on a 4-virtual-device
# CPU mesh, `tpu-ddp diagnose` over a clean run must exit 0 with "no
# suspect" while NAMING every absent observatory as a refusal; a chaos
# data_stall, a live chaos comm_stall (diagnosed MID-stall from the hop
# monitor's in-flight marker), and an injected all-NaN batch must each
# yield exactly their own verdict — DIA001 naming the stalled stage,
# DIA002 naming the wedged ring collective, DIA006 naming the poisoned
# step — with no second rule riding along (cross-attribution fails the
# demo); the clean artifact must `registry record` as kind "diagnose";
# and `bench compare` must regress the clean baseline the moment a
# fresh suspect class appears. Exits nonzero on any miss
# (tpu_ddp/tools/diagnose_demo.py).
DIAGNOSE_DEMO_DIR ?= /tmp/tpu_ddp_diagnose_demo
diagnose-demo:
	rm -rf $(DIAGNOSE_DEMO_DIR)
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=4" \
	  $(PYTHON) -m tpu_ddp.tools.diagnose_demo --dir $(DIAGNOSE_DEMO_DIR)

# Deviceless perf-regression gate: re-capture the AOT artifact with the
# real XLA:TPU toolchain (needs libtpu; ~30+ min of compiles) and diff
# it against the committed baseline — exits nonzero on an extra
# collective, a widened payload dtype, or memory/flops growth beyond
# tolerance. `make aot` rewrites benchmarks/aot_v5e.json in place, so
# the baseline is snapshotted first.
bench-compare:
	cp benchmarks/aot_v5e.json /tmp/tpu_ddp_aot_baseline.json
	$(PYTHON) benchmarks/aot_v5e.py
	$(PYTHON) -m tpu_ddp.cli.main bench compare --tolerance 0.1 \
	  /tmp/tpu_ddp_aot_baseline.json benchmarks/aot_v5e.json

# 2-epoch end-to-end CLI run on the virtual mesh (fast sanity check).
smoke:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	  $(PYTHON) main.py --device cpu --synthetic-data --epochs 2 \
	  --log-every-epochs 1 --eval-each-epoch
