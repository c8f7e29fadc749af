# Test/verification entry points. The suite runs on 8 virtual CPU devices
# (conftest.py pins the platform), so no TPU is needed for any target here.

PYTHON ?= python

.PHONY: test test-all dryrun smoke aot real-data lint bench-compare

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
