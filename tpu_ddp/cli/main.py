"""``tpu-ddp`` — the umbrella CLI.

Subcommands:

- ``tpu-ddp train ...``   — the training CLI (same flags as tpu-ddp-train)
- ``tpu-ddp launch ...``  — the multi-process launcher (tpu-ddp-launch)
- ``tpu-ddp elastic train ...`` — supervised elastic training: wraps
  the train CLI in a restart loop that classifies each death via the
  goodput ledger's exit taxonomy, applies per-failure-class bounded-
  backoff budgets, re-meshes to the surviving device set (named
  refusals; ``--fallback-plan tune.json`` re-plans through the
  auto-tuner's next-ranked candidate), resumes from the newest
  checksum-VERIFIED checkpoint, and logs every decision to
  ``elastic.jsonl`` — which ``tpu-ddp goodput`` joins
  (docs/resilience.md).
- ``tpu-ddp trace summarize <run_dir>`` — aggregate a telemetry JSONL
  trace into per-phase percentiles (p50/p95/max) and the final
  counters/gauges snapshot.
- ``tpu-ddp health <run_dir>`` — render a monitored run's numerics
  timeline (loss/grad-norm percentiles + sparkline, non-finite and
  loss-spike steps) and any anomaly dumps (docs/health.md).
- ``tpu-ddp watch <run_dir>`` — LIVE fleet monitor: tails the run
  dir's per-host telemetry/health/heartbeat files into a rolling
  snapshot (per-host steps/sec, phase p50s, data-wait share), flags
  stragglers and lost hosts, and runs the alert rules
  (``alerts.jsonl``); ``--once --json`` for scripting and CI
  (docs/monitoring.md).
- ``tpu-ddp profile <run_dir>`` — render anomaly-profiler capture
  bundles (``<run_dir>/profiles/``): trigger/alert provenance, host
  top stacks (folded-stack sampler), where the device trace and the
  run's program map are, and the cross-host straggler diff
  (docs/profiling.md).
- ``tpu-ddp goodput <run_dir>`` — cross-incarnation goodput ledger:
  stitches every kill→``--resume`` life of a logical run into one
  timeline, classifies every wall-clock second into the badput
  taxonomy (restart gaps, replayed steps, stalls, checkpoint/compile/
  data-wait costs), and recommends a Young–Daly checkpoint interval
  from measured save cost + MTBF (docs/goodput.md).
- ``tpu-ddp diagnose <run_dir>`` — cross-observatory root-cause
  engine: joins every artifact family the run left behind into one
  evidence table and runs the DIA rule registry over it — a ranked
  incident verdict with citations and a recommended action
  (docs/diagnose.md).
- ``tpu-ddp curves <run_dir>`` — convergence observatory: extract the
  run's learning curve (per-step loss/grad-norm from the health sinks
  across every incarnation, the eval-instant history from the trace);
  ``--against <registry>`` judges it against the seed band of archived
  baseline runs sharing its seed-invariant quality digest (CRV001-004
  findings, exit 1 on any); ``tpu-ddp curves diff A B`` is the
  step-aligned overlay-parity verdict of two recorded runs
  (docs/curves.md).
- ``tpu-ddp mem <run_dir>`` — memory truth loop: the live sampler's
  per-host HBM timeline, measured high-water reconciled against the
  recorded program's static plan (memplan convention) into a
  measured-over-planned ratio per chip kind, fragmentation, and any
  OOM postmortem bundles; ``--json`` is registry-recordable and the
  tuner's HBM-cap calibration food (docs/memory.md).
- ``tpu-ddp analyze [run_dir]`` — static step-time anatomy: XLA
  cost-model flops/bytes, collective inventory, roofline bound
  classification, per-strategy collective fingerprint; given a run dir,
  joins the measured telemetry (achieved-vs-roofline, MFU, data-wait
  share). Compiles the real step, so it needs jax (docs/analysis.md).
- ``tpu-ddp lint [--strategy all]`` — static verifier over every
  strategy's compiled step: donation accounting (DON001), dtype
  widening (DTY001), physical sharding (SHD001), collective order /
  participation (COL001), host transfers (XFR001), plus the RCP001
  recompile-hazard AST tier over ``tpu_ddp/`` source. Exits 1 on any
  finding; ``--json`` output gates through ``bench compare``
  (docs/lint.md).
- ``tpu-ddp bench compare old.json new.json`` — structured diff of two
  bench/AOT/analyze/lint artifacts; exits 1 on regressions (extra
  collectives, widened payload dtypes, memory/flops growth, new lint
  findings). ``--against <registry>`` auto-selects the baseline from
  the perf registry instead of a hand-pointed file.
- ``tpu-ddp registry record|list|show|trend|diff`` — the cross-run
  perf results archive: append-only provenance-stamped store of every
  artifact family, REG-rule drift detection over per-(metric × config
  × chip) series, and entry-vs-entry diffs with the exact ``bench
  compare`` gating semantics (docs/registry.md).
- ``tpu-ddp comms bench|calibrate|exposure|forensics`` — the comms
  observatory: measure collective microbenchmarks over the real local
  mesh and fit the per-link α-β interconnect model (schema-versioned
  artifact; registry kind "comms", ``bench compare`` gates achieved
  bandwidth), assemble the per-chip calibrated model (``tune
  --comms-from`` consumes it), measure a recorded run's exposed
  (non-overlapped) comm share against its comm-stripped twin, and name
  a hung run's suspect collective against the program-order schedule
  (docs/comms.md).
- ``tpu-ddp ops bench|calibrate`` — the fused-kernel tier: measure
  each Pallas kernel (``fused_update``, ``fused_quant``,
  ``fused_dequant``) against its XLA path under jit with an in-bench
  bit-parity gate (exit 1 names any failing kernel; schema-versioned
  artifact, registry kind "ops"), and assemble the per-chip kernel
  cost model ``tune --ops-from`` prices the ``--kernels`` switch with
  (docs/kernels.md).
- ``tpu-ddp data bench|audit|report`` — the data-path observatory:
  measure per-stage loader microbenchmarks over the staged input
  pipeline (schema-versioned artifact; registry kind "data", ``bench
  compare`` gates per-stage throughput, ``tune --data-from`` consumes
  the per-image cost), verify a run's seeded batch-content digests
  replay identically across kill→resume and re-mesh (fail-closed,
  naming the diverging step), and decompose a recorded run's
  ``data_wait`` into per-stage percentiles with an input-bound verdict
  (docs/data.md).
- ``tpu-ddp tune`` — roofline-guided auto-tuner: enumerates parallelism
  strategy × mesh shape × ``--zero1``/``--grad-compress`` overlays ×
  batch × ``steps_per_call``, compiles every candidate devicelessly,
  prices each on the chip roofline under the HBM cap, rejects lint
  findings, ranks by predicted images/sec/chip, and emits the winner
  as a ready-to-run TrainConfig + CLI line. ``--validate-top K`` runs
  short measured trials and re-ranks (docs/tuning.md).

``trace summarize``, ``health``, ``watch``, ``profile``, ``mem`` (modulo its lazy plan rebuild; ``--no-plan``
is import-free), ``curves``, ``registry``, and ``bench compare`` are
stdlib-only
end to end (no jax import): records are summarized wherever they land —
a laptop, a CI box, the pod host itself. The train/launch/analyze
subcommands import lazily so the read-back commands keep that property.
"""

from __future__ import annotations

import argparse
import sys
from typing import Optional, Sequence


def _trace_summarize(args) -> int:
    from tpu_ddp.telemetry.summarize import summarize, summarize_json

    try:
        if getattr(args, "json", False):
            import json as _json

            print(_json.dumps(summarize_json(args.path), indent=1))
        else:
            print(summarize(args.path))
    except (FileNotFoundError, ValueError) as e:
        print(f"tpu-ddp trace summarize: {e}", file=sys.stderr)
        return 2
    return 0


def _health_summarize(args) -> int:
    from tpu_ddp.health.summarize import summarize_health

    try:
        print(summarize_health(args.path))
    except (FileNotFoundError, ValueError) as e:
        print(f"tpu-ddp health: {e}", file=sys.stderr)
        return 2
    return 0


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    # train/launch own their argparse surface: hand the remainder through
    # untouched so `tpu-ddp train --help` shows the full trainer surface
    if argv[:1] == ["train"]:
        from tpu_ddp.cli.train import main as train_main

        train_main(argv[1:])
        return 0
    if argv[:1] == ["launch"]:
        from tpu_ddp.cli.launch import main as launch_main

        return launch_main(argv[1:])
    # elastic is stdlib-only: the supervisor must not import jax (it
    # outlives the runtime it supervises); the child process it execs
    # is where jax lives
    if argv[:1] == ["elastic"]:
        from tpu_ddp.elastic.supervisor import main as elastic_main

        return elastic_main(argv[1:])
    # analyze / bench own their argparse surfaces (like train/launch):
    # hand the remainder through so their --help shows the full surface
    if argv[:1] == ["analyze"]:
        from tpu_ddp.analysis.explain import main as analyze_main

        return analyze_main(argv[1:])
    if argv[:1] == ["lint"]:
        from tpu_ddp.analysis.lint import main as lint_main

        return lint_main(argv[1:])
    # watch owns its argparse surface and stays stdlib-only (no jax
    # import unless --roofline is passed)
    if argv[:1] == ["watch"]:
        from tpu_ddp.monitor.watch import main as watch_main

        return watch_main(argv[1:])
    # profile is stdlib-only too
    if argv[:1] == ["profile"]:
        from tpu_ddp.profiler.report import main as profile_main

        return profile_main(argv[1:])
    # goodput is stdlib-only end to end (pure file archaeology)
    if argv[:1] == ["goodput"]:
        from tpu_ddp.ledger.report import main as goodput_main

        return goodput_main(argv[1:])
    # diagnose is stdlib-only end to end (cross-observatory file
    # archaeology + the causal rule registry)
    if argv[:1] == ["diagnose"]:
        from tpu_ddp.diagnose.cli import main as diagnose_main

        return diagnose_main(argv[1:])
    # mem is stdlib-only except the static-plan rebuild (lazy jax;
    # --no-plan keeps it import-free)
    if argv[:1] == ["mem"]:
        from tpu_ddp.memtrack.report import main as mem_main

        return mem_main(argv[1:])
    # curves is stdlib-only end to end (file archaeology + band math)
    if argv[:1] == ["curves"]:
        from tpu_ddp.curves.report import main as curves_main

        return curves_main(argv[1:])
    # registry is stdlib-only too (record/list/show/trend/diff)
    if argv[:1] == ["registry"]:
        from tpu_ddp.registry.cli import main as registry_main

        return registry_main(argv[1:])
    # tune compiles the candidate grid, so it needs jax — but the
    # import stays inside its own main so the read-back commands keep
    # their stdlib-only property
    if argv[:1] == ["tune"]:
        from tpu_ddp.tuner.cli import main as tune_main

        return tune_main(argv[1:])
    # comms owns its argparse surface; bench/exposure/forensics compile
    # real programs (lazy jax), calibrate stays stdlib-only
    if argv[:1] == ["comms"]:
        from tpu_ddp.comms.cli import main as comms_main

        return comms_main(argv[1:])
    # data owns its argparse surface; bench touches jax only for the
    # h2d stage (lazy), audit/report are stdlib-only file archaeology
    if argv[:1] == ["data"]:
        from tpu_ddp.datapath.cli import main as data_main

        return data_main(argv[1:])
    # ops owns its argparse surface; bench runs the fused kernels (lazy
    # jax), calibrate stays stdlib-only
    if argv[:1] == ["ops"]:
        from tpu_ddp.ops.cli import main as ops_main

        return ops_main(argv[1:])
    if argv[:2] == ["bench", "compare"]:
        from tpu_ddp.analysis.regress import main as compare_main

        return compare_main(argv[2:])

    ap = argparse.ArgumentParser(
        prog="tpu-ddp",
        description="tpu_ddp umbrella CLI (train / launch / trace)",
    )
    sub = ap.add_subparsers(dest="command", required=True)
    sub.add_parser("train", help="run the trainer (tpu-ddp train --help)")
    sub.add_parser("launch", help="multi-process launcher "
                                  "(tpu-ddp launch --help)")
    sub.add_parser(
        "elastic",
        help="supervised elastic training: restart loop with failure-"
             "class budgets, re-mesh to survivors, verified-checkpoint "
             "recovery, elastic.jsonl decision log "
             "(tpu-ddp elastic --help)",
    )
    trace = sub.add_parser("trace", help="telemetry trace tools")
    trace_sub = trace.add_subparsers(dest="trace_command", required=True)
    summ = trace_sub.add_parser(
        "summarize",
        help="per-phase p50/p95 table from a run dir's JSONL trace",
    )
    summ.add_argument("path", help="run dir (holding trace-p*.jsonl) or a "
                                   "trace file")
    summ.add_argument("--json", action="store_true",
                      help="emit the schema-versioned machine summary "
                           "(perf-registry-recordable)")
    summ.set_defaults(func=_trace_summarize)
    health = sub.add_parser(
        "health",
        help="numerics timeline + anomalies from a run dir's health "
             "record (see --health on tpu-ddp train)",
    )
    health.add_argument("path", help="run dir (holding health-p*.jsonl) "
                                     "or a health file")
    health.set_defaults(func=_health_summarize)
    sub.add_parser(
        "watch",
        help="live fleet monitor over a run dir: per-host steps/sec + "
             "phase p50s, straggler/lost-host flags, alert rules "
             "(tpu-ddp watch --help)",
    )
    sub.add_parser(
        "profile",
        help="render anomaly-profiler capture bundles: host top stacks, "
             "device trace and program map, straggler diff "
             "(tpu-ddp profile --help)",
    )
    sub.add_parser(
        "goodput",
        help="cross-incarnation goodput/badput ledger + Young–Daly "
             "checkpoint-interval advisor over a run dir "
             "(tpu-ddp goodput --help)",
    )
    sub.add_parser(
        "mem",
        help="memory truth loop over a run dir: live-HBM timeline, "
             "measured-vs-planned reconciliation, OOM postmortems "
             "(tpu-ddp mem --help)",
    )
    sub.add_parser(
        "diagnose",
        help="cross-observatory root-cause verdict for a run dir: "
             "every artifact family joined into one ranked, cited "
             "incident report (tpu-ddp diagnose --help)",
    )
    sub.add_parser(
        "curves",
        help="learning-curve extraction + seed-band trajectory gating "
             "over a run dir; `curves diff A B` for overlay parity "
             "(tpu-ddp curves --help)",
    )
    sub.add_parser(
        "registry",
        help="cross-run perf results archive: record artifacts with "
             "provenance, trend-detect drift, diff entries "
             "(tpu-ddp registry --help)",
    )
    sub.add_parser(
        "analyze",
        help="static step anatomy + roofline + collective fingerprint, "
             "optionally joined with a run dir's telemetry "
             "(tpu-ddp analyze --help)",
    )
    sub.add_parser(
        "comms",
        help="comms observatory: measured collective microbenchmarks + "
             "alpha-beta link calibration, exposed-comm attribution, "
             "stuck-collective forensics (tpu-ddp comms --help)",
    )
    sub.add_parser(
        "data",
        help="data-path observatory: per-stage loader microbenchmarks, "
             "batch-provenance determinism audit across kill/resume and "
             "re-mesh, per-stage data_wait decomposition "
             "(tpu-ddp data --help)",
    )
    sub.add_parser(
        "ops",
        help="fused-kernel tier: fused-vs-XLA microbenchmarks with a "
             "bit-parity gate + per-chip kernel cost calibration "
             "(tpu-ddp ops --help)",
    )
    sub.add_parser(
        "tune",
        help="roofline-guided auto-tuner: search strategy x mesh x "
             "overlay x batch x steps_per_call devicelessly, emit the "
             "fastest lint-clean config (tpu-ddp tune --help)",
    )
    sub.add_parser(
        "lint",
        help="static sharding/donation/numerics verifier over every "
             "strategy's compiled step (tpu-ddp lint --help)",
    )
    bench = sub.add_parser("bench", help="bench artifact tools")
    bench_sub = bench.add_subparsers(dest="bench_command", required=True)
    bench_sub.add_parser(
        "compare",
        help="diff two bench/AOT/analyze JSON artifacts; exit 1 on "
             "regression (tpu-ddp bench compare --help)",
    )
    args = ap.parse_args(argv)
    return args.func(args)


if __name__ == "__main__":
    sys.exit(main())
