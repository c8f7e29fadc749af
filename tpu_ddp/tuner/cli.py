"""``tpu-ddp tune`` — search the layout space, emit the fastest config.

Deviceless end to end: on a CPU-only host, ``tpu-ddp tune --chip v5e
--devices 8`` compiles the whole candidate grid for an 8-chip mesh
(forcing the virtual CPU device count itself when the backend has not
initialized yet), prices it against the v5e roofline, and ranks. Every
ranked candidate is lint-clean and under the chip's HBM cap by
construction; the excluded list says exactly why each rejected
candidate fell (over_hbm / lint / compile_error / unpriceable).

Artifacts:

- ``--json out.json`` — the schema-versioned ranked table
  (``tune_schema_version``), provenance-stamped: ``tpu-ddp registry
  record`` archives it, ``registry trend`` watches the winner's
  predicted throughput/step drift, ``bench compare`` gates it.
- ``--emit-config winner.json`` — the ready-to-run winner: a
  ``TrainConfig`` field dict (validated before writing) plus the
  equivalent ``tpu-ddp train`` CLI line (its ``cli`` key), which is
  how the winner is run.
- ``--validate-top K`` — short measured trials of the top K candidates
  (``validate.py``), re-ranked on measurement.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Optional, Sequence

from tpu_ddp.tuner.grid import STRATEGY_TOKENS


def _bootstrap_devices(n: Optional[int]) -> None:
    """Force ``n`` virtual CPU devices BEFORE jax initializes, when the
    process targets the CPU backend (a TPU host keeps its real chips;
    the host-platform flag only affects the cpu backend)."""
    if not n or "jax" in sys.modules:
        return
    if os.environ.get("JAX_PLATFORMS", "cpu") not in ("", "cpu"):
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()


def build_tune_model(model_name: str, *, n_chans1: int, n_blocks: int,
                     num_classes: int, image_size: int,
                     compute_dtype: str):
    """(model, model_name_label): the Trainer-buildable model the tune
    sweep compiles. ``netresdeep`` honors the width/depth knobs (the
    label carries them so the compile cache can't conflate a reduced
    netresdeep with the full one)."""
    import jax.numpy as jnp

    from tpu_ddp.models import NetResDeep
    from tpu_ddp.models.zoo import MODEL_REGISTRY

    dtype = {"float32": jnp.float32,
             "bfloat16": jnp.bfloat16}[compute_dtype]
    if model_name == "netresdeep":
        model = NetResDeep(n_chans1=n_chans1, n_blocks=n_blocks,
                           num_classes=num_classes, dtype=dtype)
        label = model_name
        if (n_chans1, n_blocks) != (32, 10):
            label = f"netresdeep_c{n_chans1}b{n_blocks}"
        return model, label
    if model_name not in MODEL_REGISTRY:
        raise ValueError(
            f"unknown model {model_name!r}; choose netresdeep or one of "
            f"{sorted(MODEL_REGISTRY)}"
        )
    if model_name.startswith("resnet"):
        model = MODEL_REGISTRY[model_name](
            num_classes=num_classes, dtype=dtype,
            cifar_stem=(image_size <= 64))
    else:
        model = MODEL_REGISTRY[model_name](num_classes=num_classes,
                                           dtype=dtype)
    return model, model_name


def winner_config_fields(priced, *, model_name: str, n_chans1: int,
                         n_blocks: int, num_classes: int,
                         compute_dtype: str, n_devices: int) -> dict:
    """The TrainConfig field dict a ranked candidate trains as — the
    exact program the tuner priced (``n_microbatches`` pinned to the
    priced program's value for pp)."""
    c = priced.candidate
    fields = {
        "model": model_name,
        "num_classes": num_classes,
        "compute_dtype": compute_dtype,
        "parallelism": c.parallelism,
        "mesh": c.mesh_sizes(n_devices),
        "zero1": c.zero1,
        "zero3": c.zero3,
        "grad_compress": c.grad_compress or "none",
        "per_shard_batch": c.per_shard_batch,
        "steps_per_call": c.steps_per_call,
        "n_devices": n_devices,
    }
    if model_name == "netresdeep":
        fields["n_chans1"] = n_chans1
        fields["n_blocks"] = n_blocks
    if c.grad_compress:
        fields["grad_compress_block"] = 256
    if c.parallelism == "pp":
        fields["n_microbatches"] = 2
    if c.kernels:
        fields["kernels"] = True
    return fields


def winner_cli_line(fields: dict) -> str:
    """The ``tpu-ddp train`` invocation equivalent to the winner's
    TrainConfig (data/telemetry flags left to the operator)."""
    parts = ["tpu-ddp train", f"--model {fields['model']}"]
    if "n_chans1" in fields:
        parts.append(f"--n-chans1 {fields['n_chans1']}")
    if "n_blocks" in fields:
        parts.append(f"--n-blocks {fields['n_blocks']}")
    parts.append(f"--parallelism {fields['parallelism']}")
    mesh = ",".join(f"{a}={s}" for a, s in (fields.get("mesh") or {}).items())
    if mesh:
        parts.append(f"--mesh {mesh}")
    parts.append(f"--batch-size {fields['per_shard_batch']}")
    if fields.get("steps_per_call", 1) > 1:
        parts.append(f"--steps-per-call {fields['steps_per_call']}")
    if fields.get("zero1"):
        parts.append("--zero1")
    if fields.get("zero3"):
        parts.append("--zero3")
    if fields.get("grad_compress", "none") != "none":
        parts.append(f"--grad-compress {fields['grad_compress']}")
    if fields.get("kernels"):
        parts.append("--kernels")
    if fields.get("n_microbatches"):
        parts.append(f"--microbatches {fields['n_microbatches']}")
    parts.append(f"--compute-dtype {fields['compute_dtype']}")
    if fields.get("num_classes", 10) != 10:
        parts.append(f"--num-classes {fields['num_classes']}")
    return " ".join(parts)


def _human_time(s: Optional[float]) -> str:
    if s is None:
        return "n/a"
    if s >= 1:
        return f"{s:.2f} s"
    if s >= 1e-3:
        return f"{s * 1e3:.2f} ms"
    return f"{s * 1e6:.0f} us"


def render_result(result, *, top: int = 0) -> str:
    """The ranked table + exclusions, human-form."""
    from tpu_ddp.analysis.roofline import chip_spec

    note = []
    compiled_spec = chip_spec(result.compiled_for)
    if compiled_spec is None or compiled_spec.key != result.chip:
        # the roofline is over ANOTHER compiler's cost analysis: its
        # fusion and layout choices, not the target chip's. On jax 0.9.0
        # XLA:CPU's bytes per image rank per-shard batch 32 above 256 for
        # netresdeep, against the one chip sweep on record (docs/tuning.md)
        note = [
            f"note: programs compiled for {result.compiled_for!r}, priced "
            f"on {result.chip}: the order ACROSS batch sizes follows that "
            "compiler's bytes-accessed estimate and is not calibrated — "
            "confirm it with --validate-top on the target"]
    lines = [
        f"tune: model={result.model_name} chip={result.chip} "
        f"devices={result.n_devices} dtype={result.compute_dtype} "
        f"(compiled {result.compiled_programs} distinct programs, "
        f"calibration x{result.calibration_ratio:g} "
        f"[{result.calibration_source}], hbm "
        f"x{result.hbm_calibration_ratio:g} "
        f"[{result.hbm_calibration_source}], comms "
        f"[{result.comms_calibration_source}], data "
        f"[{result.data_calibration_source}], ops "
        f"[{result.ops_calibration_source}])",
        *note,
        "",
    ]
    rows = result.ranked[:top] if top else result.ranked
    if rows:
        header = (f"  {'#':>3} {'candidate':<38} {'step':>10} "
                  f"{'img/s/chip':>11} {'bound':<7} {'hbm':>6}")
        lines += [header, "  " + "-" * (len(header) - 2)]
        for i, p in enumerate(rows):
            hbm = (f"{p.hbm_fraction:.1%}"
                   if p.hbm_fraction is not None else "n/a")
            meas = ""
            if p.measured and "error" not in p.measured:
                meas = (" measured "
                        f"{p.measured['measured_images_per_sec_per_chip']:g}"
                        " img/s/chip")
            lines.append(
                f"  {i:>3} {p.name:<38} "
                f"{_human_time(p.effective_step_s):>10} "
                f"{p.predicted_images_per_sec_per_chip:>11.0f} "
                f"{p.bound or '?':<7} {hbm:>6}{meas}"
            )
        if top and len(result.ranked) > top:
            lines.append(f"  ... ({len(result.ranked) - top} more ranked)")
    else:
        lines.append("  no rankable candidates")
    if result.excluded:
        lines.append("")
        lines.append(f"excluded ({len(result.excluded)}):")
        for p in result.excluded:
            lines.append(f"  {p.name}: {p.status}: {p.reason}")
    if result.winner:
        lines.append("")
        lines.append(f"winner: {result.winner.name} — predicted "
                     f"{result.winner.predicted_images_per_sec_per_chip:g} "
                     "img/s/chip (lint-clean, under the "
                     f"{result.chip} HBM cap)")
    return "\n".join(lines)


def tune_artifact(result) -> dict:
    """The schema-versioned ``tune --json`` artifact."""
    import jax

    from tpu_ddp.telemetry.provenance import artifact_provenance

    winner = result.winner
    rec = {
        "chip": result.chip,
        "model": result.model_name,
        "n_devices": result.n_devices,
        "compute_dtype": result.compute_dtype,
        "dispatch_overhead_us": round(result.dispatch_overhead_s * 1e6, 1),
        "calibration": {"ratio": result.calibration_ratio,
                        "source": result.calibration_source},
        "hbm_calibration": {"ratio": result.hbm_calibration_ratio,
                            "source": result.hbm_calibration_source},
        "comms_calibration": {"source": result.comms_calibration_source},
        "data_calibration": {"source": result.data_calibration_source},
        "ops_calibration": {"source": result.ops_calibration_source},
        "grid": result.grid_descriptor(),
        "n_candidates": len(result.ranked) + len(result.excluded),
        "n_ranked": len(result.ranked),
        "n_excluded": len(result.excluded),
        "compiled_programs": result.compiled_programs,
        "winner": winner.name if winner else None,
        # the two gate-able headline figures: predicted throughput is
        # the quality-class (higher-is-better) metric `bench compare` /
        # `registry trend` watch; predicted step gates as a size
        "predicted_images_per_sec_per_chip":
            winner.predicted_images_per_sec_per_chip if winner else None,
        "predicted_step_us": winner.predicted_step_us if winner else None,
        "ranked": [p.row_json(result.n_devices) for p in result.ranked],
        "excluded": [p.row_json(result.n_devices) for p in result.excluded],
        "validated": [
            {**{"name": p.name, "device_kind":
                (p.measured or {}).get("device_kind")},
             **{k: v for k, v in (p.measured or {}).items()
                if k != "device_kind"}}
            for p in result.ranked if p.measured is not None
        ],
    }
    art = {
        "tune_schema_version": None,  # replaced below (keeps key order)
        "tune": rec,
        "provenance": artifact_provenance(
            # the digest folds the FULL searched-space identity (grid
            # dimensions + pricing knobs), not just model/chip — two
            # differently-scoped sweeps must form two registry series
            descriptor={"artifact": "tune", "model": result.model_name,
                        "chip": result.chip,
                        "n_devices": result.n_devices,
                        "compute_dtype": result.compute_dtype,
                        "grid": result.grid_descriptor()},
            # predictions are properties of (program, chip), not of the
            # compiling host — the chip IS the device identity, so tune
            # series line up across any host that priced the same grid
            device_kind=result.chip,
            jax_version=jax.__version__,
        ),
    }
    from tpu_ddp.tuner.price import TUNE_SCHEMA_VERSION

    art["tune_schema_version"] = TUNE_SCHEMA_VERSION
    return art


def main(argv: Optional[Sequence[str]] = None) -> int:
    """``tpu-ddp tune [--chip v5e] [--devices N] ...`` — exit 0 with a
    winner, 2 on usage/env errors."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="tpu-ddp tune",
        description="roofline-guided auto-tuner: enumerate strategy x "
                    "mesh x overlay x batch x steps_per_call, compile "
                    "each candidate devicelessly, price on the chip "
                    "roofline under the HBM cap, reject lint findings, "
                    "rank, and emit the winner (docs/tuning.md)",
    )
    ap.add_argument("--chip", default=None,
                    help="chip spec to price against (v2..v6e); default: "
                         "the local backend's device kind — REQUIRED on "
                         "CPU-only hosts, which have no published peak")
    ap.add_argument("--devices", type=int, default=None,
                    help="target chip count (default: all local devices; "
                         "on a CPU host the virtual device count is "
                         "forced up to this automatically)")
    ap.add_argument("--model", default="netresdeep",
                    help="zoo model name or netresdeep (default)")
    ap.add_argument("--n-chans1", type=int, default=8,
                    help="netresdeep width (default 8: the fast sweep "
                         "model; the full reference model is 32)")
    ap.add_argument("--n-blocks", type=int, default=2,
                    help="netresdeep depth (default 2; reference is 10)")
    ap.add_argument("--num-classes", type=int, default=10)
    ap.add_argument("--image-size", type=int, default=32)
    ap.add_argument("--compute-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--batches", default="8,32",
                    help="comma-separated per-shard batch sizes")
    ap.add_argument("--steps-per-call", default="1,8,32",
                    help="comma-separated scan-fusion factors "
                         "(dp family only)")
    ap.add_argument("--strategies", default=None,
                    help="comma-separated strategy tokens "
                         f"({', '.join(STRATEGY_TOKENS)}); default: "
                         "every token the model family supports")
    ap.add_argument("--dispatch-overhead-us", type=float, default=None,
                    help="host overhead charged per dispatch, amortized "
                         "by steps_per_call (default 200)")
    ap.add_argument("--overlap", default="overlapped",
                    choices=["overlapped", "serial"],
                    help="roofline overlap assumption")
    ap.add_argument("--calibrate-from", action="append", default=[],
                    metavar="PATH",
                    help="run dir (profile bundles), analyze --json "
                         "artifact (time calibration), or mem --json "
                         "artifact (measured HBM-cap calibration) to "
                         "read measured-over-predicted ratios from "
                         "(repeatable)")
    ap.add_argument("--comms-from", action="append", default=[],
                    metavar="PATH", dest="comms_from",
                    help="`tpu-ddp comms bench --json` artifact whose "
                         "fitted alpha-beta link model replaces the "
                         "spec-sheet ICI term in every candidate's "
                         "roofline (repeatable; wrong-chip evidence is "
                         "ignored; docs/comms.md). With measured comms "
                         "evidence, peak-less chips (cpu) price on the "
                         "comm term alone")
    ap.add_argument("--data-from", action="append", default=[],
                    metavar="PATH", dest="data_from",
                    help="`tpu-ddp data bench --json` artifact whose "
                         "benched per-image host cost prices each "
                         "candidate's input-bound floor (repeatable; "
                         "docs/data.md). Candidates the loader cannot "
                         "feed are excluded input_bound, named like "
                         "over_hbm exclusions")
    ap.add_argument("--ops-from", action="append", default=[],
                    metavar="PATH", dest="ops_from",
                    help="`tpu-ddp ops bench --json` artifact whose "
                         "fitted per-kernel cost lines price the fused "
                         "Pallas kernel switch (repeatable; wrong-chip "
                         "evidence is ignored; docs/kernels.md). With "
                         "measured ops evidence the grid doubles along "
                         "a kernels on/off axis for the dp family and "
                         "the SIGNED measured saving ranks the switch "
                         "honestly — negative savings rank kernel-off "
                         "first")
    ap.add_argument("--registry", default=None, metavar="DIR",
                    help="perf-registry workspace: archived validated "
                         "tune entries join the time calibration, "
                         "mem-kind entries the HBM-cap calibration, "
                         "comms-kind entries the interconnect model")
    ap.add_argument("--top", type=int, default=15,
                    help="ranked rows to print (0 = all)")
    ap.add_argument("--json", default=None,
                    help="write the schema-versioned ranked-table "
                         "artifact here (registry-recordable, "
                         "bench-compare-able)")
    ap.add_argument("--emit-config", default=None, metavar="OUT.json",
                    help="write the winner's ready-to-run TrainConfig "
                         "artifact here (its `cli` key is the "
                         "tpu-ddp train line that runs it)")
    ap.add_argument("--validate-top", type=int, default=0, metavar="K",
                    help="run short measured trials of the top K "
                         "candidates and re-rank on measurement")
    ap.add_argument("--validate-dir", default=None,
                    help="where --validate-top trial run dirs go "
                         "(default: a temp dir)")
    args = ap.parse_args(list(argv) if argv is not None else None)

    _bootstrap_devices(args.devices)
    try:
        return _run(args)
    except (FileNotFoundError, ValueError) as e:
        print(f"tpu-ddp tune: {e}", flush=True)
        return 2


def _run(args) -> int:
    import jax

    from tpu_ddp.analysis.roofline import chip_spec
    from tpu_ddp.tuner.calibrate import calibration_for_chip
    from tpu_ddp.tuner.grid import enumerate_grid
    from tpu_ddp.tuner.price import DEFAULT_DISPATCH_OVERHEAD_S, tune

    local = jax.devices()
    n = args.devices or len(local)
    if n > len(local):
        raise ValueError(
            f"--devices {n} but the local backend has {len(local)} — on "
            "a CPU host rerun under XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n}"
        )
    devices = local[:n]
    chip = args.chip or devices[0].device_kind
    spec = chip_spec(chip)
    # measured interconnect model (docs/comms.md): `comms bench`
    # artifacts + comms-kind registry entries; with evidence, the
    # roofline's ICI term is measurement, and a peak-less chip (cpu)
    # becomes priceable on its comm term alone
    from tpu_ddp.comms.model import comms_model_for_chip

    comms_model = comms_model_for_chip(
        chip, sources=args.comms_from, registry_dir=args.registry)
    # measured input-cost model (docs/data.md): `data bench` artifacts
    # + data-kind registry entries; with evidence, every candidate gets
    # an input-bound floor and unfeedable ones are excluded by name
    from tpu_ddp.datapath.model import data_model_from_sources

    data_model = data_model_from_sources(
        args.data_from, registry_dir=args.registry)
    # measured fused-kernel model (docs/kernels.md): `ops bench`
    # artifacts + ops-kind registry entries; with evidence, dp-family
    # candidates grow a kernels-on twin priced by the SIGNED saving
    from tpu_ddp.ops.model import ops_model_for_chip

    ops_model = ops_model_for_chip(
        chip, sources=args.ops_from, registry_dir=args.registry)
    if spec is None or (spec.peak_bf16_flops is None
                        and not comms_model):
        raise ValueError(
            f"no published peak for {chip!r}: pass --chip v5e (or "
            "another CHIP_SPECS key) to price against real hardware — "
            "or --comms-from with measured comms evidence for this "
            "chip (comm-term-only pricing)"
        )

    model, model_label = build_tune_model(
        args.model, n_chans1=args.n_chans1, n_blocks=args.n_blocks,
        num_classes=args.num_classes, image_size=args.image_size,
        compute_dtype=args.compute_dtype)
    batches = [int(b) for b in args.batches.split(",") if b.strip()]
    ks = [int(k) for k in args.steps_per_call.split(",") if k.strip()]
    strategies = ([s.strip() for s in args.strategies.split(",")
                   if s.strip()] if args.strategies else None)
    if args.image_size != 32 and (args.validate_top > 0
                                  or args.emit_config):
        raise ValueError(
            f"--image-size {args.image_size} prices a program the "
            "Trainer cannot run (TrainConfig has no image-size field; "
            "training is 32x32) — a measured trial or emitted winner "
            "would describe a different program than was priced. Drop "
            "--validate-top/--emit-config for a pricing-only sweep at "
            "this size"
        )
    candidates = enumerate_grid(
        model, n, batches=batches, steps_per_call=ks,
        strategies=strategies, image_size=args.image_size)
    if not candidates:
        raise ValueError("the grid enumerated no candidates (check "
                         "--strategies against the model family)")
    if ops_model:
        # double the dp family along the kernel switch: the twin shares
        # its base's compiled program + lint audit (program_key ignores
        # `kernels` — the fused tier is bit-identical by contract) and
        # differs only in the measured savings term
        import dataclasses as _dc

        candidates = candidates + [
            _dc.replace(c, kernels=True)
            for c in candidates if c.parallelism == "dp"]
    calibration = calibration_for_chip(
        chip, sources=args.calibrate_from, registry_dir=args.registry)
    # HBM-cap calibration (docs/memory.md): `tpu-ddp mem --json`
    # artifacts in --calibrate-from and mem-kind registry entries feed
    # the measured-over-planned peak ratio into the capacity gate
    from tpu_ddp.tuner.calibrate import hbm_calibration_for_chip

    hbm_calibration = hbm_calibration_for_chip(
        chip, sources=args.calibrate_from, registry_dir=args.registry)
    print(f"tpu-ddp tune: {len(candidates)} candidates "
          f"({len({c.program_key() for c in candidates})} distinct "
          f"programs) for {model_label} on {n}x {spec.key}", flush=True)
    result = tune(
        model=model, model_name=model_label, devices=devices,
        chip=chip, candidates=candidates,
        compute_dtype=args.compute_dtype, image_size=args.image_size,
        num_classes=args.num_classes,
        calibration_ratio=calibration.ratio,
        calibration_source=calibration.source,
        hbm_calibration_ratio=hbm_calibration.ratio,
        hbm_calibration_source=hbm_calibration.source,
        comms_model=comms_model or None,
        comms_calibration_source=comms_model.source
        if comms_model else "none",
        data_model=data_model or None,
        data_calibration_source=data_model.source
        if data_model else "none",
        ops_model=ops_model or None,
        ops_calibration_source=ops_model.source
        if ops_model else "none",
        dispatch_overhead_s=(
            args.dispatch_overhead_us * 1e-6
            if args.dispatch_overhead_us is not None
            else DEFAULT_DISPATCH_OVERHEAD_S),
        overlap=args.overlap,
    )
    if result.winner is None:
        print(render_result(result, top=args.top), flush=True)
        print("tpu-ddp tune: no rankable candidates (every candidate "
              "was excluded — see the reasons above)", flush=True)
        return 2

    def _fields(priced):
        return winner_config_fields(
            priced, model_name=args.model, n_chans1=args.n_chans1,
            n_blocks=args.n_blocks, num_classes=args.num_classes,
            compute_dtype=args.compute_dtype, n_devices=n)

    if args.validate_top > 0:
        import tempfile

        from tpu_ddp.tuner.validate import validate_top

        workdir = args.validate_dir or tempfile.mkdtemp(
            prefix="tpu_ddp_tune_validate_")
        print(f"tpu-ddp tune: validating top {args.validate_top} with "
              f"measured trials under {workdir}", flush=True)
        validate_top(result, _fields, top=args.validate_top,
                     workdir=workdir)

    winner_fields = _fields(result.winner)
    # the winner must be runnable as emitted: validate() the exact
    # field dict before writing anything
    from tpu_ddp.tuner.validate import train_config_for

    train_config_for(winner_fields).validate()
    cli_line = winner_cli_line(winner_fields)

    print(render_result(result, top=args.top), flush=True)
    print(f"\nwinner cli: {cli_line}", flush=True)

    if args.json:
        art = tune_artifact(result)
        art["winner_config"] = winner_fields
        art["winner_cli"] = cli_line
        with open(args.json, "w") as f:
            json.dump(art, f, indent=1)
        print(f"tpu-ddp tune: wrote {args.json}", flush=True)
    if args.emit_config:
        winner_art = {
            "tune_winner_schema_version": 1,
            "config": winner_fields,
            "cli": cli_line,
            "predicted": {
                "chip": result.chip,
                "images_per_sec_per_chip":
                    result.winner.predicted_images_per_sec_per_chip,
                "step_us": result.winner.predicted_step_us,
                "bound": result.winner.bound,
                "hbm_fraction": result.winner.hbm_fraction,
            },
        }
        if result.winner.measured is not None:
            winner_art["measured"] = result.winner.measured
        with open(args.emit_config, "w") as f:
            json.dump(winner_art, f, indent=1)
        print(f"tpu-ddp tune: wrote {args.emit_config} (run it: "
              f"{cli_line})", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
