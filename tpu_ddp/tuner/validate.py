"""Measured validation: short real trials of the top candidates.

``tpu-ddp tune --validate-top K`` stops trusting the model for the
candidates that matter: each of the best K predictions runs a short
synthetic-data training through the REAL ``Trainer`` (the product's
step builders, scan fusion, overlays — not a re-implementation) with
telemetry on, and the measurement is joined back through the PR 5
run-metadata header: the header's recorded strategy/mesh must match the
candidate (a trial that silently trained a different layout would
poison the re-rank), and the per-step time comes from the trace's
``compiled_step`` spans with scan-fusion normalization
(``analysis/explain.py::measured_phases``). Validated candidates
re-rank on measurement; each trial also records its
``measured_vs_model`` ratio + device kind — the calibration food
``calibrate.py`` reads back from archived tune artifacts.

:func:`measure_config` takes the same ``TrainConfig`` fields the
winner artifact holds, so what ``tune --emit-config`` writes is what
:func:`validate_top` measured.
"""

from __future__ import annotations

import dataclasses
import os

#: trial length: dispatch calls per trial (each call covers
#: ``steps_per_call`` optimizer steps) — enough for a p50 past the
#: first-call jitter without turning the sweep into a bench run
DEFAULT_TRIAL_CALLS = 3

#: TrainConfig fields a tune winner artifact carries (the
#: program-shaping subset; everything else keeps its default)
WINNER_CONFIG_FIELDS = (
    "model", "n_chans1", "n_blocks", "num_classes", "compute_dtype",
    "parallelism", "mesh", "zero1", "zero3", "grad_compress",
    "grad_compress_block",
    "per_shard_batch", "steps_per_call", "n_devices", "n_microbatches",
    "kernels",
)


def train_config_for(config_fields: dict):
    """A ``TrainConfig`` from a winner artifact's ``config`` dict
    (unknown keys refused — a winner emitted by a NEWER tuner must not
    silently drop program-shaping fields)."""
    from tpu_ddp.train.trainer import TrainConfig

    known = {f.name for f in dataclasses.fields(TrainConfig)}
    unknown = sorted(set(config_fields) - known)
    if unknown:
        raise ValueError(
            f"winner config carries unknown TrainConfig fields "
            f"{unknown} (emitted by a newer tuner?)"
        )
    return TrainConfig(**config_fields)


def measure_config(
    config_fields: dict,
    run_dir: str,
    *,
    trial_calls: int = DEFAULT_TRIAL_CALLS,
    seed: int = 0,
) -> dict:
    """Run one short measured trial of ``config_fields`` and return the
    joined measurement. The trial trains synthetic data for exactly
    ``trial_calls`` dispatches (x ``steps_per_call`` optimizer steps)
    in one epoch with telemetry into ``run_dir``; the result joins the
    run-metadata header (refusing a strategy/mesh mismatch) with the
    measured per-step p50."""
    import jax

    from tpu_ddp.analysis.explain import measured_phases, read_run_meta
    from tpu_ddp.train.trainer import Trainer

    cfg = train_config_for(dict(
        config_fields,
        synthetic_data=True,
        synthetic_size=max(
            64,
            int(config_fields.get("per_shard_batch", 32))
            * _data_size(config_fields)
            * max(int(config_fields.get("steps_per_call", 1)), 1)
            * trial_calls,
        ),
        epochs=1,
        eval_each_epoch=False,
        prefetch_depth=0,
        log_every_epochs=1,
        seed=seed,
        telemetry_dir=run_dir,
    )).validate()
    Trainer(cfg).run()

    meta = read_run_meta(run_dir)
    want_mesh = {a: s for a, s in (config_fields.get("mesh") or {}).items()
                 if s > 1}
    got_mesh = {a: s for a, s in (meta.get("mesh") or {}).items() if s > 1}
    if want_mesh and got_mesh != want_mesh:
        raise ValueError(
            f"trial header mesh {got_mesh} does not match the candidate "
            f"mesh {want_mesh} — refusing to join the measurement"
        )
    want_par = config_fields.get("parallelism") or "dp"
    if meta.get("strategy") != want_par:
        raise ValueError(
            f"trial header strategy {meta.get('strategy')!r} does not "
            f"match the candidate parallelism {want_par!r}"
        )
    phases = measured_phases(run_dir)
    step = phases.get("compiled_step", {})
    step_s = step.get("per_step_p50_s") or step.get("p50_s")
    if not step_s:
        raise ValueError(
            f"trial wrote no compiled_step spans into {run_dir}"
        )
    n = meta.get("n_devices") or len(jax.devices())
    data = got_mesh.get("data", n if not got_mesh else 1)
    global_batch = int(config_fields.get("per_shard_batch", 32)) * data
    return {
        "measured_step_s": step_s,
        "measured_images_per_sec_per_chip": round(
            global_batch / step_s / n, 1),
        "device_kind": meta.get("device_kind"),
        "n_devices": n,
        "run_id": meta.get("run_id"),
        "run_dir": os.path.abspath(run_dir),
    }


def _data_size(config_fields: dict) -> int:
    mesh = config_fields.get("mesh") or {}
    if mesh:
        return int(mesh.get("data", 1))
    n = config_fields.get("n_devices")
    return int(n) if n else 1


def validate_top(
    result,
    winner_config_fn,
    *,
    top: int,
    workdir: str,
    trial_calls: int = DEFAULT_TRIAL_CALLS,
) -> None:
    """Measured trials for ``result``'s top ``top`` ranked candidates,
    in place: each validated candidate gains a ``measured`` record
    (step time, throughput, measured_vs_model) and the validated prefix
    re-ranks by MEASURED throughput. ``winner_config_fn(priced)`` maps
    a ranked candidate to its TrainConfig field dict (the cli owns that
    mapping). A trial that fails records the failure on the candidate
    instead of aborting the sweep."""
    os.makedirs(workdir, exist_ok=True)
    subset = result.ranked[:max(top, 0)]
    for i, priced in enumerate(subset):
        run_dir = os.path.join(workdir, f"trial_{i:02d}")
        try:
            measured = measure_config(
                winner_config_fn(priced), run_dir,
                trial_calls=trial_calls)
            if priced.model_step_s:
                measured["measured_vs_model"] = round(
                    measured["measured_step_s"] / priced.model_step_s, 4)
            priced.measured = measured
        except Exception as e:
            priced.measured = {"error": f"{type(e).__name__}: {e}"}
    measured_ok = [p for p in subset
                   if p.measured and "error" not in p.measured]
    if measured_ok:
        measured_ok.sort(key=lambda p: -p.measured[
            "measured_images_per_sec_per_chip"])
        rest = [p for p in result.ranked if p not in measured_ok]
        result.ranked[:] = measured_ok + rest
