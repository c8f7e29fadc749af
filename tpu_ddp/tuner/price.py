"""Deviceless pricing: compile, lint, cap, and rank every candidate.

One candidate's price is built from the exact artifacts the rest of the
framework already trusts:

- the **program** comes from ``train/strategy.py::build_abstract_step``
  through the shared ``analysis/hlo.py`` compile cache (cache keys match
  ``analysis/explain.py::prepare_strategy_program``'s format, so a tune
  after an analyze/lint of the same program is free — and a second tune
  over the same grid compiles **0** new programs);
- the **verdict gate** is ``analysis/lint.py::lint_program`` over that
  compiled program: any error-severity finding excludes the candidate,
  so every ranked candidate is lint-clean by construction;
- the **capacity gate** is ``tools/memplan.py``'s convention — compiled
  peak = argument + temp bytes per device — against the target chip's
  HBM capacity from ``analysis/roofline.py::CHIP_SPECS``;
- the **time model** is ``analysis/roofline.py::roofline`` (predicted
  step time per chip under the stated overlap assumption), scaled by a
  per-chip-kind calibration ratio (``calibrate.py``), plus a host
  dispatch-overhead term amortized by ``steps_per_call``:

      effective_step_s = roofline_step_s * calibration
                         + dispatch_overhead_s / steps_per_call

  The overhead term is why the tuner can rank scan fusion at all — the
  compiled per-step program is IDENTICAL for every ``steps_per_call``
  (that is the point of scan fusion), so devicelessly only the
  amortized dispatch cost separates k=1 from k=32. The default
  (``DEFAULT_DISPATCH_OVERHEAD_S``) is a deliberately conservative
  figure for one jax dispatch; ``--dispatch-overhead-us`` tunes it, and
  ``--validate-top`` replaces the model with measurement.

Ranking metric: predicted images/sec/chip =
``per_shard_batch * data_axis / n_devices / effective_step_s`` — the
cross-batch, cross-mesh comparable unit (step time alone is not: a
bigger batch legitimately takes a longer step).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

from tpu_ddp.tuner.grid import Candidate

#: bump on any breaking change to the ``tune --json`` artifact shape
TUNE_SCHEMA_VERSION = 1

#: host overhead charged per dispatch (one ``step()`` call): a
#: conservative figure for jax dispatch + host loop bookkeeping on an
#: uncontended host; not measured on the chip yet. A higher real figure
#: only strengthens the fused candidates this term already prefers.
DEFAULT_DISPATCH_OVERHEAD_S = 200e-6

#: exclusion reasons (the ``status`` of a non-ranked candidate)
STATUS_OK = "ok"
STATUS_OVER_HBM = "over_hbm"
STATUS_LINT = "lint"
STATUS_COMPILE_ERROR = "compile_error"
STATUS_UNPRICEABLE = "unpriceable"
STATUS_INPUT_BOUND = "input_bound"
STATUS_REPLICATED_FITS = "replicated_fits"


@dataclasses.dataclass
class PricedCandidate:
    """One candidate's verdict. ``status == "ok"`` means ranked; every
    other status carries a ``reason`` and lands in the excluded list."""

    candidate: Candidate
    name: str
    status: str
    reason: str = ""
    model_step_s: Optional[float] = None      # raw roofline prediction
    effective_step_s: Optional[float] = None  # calibrated + dispatch
    predicted_images_per_sec_per_chip: Optional[float] = None
    bound: Optional[str] = None
    peak_bytes: Optional[int] = None
    hbm_fraction: Optional[float] = None
    lint_rule_counts: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    measured: Optional[dict] = None           # --validate-top join
    input_floor_s: Optional[float] = None     # --data-from measured floor
    kernel_savings_s: Optional[float] = None  # --ops-from SIGNED saving

    @property
    def predicted_step_us(self) -> Optional[int]:
        if self.effective_step_s is None:
            return None
        return int(round(self.effective_step_s * 1e6))

    def row_json(self, n_devices: int) -> dict:
        c = self.candidate
        rec = {
            "name": self.name,
            "parallelism": c.parallelism,
            "mesh": c.mesh_sizes(n_devices),
            "zero1": c.zero1,
            "zero3": c.zero3,
            "grad_compress": c.grad_compress,
            "per_shard_batch": c.per_shard_batch,
            "steps_per_call": c.steps_per_call,
            "kernels": c.kernels,
            "status": self.status,
            "predicted_step_us": self.predicted_step_us,
            "predicted_images_per_sec_per_chip":
                self.predicted_images_per_sec_per_chip,
            "bound": self.bound,
            "peak_bytes": self.peak_bytes,
            "hbm_fraction": self.hbm_fraction,
        }
        if self.reason:
            rec["reason"] = self.reason
        if self.lint_rule_counts:
            rec["lint_rule_counts"] = self.lint_rule_counts
        if self.measured is not None:
            rec["measured"] = self.measured
        if self.input_floor_s is not None:
            rec["input_floor_us"] = int(round(self.input_floor_s * 1e6))
        if self.kernel_savings_s is not None:
            rec["kernel_savings_us"] = round(
                self.kernel_savings_s * 1e6, 1)
        return rec


@dataclasses.dataclass
class TuneResult:
    """Everything one tune run produced, pre-rendering."""

    chip: str
    model_name: str
    n_devices: int
    compute_dtype: str
    dispatch_overhead_s: float
    calibration_ratio: float
    calibration_source: str
    ranked: List[PricedCandidate]
    excluded: List[PricedCandidate]
    compiled_programs: int
    image_size: int = 32
    overlap: str = "overlapped"
    # device kind the candidate programs were COMPILED for (the devices
    # `tune` was given): the cost analysis priced is that compiler's
    compiled_for: str = "unknown"
    # HBM-cap calibration (docs/memory.md): measured-over-planned peak
    # from `tpu-ddp mem` evidence, multiplied into every candidate's
    # compiled peak before the over_hbm verdict
    hbm_calibration_ratio: float = 1.0
    hbm_calibration_source: str = "none"
    # measured interconnect calibration (docs/comms.md): names the
    # `--comms-from` evidence whose α-β link model replaced the
    # spec-sheet ICI term in every candidate's roofline
    comms_calibration_source: str = "none"
    # measured input-cost calibration (docs/data.md): names the
    # `--data-from` evidence whose per-image host cost priced every
    # candidate's input-bound floor
    data_calibration_source: str = "none"
    # measured fused-kernel calibration (docs/kernels.md): names the
    # `--ops-from` evidence whose per-kernel cost model priced the
    # kernel-on candidates' SIGNED savings term
    ops_calibration_source: str = "none"

    @property
    def winner(self) -> Optional[PricedCandidate]:
        return self.ranked[0] if self.ranked else None

    def grid_descriptor(self) -> dict:
        """WHAT was searched, derived from the candidate set itself —
        the searched-space identity the artifact's config digest folds
        in, so a `--batches 8,256` sweep and a `--batches 8` sweep can
        never collapse into one registry trend/baseline series (the
        winner throughputs of differently-scoped grids are not
        comparable points)."""
        cands = [p.candidate for p in self.ranked + self.excluded]
        return {
            "strategies": sorted({c.strategy_token for c in cands}),
            "batches": sorted({c.per_shard_batch for c in cands}),
            "steps_per_call": sorted({c.steps_per_call for c in cands}),
            "image_size": self.image_size,
            "overlap": self.overlap,
            "dispatch_overhead_us": round(
                self.dispatch_overhead_s * 1e6, 1),
            "calibration_ratio": self.calibration_ratio,
            "hbm_calibration_ratio": self.hbm_calibration_ratio,
            "comms_calibration_source": self.comms_calibration_source,
            "data_calibration_source": self.data_calibration_source,
            "ops_calibration_source": self.ops_calibration_source,
        }


def _program_cache_key(cand: Candidate, *, model_name: str,
                       compute_dtype: str, image_size: int,
                       num_classes: int, mesh, devices,
                       n_microbatches: int) -> Tuple:
    """Compile-cache key in the exact format
    ``prepare_strategy_program`` uses, so plain candidates share their
    compiled program with ``tpu-ddp analyze``/``lint`` runs of the same
    strategy in the same process."""
    return (
        "analyze", cand.strategy_token, model_name, cand.per_shard_batch,
        compute_dtype, image_size, num_classes, False, 1,
        tuple(zip(mesh.axis_names, mesh.devices.shape)),
        devices[0].device_kind, len(devices),
        cand.grad_compress,
        256 if cand.grad_compress else None, n_microbatches,
        True,
    )


def prepare_candidate_program(
    cand: Candidate,
    *,
    model,
    model_name: str,
    devices,
    compute_dtype: str = "float32",
    image_size: int = 32,
    num_classes: int = 10,
    n_microbatches: int = 2,
):
    """The candidate's compile-ready abstract program — a
    ``StrategyProgram`` built on ``build_abstract_step`` exactly like
    ``prepare_strategy_program``, but composing the dp-family overlays
    (``zero1`` + ``grad_compress`` together, the bf16 ring) the analyze
    strategy tokens cannot name."""
    from tpu_ddp.analysis.explain import StrategyProgram, abstract_batch
    from tpu_ddp.parallel import MeshSpec, create_mesh
    from tpu_ddp.train import make_optimizer
    from tpu_ddp.train.strategy import build_abstract_step

    devices = list(devices)
    mesh = create_mesh(MeshSpec(**cand.mesh_sizes(len(devices))), devices)
    # same optimizer knobs as prepare_strategy_program: the cache keys
    # only stay shared if the compiled programs really are identical
    tx = make_optimizer(
        lr=1e-1, momentum=0.9,
        zero1_axis="data" if (cand.zero1 or cand.zero3) else None)
    grad_compress = (
        {"mode": cand.grad_compress, "block": 256, "error_feedback": False}
        if cand.grad_compress else None
    )
    step, state = build_abstract_step(
        cand.parallelism, model, tx, mesh, image_size=image_size,
        zero1=cand.zero1, zero3=cand.zero3, grad_compress=grad_compress,
        n_microbatches=n_microbatches,
    )
    key = _program_cache_key(
        cand, model_name=model_name, compute_dtype=compute_dtype,
        image_size=image_size, num_classes=num_classes, mesh=mesh,
        devices=devices, n_microbatches=n_microbatches,
    )
    return StrategyProgram(
        strategy=cand.strategy_token, parallelism=cand.parallelism,
        step=step, state=state,
        batch=abstract_batch(mesh, cand.per_shard_batch, image_size),
        mesh=mesh, model_name=model_name, compute_dtype=compute_dtype,
        per_shard_batch=cand.per_shard_batch, image_size=image_size,
        cache_key=key,
    )


def price_anatomy(
    cand: Candidate,
    anatomy,
    *,
    chip: str,
    n_devices: int,
    calibration_ratio: float = 1.0,
    hbm_calibration_ratio: float = 1.0,
    dispatch_overhead_s: float = DEFAULT_DISPATCH_OVERHEAD_S,
    overlap: str = "overlapped",
    lint_rule_counts: Optional[Dict[str, int]] = None,
    lint_errors: Sequence[str] = (),
    comms_model=None,
    data_model=None,
    ops_model=None,
    param_elements: Optional[int] = None,
) -> PricedCandidate:
    """The pure pricing tail over an already-extracted anatomy: lint
    verdict -> HBM cap -> roofline -> calibration -> dispatch
    amortization -> throughput. Split out so tests can price synthetic
    anatomies without compiling.

    ``hbm_calibration_ratio`` is the measured-over-planned peak from
    the memory truth loop (``tpu-ddp mem``, docs/memory.md): the
    capacity gate checks ``peak * ratio`` against the chip's HBM, so a
    chip kind whose measured high-water runs hot against the static
    plan excludes borderline candidates BEFORE they OOM on hardware.

    ``comms_model`` (a ``comms/model.py`` LinkModel with evidence)
    swaps the roofline's spec-sheet ICI term for measured per-link α-β
    pricing — and unlocks peak-less chips (CPU hosts): their price is
    comm-term-only, honest about what was measured.

    ``data_model`` (a ``datapath/model.py`` DataModel with evidence,
    ``--data-from``) prices a measured INPUT-BOUND floor per candidate:
    the host must produce ``per_shard_batch * data_axis`` images per
    step at the benched per-image cost (single-host conservative — a
    symmetric pod divides the load by its host count), and a candidate
    whose floor exceeds its compute-side step cannot be fed — it is
    excluded ``input_bound``, named like an ``over_hbm`` exclusion
    (docs/data.md).

    ``ops_model`` (an ``ops/model.py`` OpsModel with evidence,
    ``--ops-from``) prices the fused-kernel switch on kernel-on
    candidates: the benched per-element cost lines give a SIGNED
    per-step saving for ``fused_update`` over the optimizer's shard and
    for ``fused_quant``/``fused_dequant`` over the int8 ring's hops.
    The sign is honest — where the bench measured the fused path slower
    (e.g. interpret mode on CPU), the saving is negative and kernel-off
    outranks kernel-on (docs/kernels.md)."""
    from tpu_ddp.analysis.roofline import chip_spec, roofline

    name = cand.name(n_devices)
    counts = dict(lint_rule_counts or {})
    if lint_errors:
        return PricedCandidate(
            candidate=cand, name=name, status=STATUS_LINT,
            reason="; ".join(lint_errors), lint_rule_counts=counts,
            peak_bytes=anatomy.peak_bytes,
        )
    spec = chip_spec(chip)
    if spec is None or (spec.peak_bf16_flops is None
                        and not comms_model):
        raise ValueError(
            f"no published peak for chip {chip!r}: pass --chip with a "
            "CHIP_SPECS key (v2..v6e), or --comms-from with measured "
            "comms evidence for this chip (comm-term-only pricing)"
        )
    peak = anatomy.peak_bytes
    expected_peak = (peak * hbm_calibration_ratio
                     if peak is not None else None)
    hbm_fraction = (expected_peak / spec.hbm_bytes
                    if expected_peak is not None and spec.hbm_bytes
                    else None)
    if hbm_fraction is not None and hbm_fraction >= 1.0:
        calibrated = (f" (x{hbm_calibration_ratio:g} measured HBM "
                      "calibration)" if hbm_calibration_ratio != 1.0
                      else "")
        return PricedCandidate(
            candidate=cand, name=name, status=STATUS_OVER_HBM,
            reason=(f"compiled peak (args+temp) {peak} B{calibrated} is "
                    f"{hbm_fraction:.2f}x the {spec.key} HBM capacity "
                    f"({spec.hbm_bytes} B)"),
            peak_bytes=peak, hbm_fraction=round(hbm_fraction, 4),
            lint_rule_counts=counts,
        )
    rl = roofline(anatomy, chip, overlap=overlap,
                  comms_model=comms_model)
    if not rl.predicted_step_s:
        return PricedCandidate(
            candidate=cand, name=name, status=STATUS_UNPRICEABLE,
            reason="cost model exposed no flops/bytes to price "
                   f"({'; '.join(rl.notes) or 'empty roofline'})",
            peak_bytes=peak,
            hbm_fraction=(round(hbm_fraction, 4)
                          if hbm_fraction is not None else None),
            lint_rule_counts=counts,
        )
    effective = (rl.predicted_step_s * calibration_ratio
                 + dispatch_overhead_s / max(cand.steps_per_call, 1))
    data = cand.mesh_sizes(n_devices).get("data", 1)
    kernel_savings = None
    if cand.kernels and ops_model is not None and param_elements:
        parts = []
        # fused_update sweeps the optimizer's own shard: the zero1/
        # zero3 scatter leaves each chip 1/data of the flat param space
        sharded = cand.zero1 or cand.zero3
        shard = max(param_elements // (data if sharded else 1), 1)
        s = ops_model.savings_s("fused_update", shard)
        if s is not None:
            parts.append(s)
        if cand.grad_compress == "int8" and data > 1:
            # the compressed ring moves per-chip chunks of 1/data of
            # the grads; reduce-scatter quantizes/dequant-accumulates
            # data-1 hops, and the plain all-reduce's gather phase
            # adds one more encode and data more decodes
            chunk = max(param_elements // data, 1)
            hops = data - 1
            q_count = hops + (0 if sharded else 1)
            d_count = hops + (0 if sharded else data)
            for kname, count in (("fused_quant", q_count),
                                 ("fused_dequant", d_count)):
                s = ops_model.savings_s(kname, chunk, count=count)
                if s is not None:
                    parts.append(s)
        if parts:
            kernel_savings = sum(parts)
            # SIGNED: a bench that measured the fused path slower
            # (interpret mode) makes effective LONGER — kernel-off wins
            effective = max(effective - kernel_savings, 1e-9)
    input_floor = None
    if data_model:
        images_per_step = cand.per_shard_batch * data
        input_floor = data_model.input_floor_s(images_per_step)
        if input_floor > effective:
            dominant = (f"; dominant stage: {data_model.dominant_stage}"
                        if data_model.dominant_stage else "")
            return PricedCandidate(
                candidate=cand, name=name, status=STATUS_INPUT_BOUND,
                reason=(f"measured input floor "
                        f"{input_floor * 1e6:.0f} us/step "
                        f"({images_per_step} images x "
                        f"{data_model.per_image_s * 1e6:.2f} us/image "
                        "benched host input cost) exceeds the "
                        f"{effective * 1e6:.0f} us compute step — the "
                        f"loader cannot feed this candidate{dominant}"),
                model_step_s=rl.predicted_step_s,
                effective_step_s=effective,
                bound=rl.bound, peak_bytes=peak,
                hbm_fraction=(round(hbm_fraction, 4)
                              if hbm_fraction is not None else None),
                lint_rule_counts=counts, input_floor_s=input_floor,
                kernel_savings_s=kernel_savings,
            )
    throughput = cand.per_shard_batch * data / n_devices / effective
    return PricedCandidate(
        candidate=cand, name=name, status=STATUS_OK,
        model_step_s=rl.predicted_step_s,
        effective_step_s=effective,
        predicted_images_per_sec_per_chip=round(throughput, 1),
        bound=rl.bound, peak_bytes=peak,
        hbm_fraction=(round(hbm_fraction, 4)
                      if hbm_fraction is not None else None),
        lint_rule_counts=counts, input_floor_s=input_floor,
        kernel_savings_s=kernel_savings,
    )


def tune(
    *,
    model,
    model_name: str,
    devices,
    chip: str,
    candidates: Sequence[Candidate],
    compute_dtype: str = "float32",
    image_size: int = 32,
    num_classes: int = 10,
    calibration_ratio: float = 1.0,
    calibration_source: str = "none",
    hbm_calibration_ratio: float = 1.0,
    hbm_calibration_source: str = "none",
    comms_model=None,
    comms_calibration_source: str = "none",
    data_model=None,
    data_calibration_source: str = "none",
    ops_model=None,
    ops_calibration_source: str = "none",
    dispatch_overhead_s: float = DEFAULT_DISPATCH_OVERHEAD_S,
    overlap: str = "overlapped",
    lint_config=None,
) -> TuneResult:
    """Compile + lint + price every candidate; rank the survivors by
    predicted images/sec/chip (descending; predicted step time per chip
    breaks ties toward the cheaper step). Candidates sharing a
    ``program_key()`` (steps_per_call variants) share one compile and
    one lint audit."""
    from tpu_ddp.analysis.lint import lint_program, rule_counts
    from tpu_ddp.analysis.roofline import chip_spec

    spec = chip_spec(chip)
    if spec is None or (spec.peak_bf16_flops is None
                        and not comms_model):
        raise ValueError(
            f"no published peak for chip {chip!r}: pass --chip with a "
            "CHIP_SPECS key (v2..v6e), or --comms-from with measured "
            "comms evidence for this chip (comm-term-only pricing)"
        )
    devices = list(devices)
    n = len(devices)
    audits: Dict[Tuple, Any] = {}
    ranked: List[PricedCandidate] = []
    excluded: List[PricedCandidate] = []
    for cand in candidates:
        pkey = cand.program_key()
        if pkey not in audits:
            try:
                prog = prepare_candidate_program(
                    cand, model=model, model_name=model_name,
                    devices=devices, compute_dtype=compute_dtype,
                    image_size=image_size, num_classes=num_classes,
                )
                findings, audit = lint_program(
                    prog.step, prog.state, prog.batch, prog.mesh,
                    strategy=cand.lint_label(n),
                    compute_dtype=compute_dtype,
                    cache_key=prog.cache_key, config=lint_config,
                    program=cand.name(n), model_name=model_name,
                )
                import math

                import jax

                n_params = sum(
                    int(math.prod(leaf.shape))
                    for leaf in jax.tree.leaves(prog.state.params))
                audits[pkey] = (findings, audit, n_params, None)
            except Exception as e:  # an uncompilable candidate is a
                # grid bug (the enumeration contract) — surface it as
                # an excluded row, never a crashed sweep
                audits[pkey] = (None, None, None,
                                f"{type(e).__name__}: {e}")
        findings, audit, n_params, err = audits[pkey]
        if err is not None:
            excluded.append(PricedCandidate(
                candidate=cand, name=cand.name(n),
                status=STATUS_COMPILE_ERROR, reason=err))
            continue
        errors = [f"{f.rule}: {f.message}" for f in findings
                  if f.severity == "error"]
        priced = price_anatomy(
            cand, audit.anatomy, chip=chip, n_devices=n,
            calibration_ratio=calibration_ratio,
            hbm_calibration_ratio=hbm_calibration_ratio,
            dispatch_overhead_s=dispatch_overhead_s, overlap=overlap,
            lint_rule_counts=rule_counts(findings), lint_errors=errors,
            comms_model=comms_model, data_model=data_model,
            ops_model=ops_model, param_elements=n_params,
        )
        (ranked if priced.status == STATUS_OK else excluded).append(priced)
    # zero3 is HBM relief, not a speedup: the streaming schedule pays
    # prefetch all-gather wire bytes every step (priced above through
    # the same roofline/comms model as every other collective) to free
    # the replicated param residency. A zero3 candidate therefore only
    # EARNS a rank when its replicated twin — the same grid point with
    # zero3 off — is over the HBM cap or strictly slower; otherwise it
    # is refused by name (`replicated_fits`), like an over_hbm row.
    def _point(c: Candidate, zero3: bool) -> Tuple:
        return (c.parallelism, c.axis_size, c.zero1, zero3,
                c.grad_compress, c.per_shard_batch, c.steps_per_call,
                c.kernels)

    by_point = {_point(p.candidate, p.candidate.zero3): p
                for p in ranked + excluded}
    kept: List[PricedCandidate] = []
    for priced in ranked:
        c = priced.candidate
        if not c.zero3:
            kept.append(priced)
            continue
        twin = by_point.get(_point(c, False))
        if (twin is not None and twin.status == STATUS_OK
                and twin.effective_step_s is not None
                and priced.effective_step_s is not None
                and twin.effective_step_s <= priced.effective_step_s):
            priced.status = STATUS_REPLICATED_FITS
            priced.reason = (
                f"replicated twin {twin.name} fits the HBM cap "
                f"({twin.hbm_fraction:.1%} used) at "
                f"{twin.effective_step_s * 1e6:.0f} us/step <= this "
                f"candidate's {priced.effective_step_s * 1e6:.0f} us — "
                "the prefetch all-gather wire bytes buy HBM this mesh "
                "does not need" if twin.hbm_fraction is not None else
                f"replicated twin {twin.name} prices at "
                f"{twin.effective_step_s * 1e6:.0f} us/step <= this "
                f"candidate's {priced.effective_step_s * 1e6:.0f} us")
            excluded.append(priced)
        else:
            kept.append(priced)
    ranked = kept
    ranked.sort(key=lambda p: (-p.predicted_images_per_sec_per_chip,
                               p.effective_step_s, p.name))
    return TuneResult(
        chip=spec.key, model_name=model_name, n_devices=n,
        compute_dtype=compute_dtype,
        dispatch_overhead_s=dispatch_overhead_s,
        calibration_ratio=calibration_ratio,
        calibration_source=calibration_source,
        hbm_calibration_ratio=hbm_calibration_ratio,
        hbm_calibration_source=hbm_calibration_source,
        comms_calibration_source=comms_calibration_source,
        data_calibration_source=data_calibration_source,
        ops_calibration_source=ops_calibration_source,
        ranked=ranked, excluded=excluded,
        compiled_programs=len(audits),
        image_size=image_size, overlap=overlap,
        compiled_for=devices[0].device_kind,
    )
