"""``tpu-ddp analyze`` — where the step time must go, and where it went.

Static mode (a strategy/model/mesh): compile the exact product train step
(``train/strategy.py::build_abstract_step``), extract its
:class:`~tpu_ddp.analysis.hlo.StepAnatomy`, attribute it on the chip
roofline (``analysis/roofline.py``), verify the strategy's expected
collective fingerprint, and render the report.

Run-dir mode (a directory a ``--telemetry-dir`` run wrote): read the
run-metadata header from the JSONL trace, rebuild + recompile the SAME
program the run trained with, and JOIN the static anatomy against the
measured per-phase telemetry — achieved-vs-roofline %, MFU, comm share,
and the straggler-visible data-wait share. Runs recorded before the
metadata header existed (or whose mesh doesn't fit the local backend)
are refused with an explanation, not mis-attributed.

The **fingerprints** double as a parallelism-correctness regression net:
each strategy has a pinned set of collective kinds its compiled step must
(and must not) contain — an accidental extra all-gather in the dp step,
or the int8 ring silently degrading to f32, flips the verdict on CPU,
devicelessly, before any TPU run (``tests/test_analysis.py`` holds it).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Optional, Sequence

from tpu_ddp.analysis.hlo import StepAnatomy, cached_compile, extract_anatomy
from tpu_ddp.analysis.roofline import RooflineReport, roofline

#: the analyzer's strategy surface: every parallelism family, plus the
#: dp-family layout variants that change the collective story
STRATEGIES = ("dp", "zero1", "zero3", "grad_compress", "sp", "fsdp", "tp",
              "fsdp_tp", "pp", "ep")

# strategy -> sharded non-data axis lives in ONE place:
# train/strategy.py::MODE_AXIS (imported lazily where needed — this
# module stays jax-import-free at module level)

#: Expected collective fingerprint per strategy. ``required`` is a list
#: of ALTERNATION GROUPS: each group is a list of (kind, dtype-or-None)
#: options, at least one of which must appear in the compiled step's
#: inventory. ``forbidden`` kinds must not appear at all. Alternations
#: absorb legitimate partitioner freedom — XLA:TPU lowers zero1's
#: psum_scatter without a literal reduce-scatter op (the committed
#: aot_v5e.json shows all-reduce + all-gather), and the CPU partitioner
#: implements the MoE token dispatch with all-gathers where the TPU
#: partitioner emits all-to-all. ``forbidden`` stays conservative for the
#: same reason (GSPMD may insert resharding collective-permutes /
#: all-to-alls in the GSPMD family); the EXACT per-backend kind sets are
#: pinned in tests/test_analysis.py, which is the regression net proper.
EXPECTED_FINGERPRINTS: Dict[str, Dict[str, Sequence]] = {
    # plain DDP: ONE grad/metrics sync family — any scatter/gather means
    # the layout is no longer "replicated state + all-reduce"
    "dp": {"required": [[("all-reduce", None)]],
           "forbidden": ["reduce-scatter", "all-gather",
                         "collective-permute", "all-to-all"]},
    # ZeRO-1: grads reduce-scatter into the 1/N update shard (TPU may
    # lower that as all-reduce + slice), params all-gather back
    "zero1": {"required": [[("reduce-scatter", None), ("all-reduce", None)],
                           [("all-gather", None)]],
              "forbidden": ["collective-permute", "all-to-all"]},
    # ZeRO-3 parameter streaming (the explicit-schedule counterpart of
    # fsdp): per-block param all-gathers on the prefetch schedule, grads
    # reduce-scatter straight into shard space; the backward is
    # re-gather-free — the COL001 zero3 pin (analysis/lint.py) checks
    # scope-level that NO all-gather lives outside the prefetch schedule,
    # which a kind inventory cannot see
    "zero3": {"required": [[("all-gather", None)],
                           [("reduce-scatter", None), ("all-reduce", None)]],
              "forbidden": ["collective-permute", "all-to-all"]},
    # int8-quantized ring: the gradient sync is ppermute hops whose
    # payloads are s8 (scales ride separate small f32 transfers); the
    # ring degrading to full precision flips this devicelessly
    "grad_compress": {"required": [[("collective-permute", "s8")]],
                      "forbidden": ["all-to-all"]},
    # bf16 ring (the label run_strategy_label gives --grad-compress bf16
    # runs): the ring SCHEDULE (permute hops) is the portable
    # fingerprint — the wire dtype cannot be pinned here because XLA:CPU
    # legalizes bf16 arrays to f32 in the optimized HLO (on TPU the
    # payloads are bf16; bench compare's inventory diff pins that)
    "grad_compress_bf16": {"required": [[("collective-permute", None)]],
                           "forbidden": ["all-to-all"]},
    # ring attention rotates K/V over the sequence axis; grad sync is
    # still an all-reduce family over data+sequence
    "sp": {"required": [[("collective-permute", None)],
                        [("all-reduce", None)]],
           "forbidden": ["all-to-all"]},
    # ZeRO-3: params all-gather per layer; grads drop back sharded
    "fsdp": {"required": [[("all-gather", None)]],
             "forbidden": []},
    # Megatron TP: activation partial-sums all-reduce over `model` (GSPMD:
    # resharding all-to-alls may come and go with the partitioner)
    "tp": {"required": [[("all-reduce", None)]],
           "forbidden": []},
    "fsdp_tp": {"required": [[("all-gather", None)], [("all-reduce", None)]],
                "forbidden": []},
    # GPipe: microbatch activations rotate stage-to-stage
    "pp": {"required": [[("collective-permute", None)]],
           "forbidden": ["all-to-all"]},
    # expert parallel: token dispatch/combine — all-to-all on the TPU
    # partitioner (aot_v5e.json), all-gather on XLA:CPU's
    "ep": {"required": [[("all-to-all", None), ("all-gather", None)]],
           "forbidden": []},
}


def check_fingerprint(anatomy: StepAnatomy,
                      strategy: Optional[str] = None) -> dict:
    """Verify ``anatomy`` against its strategy's expected fingerprint.
    Returns ``{ok, strategy, missing, unexpected}`` — ``missing`` entries
    fail the analyze exit code; ``unexpected`` are forbidden kinds that
    appeared (equally fatal: a collective that shouldn't exist is how a
    parallelism bug usually announces itself)."""
    strategy = strategy or anatomy.strategy
    expected = EXPECTED_FINGERPRINTS.get(strategy)
    if expected is None:
        return {"ok": None, "strategy": strategy, "missing": [],
                "unexpected": [],
                "note": f"no pinned fingerprint for {strategy!r}"}
    present = {(c.kind, c.dtype) for c in anatomy.collectives}
    present_kinds = {k for k, _ in present}
    missing = []
    for group in expected["required"]:
        hit = any(
            (kind in present_kinds if dtype is None
             else (kind, dtype) in present)
            for kind, dtype in group
        )
        if not hit:
            missing.append(" | ".join(
                kind + (f"[{dtype}]" if dtype else "")
                for kind, dtype in group
            ))
    unexpected = sorted(
        k for k in present_kinds if k in expected["forbidden"]
    )
    return {"ok": not missing and not unexpected, "strategy": strategy,
            "missing": missing, "unexpected": unexpected}


# -- building an anatomy for a strategy -----------------------------------

def _tiny_model(strategy: str, num_classes: int, dtype):
    """Small per-family models for fast CPU analysis (the tests'
    path; pass ``model_name`` for the real zoo)."""
    if strategy in ("sp", "pp", "tp", "fsdp_tp", "fsdp"):
        from tpu_ddp.models.vit import ViT

        return ViT(patch_size=8, hidden_dim=32, depth=2, num_heads=2,
                   num_classes=num_classes, dtype=dtype), "vit_tiny"
    if strategy == "ep":
        from tpu_ddp.models.moe import MoEViT

        return MoEViT(patch_size=8, hidden_dim=32, depth=2, num_heads=2,
                      num_experts=4, top_k=1, moe_every=2,
                      num_classes=num_classes, dtype=dtype), "vit_moe_tiny"
    from tpu_ddp.models import NetResDeep

    return NetResDeep(n_chans1=8, n_blocks=2, num_classes=num_classes,
                      dtype=dtype), "netresdeep_tiny"


def _zoo_model(model_name: str, num_classes: int, image_size: int, dtype):
    from tpu_ddp.models import NetResDeep
    from tpu_ddp.models.zoo import MODEL_REGISTRY

    if model_name == "netresdeep":
        return NetResDeep(num_classes=num_classes, dtype=dtype)
    if model_name.startswith("resnet"):
        return MODEL_REGISTRY[model_name](
            num_classes=num_classes, dtype=dtype,
            cifar_stem=(image_size <= 64))
    return MODEL_REGISTRY[model_name](num_classes=num_classes, dtype=dtype)


@dataclasses.dataclass
class StrategyProgram:
    """Everything one strategy's compile-ready abstract program consists
    of — the shared product of :func:`prepare_strategy_program`, consumed
    by :func:`anatomy_for_strategy` (extraction) and
    ``analysis/lint.py`` (static verification), so both reason about the
    SAME program under the same compile-cache key."""

    strategy: str
    parallelism: str
    step: Any
    state: Any
    batch: Dict[str, Any]
    mesh: Any
    model_name: str
    compute_dtype: str
    per_shard_batch: int
    image_size: int
    cache_key: tuple

    def compile(self):
        """The cached compiled executable for this program."""
        return cached_compile(
            self.cache_key,
            lambda: self.step.trace(self.state, self.batch)
            .lower().compile(),
        )


def abstract_batch(mesh, per_shard_batch: int, image_size: int) -> dict:
    """The abstract CIFAR-shaped global batch every anatomy/lint compile
    uses: batch scales with the data axis only, sharded on axis 0."""
    import jax
    import jax.numpy as jnp

    from tpu_ddp.parallel import batch_sharding

    gb = per_shard_batch * mesh.shape["data"]
    bs = batch_sharding(mesh)
    return {
        "image": jax.ShapeDtypeStruct((gb, image_size, image_size, 3),
                                      jnp.float32, sharding=bs),
        "label": jax.ShapeDtypeStruct((gb,), jnp.int32, sharding=bs),
        "mask": jax.ShapeDtypeStruct((gb,), bool, sharding=bs),
    }


def prepare_strategy_program(
    strategy: str,
    *,
    devices=None,
    model_name: Optional[str] = None,
    model=None,
    per_shard_batch: int = 8,
    compute_dtype: str = "float32",
    image_size: int = 32,
    num_classes: int = 10,
    axis_size: Optional[int] = None,
    grad_accum_steps: int = 1,
    remat: bool = False,
    compress_mode: str = "int8",
    compress_block: int = 256,
    n_microbatches: int = 2,
    donate: bool = True,
) -> StrategyProgram:
    """Build the strategy's real abstract train step + inputs (via the
    shared ``build_abstract_step``) without compiling. ``devices``
    default to the current backend's; pass deviceless topology devices
    for TPU-target analysis on a CPU host. ``donate=False`` exists for
    the lint tier's injected-violation path only — the product always
    donates the state."""
    import jax
    import jax.numpy as jnp

    from tpu_ddp.parallel import MeshSpec, create_mesh
    from tpu_ddp.train import make_optimizer
    from tpu_ddp.train.strategy import MODE_AXIS, build_abstract_step

    if strategy not in STRATEGIES:
        raise ValueError(
            f"unknown strategy {strategy!r}; choose from {STRATEGIES}"
        )
    devices = list(devices if devices is not None else jax.devices())
    # zero1/grad_compress are dp-family layout variants; everything else
    # names its parallelism directly
    parallelism = {"zero1": "dp", "zero3": "dp", "grad_compress": "dp"}.get(
        strategy, strategy)
    axis = MODE_AXIS.get(strategy)
    if axis is None:
        mesh = create_mesh(MeshSpec(data=-1), devices)
    else:
        if axis_size is None:
            axis_size = 2 if strategy in ("pp", "sp") else min(
                4, len(devices))
        if len(devices) % axis_size:
            raise ValueError(
                f"axis_size {axis_size} does not divide "
                f"{len(devices)} devices"
            )
        mesh = create_mesh(
            MeshSpec(data=len(devices) // axis_size, **{axis: axis_size}),
            devices,
        )

    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[compute_dtype]
    if model is None:
        if model_name:
            model = _zoo_model(model_name, num_classes, image_size, dtype)
        else:
            model, model_name = _tiny_model(strategy, num_classes, dtype)
    zero1 = strategy == "zero1"
    zero3 = strategy == "zero3"
    grad_compress = (
        {"mode": compress_mode, "block": compress_block,
         "error_feedback": False}
        if strategy == "grad_compress" else None
    )
    tx = make_optimizer(lr=1e-1, momentum=0.9,
                        zero1_axis="data" if (zero1 or zero3) else None)
    step, state = build_abstract_step(
        parallelism, model, tx, mesh, image_size=image_size, remat=remat,
        grad_accum_steps=grad_accum_steps, zero1=zero1, zero3=zero3,
        grad_compress=grad_compress, n_microbatches=n_microbatches,
        donate=donate,
    )
    key = (
        # an explicitly passed model object has no zoo name: key on its
        # repr (flax modules render their full field values) so two
        # custom models never share a cached anatomy
        "analyze", strategy, model_name or repr(model), per_shard_batch,
        compute_dtype, image_size, num_classes, remat, grad_accum_steps,
        tuple(zip(mesh.axis_names, mesh.devices.shape)),
        devices[0].device_kind, len(devices),
        compress_mode if grad_compress else None,
        compress_block if grad_compress else None, n_microbatches,
        donate,
    )
    return StrategyProgram(
        strategy=strategy, parallelism=parallelism, step=step, state=state,
        batch=abstract_batch(mesh, per_shard_batch, image_size),
        mesh=mesh, model_name=model_name or "custom",
        compute_dtype=compute_dtype, per_shard_batch=per_shard_batch,
        image_size=image_size, cache_key=key,
    )


def anatomy_for_strategy(strategy: str, **kwargs) -> StepAnatomy:
    """Compile the strategy's real train step (abstractly, via the shared
    builder + compile cache) and extract its anatomy. Accepts every
    :func:`prepare_strategy_program` keyword."""
    prog = prepare_strategy_program(strategy, **kwargs)
    return extract_anatomy(
        prog.compile(), strategy=prog.strategy, model=prog.model_name,
        mesh=prog.mesh, per_shard_batch=prog.per_shard_batch,
        compute_dtype=prog.compute_dtype,
    )


def _compile_anatomy(step, state, mesh, *, cache_key, strategy, model_name,
                     per_shard_batch, image_size, compute_dtype):
    """Shared tail of every anatomy builder: abstract batch -> cached
    compile -> extraction."""
    batch = abstract_batch(mesh, per_shard_batch, image_size)
    compiled = cached_compile(
        cache_key, lambda: step.trace(state, batch).lower().compile()
    )
    return extract_anatomy(
        compiled, strategy=strategy, model=model_name,
        mesh=mesh, per_shard_batch=per_shard_batch,
        compute_dtype=compute_dtype,
    )


def run_strategy_label(meta: dict) -> str:
    """The analyzer's strategy label for a recorded run: the run's
    parallelism family, refined to the dp-family layout variant when the
    config says so (``grad_compress`` wins the LABEL when composed with
    ``zero1`` — the fingerprint to hold is the s8 ring's; the rebuild
    itself honors both flags)."""
    config = meta.get("config") or {}
    strategy = meta.get("strategy", "dp")
    if strategy == "dp":
        mode = config.get("grad_compress", "none")
        if mode not in (None, "none"):
            return "grad_compress_bf16" if mode == "bf16" else "grad_compress"
        if config.get("zero3"):
            return "zero3"
        if config.get("zero1"):
            return "zero1"
    return strategy


def _run_meta_program(meta: dict, devices):
    """The compile-ready rebuild behind :func:`anatomy_for_run_meta` and
    :func:`compiled_for_run_meta`: ``(step, state, mesh, cache_key,
    cfg)`` for the recorded program. Raises for programs the abstract
    builder cannot reproduce."""
    import dataclasses as _dc

    import jax

    from tpu_ddp.parallel import MeshSpec, create_mesh
    from tpu_ddp.train.optim import make_optimizer
    from tpu_ddp.train.strategy import build_abstract_step
    from tpu_ddp.train.trainer import TrainConfig, build_model

    config_rec = meta.get("config") or {}
    fields = {f.name for f in _dc.fields(TrainConfig)}
    cfg = TrainConfig(**{k: v for k, v in config_rec.items()
                         if k in fields})
    parallelism = meta.get("strategy", "dp")
    zero1 = bool(cfg.zero1)
    zero3 = bool(getattr(cfg, "zero3", False))
    compress_on = cfg.grad_compress not in (None, "none")
    if (zero1 or zero3 or compress_on) and parallelism != "dp":
        raise ValueError(
            f"cannot rebuild a {parallelism}+"
            f"{'zero1' if zero1 else 'zero3' if zero3 else 'grad-compress'} "
            "run abstractly (build_abstract_step composes those with dp "
            "only); analyze the family statically via --strategy instead"
        )
    # scan fusion is dp-only (the Trainer warns and ignores the flag for
    # every other family, trainer.py), so only dp runs actually compiled
    # the fused program this rebuild can't reproduce
    if parallelism == "dp" and int(getattr(cfg, "steps_per_call", 1) or 1) > 1:
        raise ValueError(
            f"run fused steps_per_call={cfg.steps_per_call} optimizer "
            "steps per dispatch (a scan-fused program this rebuild does "
            "not reproduce); analyze the family statically via "
            "--strategy instead"
        )
    mesh_shape = {a: s for a, s in (meta.get("mesh") or {}).items()}
    mesh = create_mesh(MeshSpec(**mesh_shape), list(devices))

    model = build_model(cfg)
    # mirror the Trainer's optimizer construction (trainer.py): zero1
    # runs the chain on flattened shards, so the decay mask must be
    # precomputed on the original shapes
    decay_mask = None
    if (zero1 or zero3) and cfg.weight_decay > 0:
        from tpu_ddp.train.optim import _decay_mask
        from tpu_ddp.train.state import init_model_variables

        abstract_params, _ = jax.eval_shape(
            lambda: init_model_variables(model, jax.random.key(0))
        )
        decay_mask = _decay_mask(abstract_params)
    freeze = None
    if cfg.freeze_prefixes:
        from tpu_ddp.train.optim import freeze_all_but

        freeze = freeze_all_but(tuple(cfg.freeze_prefixes))
    tx = make_optimizer(
        lr=cfg.lr, optimizer=cfg.optimizer, momentum=cfg.momentum,
        weight_decay=cfg.weight_decay, grad_clip_norm=cfg.grad_clip_norm,
        ema_decay=cfg.ema_decay, decay_mask=decay_mask,
        freeze_predicate=freeze,
        # the schedule changes the opt_state tree structure (injected
        # step count), so it must be mirrored; the step COUNT it anneals
        # over is a baked Python scalar that doesn't alter the program
        # shape, and the run's true total isn't recorded — any total
        # past the warmup is structurally identical
        schedule=cfg.schedule,
        total_steps=max(1000, 2 * cfg.warmup_steps),
        warmup_steps=cfg.warmup_steps,
        zero1_axis="data" if (zero1 or zero3) else None,
    )
    grad_compress = (
        {"mode": cfg.grad_compress, "block": cfg.grad_compress_block,
         "error_feedback": cfg.grad_compress_error_feedback}
        if compress_on else None
    )
    # the numerics recorder's in-graph half changes the compiled program
    # (extra psum'd norm all-reduces): mirror it like the Trainer does
    health = None
    if cfg.health != "off":
        from tpu_ddp.health import HealthConfig

        health = HealthConfig(
            per_layer=cfg.health_per_layer_stride > 0,
            skip_nonfinite=cfg.health_policy == "skip_step",
        )
    step, state = build_abstract_step(
        parallelism, model, tx, mesh, remat=cfg.remat,
        grad_accum_steps=cfg.grad_accum_steps, zero1=zero1, zero3=zero3,
        grad_compress=grad_compress, n_microbatches=cfg.n_microbatches,
        health=health, pp_schedule=cfg.pp_schedule, sp_flash=cfg.sp_flash,
    )
    key = ("analyze-run", json.dumps(config_rec, sort_keys=True),
           parallelism, tuple(sorted(mesh_shape.items())),
           devices[0].device_kind, len(list(devices)))
    return step, state, mesh, key, cfg


def anatomy_for_run_meta(meta: dict, devices) -> StepAnatomy:
    """Rebuild the EXACT program a recorded run trained with, from its
    run-metadata header: the real model (``build_model`` on the recorded
    config snapshot — widths, depths, num_classes and all), the real
    optimizer chain (kind / momentum / weight-decay mask / EMA / clip /
    zero1 sharding), the real dp-family layout composition
    (``--zero1 --grad-compress`` builds BOTH, exactly like the Trainer),
    and the program-shaping extras (``--health on`` in-graph stats,
    ``--pp-schedule``, ``--sp-flash``). Raises for programs the abstract
    builder cannot reproduce (sp+zero1 composition, scan-fused
    ``--steps-per-call``) — refusing beats mis-attributing."""
    step, state, mesh, key, cfg = _run_meta_program(meta, devices)
    return _compile_anatomy(
        step, state, mesh, cache_key=key,
        strategy=run_strategy_label(meta), model_name=cfg.model,
        per_shard_batch=cfg.per_shard_batch, image_size=32,
        compute_dtype=cfg.compute_dtype,
    )


def compiled_for_run_meta(meta: dict, devices):
    """The cached COMPILED executable of a recorded run's rebuilt
    program — what the memory truth loop's plan side reads buffer sizes
    and the memory analysis from (``memtrack/postmortem.py``). Shares
    :func:`anatomy_for_run_meta`'s cache key, so plan-after-anatomy (or
    vice versa) compiles once."""
    step, state, mesh, key, cfg = _run_meta_program(meta, devices)
    batch = abstract_batch(mesh, cfg.per_shard_batch, 32)
    return cached_compile(
        key, lambda: step.trace(state, batch).lower().compile())


# -- run-dir metadata + measured-phase join -------------------------------

def read_run_meta(run_dir: str) -> dict:
    """The run-metadata header the JSONL telemetry sink writes as its
    first line. Raises with a pointed message for pre-header (anonymous)
    runs — refusing beats mis-labelling."""
    from tpu_ddp.telemetry.events import RUN_META_SCHEMA_VERSION
    from tpu_ddp.telemetry.summarize import find_trace_files

    files = find_trace_files(run_dir)
    # the header is the sink's FIRST line by contract: read just it, not
    # the whole (per-step-growing) trace
    with open(files[0]) as f:
        first = f.readline()
    try:
        rec = json.loads(first) if first.strip() else {}
    except json.JSONDecodeError:
        rec = {}
    if rec.get("type") == "header":
        meta = rec.get("run_meta")
        if meta:
            version = meta.get("run_meta_schema_version", 0)
            if version > RUN_META_SCHEMA_VERSION:
                raise ValueError(
                    f"{files[0]}: run_meta_schema_version {version} is "
                    "newer than this tool understands "
                    f"({RUN_META_SCHEMA_VERSION})"
                )
            return meta
    raise ValueError(
        f"{files[0]}: no run-metadata header (run predates the metadata "
        "header, or the trace is hand-rolled) — re-run with telemetry on, "
        "or use static mode (--strategy/--model) instead"
    )


def measured_phases(run_dir: str) -> Dict[str, dict]:
    """Aggregate the run's span records into per-phase totals and a
    per-STEP compiled_step median (scan-fused spans carry a ``steps``
    attr: one span covers K fused steps)."""
    from tpu_ddp.telemetry.registry import Histogram
    from tpu_ddp.telemetry.summarize import find_trace_files, read_records

    records = read_records(find_trace_files(run_dir))
    phases: Dict[str, Histogram] = {}
    per_step = Histogram()
    for rec in records:
        if rec.get("type") != "span":
            continue
        name, dur = rec.get("name"), rec.get("dur_s")
        if not isinstance(name, str) or not isinstance(dur, (int, float)):
            continue
        phases.setdefault(name, Histogram()).record(dur)
        if name == "compiled_step":
            steps = (rec.get("attrs") or {}).get("steps", 1)
            per_step.record(dur / max(int(steps), 1))
    out = {
        name: {"count": h.count, "total_s": h.sum,
               "p50_s": h.percentile(50)}
        for name, h in phases.items()
    }
    if per_step.count:
        out["compiled_step"]["per_step_p50_s"] = per_step.percentile(50)
    return out


def data_wait_share(run_dir: str, phases: Dict[str, dict]
                    ) -> Optional[float]:
    """The input pipeline's share of the run's time, as the live monitor
    counts it (``monitor/aggregate.py``): where the step stamper wrote
    ``device_step`` spans, only the part of a ``data_wait`` that none of
    them covers, over the wall time they reach across; else a share of
    the loop's own phases."""
    from tpu_ddp.monitor.aggregate import DEVICE_PHASE, LOOP_PHASES
    from tpu_ddp.telemetry.stamper import uncovered_share
    from tpu_ddp.telemetry.summarize import find_trace_files, read_records

    if DEVICE_PHASE in phases:
        # one host's clock: ts_s of two hosts share no origin
        spans = {"data_wait": [], DEVICE_PHASE: []}
        for rec in read_records(find_trace_files(run_dir)[:1]):
            if rec.get("type") == "span" and rec.get("name") in spans \
                    and isinstance(rec.get("ts_s"), (int, float)) \
                    and isinstance(rec.get("dur_s"), (int, float)):
                spans[rec["name"]].append(
                    (rec["ts_s"], rec["ts_s"] + rec["dur_s"]))
        return uncovered_share(spans["data_wait"],
                               sorted(spans[DEVICE_PHASE]))
    loop = [phases.get(p, {}).get("total_s", 0.0) for p in LOOP_PHASES]
    return loop[0] / sum(loop) if sum(loop) else None


def join_measurements(anatomy: StepAnatomy, rl: RooflineReport,
                      run_dir: str, *, chip: Optional[str] = None) -> dict:
    """Static-vs-measured join: what fraction of the roofline the run
    achieved, MFU, and where host time went."""
    from tpu_ddp.analysis.roofline import chip_spec

    phases = measured_phases(run_dir)
    step = phases.get("compiled_step", {})
    step_s = step.get("per_step_p50_s") or step.get("p50_s")
    joined: Dict[str, Any] = {"phases": phases, "step_p50_s": step_s}
    if step_s:
        if rl.predicted_step_s:
            joined["roofline_fraction"] = rl.predicted_step_s / step_s
        spec = chip_spec(chip or anatomy.device_kind)
        if anatomy.flops and spec and spec.peak_bf16_flops:
            joined["mfu"] = anatomy.flops / step_s / spec.peak_bf16_flops
            joined["mfu_vs"] = spec.key
        if rl.ici_s is not None:
            joined["comm_share_of_step"] = min(rl.ici_s / step_s, 1.0)
    share = data_wait_share(run_dir, phases)
    if share is not None:
        joined["data_wait_share"] = share
    # measured exposed-comm attribution (`tpu-ddp comms exposure`,
    # docs/comms.md): the comm share that actually stayed exposed, to
    # set against the modeled comm_share_of_step above
    from tpu_ddp.comms.exposure import read_exposure

    exp = read_exposure(run_dir)
    if exp is not None:
        joined["measured_comm_share"] = exp.get("measured_comm_share")
        joined["exposed_comm_s"] = exp.get("exposed_comm_s")
    return joined


# -- rendering ------------------------------------------------------------

def _human_bytes(n: Optional[float]) -> str:
    if n is None:
        return "n/a"
    from tpu_ddp.telemetry.summarize import _human_bytes as fmt

    return fmt(n)


def _human_time(s: Optional[float]) -> str:
    if s is None:
        return "n/a"
    if s >= 1:
        return f"{s:.2f} s"
    if s >= 1e-3:
        return f"{s * 1e3:.2f} ms"
    return f"{s * 1e6:.1f} us"


def render_report(anatomy: StepAnatomy, rl: RooflineReport,
                  fingerprint: Optional[dict] = None,
                  joined: Optional[dict] = None) -> str:
    mesh = ",".join(f"{a}={s}" for a, s in anatomy.mesh.items() if s != 1)
    lines = [
        f"step anatomy: strategy={anatomy.strategy} model={anatomy.model} "
        f"mesh={mesh or 'n/a'} device={anatomy.device_kind}",
        f"  flops/step/device     = "
        + (f"{anatomy.flops:.3e}" if anatomy.flops else "n/a"),
        f"  hbm bytes accessed    = {_human_bytes(anatomy.bytes_accessed)}",
        f"  argument/output/temp  = {_human_bytes(anatomy.argument_bytes)}"
        f" / {_human_bytes(anatomy.output_bytes)}"
        f" / {_human_bytes(anatomy.temp_bytes)}",
        f"  est peak (args+temp)  = {_human_bytes(anatomy.peak_bytes)}",
        f"  fusions               = {anatomy.fusion_count}",
        "",
    ]
    if anatomy.collectives:
        header = (f"  {'kind':<20} {'dtype':<6} {'axis':<9} {'count':>5} "
                  f"{'payload':>10} {'wire/step':>10}")
        lines += ["collective inventory (per device per step):",
                  header, "  " + "-" * (len(header) - 2)]
        for c in anatomy.collectives:
            lines.append(
                f"  {c.kind:<20} {c.dtype:<6} {c.axis:<9} {c.count:>5} "
                f"{_human_bytes(c.payload_bytes):>10} "
                f"{_human_bytes(c.wire_bytes):>10}"
            )
    else:
        lines.append("collective inventory: none (single-device program)")
    lines.append("")
    fr = rl.fractions()
    lines.append(
        f"roofline ({rl.chip or 'no chip spec'}, {rl.overlap}):"
    )
    for term, label in (("compute", "compute (MXU)"),
                        ("hbm", "hbm"), ("ici", "ici")):
        val = getattr(rl, f"{term}_s")
        mark = "  <- bound" if rl.bound == term else ""
        frac = f"  ({fr[term]:.0%})" if term in fr else ""
        lines.append(f"  {label:<14} = {_human_time(val):>10}{frac}{mark}")
    lines.append(
        f"  predicted step time = {_human_time(rl.predicted_step_s)} "
        f"(bound: {rl.bound})"
    )
    for note in rl.notes:
        lines.append(f"  note: {note}")
    from tpu_ddp.ops import kernel_hints

    hints = kernel_hints(anatomy.strategy)
    if hints:
        lines.append("")
        lines.append("kernel candidates (fused Pallas tier, opt-in via "
                     "--kernels; docs/kernels.md):")
        for h in hints:
            avail = ("available" if h["available"]
                     else "NOT available here (switch fails closed)")
            lines.append(f"  {h['kernel']:<16} {avail} "
                         f"[backend: {h['backend'] or 'none'}]")
            lines.append(f"      fuses: {h['hint']}")
    if fingerprint is not None and fingerprint.get("ok") is not None:
        lines.append("")
        if fingerprint["ok"]:
            lines.append(
                f"fingerprint: OK ({fingerprint['strategy']}: expected "
                "collective set present, no forbidden kinds)"
            )
        else:
            problems = []
            if fingerprint["missing"]:
                problems.append("missing " + ", ".join(fingerprint["missing"]))
            if fingerprint["unexpected"]:
                problems.append(
                    "unexpected " + ", ".join(fingerprint["unexpected"]))
            lines.append(
                f"fingerprint: FAIL ({fingerprint['strategy']}: "
                + "; ".join(problems) + ")"
            )
    if joined is not None:
        lines.append("")
        lines.append("measured (telemetry join):")
        step_s = joined.get("step_p50_s")
        lines.append(f"  compiled step p50     = {_human_time(step_s)}")
        if "roofline_fraction" in joined:
            lines.append(
                f"  roofline achieved     = "
                f"{joined['roofline_fraction']:.0%} of predicted"
            )
        if "mfu" in joined:
            lines.append(
                f"  mfu                   = {joined['mfu']:.1%} "
                f"(vs {joined['mfu_vs']} bf16 peak)"
            )
        if "comm_share_of_step" in joined:
            lines.append(
                f"  comm share of step    = "
                f"{joined['comm_share_of_step']:.1%} (MODELED: roofline "
                "ici / measured step)"
            )
        if joined.get("measured_comm_share") is not None:
            lines.append(
                f"  exposed comm share    = "
                f"{joined['measured_comm_share']:.1%} (MEASURED: "
                f"{_human_time(joined.get('exposed_comm_s'))} vs the "
                "comm-stripped twin, tpu-ddp comms exposure)"
            )
        if "data_wait_share" in joined:
            lines.append(
                f"  data-wait share       = {joined['data_wait_share']:.1%}"
                " of the step loop (input pipeline / stragglers)"
            )
    return "\n".join(lines)


# -- CLI ------------------------------------------------------------------

def _analyze_run_dir(args) -> int:
    import jax

    meta = read_run_meta(args.path)
    strategy = run_strategy_label(meta)
    if args.strategy and args.strategy != strategy:
        print(
            f"tpu-ddp analyze: refusing: run {args.path} recorded "
            f"strategy {strategy!r}, but --strategy {args.strategy!r} "
            "was requested", flush=True,
        )
        return 2
    mesh_shape = meta.get("mesh") or {}
    n_needed = 1
    for s in mesh_shape.values():
        n_needed *= s
    local = jax.devices()
    if n_needed > len(local):
        print(
            f"tpu-ddp analyze: refusing: run used {n_needed} devices "
            f"({mesh_shape}), local backend has {len(local)} — rerun "
            "under XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n_needed}", flush=True,
        )
        return 2
    anatomy = anatomy_for_run_meta(meta, local[:n_needed])
    rl = roofline(anatomy, args.chip, overlap=args.overlap)
    fp = check_fingerprint(anatomy)
    joined = join_measurements(anatomy, rl, args.path, chip=args.chip)
    _emit(args, anatomy, rl, fp, joined, run_meta=meta)
    return 0 if (fp.get("ok") is not False) else 1


def _provenance_for(anatomy, run_meta=None) -> dict:
    """The artifact provenance header (git commit/dirty + config
    digest): the run's deterministic ``run_id`` when analyzing a run
    dir, else a digest of what was compiled — so re-analyses of the
    same program land in the same perf-registry series across
    commits."""
    import jax

    from tpu_ddp.telemetry.provenance import artifact_provenance

    return artifact_provenance(
        run_id=(run_meta or {}).get("run_id"),
        descriptor={"artifact": "analyze", "strategy": anatomy.strategy,
                    "model": anatomy.model, "mesh": anatomy.mesh},
        device_kind=anatomy.device_kind,
        jax_version=jax.__version__,
        strategy=anatomy.strategy,
        mesh=anatomy.mesh,
    )


def _emit(args, anatomy, rl, fp, joined=None, run_meta=None) -> None:
    if getattr(args, "json", None):
        from tpu_ddp.ops import kernel_hints

        payload = {
            "anatomy": anatomy.to_json(),
            "roofline": rl.to_json(),
            "fingerprint": fp,
            "kernel_candidates": kernel_hints(anatomy.strategy),
            "provenance": _provenance_for(anatomy, run_meta),
        }
        if run_meta is not None:
            payload["run_meta"] = run_meta
        if joined is not None:
            payload["measured"] = joined
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"tpu-ddp analyze: wrote {args.json}", flush=True)
    print(render_report(anatomy, rl, fp, joined), flush=True)


def _analyze_static(args) -> int:
    strategies = (list(STRATEGIES) if args.strategy == "all"
                  else [args.strategy or "dp"])
    rc = 0
    programs: Dict[str, dict] = {}
    for i, strategy in enumerate(strategies):
        if i:
            print("\n" + "=" * 72 + "\n", flush=True)
        anatomy = anatomy_for_strategy(
            strategy,
            model_name=args.model,
            per_shard_batch=args.batch_size,
            compute_dtype=args.compute_dtype,
            grad_accum_steps=args.grad_accum_steps,
            remat=args.remat,
        )
        rl = roofline(anatomy, args.chip, overlap=args.overlap)
        fp = check_fingerprint(anatomy)
        if len(strategies) == 1:
            _emit(args, anatomy, rl, fp)
        else:
            # multi-strategy: collect into ONE "programs" artifact (the
            # aot_v5e.json shape bench compare diffs per program) —
            # emitting per strategy would overwrite args.json 9 times
            # and leave only the last strategy as a baseline
            programs[strategy] = {**anatomy.to_json(),
                                  "roofline": rl.to_json(),
                                  "fingerprint": fp}
            print(render_report(anatomy, rl, fp), flush=True)
        if fp.get("ok") is False:
            rc = 1
    if programs and getattr(args, "json", None):
        import jax

        from tpu_ddp.telemetry.provenance import artifact_provenance

        with open(args.json, "w") as f:
            json.dump({
                "programs": programs,
                "provenance": artifact_provenance(
                    descriptor={"artifact": "analyze-all",
                                "strategies": sorted(programs),
                                "model": args.model,
                                "compute_dtype": args.compute_dtype},
                    jax_version=jax.__version__,
                ),
            }, f, indent=1)
        print(f"tpu-ddp analyze: wrote {args.json} "
              f"({len(programs)} programs)", flush=True)
    return rc


def main(argv: Optional[Sequence[str]] = None) -> int:
    """``tpu-ddp analyze [run_dir] [--strategy ...] ...``"""
    import argparse

    ap = argparse.ArgumentParser(
        prog="tpu-ddp analyze",
        description="static step-time anatomy (XLA cost model + roofline "
                    "+ collective inventory), optionally joined against a "
                    "run dir's measured telemetry",
    )
    ap.add_argument("path", nargs="?", default=None,
                    help="run dir holding trace-p*.jsonl (telemetry join "
                         "mode); omit for static mode")
    ap.add_argument("--strategy", default=None,
                    help=f"one of {', '.join(STRATEGIES)}, or 'all' "
                         "(static mode); in run-dir mode a mismatch with "
                         "the recorded strategy is refused")
    ap.add_argument("--model", default=None,
                    help="zoo model name (default: tiny per-family model)")
    ap.add_argument("--batch-size", type=int, default=8,
                    help="per-shard batch (static mode)")
    ap.add_argument("--compute-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--grad-accum-steps", type=int, default=1)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--chip", default=None,
                    help="chip spec to attribute against (v2..v6e); "
                         "default: the compiling backend's device kind — "
                         "pass this on CPU hosts to classify the bound")
    ap.add_argument("--overlap", default="overlapped",
                    choices=["overlapped", "serial"])
    ap.add_argument("--json", default=None,
                    help="also write the anatomy+roofline(+measured) JSON "
                         "here (bench-compare-able)")
    args = ap.parse_args(list(argv) if argv is not None else None)

    try:
        if args.path:
            return _analyze_run_dir(args)
        return _analyze_static(args)
    except (FileNotFoundError, ValueError) as e:
        print(f"tpu-ddp analyze: {e}", flush=True)
        return 2
