"""Trainer: the orchestration loop.

Covers ``train_loop``/``main`` (``/root/reference/main.py:26-65``) and the
single-device baseline (``main_no_ddp.py:36-59``) with ONE code path: the
single-device mode is just a 1-device mesh — no separate script, no DDP
wrapper to add or remove.

Reference cadence preserved: epochs 1..epochs (``range(1, 100)`` = 99,
``main.py:30``), mean-loss log + checkpoint at epoch 1 and every
``log_every`` epochs (``main.py:43-45``), total wall-clock print
(``main.py:47-49``). Extended (SURVEY.md gaps): test-set eval, per-step
timing, images/sec/chip, JSONL metrics, resume.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import time
from typing import Optional

import jax
import numpy as np

from tpu_ddp.data.loader import ShardedBatchLoader
from tpu_ddp.metrics import MetricLogger, Throughput
from tpu_ddp.parallel.mesh import (
    DATA_AXIS,
    MeshSpec,
    batch_sharding,
    create_mesh,
    replicated_sharding,
)
from tpu_ddp.parallel.runtime import enable_compile_cache
from tpu_ddp.train.optim import make_optimizer
from tpu_ddp.train.state import create_train_state
from tpu_ddp.train.steps import make_eval_step, make_train_step

log = logging.getLogger(__name__)


@dataclasses.dataclass
class TrainConfig:
    """Union of the reference's hardcoded constants and the vestigial
    script's argparse surface (SURVEY.md §5.6), as one dataclass."""

    data_dir: str = "data/CIFAR-10"      # main.py:19
    download: bool = False                # fetch + md5-verify the canonical
                                          # tarball when absent (main.py:53)
    dataset: str = "cifar10"              # cifar10 | cifar100
    synthetic_data: bool = False          # no torchvision download path
    synthetic_size: int = 2048
    synthetic_task: str = "easy"          # easy (color blobs, saturates at
                                          # 1.0) | hard (shifted zero-mean
                                          # textures + label noise: bounded
                                          # ceiling, recipe quality visible)
    synthetic_label_noise: float = 0.1    # hard task: train-label flip rate
    epochs: int = 99                      # range(1,100), main.py:30
    per_shard_batch: int = 32             # per-process bs, main.py:61
    lr: float = 1e-2                      # main.py:27
    optimizer: str = "sgd"                # sgd | adamw (ViT family) | lamb
                                          # (large-global-batch)
    momentum: float = 0.0                 # reference SGD has none
    weight_decay: float = 0.0
    schedule: Optional[str] = None        # "cosine" | None
    warmup_steps: int = 0
    grad_clip_norm: float = 0.0           # 0 = off (global-norm clip)
    ema_decay: float = 0.0                # >0: shadow EMA of params in
                                          # opt_state; eval/predict use the
                                          # averaged weights
    n_devices: Optional[int] = None       # None = all; 1 = main_no_ddp mode
    parallelism: Optional[str] = None     # dp|fsdp|tp|pp|sp|ep; None = infer
                                          # from mesh (default dp)
    zero1: bool = False                   # ZeRO-1 weight-update sharding
                                          # (dp/sp): reduce-scatter grads,
                                          # update only the local 1/N shard
                                          # of params + optimizer state
                                          # (state lives scattered — ~1/N
                                          # the optimizer HBM), all-gather
                                          # params back. Same math as the
                                          # replicated update
                                          # (parallel/zero.py)
    zero3: bool = False                   # ZeRO-3 parameter streaming
                                          # (dp): params live PERMANENTLY
                                          # scattered in the same flat
                                          # update space (1/N param + 1/N
                                          # optimizer HBM per chip); the
                                          # forward re-assembles them
                                          # block by block over a double-
                                          # buffered all-gather prefetch
                                          # schedule and the backward
                                          # reduce-scatters grads straight
                                          # into shard space — no full-
                                          # param re-gather
                                          # (parallel/zero.py::
                                          # Zero3Partition)
    grad_compress: str = "none"           # none | bf16 | int8: quantize the
                                          # DP-family gradient sync's WIRE
                                          # payloads (block-scaled int8 ~4x
                                          # fewer bytes, bf16 2x) — ring
                                          # collectives with f32 on-device
                                          # accumulation
                                          # (parallel/compression.py)
    grad_compress_block: int = 256        # elements per int8 scale block
    grad_compress_error_feedback: bool = False  # carry each device's
                                          # quantization error and add it
                                          # back next step (residual rides
                                          # TrainState.grad_residual,
                                          # per-device like zero1's opt
                                          # shards; checkpointed)
    kernels: bool = False                 # route the DP-family update
                                          # tail (fused clip+moments+
                                          # param+EMA pass) and the int8
                                          # ring's quantize/dequantize
                                          # through the Pallas kernel
                                          # tier (ops/, docs/kernels.md).
                                          # Bit-identical math by
                                          # contract; fails closed to the
                                          # XLA path per kernel on
                                          # backends without Pallas
                                          # support (lint KRN001 names
                                          # the fallback)
    mesh: Optional[dict] = None           # axis sizes, e.g. {"data": 2,
                                          # "model": 4}; None = strategy default
    n_microbatches: int = 4               # pipeline microbatches (pp only)
    pp_schedule: str = "gpipe"            # "gpipe" | "1f1b" (pp only)
    aux_weight: float = 0.01              # MoE load-balance loss weight
    seed: int = 0
    shuffle: bool = True
    reshuffle_each_epoch: bool = True     # False = faithful missing-set_epoch
    augment: bool = False                 # on-device random crop+flip
                                          # (reference has none; SURVEY §7.3)
    mixup_alpha: float = 0.0              # >0: on-device mixup (Beta(a,a)
                                          # image/loss blending; recipe knob)
    sync_bn: bool = False
    sp_flash: bool = False               # SP: flash-kernel ring blocks
    compute_dtype: str = "float32"        # float32 | bfloat16 (MXU 2x)
    steps_per_call: int = 1               # >1: fuse K optimizer steps into
                                          # one dispatch (lax.scan) — hides
                                          # host overhead on small models
    grad_accum_steps: int = 1             # >1: split each step's shard rows
                                          # into K sequential microbatches
                                          # (one optimizer step, ~1/K the
                                          # activation memory) — big-batch
                                          # knob the reference lacks
    prefetch_depth: int = 2               # >0: assemble batches ahead on the
                                          # native host prefetcher (C++ ring
                                          # buffer; 0 disables)
    prefetch_batches: int = 0             # >0: run the STAGED loader
                                          # pipeline (index/gather/augment/
                                          # collate/shard, per-stage spans
                                          # + data-health attribution) on a
                                          # background thread into a
                                          # bounded queue of N batches —
                                          # the datapath observatory's
                                          # prefetcher (docs/data.md).
                                          # Bit-identical batches to the
                                          # synchronous path; takes
                                          # precedence over prefetch_depth
    data_digests: bool = True             # record the per-step batch-
                                          # content digest into the
                                          # data-p<i>.i<k>.jsonl sink for
                                          # `tpu-ddp data audit` (active
                                          # exactly when telemetry_dir is
                                          # set; docs/data.md)
    remat: bool = False                   # jax.checkpoint the forward:
                                          # trade FLOPs for HBM on big models
    model: str = "netresdeep"
    model_overrides: Optional[dict] = None  # keyword arguments of the
                                          # registry factory beyond the
                                          # classifier's: one chip's share
                                          # of a decoder (laguna_xs2:
                                          # num_layers, experts_held,
                                          # expert_offset, vocab_rows;
                                          # models/decoder.py). No width
    n_chans1: int = 32                    # NetResDeep width (the reference's
                                          # ctor arg, model/resnet.py:5)
    n_blocks: int = 10                    # NetResDeep depth (same ctor)
    tied_blocks: bool = True              # the reference's weight-tying quirk
    attention: str = "full"               # full | flash (Pallas kernel,
                                          # ViT-family models; fwd AND bwd
                                          # run in-kernel)
    num_classes: int = 10
    log_every_epochs: int = 10            # main.py:43
    log_every_steps: Optional[int] = None  # in-epoch progress lines (the
                                          # reference's per-100-iter print,
                                          # ppe_main_ddp.py:151-152). Each
                                          # line fetches that step's loss —
                                          # an occasional host sync, by
                                          # explicit user choice
    eval_each_epoch: bool = False
    checkpoint_dir: Optional[str] = None
    checkpoint_every_epochs: int = 10     # save on log epochs, main.py:45
    checkpoint_steps: int = 0             # >0: ALSO checkpoint every N
                                          # global steps (mid-epoch) — the
                                          # cadence knob the goodput
                                          # ledger's Young–Daly advisor
                                          # recommends a value for
                                          # (docs/goodput.md); epoch-
                                          # boundary saves still happen
    keep_best: bool = False               # also retain the best-test-acc
                                          # checkpoint under
                                          # <checkpoint-dir>/best
    resume: bool = False
    jsonl_path: Optional[str] = None
    tensorboard_dir: Optional[str] = None  # TB scalar events (SURVEY §5.5)
    profile_dir: Optional[str] = None     # emit an XLA/TPU trace (Tensor-
                                          # Board/Perfetto) for ONE steady-
                                          # state epoch (SURVEY.md §5.1)
    profile_steps: Optional[str] = None   # "A:B": arm an anomaly-profiler
                                          # capture window over global steps
                                          # (A, B] — host stack sampling +
                                          # device trace + measured phases
                                          # bundled under
                                          # <telemetry_dir>/profiles/
                                          # (docs/profiling.md). Windows can
                                          # also be armed live (POST
                                          # /profile on --monitor-port) or
                                          # by the capture_profile alert
                                          # action; requires telemetry_dir
    profile_window_steps: int = 8         # default window length (steps)
                                          # for live-triggered captures
    profile_host_hz: float = 97.0         # host stack sampler rate inside
                                          # a capture window
    monitor_allow_remote_trigger: bool = False  # lift the loopback-only
                                          # restriction on POST /profile
                                          # (the endpoint is UNauthenti-
                                          # cated — see docs/monitoring.md
                                          # before opening this up)
    telemetry_dir: Optional[str] = None   # run dir for the structured
                                          # telemetry sinks (per-host JSONL
                                          # + Chrome trace + heartbeats);
                                          # None = telemetry disabled.
                                          # The loop is the untraced
                                          # run's: no fence a step; a
                                          # step's completion is stamped
                                          # off the main thread as the
                                          # span device_step
    telemetry_sinks: str = "jsonl,chrome,summary"  # comma-separated subset
    mem_sample_steps: int = 1             # >0: per-step live memory
                                          # sampler stride — device
                                          # memory_stats (live-array
                                          # accounting on CPU) into
                                          # memory/* gauges + the
                                          # incarnation-stamped
                                          # mem-p<i>.jsonl sink, read
                                          # back by `tpu-ddp mem`
                                          # (docs/memory.md); 0 disables.
                                          # Active exactly when
                                          # telemetry_dir is set
    telemetry_snapshot_steps: int = 50    # >0: flush a counters snapshot
                                          # into the JSONL sink every N
                                          # steps — a killed/preempted run
                                          # leaves a usable tail for the
                                          # fleet aggregator and `trace
                                          # summarize` (0 disables; the
                                          # epoch-boundary + final
                                          # snapshots always happen)
    monitor_port: int = 0                 # >0: per-host HTTP monitor
                                          # endpoint on this port
                                          # (/metrics OpenMetrics,
                                          # /snapshot.json, /healthz);
                                          # -1 = ephemeral port (written
                                          # to exporter-p<i>.json in the
                                          # telemetry dir); 0 = disabled
                                          # (docs/monitoring.md)
    monitor_bind: str = "0.0.0.0"         # exporter bind address; the
                                          # endpoint is UNauthenticated
                                          # (/snapshot.json serves the
                                          # config) — bind 127.0.0.1 on
                                          # untrusted networks
    watchdog_deadline_seconds: float = 0.0  # >0: hang watchdog — stack
                                          # dump + heartbeat staleness when
                                          # no step completes in time
    watchdog_abort: bool = False          # escalate after the dump: exit
                                          # with the `hang` class
                                          # (HANG_EXIT_CODE) so a wedged
                                          # runtime becomes supervisor-
                                          # restartable instead of an
                                          # eternal stall
                                          # (docs/resilience.md)
    chaos_spec: Optional[str] = None      # fault-injection spec JSON
                                          # (chaos/inject.py): step-
                                          # triggered kill/hang/corrupt/
                                          # io-flake/stall faults, seeded
                                          # and fire-once per logical run
                                          # — the elastic runtime's CI
                                          # harness (docs/resilience.md)
    comms_monitor: bool = False           # instrument the quantized ring
                                          # collectives with a per-hop
                                          # host callback: live per-axis
                                          # bandwidth in comms-health-
                                          # p<i>.json + the stuck-
                                          # collective suspect for hang
                                          # forensics (docs/comms.md).
                                          # Changes the traced program
                                          # (adds host transfers), so it
                                          # refuses --lint-on-start
    health: str = "off"                   # "on": numerics flight recorder —
                                          # in-graph grad/param/update norms
                                          # + NaN/Inf sentinels every step
                                          # (docs/health.md). Adds one
                                          # scalar fetch per step on host
    health_policy: str = "warn"           # on anomaly: warn | skip_step
                                          # (in-graph guard discards the
                                          # poisoned update, optimizer
                                          # state stays in sync) | halt
                                          # (drain + final checkpoint)
    health_per_layer_stride: int = 0      # >0: per-layer grad/param norm
                                          # breakdown compiled into the
                                          # step, recorded every N steps
                                          # (and always in anomaly dumps)
    health_dir: Optional[str] = None      # health JSONL + anomalies/ run
                                          # dir; defaults to telemetry_dir
    health_window: int = 128              # spike detector rolling window
    health_spike_threshold: float = 10.0  # spike at median + K * MAD
    lint_on_start: bool = False           # preflight: run the static
                                          # graph lint (docs/lint.md —
                                          # donation / dtype / sharding /
                                          # collective-order / host-
                                          # transfer rules) over the REAL
                                          # jitted step and refuse to
                                          # launch a violating program

    def validate(self) -> "TrainConfig":
        """Fail fast on knob values that would otherwise only explode
        mid-run (the sinks are parsed at Trainer construction, the health
        policy on the first anomaly — both too late). Returns self so
        call sites can chain."""
        from tpu_ddp.telemetry import DEFAULT_SINKS

        valid_sinks = tuple(DEFAULT_SINKS.split(","))
        for name in (self.telemetry_sinks or "").split(","):
            name = name.strip()
            if name and name not in valid_sinks:
                raise ValueError(
                    f"unknown telemetry sink {name!r}; valid sinks: "
                    f"{', '.join(valid_sinks)}"
                )
        if self.health not in ("off", "on"):
            raise ValueError(
                f"unknown health mode {self.health!r}; valid modes: "
                "off, on"
            )
        from tpu_ddp.health import POLICIES

        if self.health_policy not in POLICIES:
            raise ValueError(
                f"unknown health policy {self.health_policy!r}; valid "
                f"policies: {', '.join(POLICIES)}"
            )
        if self.health_per_layer_stride < 0:
            raise ValueError(
                "health_per_layer_stride must be >= 0, got "
                f"{self.health_per_layer_stride}"
            )
        if self.telemetry_snapshot_steps < 0:
            raise ValueError(
                "telemetry_snapshot_steps must be >= 0, got "
                f"{self.telemetry_snapshot_steps}"
            )
        if self.mem_sample_steps < 0:
            raise ValueError(
                f"mem_sample_steps must be >= 0 (0 disables the memory "
                f"sampler), got {self.mem_sample_steps}"
            )
        if self.checkpoint_steps < 0:
            raise ValueError(
                f"checkpoint_steps must be >= 0, got {self.checkpoint_steps}"
            )
        if self.checkpoint_steps and not self.checkpoint_dir:
            raise ValueError(
                "--checkpoint-steps needs --checkpoint-dir: there is "
                "nowhere to save the step-cadence checkpoints"
            )
        if self.monitor_port < -1 or self.monitor_port > 65535:
            raise ValueError(
                f"monitor_port must be -1 (ephemeral), 0 (disabled), or "
                f"a TCP port, got {self.monitor_port}"
            )
        from tpu_ddp.profiler.capture import parse_profile_steps

        # raises on a malformed window spec — at parse time, not step A
        parse_profile_steps(self.profile_steps)
        if self.profile_steps and not self.telemetry_dir:
            raise ValueError(
                "--profile-steps needs --telemetry-dir: the capture "
                "bundle is written under <telemetry_dir>/profiles/"
            )
        if self.profile_window_steps < 1:
            raise ValueError(
                "profile_window_steps must be >= 1, got "
                f"{self.profile_window_steps}"
            )
        if self.profile_host_hz <= 0:
            raise ValueError(
                f"profile_host_hz must be > 0, got {self.profile_host_hz}"
            )
        if self.health_window < 4:
            raise ValueError(
                f"health_window must be >= 4, got {self.health_window}"
            )
        if self.watchdog_abort and self.watchdog_deadline_seconds <= 0:
            raise ValueError(
                "--watchdog-abort needs --watchdog-deadline > 0: there "
                "is no hang detector to escalate from"
            )
        if self.comms_monitor:
            if not self.telemetry_dir:
                raise ValueError(
                    "--comms-monitor needs --telemetry-dir: the per-axis "
                    "health records and the hang-forensics suspect live "
                    "in the run dir"
                )
            if self.lint_on_start:
                raise ValueError(
                    "--comms-monitor does not compose with "
                    "--lint-on-start: the per-hop host callback is a "
                    "deliberate host transfer inside the step, which "
                    "the lint's host-transfer rule would (correctly) "
                    "refuse"
                )
        if self.chaos_spec:
            if not self.telemetry_dir:
                raise ValueError(
                    "--chaos needs --telemetry-dir: the fire-once fault "
                    "state lives in the run dir (and an unobserved "
                    "chaos run proves nothing)"
                )
            from tpu_ddp.chaos.inject import load_spec

            # parse + validate NOW: a typo'd fault spec must refuse the
            # launch, not detonate at its trigger step
            spec = load_spec(self.chaos_spec)
            if any(f.get("kind") == "comm_stall" for f in spec["faults"]) \
                    and not self.comms_monitor:
                raise ValueError(
                    "chaos spec contains a comm_stall fault but "
                    "--comms-monitor is off: the stall fires from the "
                    "per-hop callback seam, so without the monitor the "
                    "fault can never trigger"
                )
            if any(f.get("kind") == "data_stall" and f.get("stage")
                   for f in spec["faults"]) \
                    and self.prefetch_depth > 0 \
                    and self.prefetch_batches <= 0:
                raise ValueError(
                    "chaos spec contains a stage-targeted data_stall "
                    "fault but the staged loader pipeline is off: the "
                    "stall fires from the per-stage observer seam, which "
                    "runs only with --prefetch-batches N or "
                    "--prefetch-depth 0"
                )
        if self.prefetch_batches < 0:
            raise ValueError(
                f"prefetch_batches must be >= 0 (0 disables the staged "
                f"background prefetcher), got {self.prefetch_batches}"
            )
        if self.zero1 and self.optimizer == "lamb":
            raise ValueError(
                "--zero1 does not compose with --optimizer lamb (the "
                "layer-wise trust ratio needs whole-parameter norms; "
                "the 1/N update shards cannot provide them)"
            )
        if self.zero1 and self.parallelism not in (None, "dp", "sp"):
            raise ValueError(
                f"--zero1 is not supported with --parallelism "
                f"{self.parallelism}: fsdp/fsdp_tp already scatter the "
                "optimizer state (ZeRO-3 subsumes ZeRO-1); tp/pp/ep own "
                "their state layout"
            )
        if self.zero3 and self.zero1:
            raise ValueError(
                "--zero3 subsumes --zero1 (parameters AND optimizer "
                "state live scattered in the same flat update space); "
                "drop --zero1"
            )
        if self.zero3 and self.optimizer == "lamb":
            raise ValueError(
                "--zero3 does not compose with --optimizer lamb (the "
                "layer-wise trust ratio needs whole-parameter norms; "
                "the 1/N update shards cannot provide them)"
            )
        if self.zero3 and self.parallelism not in (None, "dp"):
            raise ValueError(
                f"--zero3 is not supported with --parallelism "
                f"{self.parallelism}: fsdp/fsdp_tp already stream "
                "scattered parameters (GSPMD owns that schedule — use "
                "them directly); tp/pp/ep/sp own their state layout. "
                "Use --zero3 with dp"
            )
        from tpu_ddp.parallel.compression import MODES as compress_modes

        if self.grad_compress not in compress_modes:
            raise ValueError(
                f"unknown grad-compress mode {self.grad_compress!r}; "
                f"valid modes: {', '.join(compress_modes)}"
            )
        if self.grad_compress_block < 1:
            raise ValueError(
                "grad_compress_block must be >= 1, got "
                f"{self.grad_compress_block}"
            )
        if (self.grad_compress != "none"
                and self.parallelism not in (None, "dp", "sp")):
            raise ValueError(
                f"--grad-compress is not supported with --parallelism "
                f"{self.parallelism}: the GSPMD/pipeline families' grad "
                "movement is partitioner-internal, not a pmean this "
                "framework owns. Use --grad-compress with dp or sp"
            )
        if self.grad_compress_error_feedback and self.grad_compress == "none":
            raise ValueError(
                "--grad-compress-error-feedback needs --grad-compress "
                "bf16 or int8 (there is no quantization error to feed "
                "back without compression)"
            )
        return self
    freeze_prefixes: Optional[tuple] = None  # e.g. ("fc",) trains head only
    loss: str = "ce"                      # "ce" | "bce" (multi-label,
                                          # ppe_main_ddp.py:147)
    label_smoothing: float = 0.0          # soft CE targets (recipe knob
                                          # for the 93% north star)
    pretrained_dir: Optional[str] = None  # fine-tune: partial restore +
                                          # head swap (ppe_main_ddp.py:104-111)
    plot_curves: Optional[str] = None     # PNG path (ppe_main_ddp.py:176-181)
    dump_predictions: Optional[str] = None  # JSON path (ppe_main_ddp.py:310-396)


def build_model(config: TrainConfig):
    import jax.numpy as jnp

    from tpu_ddp.models import NetResDeep
    from tpu_ddp.models.zoo import LAZY_MODELS, MODEL_REGISTRY

    bn_axis = DATA_AXIS if config.sync_bn else None
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[config.compute_dtype]
    name = config.model.lower()
    if name == "netresdeep":
        return NetResDeep(
            n_chans1=config.n_chans1,
            n_blocks=config.n_blocks,
            tied=config.tied_blocks,
            num_classes=config.num_classes,
            bn_cross_replica_axis=bn_axis,
            dtype=dtype,
        )
    if name in LAZY_MODELS:  # registers itself when first asked for
        import importlib

        importlib.import_module(LAZY_MODELS[name])
    if name in MODEL_REGISTRY:
        model = MODEL_REGISTRY[name](
            num_classes=config.num_classes, bn_cross_replica_axis=bn_axis,
            dtype=dtype, **(config.model_overrides or {}),
        )
        if config.attention == "flash":
            if not hasattr(model, "attention_impl"):
                raise ValueError(
                    f"--attention flash needs an attention model (ViT "
                    f"family, decoders); {config.model!r} has none"
                )
            from tpu_ddp.ops.flash_attention import flash_attention

            # a model may say which blocks suit its sequences
            # (``flash_blocks``); the kernel's defaults otherwise
            blocks = getattr(model, "flash_blocks", None)
            if blocks is not None:
                import functools

                flash_attention = functools.partial(
                    flash_attention, block_q=blocks[0], block_k=blocks[1])
            model = model.clone(attention_impl=flash_attention)
        return model
    raise ValueError(f"unknown model {config.model!r}")


def load_dataset(c: TrainConfig, task=None, model=None):
    """(train, test) (images, labels) tuples for a config — shared by the
    Trainer and the k-fold CV driver (which re-splits the train set itself,
    the reference's ``cv_mode`` path, ``ppe_main_ddp.py:91-93``). For a
    ``task`` other than image classification, what the task makes for the
    built ``model`` (``train/tasks.py``): synthetic data only, a corpus
    comes as ``train_data``."""
    if task is not None and task.synthetic is not None:
        if not c.synthetic_data:
            raise ValueError(
                f"model {c.model!r} trains on {task.name}: pass "
                "--synthetic-data, or train_data=(inputs, targets)")
        return (task.synthetic(model, c.synthetic_size, c.seed),
                task.synthetic(model, max(c.synthetic_size // 5, 8),
                               c.seed + 1))
    if c.synthetic_data:
        from tpu_ddp.data.cifar10 import (
            synthetic_cifar10,
            synthetic_cifar10_hard,
            synthetic_multilabel,
        )

        test_size = max(c.synthetic_size // 5, 64)
        if c.loss == "bce":
            train = synthetic_multilabel(c.synthetic_size, c.num_classes, c.seed)
            test = synthetic_multilabel(test_size, c.num_classes, c.seed + 1)
        elif c.synthetic_task == "hard":
            # Label noise corrupts TRAIN only; the clean test set makes the
            # recipe-quality gap readable against the noise-free ceiling.
            train = synthetic_cifar10_hard(
                c.synthetic_size, c.num_classes, c.seed,
                label_noise=c.synthetic_label_noise,
            )
            test = synthetic_cifar10_hard(
                test_size, c.num_classes, c.seed + 1, label_noise=0.0
            )
        else:
            train = synthetic_cifar10(c.synthetic_size, c.num_classes, c.seed)
            test = synthetic_cifar10(test_size, c.num_classes, c.seed + 1)
    else:
        from tpu_ddp.data.cifar10 import load_cifar10, load_cifar100
        from tpu_ddp.data.download import ensure_dataset

        # reference parity: datasets.CIFAR10(..., download=True),
        # main.py:53 — no-op unless --download and the data is absent
        ensure_dataset(c.data_dir, c.dataset, download=c.download)
        load = {"cifar10": load_cifar10, "cifar100": load_cifar100}[c.dataset]
        train = load(c.data_dir, train=True)
        test = load(c.data_dir, train=False)
    return train, test


class Trainer:
    def __init__(self, config: TrainConfig, *, train_data=None, test_data=None):
        """train_data/test_data: optional (images, labels) tuples that bypass
        the dataset loader — used by the k-fold driver and tests."""
        self.config = config
        config.validate()
        enable_compile_cache()  # before the first trace
        devices = jax.devices()
        if config.n_devices:
            devices = devices[: config.n_devices]
        from tpu_ddp.train.strategy import (
            default_mesh_sizes,
            infer_parallelism,
        )

        # Parallelism routing (dp is the flagship default): --mesh /
        # --parallelism pick the strategy; the mesh is built here so the
        # data loader can size itself off the data axis.
        self.parallelism = infer_parallelism(config.mesh, config.parallelism)
        sizes = dict(config.mesh or default_mesh_sizes(self.parallelism))
        self.mesh = create_mesh(MeshSpec(**sizes), devices)
        self.world_size = len(devices)
        # Batch rows shard over the DATA axis only — on a 2-D mesh the
        # loader produces data_size shards, not one per device.
        self.data_size = self.mesh.shape[DATA_AXIS]
        self.batch_sharding = batch_sharding(self.mesh)
        # Multi-host: every process runs this same code; loaders yield only
        # the local device block's rows and _put assembles global arrays
        # from per-host shards (SURVEY.md §7.3 multi-host data loading).
        self.process_count = jax.process_count()
        self.process_index = jax.process_index()
        self._multihost = self.process_count > 1
        if self._multihost:
            from tpu_ddp.parallel.mesh import (
                assert_process_contiguous_data_axis,
            )

            assert_process_contiguous_data_axis(self.mesh, self.process_count)

        # Telemetry first: the loaders and checkpointer it is passed to are
        # built below. Disabled (NULL) unless --telemetry-dir is given.
        # The run-metadata header (config snapshot + jax version + device
        # kind + mesh + strategy) lands as the first record of every file
        # sink, so `tpu-ddp analyze`/`trace summarize` can label this run
        # and refuse mismatched ones — run dirs used to be anonymous.
        from tpu_ddp.telemetry import (
            RUN_META_SCHEMA_VERSION,
            build_telemetry,
            config_digest,
            git_provenance,
            next_incarnation,
            quality_digest,
        )

        # run_id: a short stable config digest — deterministic, so every
        # host of a multihost run derives the SAME id without a
        # coordination round, and the monitor exporter's /metrics labels
        # line up across the fleet scrape. The recipe lives in
        # telemetry.provenance so the perf registry's baseline matching
        # shares the identity space.
        config_snapshot = dataclasses.asdict(config)
        run_id = config_digest(config_snapshot)
        # incarnation: which life of this logical run this process is —
        # derived from the trace files already in the run dir, so a
        # --resume after a preemption/SIGKILL gets a fresh monotonic
        # index with zero coordination. Incarnation k > 0 writes
        # trace-p<i>.i<k>.jsonl instead of truncating the dead life's
        # file; the goodput ledger stitches all of them back into one
        # cross-incarnation timeline (docs/goodput.md).
        self.incarnation = next_incarnation(
            config.telemetry_dir, self.process_index)
        self.run_meta = {
            "run_meta_schema_version": RUN_META_SCHEMA_VERSION,
            "run_id": run_id,
            # the seed-invariant sibling of run_id: N seeded runs of one
            # learning recipe share it, so the convergence observatory
            # (docs/curves.md) can build seed-band baselines across runs
            # whose run_ids all differ
            "quality_digest": quality_digest(
                config_snapshot, data_size=self.data_size),
            "incarnation": self.incarnation,
            "config": config_snapshot,
            "jax_version": jax.__version__,
            "device_kind": devices[0].device_kind,
            "strategy": self.parallelism,
            "mesh": dict(zip(self.mesh.axis_names,
                             (int(s) for s in self.mesh.devices.shape))),
            "n_devices": self.world_size,
            "process_count": self.process_count,
            # commit identity at the SOURCE: every downstream artifact
            # (trace header, analyze/goodput/watch JSON, registry
            # entries) inherits it instead of re-deriving; null outside
            # a git checkout or without a git binary
            **git_provenance(),
        }
        self.telemetry = build_telemetry(
            config.telemetry_dir,
            config.telemetry_sinks,
            process_index=self.process_index,
            run_meta=self.run_meta,
            incarnation=self.incarnation,
        )
        # What is told of every step (``on_step(host_step)`` after each
        # dispatch, in this list's order; ``close()`` in its reverse when
        # the run releases its workers). Each watcher below appends itself
        # where it is built, and the order they are built in is the order
        # they hear of a step: watchdog beat (put first by the start of
        # ``run``), then chaos (an injected hang blocks the loop inside
        # its on_step, so the beat before it is the last one — exactly the
        # silhouette of a wedged collective), hop monitor, stage monitor,
        # capture, memory sampler. With telemetry off and nothing
        # configured the list is empty and the loop keeps no host step.
        self._watchers = []
        self._watchdog = None   # HangWatchdog (started in run())
        self._exporter = None   # monitor HTTP endpoint (started in run())
        self._stamper = None    # StepStamper (started in run(), telemetry on)
        # Numerics flight recorder (docs/health.md): the in-graph half is
        # compiled into the step builders below (health=self._health);
        # this monitor is the host half — JSONL record, spike detection,
        # anomaly dumps, policy verdicts.
        self._health_monitor = None
        self._health = None
        self._health_halted = None
        if config.health != "off":
            from tpu_ddp.health import HealthConfig, HealthMonitor

            self._health = HealthConfig(
                per_layer=config.health_per_layer_stride > 0,
                skip_nonfinite=config.health_policy == "skip_step",
            )
            if not (config.health_dir or config.telemetry_dir):
                # legitimate (the in-graph sentinels + policy still run,
                # e.g. skip_step-only protection) but easy to mistake for
                # a recorded run — say so up front
                log.warning(
                    "health=on with neither health_dir nor telemetry_dir:"
                    " detection and the %r policy are active, but no "
                    "health JSONL or anomaly dumps will be written",
                    config.health_policy,
                )
            self._health_monitor = HealthMonitor(
                run_dir=config.health_dir or config.telemetry_dir,
                policy=config.health_policy,
                per_layer_stride=config.health_per_layer_stride,
                telemetry=self.telemetry,
                process_index=self.process_index,
                window=config.health_window,
                spike_threshold=config.health_spike_threshold,
                run_meta=dataclasses.asdict(config),
                incarnation=self.incarnation,
            )
        if config.profile_dir:
            # satellite fix: create the profiler dir up front — a typo'd
            # path fails NOW, not after an epoch of training
            os.makedirs(config.profile_dir, exist_ok=True)
        # Chaos injector (docs/resilience.md): deterministic step-
        # triggered fault injection — exists exactly when --chaos is
        # given; its save_fault_hook threads into the Checkpointer below
        self._chaos = None
        if config.chaos_spec:
            from tpu_ddp.chaos.inject import ChaosInjector

            self._chaos = ChaosInjector(
                config.chaos_spec,
                config.telemetry_dir,
                process_index=self.process_index,
                checkpoint_dir=config.checkpoint_dir,
                telemetry=self.telemetry,
            )
            self._watchers.append(self._chaos)

        # Comms observatory (docs/comms.md): per-hop host callback on the
        # quantized ring collectives -> live per-axis bandwidth + the
        # in-flight collective, the hang forensics' suspect evidence; its
        # on_step stamps the host step onto subsequent hop records so the
        # forensics can say WHEN the ring wedged.
        # Installed BEFORE the strategy builds its jitted step so the
        # hook is baked into the traced ring; the chaos comm_stall fault
        # rides the same seam (fault_hook), which is why the injector
        # must exist first.
        self._comms_monitor = None
        if config.comms_monitor:
            from tpu_ddp.comms.forensics import HopMonitor
            from tpu_ddp.parallel.collectives import set_ring_hop_hook

            self._comms_monitor = HopMonitor(
                config.telemetry_dir,
                process_index=self.process_index,
                n_devices=len(devices),
                fault_hook=(
                    self._chaos.comm_stall_hook
                    if self._chaos is not None else None
                ),
                telemetry=self.telemetry,
            )
            set_ring_hop_hook(self._comms_monitor.on_hop)
            self._watchers.append(self._comms_monitor)

        # Data-path observatory (docs/data.md): the per-stage loader
        # observer keeps data-health-p<i>.json fresh for the fleet
        # aggregator / DAT001 (its in-flight stage marker names the step
        # a stall wedged on) and carries the chaos per-stage stall seam;
        # the digest writer records each step's batch-content digest into
        # the incarnation-stamped data-p<i>.i<k>.jsonl sink for the
        # determinism audit. Both exist exactly when telemetry does, and
        # must be built BEFORE _load_data so the train loader is born
        # with its observer attached.
        self._datapath = None
        self._data_digests = None
        if config.telemetry_dir:
            from tpu_ddp.datapath.stages import StageMonitor

            self._datapath = StageMonitor(
                config.telemetry_dir,
                process_index=self.process_index,
                stall_hook=(
                    self._chaos.data_stall_hook
                    if self._chaos is not None else None
                ),
                telemetry=self.telemetry,
            )
            self._watchers.append(self._datapath)
            if config.data_digests:
                from tpu_ddp.datapath.audit import DataDigestWriter

                self._data_digests = DataDigestWriter(
                    config.telemetry_dir,
                    process_index=self.process_index,
                    incarnation=self.incarnation,
                    seed=config.seed,
                    run_id=self.run_meta.get("run_id"),
                    global_batch=config.per_shard_batch * self.data_size,
                )
        # Anomaly profiler (docs/profiling.md): the capture manager sits
        # dormant until a window is armed — by --profile-steps here, by
        # POST /profile on the exporter, or by the capture_profile alert
        # action; its on_step opens an armed window when its start step
        # arrives and closes + writes the bundle when it ends (boundaries
        # snap to dispatch boundaries under scan fusion). Needs the run
        # dir for its bundles, so it exists exactly when telemetry does.
        self._capture = None
        if config.telemetry_dir:
            from tpu_ddp.profiler.capture import (
                CaptureManager,
                parse_profile_steps,
            )

            self._capture = CaptureManager(
                config.telemetry_dir,
                process_index=self.process_index,
                window_steps=config.profile_window_steps,
                host_hz=config.profile_host_hz,
                telemetry=self.telemetry,
                run_meta=self.run_meta,
            )
            window = parse_profile_steps(config.profile_steps)
            if window:
                self._capture.arm_window(*window)
            self._watchers.append(self._capture)

        # Live memory sampler (docs/memory.md): per-step device
        # memory_stats (host-side runtime reads, no device sync) ->
        # memory/* gauges + the incarnation-stamped mem-p<i>.jsonl sink.
        # Exists exactly when telemetry does (dormant otherwise, like the
        # capture manager); its ring of recent samples is the OOM
        # postmortem's evidence.
        self._memtrack = None
        if config.telemetry_dir and config.mem_sample_steps > 0:
            from tpu_ddp.memtrack.sampler import MemorySampler

            local = set(jax.local_devices())
            self._memtrack = MemorySampler(
                config.telemetry_dir,
                process_index=self.process_index,
                incarnation=self.incarnation,
                telemetry=self.telemetry,
                every=config.mem_sample_steps,
                run_meta=self.run_meta,
                devices=[d for d in devices if d in local],
            )
            self._watchers.append(self._memtrack)

        self._data_prefetcher = None  # staged background prefetcher
        self.model = build_model(config)
        from tpu_ddp.train.tasks import IMAGE_CLASSIFICATION, task_of

        # what the model reads from a batch and which loss it takes
        self.task = task_of(self.model)
        if (self.task is not IMAGE_CLASSIFICATION
                and self.parallelism != "dp"):
            raise ValueError(
                f"model {config.model!r} trains on {self.task.name}; the "
                f"{self.parallelism} strategy's step and layout rules are "
                "an image classifier's. Use --parallelism dp (zero1, "
                "accumulation and recomputation come with it)")
        self._load_data(train_data, test_data)
        total_steps = self.train_loader.steps_per_epoch * config.epochs
        freeze = None
        if config.freeze_prefixes:
            from tpu_ddp.train.optim import freeze_all_but

            freeze = freeze_all_but(tuple(config.freeze_prefixes))
        # ZeRO-1: the optimizer chain runs on flattened 1/N update-space
        # shards inside the step, so structure-dependent pieces must be
        # precomputed on the ORIGINAL shapes: the kernels-only decay mask
        # from an abstract init (ndim is gone after flattening), and
        # global-norm clipping switches to the psum-over-data variant
        # (see make_optimizer's zero1_axis).
        decay_mask = None
        zero1_axis = None
        if config.zero1 or config.zero3:
            zero1_axis = DATA_AXIS
            if config.weight_decay > 0:
                from tpu_ddp.train.optim import _decay_mask
                from tpu_ddp.train.state import init_model_variables

                abstract_params, _ = jax.eval_shape(
                    lambda: init_model_variables(
                        self.model, jax.random.key(0),
                        example=self._init_example())
                )
                decay_mask = _decay_mask(abstract_params)
        self.tx = make_optimizer(
            lr=config.lr,
            optimizer=config.optimizer,
            momentum=config.momentum,
            weight_decay=config.weight_decay,
            schedule=config.schedule,
            total_steps=total_steps,
            warmup_steps=config.warmup_steps,
            grad_clip_norm=config.grad_clip_norm,
            freeze_predicate=freeze,
            ema_decay=config.ema_decay,
            decay_mask=decay_mask,
            zero1_axis=zero1_axis,
            kernels=config.kernels,
        )
        from tpu_ddp.train.losses import (
            binary_cross_entropy_with_logits,
            cross_entropy_loss,
        )

        if config.loss == "ce":
            loss_fn, with_acc = cross_entropy_loss, True
            if config.label_smoothing:
                import functools

                loss_fn = functools.partial(
                    cross_entropy_loss,
                    label_smoothing=config.label_smoothing,
                )
        elif config.loss == "bce":
            loss_fn, with_acc = binary_cross_entropy_with_logits, False
        else:
            raise ValueError(f"unknown loss {config.loss!r}")
        self._loss_fn, self._with_acc = loss_fn, with_acc

        self.state_shardings = None   # None == fully replicated (dp/sp)
        self._prepare_eval = None     # strategy hook (pp re-layouts params)
        self._zero1 = None            # Zero1Partition when --zero1
                                      # (Zero3Partition when --zero3 —
                                      # same interface, params scattered)
        self._compress = None         # GradCompressor when --grad-compress
        self._comm_bytes_per_step = None  # (wire, f32) per device per step
        if self.parallelism == "dp":
            self._init_dp_steps(loss_fn, with_acc)
        else:
            self._init_strategy_steps(loss_fn, with_acc)
        # The program map (telemetry/program_map.py): with telemetry on,
        # the phase and module of every instruction of each step program,
        # written once the program has settled. It holds the jitted
        # callables built just above, not the attributes: a caller may
        # stand a probe in ``train_step`` before ``run``. With telemetry
        # off nothing is built, lowered or written.
        self._program_map = None
        if self.telemetry.enabled:
            from tpu_ddp.telemetry.program_map import ProgramMapExporter

            programs = {"single": ("train_step", self.train_step)}
            if self.multi_step is not None:
                programs["stacked"] = ("multi_step", self.multi_step)
            self._program_map = ProgramMapExporter(
                config.telemetry_dir, programs,
                process_index=self.process_index,
                incarnation=self.incarnation,
            )
        self._prefetcher = None   # built lazily on first epoch
        self.history: dict = {"epoch": [], "train_loss": []}
        self.logger = MetricLogger(
            jsonl_path=config.jsonl_path,
            tensorboard_dir=config.tensorboard_dir,
        )

        self.checkpointer = None
        self.best_checkpointer = None
        self.resumed_step = None      # set iff --resume restored a checkpoint
        self._best_acc = float("-inf")
        if config.keep_best and not (
            config.checkpoint_dir and config.eval_each_epoch
            and config.loss == "ce"
        ):
            raise ValueError(
                "--keep-best needs --checkpoint-dir and --eval-each-epoch "
                "(and a CE loss: 'best' is keyed on test accuracy)"
            )
        if config.checkpoint_dir:
            from tpu_ddp.checkpoint import Checkpointer

            self.checkpointer = Checkpointer(
                config.checkpoint_dir, telemetry=self.telemetry,
                fault_hook=(
                    self._chaos.save_fault_hook
                    if self._chaos is not None else None
                ),
            )
            if config.keep_best:
                best_dir = os.path.join(config.checkpoint_dir, "best")
                self.best_checkpointer = Checkpointer(
                    best_dir, max_to_keep=1, telemetry=self.telemetry
                )
                meta = os.path.join(best_dir, "metadata.json")
                if config.resume and os.path.isfile(meta):
                    # don't demote a resumed run's best on the first eval;
                    # a corrupt/truncated metadata file (crash mid-write
                    # before the writes became atomic, torn copy) falls
                    # back to -inf with a warning instead of killing the
                    # resume — the stored best may be re-replaced, never
                    # silently trusted
                    try:
                        with open(meta) as f:
                            self._best_acc = json.load(f)["test_accuracy"]
                    except (OSError, ValueError, KeyError) as e:
                        log.warning(
                            "unreadable best metadata %s (%s); treating "
                            "best accuracy as unset", meta, e)
            if config.resume and self.checkpointer.latest_step() is not None:
                # Checkpoints are ALWAYS the de-sharded, device-count-
                # independent layout — _ckpt_state below: zero1 opt
                # shards gathered back to the original optax layout, the
                # error-feedback residual de-flattened to param layout —
                # so a --zero1/--grad-compress run restores a replicated
                # run's checkpoint and vice versa, AND a checkpoint cut
                # on one device count resumes on another (the elastic
                # re-mesh path, docs/resilience.md). Restore through the
                # de-sharded template, then re-scatter onto THIS mesh;
                # _place_state below lays the rest out in the TRAINING
                # layout (fsdp/tp/pp/ep scattered, dp/sp replicated).
                restored = self._restore_checkpoint(self._ckpt_state())
                if (self._compress is not None
                        and restored.grad_residual is not None):
                    restored = restored.replace(
                        grad_residual=self._compress.shard_residual(
                            restored.grad_residual, self.mesh))
                if self._zero1 is not None:
                    restored = self._zero1.shard_state(restored, self.mesh)
                self.state = restored
                self.resumed_step = int(self.state.step)
                self.logger.log_text(
                    f"resumed from step {self.resumed_step}"
                )
        self.state = self._place_state(self.state)

    def _place_state(self, state):
        """``state`` as a step returns it: every leaf (``step`` included) a
        committed array on the mesh, laid out by ``state_shardings`` where
        a builder set one (zero1/zero3, the error-feedback residual, the
        sharded strategies) and replicated otherwise. A copy, bit for bit;
        a leaf that is there already is kept as it is.

        The one place that knows a state's layout, called once, whichever
        branch made the state (fresh, pretrained, resumed): the first call
        of a step then takes the kind of argument every later call does,
        its own output, so jit traces, lowers and loads the step once per
        ``Trainer``. One uncommitted leaf is enough for a second round."""
        return jax.device_put(
            state, self.state_shardings or replicated_sharding(self.mesh))

    def _restore_checkpoint(self, template):
        """``Checkpointer.restore`` with grad-residual tolerance: the
        error-feedback residual (``TrainState.grad_residual``) is the one
        state field whose presence depends on a flag, so --resume must
        compose across runs that disagree about it. A checkpoint WITHOUT
        a residual restores into an error-feedback run with a fresh zero
        residual; a checkpoint WITH one restores into a plain run by
        rebuilding the residual's abstract template from the checkpoint
        metadata and discarding it after the restore."""
        try:
            return self.checkpointer.restore(template)
        except Exception as e:
            if template.grad_residual is not None:
                restored = self.checkpointer.restore(
                    template.replace(grad_residual=None))
                log.warning(
                    "checkpoint carries no (matching) grad_residual; "
                    "starting the error-feedback residual from zero (%s)",
                    e,
                )
                return restored.replace(
                    grad_residual=template.grad_residual)
            res_template = self._ckpt_residual_template()
            if res_template is None:
                raise
            restored = self.checkpointer.restore(
                template.replace(grad_residual=res_template))
            log.warning(
                "checkpoint carries a grad-compress residual this run "
                "does not use; discarding it"
            )
            return restored.replace(grad_residual=None)

    def _ckpt_residual_template(self):
        """Abstract (shape/dtype) template of the newest checkpoint's
        ``grad_residual`` subtree, from the checkpoint metadata — None
        when the checkpoint has no residual or the metadata is
        unreadable."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        try:
            step = self.checkpointer.latest_step()
            meta = self.checkpointer.manager.item_metadata(step)
            res = (meta.get("grad_residual") if hasattr(meta, "get")
                   else getattr(meta, "grad_residual", None))
            if res is None or not jax.tree.leaves(res):
                return None
            rep = NamedSharding(self.mesh, P())  # discarded post-restore
            return jax.tree.map(
                lambda m: jax.ShapeDtypeStruct(
                    tuple(m.shape), m.dtype, sharding=rep),
                res,
            )
        except Exception:
            return None

    def _build_compressor(self, params_template):
        """GradCompressor for this run's --grad-compress knobs (also
        precomputes the per-step wire-byte accounting the telemetry
        counters report)."""
        from tpu_ddp.parallel.compression import (
            GradCompression,
            GradCompressor,
        )

        config = self.config
        comp = GradCompressor(
            GradCompression(
                mode=config.grad_compress,
                block=config.grad_compress_block,
                error_feedback=config.grad_compress_error_feedback,
                kernels=config.kernels,
            ),
            params_template, self.data_size, axis=DATA_AXIS,
        )
        self._set_comm_accounting(comp)
        return comp

    def _set_comm_accounting(self, comp) -> None:
        """Precompute the per-step wire-byte pair the epoch loop feeds
        into the comm/* counters: under --zero1 only the reduce-scatter
        phase is the compressed collective (the params all-gather is
        unchanged), plain DP pays the full ring all-reduce."""
        acct = comp.accounting()
        zero_sharded = self.config.zero1 or self.config.zero3
        key = "reduce_scatter" if zero_sharded else "all_reduce"
        self._comm_bytes_per_step = (
            acct[f"{key}_bytes_on_wire_per_device"],
            acct[f"{key}_bytes_f32_per_device"],
        )

    def _residual_shardings(self, base):
        """State-shardings tree with the error-feedback residual laid out
        ``P(data)``: extends the zero1 shardings when present, else builds
        a fully-replicated tree around the residual (the dp path's state
        was previously 'None == replicated everywhere', which can no
        longer describe the mixed layout)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        if base is None:
            rep = NamedSharding(self.mesh, P())
            base = jax.tree.map(
                lambda _: rep,
                self.state.replace(grad_residual=None),
            )
        return base.replace(
            grad_residual=self._compress.residual_shardings(self.mesh))

    def _init_example(self):
        """The dummy input ``model.init`` is shown (None: float32 images)."""
        return self.task.example_input(self.train_loader.images)

    def _init_dp_steps(self, loss_fn, with_acc):
        """Flagship data-parallel path: shard_map DDP-semantics step, scan
        fusion, on-device augmentation, replicated state (``--zero1``:
        replicated params, SCATTERED optimizer state; ``--zero3``: params
        AND optimizer state scattered, forward streams params over the
        prefetch schedule)."""
        config = self.config
        if config.pretrained_dir:
            from tpu_ddp.train.finetune import load_pretrained_for_finetune

            self.state = load_pretrained_for_finetune(
                config.pretrained_dir,
                self.model,
                self.tx,
                rng=jax.random.key(config.seed),
            )
        elif config.zero1 or config.zero3:
            # Fresh zero1/zero3 init: the SAME init recipe as
            # create_train_state (init_model_variables — seed-parity with
            # the replicated path depends on sharing it), but tx.init runs
            # under out_shardings that scatter the update-space leaves —
            # the replicated optimizer state (the HBM being saved) is
            # never materialized, not even transiently at step 0. Under
            # --zero3 the params themselves then move into the same flat
            # scattered layout (the full init copy is transient, host-side
            # model init being the unavoidable floor).
            import jax.numpy as jnp

            from tpu_ddp.parallel.zero import Zero1Partition, Zero3Partition
            from tpu_ddp.train.state import TrainState, init_model_variables

            params, batch_stats = init_model_variables(
                self.model, jax.random.key(config.seed),
                example=self._init_example())
            # on the mesh before the scatters below read them; the step and
            # the batch statistics are left to _place_state
            params = jax.device_put(params, replicated_sharding(self.mesh))
            cls = Zero3Partition if config.zero3 else Zero1Partition
            self._zero1 = cls(
                self.tx, params, self.data_size, axis=DATA_AXIS)
            opt_state = self._zero1.init_opt_state(params, self.mesh)
            if config.zero3:
                params = self._zero1.shard_params(params, self.mesh)
            self.state = TrainState(
                step=jnp.zeros((), jnp.int32),
                params=params,
                batch_stats=batch_stats,
                opt_state=opt_state,
            )
        else:
            self.state = create_train_state(
                self.model, self.tx, jax.random.key(config.seed),
                example=self._init_example(),
            )
        if config.zero1 or config.zero3:
            if self._zero1 is None:  # finetune path: scatter the restored
                from tpu_ddp.parallel.zero import (
                    Zero1Partition,
                    Zero3Partition,
                )

                cls = Zero3Partition if config.zero3 else Zero1Partition
                self._zero1 = cls(
                    self.tx, self.state.params, self.data_size,
                    axis=DATA_AXIS,
                )
                self.state = self._zero1.shard_state(self.state, self.mesh)
            self.state_shardings = self._zero1.state_shardings(
                self.state, self.mesh
            )
        if config.grad_compress != "none":
            # --grad-compress: the grad sync's wire payloads go int8/bf16
            # through the ppermute ring (parallel/compression.py); under
            # --zero1 the partition's reduce-scatter runs the same ring.
            if self._zero1 is not None and getattr(
                    self._zero1, "scattered_params", False):
                # zero3: state.params are already flat shards — the
                # compressor derives its per-leaf layout from the
                # ORIGINAL shapes (the partition kept the template)
                params_template = self._zero1.param_template
            else:
                params_template = self.state.params
            self._compress = self._build_compressor(params_template)
            if self._zero1 is not None:
                self._zero1.set_compression(self._compress)
            if config.grad_compress_error_feedback:
                self.state = self.state.replace(
                    grad_residual=self._compress.init_residual(self.mesh))
                self.state_shardings = self._residual_shardings(
                    self.state_shardings)
        # one builder for every DP step; it refuses --augment/--mixup-alpha
        # under --grad-accum-steps
        step_options = dict(
            accum_steps=config.grad_accum_steps,
            loss_fn=loss_fn, compute_accuracy=with_acc, remat=config.remat,
            augment=config.augment, augment_seed=config.seed,
            mixup_alpha=config.mixup_alpha, aux_weight=config.aux_weight,
            health=self._health, zero1=self._zero1,
            compress=self._compress, task=self.task,
        )
        self.train_step = make_train_step(
            self.model, self.tx, self.mesh, **step_options)
        self.multi_step = None
        # Clamp to the epoch length: a scan longer than the epoch would
        # compile but never fill, silently running every step un-fused.
        self.steps_per_call = min(
            config.steps_per_call, self.train_loader.steps_per_epoch
        )
        if self.steps_per_call > 1 and config.grad_accum_steps > 1:
            raise ValueError(
                "--steps-per-call and --grad-accum-steps are opposite "
                "trades (fuse more steps per dispatch vs split one step "
                "into microbatches); pick one"
            )
        if self.steps_per_call > 1:
            from tpu_ddp.parallel.mesh import stacked_batch_sharding

            self.multi_step = make_train_step(
                self.model, self.tx, self.mesh,
                steps_per_call=self.steps_per_call, **step_options)
            self.stacked_sharding = stacked_batch_sharding(self.mesh)
        self.eval_step = make_eval_step(
            self.model, self.mesh, loss_fn=loss_fn,
            compute_accuracy=with_acc, task=self.task,
        )
        self.predict_step = None  # built lazily in predict()

    def _init_strategy_steps(self, loss_fn, with_acc):
        """Sharded-parallelism path (fsdp/tp/pp/sp/ep): route to the
        strategy's step builders, lay the state out on the mesh, and take
        the strategy's sharded eval/predict."""
        config = self.config
        from tpu_ddp.train.strategy import build_strategy

        # Genuinely dp-only knobs: the augmentation pipeline and cross-
        # replica BN live in the dp shard_map step. The memory knobs
        # (--remat / --grad-accum-steps) compose with the GSPMD family
        # via build_strategy (round-4 verdict item 4) and raise there for
        # pp/sp, which own their own microbatching/remat story.
        for flag, name in (
            (config.augment, "--augment"),
            (config.mixup_alpha > 0, "--mixup-alpha"),
            (config.sync_bn, "--sync-bn"),
        ):
            if flag:
                raise ValueError(
                    f"{name} is only supported with data parallelism "
                    f"(got --parallelism {self.parallelism})"
                )
        if config.steps_per_call > 1:
            import warnings

            warnings.warn(
                f"steps_per_call={config.steps_per_call} ignored: scan "
                "fusion is dp-only",
                stacklevel=2,
            )
        initial = None
        if config.pretrained_dir:
            from tpu_ddp.train.finetune import load_pretrained_for_finetune

            initial = load_pretrained_for_finetune(
                config.pretrained_dir,
                self.model,
                self.tx,
                rng=jax.random.key(config.seed),
            )
        strategy = build_strategy(
            self.parallelism,
            self.mesh,
            self.model,
            self.tx,
            jax.random.key(config.seed),
            loss_fn=loss_fn,
            compute_accuracy=with_acc,
            aux_weight=config.aux_weight,
            n_microbatches=config.n_microbatches,
            pp_schedule=config.pp_schedule,
            sp_flash=config.sp_flash,
            initial_state=initial,
            remat=config.remat,
            grad_accum_steps=config.grad_accum_steps,
            health=self._health,
            zero1=config.zero1,
            grad_compress=(
                None if config.grad_compress == "none" else {
                    "mode": config.grad_compress,
                    "block": config.grad_compress_block,
                    "error_feedback": config.grad_compress_error_feedback,
                }
            ),
        )
        self.state = strategy.state
        self.train_step = strategy.train_step
        self.eval_step = strategy.eval_step
        self.predict_step = strategy.predict_step
        self.batch_sharding = strategy.batch_shardings
        self.state_shardings = strategy.state_shardings
        self._prepare_eval = strategy.prepare_eval
        self._zero1 = strategy.zero1
        self._compress = strategy.compress
        if self._compress is not None:
            self._set_comm_accounting(self._compress)
        self.multi_step = None
        self.steps_per_call = 1

    def _load_data(self, train_data=None, test_data=None):
        c = self.config
        if train_data is not None:
            train = train_data
            test = test_data if test_data is not None else train_data
        else:
            train, test = load_dataset(c, self.task, self.model)
        self.train_loader = ShardedBatchLoader(
            *train,
            world_size=self.data_size,
            per_shard_batch=c.per_shard_batch,
            shuffle=c.shuffle,
            reshuffle_each_epoch=c.reshuffle_each_epoch,
            seed=c.seed,
            process_index=self.process_index,
            process_count=self.process_count,
            telemetry=self.telemetry,
            observer=self._datapath,
            keys=(self.task.input_key, self.task.target_key),
        )
        if c.loss == "bce" and np.asarray(train[1]).ndim != 2:
            raise ValueError(
                "--loss bce needs multi-hot (N, C) targets; this dataset "
                "yields class indices. Use --synthetic-data (multi-label "
                "generator) or pass multi-hot train_data."
            )
        self.test_loader = ShardedBatchLoader(
            *test,
            world_size=self.data_size,
            per_shard_batch=c.per_shard_batch,
            shuffle=False,
            exclude_sampler_pad=True,  # metrics count each sample once
            process_index=self.process_index,
            process_count=self.process_count,
            telemetry=self.telemetry,
            keys=(self.task.input_key, self.task.target_key),
        )

    def _put(self, batch):
        return self._put_with(batch, self.batch_sharding)

    def _put_with(self, batch, sharding):
        """Host batch -> global device array. Single-host: device_put.
        Multi-host: each process contributes its local rows and the runtime
        stitches the global array (no host ever materializes the full
        batch) — the SPMD replacement for per-rank loaders."""
        pick = (
            sharding.get if isinstance(sharding, dict)
            else (lambda k, s=sharding: s)
        )
        if self._multihost:
            return {
                k: jax.make_array_from_process_local_data(pick(k), v)
                for k, v in batch.items()
            }
        return jax.device_put(
            batch, {k: pick(k) for k in batch} if isinstance(sharding, dict)
            else sharding
        )

    def _epoch_stream(self):
        """Yield ``(kind, device_batch, n_real)``: kind is "stacked" for
        fused K-step groups (arrays carry a leading (K,) scan axis) and
        "single" for lone steps — the epoch remainder smaller than
        steps_per_call runs as plain steps so the scan's stacked shapes stay
        static. Batches come back already device_put with the right
        sharding; ``n_real`` is the host-side count of unmasked samples (so
        throughput accounting never forces a device sync).

        With ``prefetch_depth > 0`` batches assemble ahead of consumption on
        the host prefetcher (native C++ ring when available); with
        ``prefetch_batches > 0`` the STAGED loader pipeline (per-stage
        spans + data-health attribution, docs/data.md) runs ahead on a
        background thread instead — bit-identical batches, and it takes
        precedence over the native prefetcher."""
        K = self.steps_per_call if self.multi_step is not None else 1
        depth = self.config.prefetch_depth
        if self.config.prefetch_batches > 0:
            from tpu_ddp.datapath.prefetch import BackgroundPrefetcher

            if self._data_prefetcher is not None:
                self._data_prefetcher.close()
            pf = BackgroundPrefetcher(
                self._digested_batches,
                depth=self.config.prefetch_batches,
                telemetry=self.telemetry,
            )
            self._data_prefetcher = pf
            try:
                yield from self._host_batch_stream(iter(pf), K)
            finally:
                pf.close()
                self._data_prefetcher = None
            return
        if depth > 0:
            if self._prefetcher is None:
                from tpu_ddp.native.prefetch import BatchPrefetcher

                self._prefetcher = BatchPrefetcher(
                    self.train_loader.images,
                    self.train_loader.labels,
                    # local_batch: this host only ever gathers its own rows
                    max_batch=K * self.train_loader.local_batch,
                    depth=depth + 1,
                )
            yield from self._prefetched_stream(K, depth)
            return
        yield from self._host_batch_stream(self._digested_batches(), K)

    def _digested_batches(self):
        """The train loader's staged epoch stream, with each batch's
        content digest recorded against its GLOBAL step number (epochs
        are 1-based; batch j of epoch E is step (E-1)*steps_per_epoch+j)
        — the determinism audit's evidence (docs/data.md). Runs on the
        producer thread under --prefetch-batches; digest cost rides the
        pipeline, not the step loop."""
        loader = self.train_loader
        base = (max(loader._epoch, 1) - 1) * loader.steps_per_epoch
        # iterator protocol, not epoch_batches(): the loader attribute may
        # be wrapped (fault-injection shims override __iter__ only)
        for i, batch in enumerate(loader):
            if self._data_digests is not None:
                self._data_digests.record(base + i, batch)
            yield batch

    def _host_batch_stream(self, it, K: int):
        """The consuming half of the synchronous/staged-prefetch paths:
        draw host batches from ``it`` (``data_wait``), device_put them
        (``h2d``), fusing K-step groups into stacked submissions."""
        tel = self.telemetry
        if K <= 1:
            while True:
                with tel.span("data_wait"):
                    batch = next(it, None)
                if batch is None:
                    return
                with tel.span("h2d"):
                    dev = self._put(batch)
                yield "single", dev, int(batch["mask"].sum())
        pending = []
        while True:
            with tel.span("data_wait"):
                batch = next(it, None)
            if batch is None:
                break
            pending.append(batch)
            if len(pending) == K:
                with tel.span("h2d"):
                    stacked = {
                        k: np.stack([b[k] for b in pending])
                        for k in pending[0]
                    }
                    dev = self._put_with(stacked, self.stacked_sharding)
                yield "stacked", dev, int(stacked["mask"].sum())
                pending = []
        for batch in pending:
            with tel.span("h2d"):
                dev = self._put(batch)
            yield "single", dev, int(batch["mask"].sum())

    def _prefetched_stream(self, K: int, depth: int):
        """Prefetcher-backed _epoch_stream body. A fused K-step group is ONE
        submission (concatenated indices -> one native gather whose output
        IS the stacked (K*B, ...) layout) — no host-side np.stack at all.

        Slot lifetime: the gathered views alias reusable native buffers. On
        TPU, ``device_put`` + ``block_until_ready`` is a real H2D copy, so
        the slot recycles right after the fence. On the CPU backend,
        ``device_put`` zero-copy ALIASES 64-byte-aligned numpy inputs — and
        ignores ``may_alias=False`` (verified empirically) — so the views
        are np.copy'd first; without this, slot reuse corrupts batches the
        compiled step hasn't consumed yet, nondeterministically (it depends
        on the C++ heap handing back 64-aligned slots)."""
        from collections import deque

        pf = self._prefetcher
        loader = self.train_loader
        img_tail = loader.images.shape[1:]
        lbl_tail = loader.labels.shape[1:]
        in_key, target_key = self.task.input_key, self.task.target_key
        # Copy UNLESS the backend is known to complete a real H2D copy by
        # block_until_ready (TPU/GPU): any backend that may zero-copy-alias
        # host memory (CPU does, and ignores may_alias=False) would
        # otherwise see slot reuse corrupt batches the compiled step hasn't
        # consumed yet. Unknown backends fail SAFE (copy).
        real_h2d = jax.default_backend() in ("tpu", "gpu", "cuda", "rocm")
        host_copy = pf.reusable_slots and not real_h2d

        def submissions():
            seq = 0  # batch index within the epoch (digest step anchors)
            buf_idx, buf_masks = [], []
            for idx, mask in loader.epoch_index_batches():
                if K <= 1:
                    yield "single", idx, mask, seq
                    seq += 1
                    continue
                buf_idx.append(idx)
                buf_masks.append(mask)
                if len(buf_idx) == K:
                    yield (
                        "stacked",
                        np.concatenate(buf_idx),
                        np.stack(buf_masks),
                        seq,
                    )
                    seq += K
                    buf_idx, buf_masks = [], []
            for idx, mask in zip(buf_idx, buf_masks):
                yield "single", idx, mask, seq
                seq += 1

        in_flight = deque()

        tel = self.telemetry
        step_base = (max(loader._epoch, 1) - 1) * loader.steps_per_epoch

        def emit():
            kind, mask, seq = in_flight.popleft()
            with tel.span("data_wait"):
                # blocks until the prefetcher finishes the oldest gather
                img, lbl, slot = pf.acquire()  # FIFO: matches oldest submission
            with tel.span("h2d"):
                if host_copy:
                    img, lbl = np.copy(img), np.copy(lbl)
                if kind == "stacked":
                    img = img.reshape((K, -1) + img_tail)
                    lbl = lbl.reshape((K, -1) + lbl_tail)
                    sharding = self.stacked_sharding
                else:
                    sharding = self.batch_sharding
                dev = self._put_with(
                    {in_key: img, target_key: lbl, "mask": mask}, sharding
                )
                # Fence ONLY the H2D transfer, then recycle the slot; the
                # copy of batch N+depth overlaps the device computing batch N.
                jax.block_until_ready(dev)
            dw = self._data_digests
            if dw is not None:
                # digest BEFORE the slot recycles (img/lbl may alias it)
                if kind == "stacked":
                    for k in range(K):
                        dw.record(step_base + seq + k, {
                            in_key: img[k], target_key: lbl[k],
                            "mask": mask[k],
                        })
                else:
                    dw.record(step_base + seq, {
                        in_key: img, target_key: lbl, "mask": mask,
                    })
            pf.release(slot)
            return kind, dev, int(mask.sum())

        for kind, idx, mask, seq in submissions():
            pf.submit(idx)
            in_flight.append((kind, mask, seq))
            if len(in_flight) > depth:
                yield emit()
        while in_flight:
            yield emit()

    def _release_workers(self) -> None:
        """Stop the host-side helpers: prefetcher (worker thread + slot
        buffers), monitor exporter, the step stamper (drained first),
        everything in ``_watchers`` (the watchdog among them), and the
        health monitor (flushes its JSONL footer). Idempotent; does NOT
        close the telemetry sinks."""
        if self._prefetcher is not None:
            self._prefetcher.close()
            self._prefetcher = None
        if self._data_prefetcher is not None:
            self._data_prefetcher.close()
            self._data_prefetcher = None
        if self._data_digests is not None:
            self._data_digests.close()
        if self._exporter is not None:
            self._exporter.close()
            self._exporter = None
        if self._stamper is not None:
            # drained before the sinks close: the run-end counters carry
            # every step's device_step
            self._stamper.close()
            self._stamper = None
        # each watcher once, the last told first, so that the watchdog
        # outlives the others' last writes (a capture window still open
        # when the run drains is written as a truncated bundle — a
        # preempted run's capture is evidence too)
        watchers, self._watchers = self._watchers, []
        self._watchdog = None
        for watcher in reversed(watchers):
            if watcher is self._comms_monitor:
                # uninstall the hop hook BEFORE its monitor closes: a
                # straggling dispatch must not write through a closed one
                from tpu_ddp.parallel.collectives import set_ring_hop_hook

                set_ring_hop_hook(None)
            watcher.close()
        if self._health_monitor is not None:
            self._health_monitor.close()

    def close(self) -> None:
        """Release the workers and finalize the telemetry sinks (writes the
        Chrome trace, prints the phase summary). Idempotent."""
        self._release_workers()
        self.telemetry.close()

    def run(self, *, close: bool = True) -> dict:
        """Train. ``close=False`` keeps the telemetry sinks open (workers
        are still released) so the caller can fold post-run results into
        the final counters snapshot — ``record_final_eval`` — before
        calling ``close()`` itself; the CLI does exactly that, making the
        JSONL trace a self-contained run record."""
        try:
            return self._run_impl()
        finally:
            self._release_workers()
            if close:
                self.close()

    def record_final_eval(self, *, accuracy=None, loss=None) -> None:
        """Mirror end-of-run eval results into telemetry gauges
        (``eval/final_test_*``, plus ``eval/best_test_accuracy`` when
        --keep-best tracked one) so the final counters snapshot — emitted
        by ``close()`` — carries them. No-op with telemetry disabled."""
        tel = self.telemetry
        if not tel.enabled:
            return
        if accuracy is not None:
            tel.gauge("eval/final_test_accuracy").set(accuracy)
        if loss is not None:
            tel.gauge("eval/final_test_loss").set(loss)
        if self._best_acc != float("-inf"):
            tel.gauge("eval/best_test_accuracy").set(self._best_acc)
        # the final eval point, anchored like the per-epoch ones so the
        # trace carries the whole eval history (docs/curves.md)
        from tpu_ddp.telemetry import EVAL_POINT_SCHEMA_VERSION

        tel.instant(
            "eval", step=int(self.state.step),
            eval_schema_version=EVAL_POINT_SCHEMA_VERSION,
            final=True,
            **({"test_loss": loss} if loss is not None else {}),
            **({"test_accuracy": accuracy} if accuracy is not None
               else {}),
        )

    def abstract_step_inputs(self) -> tuple:
        """``(state, batch)`` as ShapeDtypeStructs carrying the layouts
        ``self.train_step`` runs them in — what an ahead-of-time lowering
        of the REAL jitted step takes (``lint_preflight``; chip_smoke.py
        reads the compiled text for its kernels and collectives)."""
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        c = self.config
        replicated = NamedSharding(self.mesh, P())

        def _aval(x):
            # dp keeps the replicated state uncommitted (single-device
            # shardings); pin those to the mesh-replicated layout the
            # step runs them in — mesh layouts (zero1 shards, GSPMD
            # specs) pass through
            sh = getattr(x, "sharding", None)
            if not isinstance(sh, NamedSharding):
                sh = replicated
            return jax.ShapeDtypeStruct(jnp.shape(x), x.dtype, sharding=sh)

        state = jax.tree.map(_aval, self.state)
        gb = c.per_shard_batch * self.data_size
        shard_of = (self.batch_sharding.get
                    if isinstance(self.batch_sharding, dict)
                    else lambda _k: self.batch_sharding)
        # label avals must mirror the run's loss: bce trains on multi-hot
        # float targets (N, C), ce on class indices (N,)
        label_shape, label_dtype = (
            ((gb, c.num_classes), jnp.float32) if c.loss == "bce"
            else ((gb,), jnp.int32))
        from tpu_ddp.train.tasks import IMAGE_CLASSIFICATION

        if self.task is IMAGE_CLASSIFICATION:
            pair = {"image": ((gb, 32, 32, 3), jnp.float32),
                    "label": (label_shape, label_dtype)}
        else:  # the training set's own two arrays, a global batch of them
            loader = self.train_loader
            pair = {key: ((gb,) + a.shape[1:], a.dtype) for key, a in (
                (self.task.input_key, loader.images),
                (self.task.target_key, loader.labels))}
        batch = {
            key: jax.ShapeDtypeStruct(shape, dtype, sharding=shard_of(key))
            for key, (shape, dtype) in pair.items()}
        batch["mask"] = jax.ShapeDtypeStruct(
            (gb,), bool, sharding=shard_of("mask"))
        return state, batch

    def lint_preflight(self, *, raise_on_error: bool = True):
        """Run the static graph lint (``tpu_ddp/analysis/lint.py``) over
        the REAL jitted train step(s) — not the abstract twin — so the
        verdict applies to the exact program this run trains with.

        Cost: one EXTRA ahead-of-time compile per linted program (the
        AOT path does not seed jit's dispatch cache, so step 1 still
        compiles) — the persistent compilation cache makes the second
        compile a cache hit. Returns the
        findings; with ``raise_on_error`` (the ``--lint-on-start`` path)
        an error finding refuses the launch."""
        import jax as _jax

        from tpu_ddp.analysis.explain import run_strategy_label
        from tpu_ddp.analysis.lint import lint_program, render_findings

        c = self.config
        state, batch = self.abstract_step_inputs()
        label = run_strategy_label(self.run_meta)
        findings, _ = lint_program(
            self.train_step, state, batch, self.mesh, strategy=label,
            compute_dtype=c.compute_dtype, model_name=c.model,
        )
        if c.kernels:
            from tpu_ddp.analysis.lint import lint_kernels

            # KRN001 fail-closed audit: --kernels on a backend with no
            # Pallas lowering must refuse here, not silently fall back
            findings = findings + lint_kernels(True, program=label)
        if self.multi_step is not None:
            stacked = {
                k: _jax.ShapeDtypeStruct(
                    (self.steps_per_call,) + v.shape, v.dtype,
                    sharding=self.stacked_sharding)
                for k, v in batch.items()
            }
            scan_findings, _ = lint_program(
                self.multi_step, state, stacked, self.mesh, strategy=label,
                compute_dtype=c.compute_dtype, model_name=c.model,
                program=f"{label}+scan",
            )
            findings = findings + scan_findings
        print(render_findings(f"preflight ({label})", findings),
              flush=True)
        errors = [f for f in findings if f.severity == "error"]
        if errors and raise_on_error:
            raise RuntimeError(
                f"lint preflight refused the launch: {len(errors)} "
                "error finding(s) in the compiled step (see above; "
                "docs/lint.md has the rule table and fix hints)"
            )
        return findings

    def _run_impl(self) -> dict:
        c = self.config
        start = time.time()
        if c.lint_on_start:
            self.lint_preflight()
        # Preemption safety (beyond SURVEY §5.3's reference scope, which has
        # no failure handling at all): SIGTERM/SIGINT set a flag; the loop
        # drains at the next safe boundary, the tail saves a final
        # checkpoint, and --resume continues from the exact step. This is
        # what makes training survive TPU-pod preemptions and Ctrl-C
        # identically.
        self._preempted = False
        self._force_abort = False
        import signal

        old_handlers = {}

        def _on_signal(signum, frame):
            del frame
            # Async-signal-safe only: no print()/logging here (a buffered
            # write interrupted mid-print would raise a reentrancy error);
            # os.write to stderr is safe. The loop logs properly later.
            if self._preempted:
                # Second signal during the drain: escalate by SKIPPING
                # the final checkpoint — NOT by dying wherever we stand,
                # which could be mid-save and would leave a torn newest
                # checkpoint for the next --resume to trip over (the
                # checksum manifest would catch it, but the cadence save
                # it falls back to is older than the one a clean skip
                # preserves). A third signal gets the previous handler
                # (hard kill) — the escape hatch for a wedged drain.
                self._force_abort = True
                os.write(
                    2,
                    b"\ntpu_ddp: second signal - force-abort: skipping "
                    b"the final checkpoint, exiting at the next "
                    b"boundary (send again to kill outright)\n",
                )
                signal.signal(
                    signum, old_handlers.get(signum, signal.SIG_DFL))
                return
            self._preempted = True
            os.write(
                2,
                b"\ntpu_ddp: signal received - draining, will checkpoint "
                b"and exit (send again to force-abort without the final "
                b"checkpoint)\n",
            )

        try:
            for sig in (signal.SIGTERM, signal.SIGINT):
                old_handlers[sig] = signal.signal(sig, _on_signal)
        except ValueError:  # not the main thread (e.g. driven from a test)
            old_handlers = {}
        try:
            self._start_run_watchers()
            return self._run_loop(c, start)
        except Exception as e:
            # OOM forensics (docs/memory.md): an XLA allocation failure
            # at the step boundary writes a one-shot postmortem bundle
            # (last memory samples, config, run_meta) and an oom_abort
            # instant — the goodput ledger's `oom` exit evidence —
            # BEFORE re-raising. Any other exception passes untouched.
            self._handle_possible_oom(e)
            raise
        finally:
            for sig, handler in old_handlers.items():
                signal.signal(sig, handler)

    def _handle_possible_oom(self, exc: BaseException) -> None:
        """Classify + document an allocation-failure death; never raises
        (forensics must not mask the original exception)."""
        try:
            from tpu_ddp.memtrack.postmortem import (
                is_resource_exhausted,
                write_postmortem,
            )

            if not is_resource_exhausted(exc):
                return
            c = self.config
            step = int(getattr(self, "_last_host_step", 0) or 0)
            samples = []
            if self._memtrack is not None:
                try:
                    # one last reading at death: the state closest to
                    # the wall (live-array accounting still works even
                    # when the allocator is full — it only reads sizes)
                    self._memtrack.sample(step)
                except Exception:
                    pass
                samples = self._memtrack.recent()
            path = None
            if c.telemetry_dir:
                path = write_postmortem(
                    c.telemetry_dir,
                    step=step,
                    process_index=self.process_index,
                    incarnation=self.incarnation,
                    error=exc,
                    samples=samples,
                    config_snapshot=dataclasses.asdict(c),
                    run_meta=self.run_meta,
                )
            tel = self.telemetry
            if tel.enabled:
                tel.count("memory/oom_events")
                tel.instant("oom_abort", step=step,
                            bundle=path, error=str(exc)[:300])
            log.error(
                "allocation failure at step %d (%s); %s",
                step, type(exc).__name__,
                (f"postmortem bundle -> {path}" if path else
                 "no --telemetry-dir, postmortem bundle NOT written"),
            )
        except Exception:
            pass

    def _preempt_agreed(self) -> bool:
        """Cross-host agreement on the preemption flag, evaluated at a
        boundary every host reaches after the same number of steps (epoch
        end). Per-host flags can differ (signals land at different times,
        or only on the host the scheduler chose); breaking out unilaterally
        would leave the other hosts blocked in the next step's collectives.
        Single-host: the local flag is the agreement."""
        if self.process_count == 1:
            return self._preempted
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(
            np.asarray([self._preempted], dtype=np.int32)
        )
        return bool(np.asarray(flags).max())

    def _force_abort_agreed(self) -> bool:
        """Cross-host agreement on the second-signal force-abort flag:
        the final checkpoint save is a cross-process collective, so
        skipping it must be unanimous-on-any — one host skipping while
        the others save would wedge the pod in the save barrier."""
        if self.process_count == 1:
            return self._force_abort
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(
            np.asarray([self._force_abort], dtype=np.int32)
        )
        return bool(np.asarray(flags).max())

    def _dispatch_target(self, kind) -> tuple:
        """The callable one dispatch of ``_epoch_stream``'s ``kind`` goes to
        and the optimizer steps it makes. Read at each dispatch, as
        attributes: a caller may stand a probe in ``train_step`` after
        ``__init__``."""
        if kind == "stacked":
            return self.multi_step, self.steps_per_call
        return self.train_step, 1

    def _start_run_watchers(self) -> None:
        """What lives for one ``run``, not for the ``Trainer``: the hang
        watchdog (its first deadline window starts here), the monitor
        exporter and, with telemetry on, the step stamper.
        ``_release_workers`` stops all three."""
        c = self.config
        if self.telemetry.enabled:
            # the one place a traced run waits for a step: off the main
            # thread (telemetry/stamper.py), so the loop is the untraced
            # run's own
            from tpu_ddp.telemetry.stamper import StepStamper

            self._stamper = StepStamper(
                self.telemetry, jax.block_until_ready)
        if c.watchdog_deadline_seconds > 0:
            from tpu_ddp.telemetry import HangWatchdog

            on_hang = None
            if c.telemetry_dir:
                # hang forensics (docs/comms.md, docs/data.md): join the
                # stack dump with the last comms-health and data-health
                # records so the hang bundle NAMES the suspect collective
                # and/or the suspect loader stage — written before the
                # abort escalation, because after it there is no process
                # left to ask
                from tpu_ddp.comms.forensics import write_hang_bundle

                def on_hang(dump: str) -> None:
                    write_hang_bundle(c.telemetry_dir, dump_text=dump,
                                      process_index=self.process_index)

            self._watchdog = HangWatchdog(
                c.watchdog_deadline_seconds,
                heartbeat_dir=c.telemetry_dir,
                process_index=self.process_index,
                telemetry=self.telemetry,
                on_hang=on_hang,
                abort_on_hang=c.watchdog_abort,
            ).start()
            # first in the list: its beat comes before every other
            # watcher hears of the step. The dispatch is async, with
            # telemetry on or off: the beat means "the host is still
            # submitting work", which still catches wedged collectives
            # (the host blocks inside the NEXT dispatch when the device
            # queue jams, or in the epoch's one fetch)
            self._watchers.insert(0, self._watchdog)
        if c.monitor_port:
            # Per-host live scrape endpoint (docs/monitoring.md). A bind
            # failure (port taken) degrades to a warning: observability
            # must never take down the training it observes.
            from tpu_ddp.monitor.exporter import MonitorExporter

            try:
                self._exporter = MonitorExporter(
                    registry=self.telemetry.registry,
                    run_meta=self.run_meta,
                    port=c.monitor_port if c.monitor_port > 0 else 0,
                    host=c.monitor_bind,
                    process_index=self.process_index,
                    watchdog_provider=lambda: self._watchdog,
                    run_dir=c.telemetry_dir,
                    profile_trigger=(
                        self._capture.request
                        if self._capture is not None else None
                    ),
                    allow_remote_trigger=c.monitor_allow_remote_trigger,
                ).start()
                log.info(
                    "monitor exporter on port %d "
                    "(/metrics /snapshot.json /healthz)",
                    self._exporter.port,
                )
            except OSError as e:
                log.warning(
                    "monitor exporter failed to bind port %s: %s "
                    "(continuing without the live endpoint)",
                    c.monitor_port, e,
                )

    def _run_loop(self, c, start) -> dict:
        # Multi-host: this process only counts its LOCAL rows (the loader
        # yields the local slice), so rate against local chips; the per-chip
        # number — the headline metric — is then correct on any pod size,
        # and the aggregate is scaled back up below (symmetric hosts).
        n_local_chips = self.world_size // self.process_count
        tel = self.telemetry
        throughput = Throughput(n_chips=n_local_chips, registry=tel.registry)
        throughput.start()
        # Goodput accounting baseline: the registry is process-global
        # (histograms may carry a previous Trainer's sums in the same
        # process), so the live goodput gauges and the ledger's per-
        # incarnation counter deltas both measure AGAINST this snapshot.
        # The baseline record lands in the trace right after the header,
        # which is what lets `tpu-ddp goodput` attribute compile seconds
        # to the incarnation that actually paid them.
        reg = tel.registry
        self._goodput_baseline = {
            "wall": time.time(),
            "device": reg.histogram("phase/device_step").sum,
        }
        if tel.enabled:
            tel.emit_counters(name="counters_baseline")
        last_metrics = {}
        # Steady-state step time: measured per epoch between REAL sync points
        # (the device_get below), excluding the first epoch (XLA compile).
        # A per-step host-side timer would only measure async dispatch.
        steady_seconds = 0.0
        steady_steps = 0
        # (kind, device_batch) retained for the post-run MFU cost analysis;
        # holds one batch of HBM, never donated (only state is).
        mfu_probe = None
        start_epoch = int(self.state.step) // self.train_loader.steps_per_epoch
        # Mid-epoch resume (a preemption checkpoint lands wherever the
        # signal did): finish the partial epoch by SKIPPING its
        # already-trained leading batches — set_epoch's shuffle is
        # deterministic per (seed, epoch), so the skipped prefix is exactly
        # what the preempted run consumed. No data is double-counted and
        # the step counter stays aligned with epoch boundaries. (With
        # --steps-per-call fusion a group can straddle the boundary; we
        # undershoot and replay at most K-1 steps.)
        resume_skip = int(self.state.step) % self.train_loader.steps_per_epoch
        if resume_skip:
            self.logger.log_text(
                f"mid-epoch resume: skipping the first {resume_skip} "
                f"already-trained steps of epoch {start_epoch + 1}"
            )
        # Trace the FIRST STEADY-STATE epoch (epoch 2 of the run: epoch 1 is
        # XLA-compile-dominated); a 1-epoch run traces what it has.
        profile_epoch = (
            min(start_epoch + 2, c.epochs) if c.profile_dir else None
        )
        for epoch in range(start_epoch + 1, c.epochs + 1):
            self.train_loader.set_epoch(epoch)
            if epoch == profile_epoch:
                jax.profiler.start_trace(c.profile_dir)
            epoch_t0 = time.perf_counter()
            # Per-step losses stay ON DEVICE during the epoch: fetching them
            # eagerly (the reference's per-batch ``loss.item()``,
            # ``main.py:41``) would force a host sync every step and stall
            # the async dispatch pipeline (SURVEY.md §3.1). One device_get at
            # epoch end materializes them all.
            step_losses = []
            step_counters = []  # what the model counted, step by step
            epoch_metrics = None
            n_steps = 0
            # host-side global step mirror (one device sync per epoch),
            # kept for ALL consumers so watchdog heartbeats/hang logs and
            # health records carry the global step even with telemetry off
            track_step = bool(
                self._watchers
                or tel.enabled
                or self._health_monitor is not None
                or (self.checkpointer is not None
                    and c.checkpoint_steps > 0)
            )
            host_step = int(self.state.step) if track_step else 0
            tel.current_step = host_step
            skip = resume_skip if epoch == start_epoch + 1 else 0
            for kind, dev_batch, n_real in self._epoch_stream():
                # Drain at batch boundaries only when single-host: on a pod
                # the hosts must agree first (epoch boundary, below) or the
                # others would block in the next step's collectives.
                if self.process_count == 1 and self._preempted:
                    break
                step_fn, dn = self._dispatch_target(kind)
                if skip:
                    if skip >= dn:
                        skip -= dn
                        continue
                    skip = 0  # straddling fused group: replay its tail
                with tel.span("compiled_step",
                              **({"steps": dn} if dn > 1 else {})):
                    self.state, epoch_metrics = step_fn(self.state, dev_batch)
                step_losses.append(epoch_metrics["loss"])  # (K,) if fused
                step_counters.append(epoch_metrics.get("counters"))
                n_steps += dn
                if track_step:
                    host_step += dn
                    # the step the OOM forensics stamp on a postmortem
                    # bundle if this very dispatch exhausts HBM
                    self._last_host_step = host_step
                if tel.enabled:
                    # No fence, no device read: the loss (kept until the
                    # epoch's one device_get anyway) goes to the stamper's
                    # thread, which waits for it there and writes the
                    # step's completion as the span "device_step" under
                    # the id this iteration's other spans carry. The loop
                    # a traced run takes is the untraced run's. The clock
                    # is read first: this is the dispatch's return.
                    self._stamper.dispatched(
                        host_step - dn, dn, tel.clock.now(),
                        epoch_metrics["loss"])
                    if (self._program_map is not None
                            and not self._program_map.done):
                        # while the device runs the step just dispatched;
                        # over within the first dispatches of the run
                        self._program_map.after_dispatch(
                            kind, self.state, dev_batch)
                    tel.current_step = host_step
                    tel.count("train/steps", dn)
                    tel.count("train/images", n_real)
                    # Periodic counters snapshot: a killed/preempted run
                    # must leave a usable tail for the fleet aggregator
                    # and `trace summarize` — the epoch-boundary snapshot
                    # alone can be a whole epoch stale when the SIGKILL
                    # lands (docs/monitoring.md)
                    snap_every = c.telemetry_snapshot_steps
                    if snap_every and (host_step // snap_every) > (
                        (host_step - dn) // snap_every
                    ):
                        self._update_goodput_gauges(tel)
                        tel.emit_counters(name="counters_snapshot")
                for watcher in self._watchers:
                    watcher.on_step(host_step)
                if (self.checkpointer is not None and c.checkpoint_steps
                        and (host_step // c.checkpoint_steps)
                        > ((host_step - dn) // c.checkpoint_steps)):
                    # step-cadence save (--checkpoint-steps): the knob
                    # the goodput ledger's Young–Daly advisor recommends
                    # a value for. Async initiation, same as the epoch-
                    # boundary saves; a fused group checkpoints once at
                    # the boundary it crosses.
                    self.checkpointer.save(host_step, self._ckpt_state())
                if self._health_monitor is not None:
                    verdict = self._on_health(
                        host_step - dn, epoch_metrics.pop("health"),
                        dn, dev_batch,
                    )
                    if verdict == "halt":
                        # stats are replicated globals — every host reaches
                        # the same verdict at the same step, so breaking
                        # here cannot wedge a pod in mismatched collectives
                        self._health_halted = host_step
                        break
                if mfu_probe is None:
                    mfu_probe = (kind, dev_batch)
                throughput.add(n_real)
                if c.log_every_steps:
                    if (n_steps // c.log_every_steps) > (
                        (n_steps - dn) // c.log_every_steps
                    ):
                        # reference in-epoch line (ppe_main_ddp.py:151-152);
                        # fetching this loss is the line's one host sync
                        cur = float(
                            np.asarray(epoch_metrics["loss"]).reshape(-1)[-1]
                        )
                        self.logger.log_text(
                            f"Epoch {epoch}, iter {n_steps}, loss {cur:.4f}"
                        )
            with tel.span("epoch_metrics_fetch", epoch=epoch):
                # the model's counters come over with the losses: one fetch
                step_losses, step_counters = jax.device_get(
                    (step_losses, step_counters))
                mean_loss = (
                    float(
                        np.mean(
                            np.concatenate(
                                [np.atleast_1d(x) for x in step_losses]
                            )
                        )
                    )
                    if step_losses
                    else float("nan")
                )
            per_step = []  # {name: array} a step; a fused dispatch has K
            for counted, loss in zip(step_counters, step_losses):
                if not counted:
                    continue
                if np.ndim(loss):
                    per_step.extend({k: v[i] for k, v in counted.items()}
                                    for i in range(len(loss)))
                else:
                    per_step.append(counted)
            if per_step:
                last_metrics.update(tel.record_model_counters(per_step))
            trace_dump_seconds = 0.0
            if epoch == profile_epoch:
                # the device_get above already fenced the epoch's dispatches;
                # stopping here (before the preempt check) covers both the
                # normal path and a drain during the profiled epoch
                trace_t0 = time.perf_counter()
                jax.profiler.stop_trace()
                # the trace dump is host IO, not training — keep it out of
                # the steady-state throughput window below
                trace_dump_seconds = time.perf_counter() - trace_t0
                # satellite fix: the trace location goes through the
                # telemetry sinks (a machine-readable instant event); the
                # text line remains only as the no-telemetry fallback
                if tel.enabled:
                    tel.instant(
                        "profiler_trace_written",
                        path=os.path.abspath(c.profile_dir),
                        epoch=epoch,
                        dump_seconds=round(trace_dump_seconds, 3),
                    )
                else:
                    self.logger.log_text(f"profiler trace -> {c.profile_dir}")
            if self._preempt_agreed():
                self.logger.log_text(
                    f"preempted at step {int(self.state.step)} "
                    f"(epoch {epoch}): "
                    + ("saving final checkpoint"
                       if self.checkpointer else
                       "no --checkpoint-dir, progress will NOT survive")
                )
                last_metrics["preempted"] = True
                if tel.enabled:
                    # exit-classification evidence for the goodput
                    # ledger: a drained run's run_end alone would read
                    # as a clean finish, hiding the interruption MTBF
                    # is computed from
                    tel.instant("preempt_drain", step=host_step)
                break  # the tail below writes the final checkpoint
            if self._health_halted is not None:
                self.logger.log_text(
                    f"health anomaly at step {self._health_halted} with "
                    "policy 'halt': stopping training"
                    + (" (saving final checkpoint)" if self.checkpointer
                       else "")
                )
                last_metrics["health_halted"] = True
                if tel.enabled:
                    tel.instant("health_halt_drain",
                                step=self._health_halted)
                break  # same drain path as preemption
            if epoch > start_epoch + 1:  # device_get above = a sync boundary
                steady_seconds += (
                    time.perf_counter() - epoch_t0 - trace_dump_seconds
                )
                steady_steps += n_steps
            self.history["epoch"].append(epoch)
            self.history["train_loss"].append(mean_loss)
            if epoch == 1 or epoch % c.log_every_epochs == 0:
                # reference log line shape: main.py:43-44
                self.logger.log_text(
                    f"Epoch {epoch}, Training loss {mean_loss}"
                )
                extra = (
                    # last step's accuracy; a fused call yields (K,) of them
                    {
                        "train_accuracy": float(
                            np.asarray(epoch_metrics["accuracy"]).reshape(-1)[-1]
                        )
                    }
                    if "accuracy" in epoch_metrics
                    else {}
                )
                self.logger.log(
                    int(self.state.step),
                    epoch=epoch,
                    train_loss=mean_loss,
                    **extra,
                )
                if self.checkpointer and epoch % c.checkpoint_every_epochs in (0, 1):
                    self.checkpointer.save(
                        int(self.state.step), self._ckpt_state())
            if c.eval_each_epoch:
                with tel.span("eval", epoch=epoch):
                    acc, loss = self.evaluate()
                self.history.setdefault("test_loss", []).append(loss)
                if tel.enabled:
                    # last-write-wins gauges: the final counters snapshot
                    # then carries the end-of-run eval — the JSONL trace
                    # is a self-contained run record
                    tel.gauge("eval/test_loss").set(loss)
                    if c.loss == "ce":
                        tel.gauge("eval/test_accuracy").set(acc)
                    # ... and the durable HISTORY the gauges can't keep:
                    # one step/epoch-anchored eval instant per evaluation
                    # (incarnation-safe — the sink file is stamped — and
                    # replay-safe: readers key on epoch, later life wins).
                    # The convergence observatory reads these back
                    # (docs/curves.md)
                    from tpu_ddp.telemetry import EVAL_POINT_SCHEMA_VERSION

                    tel.instant(
                        "eval", step=int(self.state.step),
                        eval_schema_version=EVAL_POINT_SCHEMA_VERSION,
                        epoch=epoch, test_loss=loss,
                        **({"test_accuracy": acc} if c.loss == "ce"
                           else {}),
                    )
                if c.loss == "ce":  # accuracy undefined for multi-hot targets
                    self.logger.log(
                        int(self.state.step), test_accuracy=acc, test_loss=loss
                    )
                    self.history.setdefault("test_accuracy", []).append(acc)
                    last_metrics["test_accuracy"] = acc
                    if self.best_checkpointer and acc > self._best_acc:
                        self._best_acc = acc
                        step_now = int(self.state.step)
                        # save_as_only: resume replay can produce a new
                        # best at an existing or OLDER step number
                        self.best_checkpointer.save_as_only(
                            step_now, self._ckpt_state())
                        from tpu_ddp.parallel.runtime import (
                            is_primary_process,
                        )

                        if is_primary_process():
                            # atomic: a preemption mid-write must not
                            # leave a truncated file for the next
                            # --resume --keep-best run to choke on
                            meta = os.path.join(
                                c.checkpoint_dir, "best", "metadata.json")
                            tmp = f"{meta}.tmp.{os.getpid()}"
                            with open(tmp, "w") as f:
                                json.dump({"step": step_now,
                                           "test_accuracy": acc}, f)
                            os.replace(tmp, meta)
                else:
                    self.logger.log(int(self.state.step), test_loss=loss)
            if tel.enabled:
                # epoch boundary: refresh derived gauges and snapshot the
                # registry into the sinks (Chrome "C" series + JSONL record)
                from tpu_ddp.metrics.memory import record_memory_gauges

                epoch_seconds = time.perf_counter() - epoch_t0
                if epoch_seconds > 0 and n_steps:
                    tel.gauge("train/steps_per_sec").set(
                        n_steps / epoch_seconds
                    )
                    tel.gauge("train/images_per_sec_per_chip").set(
                        throughput.images_per_sec_per_chip
                    )
                if self._comm_bytes_per_step is not None and n_steps:
                    # --grad-compress wire accounting (static per step,
                    # parallel/compression.py): what the grad collective
                    # moved vs what the f32 ring would have — `tpu-ddp
                    # trace summarize` derives the effective ratio
                    wire, base = self._comm_bytes_per_step
                    tel.count("comm/grad_bytes_on_wire", n_steps * wire)
                    tel.count("comm/grad_bytes_uncompressed",
                              n_steps * base)
                record_memory_gauges(tel.registry)
                self._update_goodput_gauges(tel)
                tel.emit_counters()
        throughput.stop(wait_for=self.state.params)
        total = time.time() - start
        # reference wall-clock line: main.py:49
        self.logger.log_text(f"training time: {total:.3f} seconds")
        save_final = self.checkpointer is not None
        if save_final and self._force_abort_agreed():
            # second-SIGTERM escalation: the operator (or the job
            # system's kill sequence) wants OUT — skip the final save
            # rather than risk dying inside it; the last cadence/epoch
            # checkpoint remains the verified resume point
            save_final = False
            prev = self.checkpointer.latest_step()
            self.logger.log_text(
                "force-abort: skipping the final checkpoint ("
                + (f"latest checkpoint remains step {prev}"
                   if prev is not None else "no checkpoint exists")
                + ")"
            )
            if tel.enabled:
                tel.instant("force_abort_drain",
                            step=int(self.state.step))
        if save_final and self._health_halted is not None:
            # A halt on a NON-FINITE anomaly means the poisoned update was
            # applied (halt compiles no skip guard): checkpointing that
            # state would make NaN params the newest checkpoint --resume
            # restores. Keep the last good periodic checkpoint as latest
            # instead. A finite halt state (loss spike) is still saved.
            finite = all(
                bool(np.isfinite(leaf).all())
                for leaf in jax.tree.leaves(
                    jax.device_get(self.state.params))
            )
            if not finite:
                save_final = False
                prev = self.checkpointer.latest_step()
                self.logger.log_text(
                    "health halt: final params are non-finite; NOT "
                    "checkpointing them ("
                    + (f"latest good checkpoint remains step {prev}"
                       if prev is not None else "no checkpoint exists")
                    + ")"
                )
        if save_final:
            self.checkpointer.save(
                int(self.state.step), self._ckpt_state(), wait=True)
        if self.best_checkpointer:
            self.best_checkpointer.wait_until_finished()
        from tpu_ddp.parallel.runtime import is_primary_process

        if c.plot_curves and is_primary_process():
            from tpu_ddp.metrics.plotting import plot_loss_curves

            series = {"train_loss": self.history["train_loss"]}
            if self.history.get("test_loss"):
                series["test_loss"] = self.history["test_loss"]
            plot_loss_curves(series, c.plot_curves)
            self.logger.log_text(f"loss curves -> {c.plot_curves}")
        last_metrics.update(
            total_seconds=total,
            mean_step_seconds=(
                steady_seconds / steady_steps if steady_steps else float("nan")
            ),
            images_per_sec=throughput.images_per_sec * self.process_count,
            images_per_sec_per_chip=throughput.images_per_sec_per_chip,
            mfu=self._compute_mfu(mfu_probe, steady_steps, steady_seconds),
        )
        if tel.enabled:
            from tpu_ddp.metrics.mfu import record_mfu

            tel.gauge("train/images_per_sec_per_chip").set(
                throughput.images_per_sec_per_chip
            )
            record_mfu(tel.registry, last_metrics.get("mfu"))
            # final snapshot lands via tel.close() in Trainer.close()
        return last_metrics

    def _update_goodput_gauges(self, tel) -> None:
        """Live goodput gauges for /metrics and the watch dashboard:
        the fraction of THIS incarnation's wall-clock in which the device
        had a step to run: the sum of the ``device_step`` spans the step
        stamper has written so far (telemetry/stamper.py). They start at
        a dispatch's return, so the compile inside the first
        ``compiled_step`` spans is outside them and nothing is taken off
        for it; steps still in flight are not in the sum yet.
        Measured as a delta against the run-start baseline so a process-
        global registry (tests, multiple Trainers per process) can't
        leak another run's sums in. The post-hoc cross-incarnation
        truth is `tpu-ddp goodput` (docs/goodput.md); these gauges are
        its live, single-life approximation."""
        base = getattr(self, "_goodput_baseline", None)
        if base is None:
            return
        reg = tel.registry
        elapsed = time.time() - base["wall"]
        if elapsed <= 0:
            return
        productive = reg.histogram("phase/device_step").sum - base["device"]
        productive = min(max(productive, 0.0), elapsed)
        tel.gauge("goodput/fraction").set(productive / elapsed)
        tel.gauge("goodput/productive_seconds").set(productive)
        tel.gauge("goodput/elapsed_seconds").set(elapsed)

    def _on_health(self, step_base, health_out, K, dev_batch) -> str:
        """Feed one dispatch's in-graph health stats to the monitor: ONE
        device_get for the scalar subtree (a fused group of ``K`` > 1
        steps carries (K,) leaves, unstacked here into K per-step
        records), the batch fetched lazily only if an anomaly dump fires.
        Returns the strongest policy verdict across the group's steps."""
        per_layer = health_out.pop("per_layer", None)
        host = jax.device_get(health_out)
        if per_layer is not None:
            # the per-layer tree (2 scalars per param leaf) is only
            # consumed on stride steps or when a sentinel tripped — keep
            # the healthy-path fetch to the handful of scalars above
            stride = self._health_monitor.per_layer_stride
            want = not bool(np.asarray(host["all_finite"]).all()) or (
                stride and any(
                    (step_base + j) % stride == 0 for j in range(K))
            )
            if want:
                host["per_layer"] = jax.device_get(per_layer)
        verdict = "ok"
        for j in range(K):
            stats = (
                jax.tree.map(lambda x: x[j] if np.ndim(x) else x, host)
                if K > 1 else host
            )

            def batch_provider(j=j):
                if self._multihost:
                    # the global batch is not host-addressable; the dump
                    # carries stats + history only (per-host batches could
                    # be reassembled from the loaders if ever needed)
                    return None
                b = jax.device_get(dev_batch)
                if K > 1:
                    b = {k: v[j] for k, v in b.items()}
                return b

            v = self._health_monitor.on_step(
                step_base + j, stats, batch_provider=batch_provider
            )
            if v == "halt":
                verdict = "halt"
        return verdict

    def _compute_mfu(self, mfu_probe, steady_steps, steady_seconds):
        """Model FLOPs Utilization of the steady-state epochs, or None.

        Gated on a known TPU peak BEFORE the cost analysis: the analysis
        costs one extra AOT compile, pointless on backends (CPU tests)
        where no peak figure exists anyway. cost_analysis flops are PER
        DEVICE (see metrics/mfu.py), so dividing by the per-chip peak gives
        per-chip MFU directly — every chip runs the same partitioned
        program concurrently."""
        from tpu_ddp.metrics.mfu import compiled_flops, peak_flops_per_chip

        if (
            mfu_probe is None
            or not steady_steps
            or steady_seconds <= 0
            or peak_flops_per_chip() is None
        ):
            return None
        kind, dev_batch = mfu_probe
        step_fn, steps_per_exec = self._dispatch_target(kind)
        flops = compiled_flops(step_fn, self.state, dev_batch)
        if flops is None:
            return None
        achieved = (flops / steps_per_exec) * (steady_steps / steady_seconds)
        return achieved / peak_flops_per_chip()

    def _ckpt_state(self):
        """The state a checkpoint should persist: under --zero1 the
        scattered optimizer state is de-sharded back to the ORIGINAL optax
        layout, and the error-feedback residual is de-flattened to param
        layout (its per-device row-sum — the device-count-independent
        quantity), so every checkpoint on disk has ONE format and
        --resume composes with --zero1/--grad-compress in either
        direction AND across a device-count change (restore re-scatters;
        see __init__ and docs/resilience.md)."""
        state = self.state
        if self._zero1 is not None:
            state = self._zero1.deshard_state(state)
        if self._compress is not None and state.grad_residual is not None:
            state = state.replace(
                grad_residual=self._compress.deshard_residual(
                    state.grad_residual))
        return state

    def _eval_source_state(self):
        """The state eval/predict should read weights from: the EMA shadow
        when --ema-decay is on (the averaged weights are the ones an EMA
        recipe deploys), re-laid-out by the strategy hook if one exists
        (pp restacks params stage-major) — EMA swap happens FIRST so the
        hook sees a params tree in its expected training layout.

        Under --zero1 the EMA shadow lives as flat update-space shards
        inside the scattered opt state — de-flatten it back to the param
        layout (one all-gather, eval cadence); the opt state itself is
        dropped from the eval input (the eval step reads only
        params/batch_stats, and its replicated in_specs must not force a
        pointless gather of the shards). Under --zero3 the live params
        are flat shards too and get the same de-flatten."""
        s = self.state
        if s.grad_residual is not None:
            # the eval/predict steps read only params/batch_stats, and
            # their replicated in_specs must not force a re-layout of the
            # P(data)-scattered error-feedback residual
            s = s.replace(grad_residual=None)
        swapped = False
        if self.config.ema_decay:
            from tpu_ddp.train.optim import find_ema

            ema = find_ema(s.opt_state)
            if ema is not None:
                if self._zero1 is not None:
                    ema = self._zero1.deshard_params(ema)
                s = s.replace(params=ema)
                swapped = True
        if self._zero1 is not None:
            if getattr(self._zero1, "scattered_params", False) and not swapped:
                # --zero3: the training params are flat 1/N shards; the
                # eval step wants the original layout — one gather at
                # eval cadence, same price zero1 pays every step
                s = s.replace(params=self._zero1.deshard_params(s.params))
            s = s.replace(opt_state={})
        return self._prepare_eval(s) if self._prepare_eval else s

    def evaluate(self) -> tuple:
        """Test-set accuracy/loss — the eval loop the reference never had.

        Per-batch outputs stay ON DEVICE until the end: a ``float()`` per
        batch would force a host sync every dispatch and serialize the eval
        pipeline, exactly the stall the train loop avoids with its single
        epoch-end device_get."""
        eval_state = self._eval_source_state()
        outs = [
            self.eval_step(eval_state, self._put(batch))
            for batch in self.test_loader.epoch_batches(epoch=0)
        ]
        outs = jax.device_get(outs)  # ONE sync for the whole eval pass
        correct = sum(float(o["correct"]) for o in outs)
        count = sum(float(o["count"]) for o in outs)
        loss_sum = sum(float(o["loss_sum"]) for o in outs)
        return correct / max(count, 1.0), loss_sum / max(count, 1.0)

    def predict(self, loader=None):
        """Batch inference over a loader: (logits, labels) as host numpy
        arrays with sampler/batch padding removed — the reference's
        inference + prediction-dump capability (ppe_main_ddp.py:310-396).

        Multi-host: each process returns the rows of ITS device block (the
        loader yields local batches, and only this host's output shards are
        addressable); concatenating every host's return in process order
        gives the full set."""
        import numpy as np

        from tpu_ddp.train.steps import make_predict_step

        if self.predict_step is None:
            self.predict_step = make_predict_step(
                self.model, self.mesh, task=self.task)
        loader = loader if loader is not None else self.test_loader
        pred_state = self._eval_source_state()
        logits_all, labels_all = [], []
        for batch in loader.epoch_batches(epoch=0):
            out = self.predict_step(pred_state, self._put(batch))
            if self._multihost:
                # global (P('data')) output: fetch this host's contiguous
                # row block from its addressable shards, in row order
                shards = sorted(
                    out.addressable_shards, key=lambda s: s.index[0].start
                )
                logits = np.concatenate([np.asarray(s.data) for s in shards])
            else:
                logits = np.asarray(out)
            mask = batch["mask"]
            logits_all.append(logits[mask])
            labels_all.append(np.asarray(batch[self.task.target_key])[mask])
        return np.concatenate(logits_all), np.concatenate(labels_all)
