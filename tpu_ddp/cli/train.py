"""``python -m tpu_ddp.cli.train`` — the framework's training CLI.

Flag surface = union of the reference's hardcoded constants (``main.py:19,
23,27,30,61``) and the vestigial script's argparse options
(``ppe_main_ddp.py:28-37``), per SURVEY.md §5.6.
"""

from __future__ import annotations

import argparse
import json

from tpu_ddp.parallel.runtime import (
    enable_compile_cache,
    initialize_distributed,
)
from tpu_ddp.train.strategy import parse_mesh_arg
from tpu_ddp.train.trainer import TrainConfig, Trainer


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="tpu_ddp trainer")
    p.add_argument("--device", choices=["cpu", "tpu", "auto"], default="auto",
                   help="cpu forces the XLA CPU backend; tpu/auto use the "
                        "platform JAX selected (BASELINE.json north star flag)")
    p.add_argument("--data-dir", default="data/CIFAR-10")
    p.add_argument("--download", action="store_true",
                   help="fetch + md5-verify the canonical dataset tarball "
                        "into --data-dir when absent (the reference's "
                        "datasets.CIFAR10 download=True convenience)")
    p.add_argument("--dataset", choices=["cifar10", "cifar100"], default="cifar10",
                   help="cifar100 = BASELINE.json configs[2] scale-out recipe "
                        "(set --num-classes 100)")
    p.add_argument("--synthetic-data", action="store_true",
                   help="class-conditional synthetic CIFAR (no dataset needed)")
    p.add_argument("--epochs", type=int, default=99)
    p.add_argument("--batch-size", type=int, default=32,
                   help="PER-SHARD batch (reference semantics, main.py:61); "
                        "global batch = this * n_devices")
    p.add_argument("--global-batch-size", type=int, default=None,
                   help="fix the GLOBAL batch instead (sane mode; divided "
                        "across devices)")
    p.add_argument("--lr", type=float, default=1e-2)
    p.add_argument("--optimizer", choices=["sgd", "adamw", "lamb"],
                   default="sgd",
                   help="sgd = the reference family (main.py:27); adamw = "
                        "the ViT-family recipe; lamb = layer-wise-adaptive "
                        "large-global-batch training")
    p.add_argument("--momentum", type=float, default=0.0)
    p.add_argument("--weight-decay", type=float, default=0.0)
    p.add_argument("--schedule", choices=["constant", "cosine"], default="constant")
    p.add_argument("--warmup-steps", type=int, default=0)
    p.add_argument("--grad-clip-norm", type=float, default=0.0,
                   help="clip the global gradient norm before the update "
                        "(0 = off); on DP the clip sees the synchronized "
                        "gradient, so replicas clip identically")
    p.add_argument("--mixup-alpha", type=float, default=0.0,
                   help="on-device mixup: one Beta(alpha,alpha) lambda per "
                        "shard step blends images and the CE loss "
                        "(0 = off, typical 0.2); composes with --augment")
    p.add_argument("--ema-decay", type=float, default=0.0,
                   help="maintain an exponential moving average of the "
                        "params (0 = off, typical 0.999); eval and "
                        "predict use the averaged weights, and the EMA "
                        "checkpoints/resumes inside the optimizer state")
    p.add_argument("--n-devices", type=int, default=None,
                   help="1 == the main_no_ddp.py single-device baseline")
    p.add_argument("--parallelism",
                   choices=["dp", "fsdp", "tp", "fsdp_tp", "pp", "sp", "ep"],
                   default=None,
                   help="scale-out strategy: dp (default), fsdp (ZeRO-3 "
                        "sharded state), tp (Megatron tensor parallel), "
                        "fsdp_tp (2-D: TP over model + ZeRO-3 over data), "
                        "pp (GPipe pipeline), sp (sequence parallel + ring "
                        "attention), ep (expert parallel MoE). Default: "
                        "inferred from --mesh, else dp")
    p.add_argument("--zero1", action="store_true",
                   help="ZeRO-1 weight-update sharding (dp/sp): reduce-"
                        "scatter gradients instead of all-reducing them, "
                        "apply the optimizer to only this replica's 1/N "
                        "shard of params + optimizer state (the state "
                        "lives scattered — ~1/N the optimizer HBM and "
                        "update FLOPs), then all-gather the updated "
                        "params. Identical training math; checkpoints "
                        "stay in the replicated layout so --resume "
                        "composes in either direction")
    p.add_argument("--zero3", action="store_true",
                   help="ZeRO-3 parameter streaming (dp): params live "
                        "permanently scattered in the same flat update "
                        "space as --zero1's optimizer state (1/N param + "
                        "1/N optimizer HBM per chip); the forward "
                        "all-gathers them block by block with the next "
                        "block's gather prefetched under the current "
                        "block's compute, and the backward reduce-"
                        "scatters grads straight into shard space — no "
                        "full-param re-gather. Same training math; "
                        "checkpoints stay in the replicated layout so "
                        "--resume composes across zero3/zero1/replicated "
                        "and device counts")
    p.add_argument("--grad-compress", choices=["none", "bf16", "int8"],
                   default="none",
                   help="quantize the gradient sync's wire payloads "
                        "(dp/sp): the pmean/reduce-scatter becomes a "
                        "ppermute ring whose hops carry block-scaled "
                        "int8 (~4x fewer bytes) or bf16 (2x) while "
                        "accumulation stays f32 on-device. Composes "
                        "with --zero1 (the compressed ring replaces its "
                        "grad reduce-scatter)")
    p.add_argument("--grad-compress-block", type=int, default=256,
                   metavar="N",
                   help="int8 mode: elements sharing one f32 max-abs "
                        "scale (smaller = tighter error, more scale "
                        "bytes on the wire)")
    p.add_argument("--grad-compress-error-feedback", action="store_true",
                   help="carry each replica's quantization error and add "
                        "it back into the next step's gradient (the "
                        "residual rides the TrainState, is checkpointed, "
                        "and keeps long-run convergence unbiased)")
    p.add_argument("--kernels", action="store_true",
                   help="route the DP-family optimizer-update tail and "
                        "the int8 ring's quantize/dequantize through "
                        "the fused Pallas kernels (ops/, "
                        "docs/kernels.md): bit-identical math, one HBM "
                        "pass instead of the materialized XLA chain. "
                        "Fails closed per kernel on backends without "
                        "Pallas support (lint KRN001 reports)")
    p.add_argument("--mesh", default=None, metavar="AXES",
                   help="device mesh axis sizes, e.g. data=2,model=4 "
                        "(axes: data, pipeline, expert, sequence, model; "
                        "-1 = rest). Naming a non-data axis infers the "
                        "matching --parallelism")
    p.add_argument("--microbatches", type=int, default=4,
                   help="pipeline microbatches per step (pp only); more "
                        "microbatches = smaller bubble, and under "
                        "--pp-schedule 1f1b activation memory stays O(S) "
                        "regardless")
    p.add_argument("--pp-schedule", choices=["gpipe", "1f1b"],
                   default="gpipe",
                   help="pipeline schedule (pp only): gpipe = autodiff "
                        "backward, O(M) stored activations; 1f1b = "
                        "interleaved manual backward with per-stage "
                        "recompute, O(S) in-flight activations")
    p.add_argument("--aux-weight", type=float, default=0.01,
                   help="MoE load-balance loss weight (MoE models only)")
    p.add_argument("--model", default="netresdeep")
    p.add_argument("--model-overrides", type=json.loads, default=None,
                   metavar="JSON",
                   help="keyword arguments of the model's registry factory "
                        "beyond a classifier's, as a JSON object: one "
                        "chip's share of a decoder, e.g. laguna_xs2: "
                        '{"num_layers": 5, "experts_held": 32, '
                        '"expert_offset": 0, "vocab_rows": 12544}')
    p.add_argument("--attention", choices=["full", "flash"], default="full",
                   help="flash = the Pallas blockwise online-softmax kernel "
                        "(forward AND backward in-kernel), ViT-family "
                        "models; sp mode uses ring attention regardless")
    p.add_argument("--n-chans1", type=int, default=32,
                   help="NetResDeep width — the reference's n_chans1 ctor "
                        "arg (model/resnet.py:5)")
    p.add_argument("--n-blocks", type=int, default=10,
                   help="NetResDeep depth — the reference's n_blocks ctor arg")
    p.add_argument("--untied-blocks", action="store_true",
                   help="independent ResBlocks (the reference's list-repeat "
                        "quirk ties them; see SURVEY.md §2.2)")
    p.add_argument("--num-classes", type=int, default=None,
                   help="default: derived from --dataset (cifar10=10, "
                        "cifar100=100)")
    p.add_argument("--sync-bn", action="store_true")
    p.add_argument("--sp-flash", action="store_true",
                   help="sequence-parallel runs with Pallas flash-kernel "
                        "ring-attention blocks (long-context config; "
                        "falls back to the fused-jnp tile off-TPU)")
    p.add_argument("--compute-dtype", choices=["float32", "bfloat16"],
                   default="float32",
                   help="bfloat16 runs the forward/backward on the MXU at "
                        "2x throughput; params/loss stay f32")
    p.add_argument("--remat", action="store_true",
                   help="rematerialize the forward in backward: fits "
                        "deeper models in HBM (per-block for the ViT/MoE "
                        "families; composes with dp/fsdp/tp/fsdp_tp/ep)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--augment", action="store_true",
                   help="on-device random crop+flip (the reference has no "
                        "augmentation; needed for the 93%% target, "
                        "SURVEY.md §7.3)")
    p.add_argument("--no-shuffle", action="store_true")
    p.add_argument("--faithful-epoch-order", action="store_true",
                   help="reproduce the missing set_epoch(): same order every epoch")
    p.add_argument("--eval-each-epoch", action="store_true")
    p.add_argument("--log-every-epochs", type=int, default=10)
    p.add_argument("--log-every-steps", type=int, default=None,
                   help="also log an in-epoch progress line every N steps "
                        "(the reference's per-100-iter print, "
                        "ppe_main_ddp.py:151-152); each line costs one "
                        "host sync")
    p.add_argument("--cv-mode", type=int, default=None, metavar="K",
                   help="k-fold cross-validation over the train split "
                        "(the reference's -cv_mode, ppe_main_ddp.py:28-37,"
                        "91-93): trains K models, reports per-fold and "
                        "mean val accuracy; checkpointing disabled per fold")
    p.add_argument("--viz-predictions", default=None, metavar="DIR",
                   help="write predictions.png (pred-vs-true image grid) + "
                        "confusion_matrix.png after the final eval — the "
                        "classification analogue of the reference's "
                        "prediction drawing (ppe_main_ddp.py:355-396)")
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every-epochs", type=int, default=10)
    p.add_argument("--checkpoint-steps", type=int, default=0, metavar="N",
                   help=">0: ALSO save a checkpoint every N global steps "
                        "(mid-epoch, async) — the cadence knob the "
                        "goodput ledger's Young–Daly advisor recommends "
                        "a value for from measured checkpoint cost and "
                        "MTBF (`tpu-ddp goodput`, docs/goodput.md)")
    p.add_argument("--resume", action="store_true")
    p.add_argument("--eval-only", action="store_true",
                   help="skip training: restore (--resume from "
                        "--checkpoint-dir, or --pretrained-dir) and run "
                        "the test-set eval / prediction outputs — the "
                        "load-and-infer workflow of ppe_main_ddp.py:310-396")
    p.add_argument("--keep-best", action="store_true",
                   help="also retain the best-test-accuracy checkpoint "
                        "under <checkpoint-dir>/best (needs "
                        "--eval-each-epoch; best step + accuracy recorded "
                        "in best/metadata.json)")
    p.add_argument("--jsonl", default=None, help="metrics JSONL path")
    p.add_argument("--tensorboard-dir", default=None,
                   help="write TensorBoard scalar events here "
                        "(process-0 only), alongside --jsonl")
    p.add_argument("--profile-dir", default=None,
                   help="emit an XLA/TPU profiler trace (TensorBoard/"
                        "Perfetto) for one steady-state epoch")
    p.add_argument("--profile-steps", default=None, metavar="A:B",
                   help="arm an anomaly-profiler capture window over "
                        "global steps (A, B]: host stack sampling + "
                        "device trace + measured phases, bundled under "
                        "<telemetry-dir>/profiles/ and read back with "
                        "`tpu-ddp profile` (docs/profiling.md). Windows "
                        "can also be armed on a LIVE run: POST "
                        "/profile?steps=N to --monitor-port, or the "
                        "capture_profile alert action on `tpu-ddp watch`")
    p.add_argument("--profile-window-steps", type=int, default=8,
                   metavar="N",
                   help="window length (steps) for live-triggered "
                        "captures (POST /profile or alert-armed)")
    p.add_argument("--profile-host-hz", type=float, default=97.0,
                   metavar="HZ",
                   help="host stack sampler rate inside a capture window")
    p.add_argument("--telemetry-dir", default=None, metavar="DIR",
                   help="enable structured telemetry into this run dir: "
                        "per-host schema-versioned JSONL trace + Chrome "
                        "trace_event JSON (Perfetto-loadable) + terminal "
                        "phase summary; read back with `tpu-ddp trace "
                        "summarize DIR`. Adds a per-step device fence "
                        "for phase attribution")
    p.add_argument("--telemetry-sinks", default="jsonl,chrome,summary",
                   metavar="LIST",
                   help="comma-separated subset of jsonl,chrome,summary")
    p.add_argument("--telemetry-snapshot-steps", type=int, default=50,
                   metavar="N",
                   help="flush a counters snapshot into the JSONL trace "
                        "every N steps so a killed/preempted run leaves "
                        "a usable tail for `tpu-ddp watch` and `trace "
                        "summarize` (0 disables; epoch-end and final "
                        "snapshots always happen)")
    p.add_argument("--monitor-port", type=int, default=0, metavar="PORT",
                   help="per-host live monitor HTTP endpoint: /metrics "
                        "(OpenMetrics, labeled with run id/strategy/"
                        "mesh/host), /snapshot.json, /healthz (watchdog "
                        "heartbeat freshness). 0 = disabled, -1 = "
                        "ephemeral port (recorded in exporter-p<i>.json "
                        "under --telemetry-dir). See docs/monitoring.md "
                        "and `tpu-ddp watch`")
    p.add_argument("--monitor-bind", default="0.0.0.0", metavar="ADDR",
                   help="monitor endpoint bind address. The endpoint is "
                        "UNauthenticated and /snapshot.json serves the "
                        "run config — bind 127.0.0.1 (and scrape via a "
                        "tunnel) on untrusted networks")
    p.add_argument("--monitor-allow-remote-trigger", action="store_true",
                   help="accept POST /profile from non-loopback peers "
                        "(default: loopback-only — the endpoint is "
                        "unauthenticated, and this route mutates run "
                        "behavior; see docs/monitoring.md's security "
                        "note before opening it up)")
    p.add_argument("--watchdog-deadline", type=float, default=0.0,
                   metavar="SECONDS",
                   help=">0: hang watchdog — every host writes a "
                        "heartbeat file (under --telemetry-dir) per step "
                        "and dumps all thread stacks when no step "
                        "completes within the deadline (multihost wedge "
                        "forensics)")
    p.add_argument("--watchdog-abort", action="store_true",
                   help="escalate a watchdog firing: after the stack "
                        "dump, exit the wedged process with the `hang` "
                        "class so a supervisor (`tpu-ddp elastic`) can "
                        "restart it — without this the dump is forensics "
                        "only and the wedge burns chips forever "
                        "(docs/resilience.md)")
    p.add_argument("--chaos", default=None, metavar="SPEC.JSON",
                   help="deterministic fault injection: step-triggered "
                        "kill-host / hang / checkpoint-corrupt / "
                        "save-io-flake / data-stall faults on configured "
                        "hosts, seeded and fire-once per logical run "
                        "(state in --telemetry-dir) — the elastic "
                        "runtime's CI harness (docs/resilience.md)")
    p.add_argument("--comms-monitor", action="store_true",
                   help="instrument the quantized ring collectives with "
                        "a per-hop host callback: live per-axis achieved "
                        "bandwidth + the in-flight collective land in "
                        "comms-health-p<host>.json (under "
                        "--telemetry-dir), and a watchdog hang writes a "
                        "forensics bundle naming the suspect collective "
                        "(docs/comms.md). Changes the traced program, so "
                        "it refuses --lint-on-start")
    p.add_argument("--health", choices=["off", "on"], default="off",
                   help="numerics flight recorder: global grad/param/"
                        "update norms + NaN/Inf sentinels computed INSIDE "
                        "the compiled step every step, recorded to "
                        "health-p<host>.jsonl (under --health-dir / "
                        "--telemetry-dir), with a loss-spike detector and "
                        "a one-shot anomaly dump to <dir>/anomalies/. "
                        "Read back with `tpu-ddp health DIR`")
    p.add_argument("--health-policy",
                   choices=["warn", "skip_step", "halt"], default="warn",
                   help="on an anomaly: warn (log + dump), skip_step "
                        "(an in-graph guard discards NaN/Inf updates — "
                        "optimizer state stays in sync, training "
                        "continues; loss spikes are recorded but still "
                        "applied), halt (drain + final checkpoint on any "
                        "anomaly)")
    p.add_argument("--health-per-layer-stride", type=int, default=0,
                   metavar="N",
                   help=">0: also compute the per-layer grad/param norm "
                        "breakdown in-graph, recording it every N steps "
                        "(and always into anomaly dumps)")
    p.add_argument("--health-dir", default=None, metavar="DIR",
                   help="where health records + anomaly dumps go "
                        "(default: --telemetry-dir)")
    p.add_argument("--health-window", type=int, default=128,
                   help="loss-spike detector rolling window (steps)")
    p.add_argument("--health-spike-threshold", type=float, default=10.0,
                   metavar="K",
                   help="spike when loss > median + K * MAD of the window")
    p.add_argument("--lint-on-start", action="store_true",
                   help="preflight: run the static graph lint (donation / "
                        "dtype / sharding / collective-order / host-"
                        "transfer rules, docs/lint.md) over the compiled "
                        "step and refuse to launch on a finding")
    p.add_argument("--freeze", nargs="*", default=None, metavar="PREFIX",
                   help="train ONLY params whose top module starts with one "
                        "of these prefixes (working version of "
                        "ppe_main_ddp.py:116-122)")
    p.add_argument("--label-smoothing", type=float, default=0.0,
                   help="soft CE targets (0.1 typical); recipe knob for "
                        "the 93%% accuracy target")
    p.add_argument("--loss", choices=["ce", "bce"], default="ce",
                   help="bce = multi-label (the PPE fine-tune workload, "
                        "ppe_main_ddp.py:147)")
    p.add_argument("--pretrained-dir", default=None,
                   help="fine-tune: partial restore + head swap from this "
                        "checkpoint dir (strict=False semantics)")
    p.add_argument("--plot-curves", default=None, metavar="PNG",
                   help="write loss-curve PNG at end (ppe_main_ddp.py:176-181)")
    p.add_argument("--dump-predictions", default=None, metavar="JSON",
                   help="batch-infer the test set and dump predictions "
                        "(ppe_main_ddp.py:310-396)")
    p.add_argument("--synthetic-size", type=int, default=2048)
    p.add_argument("--synthetic-task", choices=["easy", "hard"],
                   default="easy",
                   help="easy: color blobs (saturates at 1.0); hard: "
                        "shift-invariant zero-mean textures + train-label "
                        "noise (bounded ceiling — recipe quality visible)")
    p.add_argument("--synthetic-label-noise", type=float, default=0.1,
                   help="hard task: fraction of TRAIN labels flipped to "
                        "uniform-random classes")
    p.add_argument("--steps-per-call", type=int, default=1,
                   help=">1 fuses K optimizer steps into one dispatch "
                        "(lax.scan) — amortizes host overhead on small "
                        "models; semantics unchanged")
    p.add_argument("--grad-accum-steps", type=int, default=1,
                   help=">1 splits each optimizer step into K sequential "
                        "microbatches (gradient accumulation): same "
                        "semantics, ~1/K activation memory — the big-"
                        "global-batch knob (composes with "
                        "dp/fsdp/tp/fsdp_tp/ep)")
    p.add_argument("--prefetch-depth", type=int, default=2,
                   help="batches assembled ahead on the native host "
                        "prefetcher (C++ ring buffer; 0 disables)")
    p.add_argument("--prefetch-batches", type=int, default=0,
                   help="batches buffered ahead by the STAGED background "
                        "prefetcher (docs/data.md): per-stage data/* "
                        "spans + queue-depth gauges, bit-identical "
                        "batch stream; takes precedence over "
                        "--prefetch-depth (0 = off)")
    p.add_argument("--no-data-digests", dest="data_digests",
                   action="store_false", default=True,
                   help="skip the per-step batch-content digest sink "
                        "(data-p<i>.jsonl) that `tpu-ddp data audit` "
                        "verifies across restarts")
    return p


def config_from_args(args) -> TrainConfig:
    import jax

    if args.device == "cpu":
        jax.config.update("jax_platforms", "cpu")
    elif args.device == "tpu":
        # Demand a TPU — fail loudly instead of silently training on
        # whatever platform JAX picked (the north-star command must be
        # unambiguous).
        from tpu_ddp.parallel.runtime import is_tpu_device

        if not is_tpu_device():
            raise SystemExit(
                f"--device tpu: default platform is "
                f"{jax.devices()[0].platform!r}, not a TPU. Check the TPU "
                "runtime, or pass --device cpu/auto."
            )
    # HERE as well as at Trainer construction: the cache config must
    # precede the first compile, whatever traces between the two
    enable_compile_cache()
    n_devices = args.n_devices
    per_shard = args.batch_size
    mesh_sizes = None if args.mesh is None else parse_mesh_arg(args.mesh)
    if args.global_batch_size:
        # The batch shards over the DATA axis only: the divisor is the
        # data-axis size of the mesh the Trainer will actually build —
        # including the default mesh a bare --parallelism implies (e.g.
        # tp's {data: -1, model: 2} halves the data axis on 8 devices).
        import math

        from tpu_ddp.train.strategy import (
            default_mesh_sizes,
            infer_parallelism,
        )

        total = n_devices or len(jax.devices())
        sizes = mesh_sizes or default_mesh_sizes(
            infer_parallelism(mesh_sizes, args.parallelism)
        )
        data = sizes.get("data", -1)
        if data == -1:
            fixed = math.prod(v for v in sizes.values() if v != -1)
            data = total // fixed
        assert args.global_batch_size % data == 0, (
            f"global batch {args.global_batch_size} not divisible by "
            f"{data} data shards"
        )
        per_shard = args.global_batch_size // data
    return TrainConfig(
        data_dir=args.data_dir,
        download=args.download,
        dataset=args.dataset,
        synthetic_data=args.synthetic_data,
        model_overrides=args.model_overrides,
        epochs=args.epochs,
        per_shard_batch=per_shard,
        lr=args.lr,
        optimizer=args.optimizer,
        momentum=args.momentum,
        weight_decay=args.weight_decay,
        schedule=None if args.schedule == "constant" else args.schedule,
        warmup_steps=args.warmup_steps,
        grad_clip_norm=args.grad_clip_norm,
        ema_decay=args.ema_decay,
        n_devices=n_devices,
        parallelism=args.parallelism,
        zero1=args.zero1,
        zero3=args.zero3,
        grad_compress=args.grad_compress,
        grad_compress_block=args.grad_compress_block,
        grad_compress_error_feedback=args.grad_compress_error_feedback,
        kernels=args.kernels,
        mesh=mesh_sizes,
        n_microbatches=args.microbatches,
        pp_schedule=args.pp_schedule,
        aux_weight=args.aux_weight,
        seed=args.seed,
        shuffle=not args.no_shuffle,
        reshuffle_each_epoch=not args.faithful_epoch_order,
        augment=args.augment,
        mixup_alpha=args.mixup_alpha,
        sync_bn=args.sync_bn,
        sp_flash=args.sp_flash,
        compute_dtype=args.compute_dtype,
        remat=args.remat,
        model=args.model,
        n_chans1=args.n_chans1,
        n_blocks=args.n_blocks,
        tied_blocks=not args.untied_blocks,
        attention=args.attention,
        num_classes=(
            args.num_classes
            if args.num_classes is not None
            else {"cifar10": 10, "cifar100": 100}[args.dataset]
        ),
        log_every_epochs=args.log_every_epochs,
        log_every_steps=args.log_every_steps,
        eval_each_epoch=args.eval_each_epoch,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every_epochs=args.checkpoint_every_epochs,
        checkpoint_steps=args.checkpoint_steps,
        resume=args.resume,
        keep_best=args.keep_best,
        jsonl_path=args.jsonl,
        tensorboard_dir=args.tensorboard_dir,
        profile_dir=args.profile_dir,
        profile_steps=args.profile_steps,
        profile_window_steps=args.profile_window_steps,
        profile_host_hz=args.profile_host_hz,
        telemetry_dir=args.telemetry_dir,
        telemetry_sinks=args.telemetry_sinks,
        telemetry_snapshot_steps=args.telemetry_snapshot_steps,
        monitor_port=args.monitor_port,
        monitor_bind=args.monitor_bind,
        monitor_allow_remote_trigger=args.monitor_allow_remote_trigger,
        watchdog_deadline_seconds=args.watchdog_deadline,
        watchdog_abort=args.watchdog_abort,
        chaos_spec=args.chaos,
        comms_monitor=args.comms_monitor,
        health=args.health,
        health_policy=args.health_policy,
        health_per_layer_stride=args.health_per_layer_stride,
        health_dir=args.health_dir,
        health_window=args.health_window,
        health_spike_threshold=args.health_spike_threshold,
        lint_on_start=args.lint_on_start,
        freeze_prefixes=tuple(args.freeze) if args.freeze else None,
        loss=args.loss,
        label_smoothing=args.label_smoothing,
        pretrained_dir=args.pretrained_dir,
        plot_curves=args.plot_curves,
        dump_predictions=args.dump_predictions,
        synthetic_size=args.synthetic_size,
        synthetic_task=args.synthetic_task,
        synthetic_label_noise=args.synthetic_label_noise,
        steps_per_call=args.steps_per_call,
        grad_accum_steps=args.grad_accum_steps,
        prefetch_depth=args.prefetch_depth,
        prefetch_batches=args.prefetch_batches,
        data_digests=args.data_digests,
    ).validate()  # satellite: bad sink/policy names fail at parse time


def run_cv(args, config) -> dict:
    """k-fold cross-validation mode (the reference's ``-cv_mode`` dispatch,
    ``ppe_main_ddp.py:91-93`` -> ``k_fold_cv`` at ``:234-307``) — but
    data-parallel over the mesh per fold instead of single-device."""
    import dataclasses

    import numpy as np

    from tpu_ddp.train.kfold import run_kfold
    from tpu_ddp.train.trainer import load_dataset

    (images, labels), _ = load_dataset(config)
    # per-fold runs are ephemeral: no checkpoint dir collisions, no resume
    fold_config = dataclasses.replace(
        config, checkpoint_dir=None, resume=False
    )

    def make_trainer(train_data, val_data, fold):
        import os

        print(f"[cv] fold {fold + 1}/{args.cv_mode}")
        # telemetry/health sinks open their files with mode "w": sharing
        # one run dir across folds would leave only the LAST fold's
        # records — give each fold a subdirectory instead
        cfg = dataclasses.replace(
            fold_config,
            telemetry_dir=(
                os.path.join(fold_config.telemetry_dir, f"fold{fold}")
                if fold_config.telemetry_dir else None),
            health_dir=(
                os.path.join(fold_config.health_dir, f"fold{fold}")
                if fold_config.health_dir else None),
        )
        return Trainer(cfg, train_data=train_data, test_data=val_data)

    results = run_kfold(
        np.asarray(images), np.asarray(labels),
        k=args.cv_mode, make_trainer=make_trainer, seed=config.seed,
    )
    preempted = any(r.get("preempted") for r in results)
    # a drained (preempted) fold carries no val metrics and is excluded from
    # the aggregate — a half-trained fold would depress the mean
    accs = [r["val_accuracy"] for r in results if "val_accuracy" in r]
    if preempted:
        print(
            f"[cv] preempted after {len(accs)}/{args.cv_mode} completed "
            "folds; aggregate covers completed folds only"
        )
    if accs:
        print(
            f"[cv] val accuracy per fold: "
            + ", ".join(f"{a:.4f}" for a in accs)
            + f" | mean {np.mean(accs):.4f} +- {np.std(accs):.4f}"
        )
    return {
        "cv_results": results,
        "preempted": preempted,
        "completed_folds": len(accs),
        "mean_val_accuracy": float(np.mean(accs)) if accs else None,
        "std_val_accuracy": float(np.std(accs)) if accs else None,
    }


def main(argv=None) -> dict:
    args = build_parser().parse_args(argv)
    # Device/platform selection MUST precede any backend-touching call
    # (initialize_distributed queries process_count): --device cpu must never
    # initialize the TPU client.
    config = config_from_args(args)
    initialize_distributed()
    if args.cv_mode:
        return run_cv(args, config)
    if args.eval_only and not (
        (config.resume and config.checkpoint_dir) or config.pretrained_dir
    ):
        raise SystemExit(
            "--eval-only needs weights: --checkpoint-dir ... --resume, "
            "or --pretrained-dir ..."
        )
    trainer = Trainer(config)
    try:
        return _run_and_report(args, config, trainer)
    finally:
        # telemetry sinks close HERE (not inside run()): the final-eval
        # gauges recorded below must land in the final counters snapshot,
        # so the JSONL trace is a self-contained run record
        trainer.close()


def _run_and_report(args, config, trainer) -> dict:
    if args.eval_only and config.resume and trainer.resumed_step is None:
        # the one mode whose entire purpose is loading weights must not
        # silently evaluate random init when the checkpoint dir is empty
        raise SystemExit(
            f"--eval-only: no checkpoint found under "
            f"{config.checkpoint_dir!r} to resume from"
        )
    metrics = (
        {"eval_only": True} if args.eval_only
        else trainer.run(close=False)
    )
    if metrics.get("preempted"):
        # Drained on a preemption signal: the checkpoint is written; every
        # second of post-run work (eval compile, prediction dumps) eats
        # into the kill grace window. Exit now — --resume picks up the
        # exact step.
        trainer.logger.log_text(
            "preempted: skipping final eval/prediction outputs "
            "(resume with --resume)"
        )
        metrics.setdefault("test_accuracy", float("nan"))
        return metrics
    # Final test-set eval — the measurement the reference never takes
    # (SURVEY.md §6: no eval loop exists upstream).
    acc, loss = trainer.evaluate()
    if args.loss == "ce" and trainer.task.accuracy:
        trainer.logger.log_text(
            f"final test accuracy: {acc:.4f}, test loss: {loss:.4f}"
        )
        metrics["test_accuracy"] = acc
        trainer.record_final_eval(accuracy=acc, loss=loss)
    else:  # accuracy is undefined for multi-hot targets; mAP covers it
        trainer.logger.log_text(f"final test loss: {loss:.4f}")
        trainer.record_final_eval(loss=loss)
    if args.dump_predictions or args.viz_predictions:
        import numpy as np

        logits, labels = trainer.predict()
        if args.loss == "bce":
            from tpu_ddp.metrics.evaluation import (
                mean_average_precision,
                multilabel_predictions,
            )

            scores = 1.0 / (1.0 + np.exp(-logits))
            ap = mean_average_precision(scores, labels)
            trainer.logger.log_text(f"test mAP: {ap['mAP']:.4f}")
            metrics["test_mAP"] = ap["mAP"]
            preds = multilabel_predictions(scores)
        else:
            preds = np.argmax(logits, axis=-1)
        if args.dump_predictions:
            import json

            with open(args.dump_predictions, "w") as f:
                json.dump(
                    {
                        "predictions": np.asarray(preds).tolist(),
                        "labels": np.asarray(labels).tolist(),
                    },
                    f,
                )
            trainer.logger.log_text(f"predictions -> {args.dump_predictions}")
        if args.viz_predictions:
            from tpu_ddp.parallel.runtime import is_primary_process

            if args.loss != "ce":
                trainer.logger.log_text(
                    "--viz-predictions skipped: class-grid/confusion images "
                    "need class-index labels (--loss ce); use the mAP/PR "
                    "plots for multi-label"
                )
            elif is_primary_process():
                from tpu_ddp.metrics.visualization import (
                    save_prediction_artifacts,
                )

                # predict() yields rows in SAMPLER order (shard-major
                # interleave, rank r takes rows r::ws), NOT dataset order —
                # recover each prediction's dataset row from the loader's
                # own index stream (same local slice predict consumed) so
                # image i really is the sample behind pred i.
                row_order = np.concatenate([
                    idx[mask]
                    for idx, mask in
                    trainer.test_loader.epoch_index_batches(epoch=0)
                ])
                assert len(row_order) == len(preds), (
                    len(row_order), len(preds)
                )
                paths = save_prediction_artifacts(
                    trainer.test_loader.images[row_order],
                    np.asarray(labels),
                    np.asarray(preds),
                    args.viz_predictions,
                    num_classes=config.num_classes,
                )
                trainer.logger.log_text(
                    f"prediction viz -> {paths['grid']}, "
                    f"{paths['confusion_matrix']}"
                )
    return metrics


if __name__ == "__main__":
    main()
