"""Sharded, static-shape batch loader.

Re-implements the semantics of ``DistributedSampler`` + ``DataLoader``
(``/root/reference/main.py:60-61``) for the SPMD world: instead of N
processes each iterating their own rank's shard, ONE loader yields *global*
batches laid out so that slicing the leading axis over the mesh's ``data``
axis gives each device exactly the shard torch's sampler would have given the
corresponding rank.

Semantics preserved from torch.utils.data.DistributedSampler:
  * pad-by-wrapping so every shard has ceil(N/ws) samples (total divisible);
  * rank r takes padded[r::ws] (interleaved assignment);
  * shuffle is a seeded permutation of the whole dataset before sharding.

Semantics *fixed* (flagged, SURVEY.md §2.1): the reference never calls
``sampler.set_epoch()``, so every epoch sees the identical order. Default here
is epoch-seeded reshuffling; ``reshuffle_each_epoch=False`` reproduces the
reference's frozen-order behavior for parity tests.

Static shapes for XLA: with ``drop_last=False`` (``main.py:61``) the final
batch is short; instead of a shape-changing remainder we pad it by wrapping
and emit a boolean ``mask`` so the loss/metrics ignore padded rows. Every
batch a jitted step sees has the same shape -> one compilation.
"""

from __future__ import annotations

import math
import time
from typing import Callable, Dict, Iterator, Optional, Tuple

import numpy as np


def _gather(arr: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """Batch row-gather via the native multithreaded library when available
    (tpu_ddp.native), else numpy fancy indexing."""
    from tpu_ddp import native

    return native.gather_rows(arr, idx)


def _out_nbytes(out) -> int:
    """Bytes produced by a stage — the throughput denominator the stage
    observer reports (dict batch, (a, b) tuple, or a bare array)."""
    if out is None:
        return 0
    if isinstance(out, dict):
        return sum(int(getattr(v, "nbytes", 0)) for v in out.values())
    if isinstance(out, tuple):
        return sum(int(getattr(v, "nbytes", 0)) for v in out)
    return int(getattr(out, "nbytes", 0))


def shard_indices(
    n: int,
    world_size: int,
    *,
    shuffle: bool,
    seed: int = 0,
    epoch: int = 0,
) -> np.ndarray:
    """(world_size, ceil(n/ws)) index matrix; row r == torch DistributedSampler
    rank-r order (wrap-padded, interleaved)."""
    if shuffle:
        order = np.random.default_rng(seed + epoch).permutation(n)
    else:
        order = np.arange(n)
    per_shard = math.ceil(n / world_size)
    total = per_shard * world_size
    if total > n:  # pad by wrapping, like DistributedSampler
        order = np.concatenate([order, order[: total - n]])
    return order.reshape(per_shard, world_size).T  # rank r -> order[r::ws]


class ShardedBatchLoader:
    """Yields dict batches {image, label, mask} of fixed global shape
    (world_size * per_shard_batch, ...). The two arrays are any two whose
    first axis is the example; ``keys`` names them in a batch (a decoder's
    are ``tokens`` and ``loss_mask``, both (N, T)).

    ``per_shard_batch`` mirrors the reference's per-process ``batch_size=32``
    (``main.py:61``): global batch = 32 * world_size, scaling with device
    count exactly like the reference's global batch scales with GPU count
    (SURVEY.md §7.3 "global-vs-per-process batch semantics").
    """

    def __init__(
        self,
        images: np.ndarray,
        labels: np.ndarray,
        *,
        world_size: int,
        per_shard_batch: int = 32,
        shuffle: bool = True,
        reshuffle_each_epoch: bool = True,
        seed: int = 0,
        drop_last: bool = False,
        exclude_sampler_pad: bool = False,
        process_index: int = 0,
        process_count: int = 1,
        telemetry=None,
        observer=None,
        host_augment: Optional[Callable] = None,
        keys: Tuple[str, str] = ("image", "label"),
    ):
        """exclude_sampler_pad: also mask out the sampler-level wrap-pad
        duplicates (the samples DistributedSampler repeats to even out
        shards). Keep False for training (torch trains on the duplicates —
        faithful semantics); set True for eval/predict loaders so metrics
        count every sample exactly once.

        process_index/process_count: multi-host mode (SURVEY.md §7.3
        "multi-host data loading"). ``world_size`` stays the GLOBAL device
        count and the sampler math is computed identically on every host
        (same seed -> same permutation); each host then yields only the
        rows for ITS contiguous block of ``world_size/process_count``
        devices, and the trainer assembles global arrays with
        ``jax.make_array_from_process_local_data``. The dataset arrays are
        host-resident in full here (CIFAR-scale); for datasets too large
        per host, pre-shard files per process and run with
        ``shuffle`` local to each host's shard — the sampler sees the
        host-local array and ``process_count=1`` semantics apply per host.

        telemetry: optional ``tpu_ddp.telemetry.Telemetry`` — the loader
        emits a ``data/<stage>`` span per pipeline stage per batch
        (index/gather/augment/collate/shard — the datapath observatory
        vocabulary, docs/data.md) and counts ``loader/batches``
        (stdlib-only import, keeps this module jax-free).

        observer: optional stage observer (duck-typed to
        ``tpu_ddp.datapath.stages.StageMonitor``: ``stage_enter(stage)``
        / ``stage_exit(stage, seconds, nbytes)``) — feeds the live
        ``data-health-p<i>.json`` file and the chaos per-stage stall
        seam. host_augment: optional host-side ``(images, labels) ->
        (images, labels)`` hook timed as the ``augment`` stage; the
        default pipeline augments on-device inside the jitted step, so
        this stays a passthrough unless installed."""
        assert len(images) == len(labels)
        assert world_size % process_count == 0, (
            f"{world_size} devices not divisible by {process_count} hosts"
        )
        self.images, self.labels = images, labels
        # what a batch calls the two arrays: the model's task says
        # (train/tasks.py); the arrays keep their first names here
        self.keys = tuple(keys)
        self.world_size = world_size
        self.per_shard_batch = per_shard_batch
        self.shuffle = shuffle
        self.reshuffle_each_epoch = reshuffle_each_epoch
        self.seed = seed
        self.drop_last = drop_last
        self.exclude_sampler_pad = exclude_sampler_pad
        self.process_index = process_index
        self.process_count = process_count
        if telemetry is None:
            from tpu_ddp.telemetry import NULL as telemetry
        self.telemetry = telemetry
        self.observer = observer
        self.host_augment = host_augment
        self.local_world_size = world_size // process_count
        self._epoch = 0
        per_shard = math.ceil(len(images) / world_size)
        if drop_last:
            self.steps_per_epoch = per_shard // per_shard_batch
        else:
            self.steps_per_epoch = math.ceil(per_shard / per_shard_batch)

    @property
    def global_batch(self) -> int:
        return self.per_shard_batch * self.world_size

    @property
    def local_batch(self) -> int:
        """Rows this host materializes per step (== global_batch when
        single-host)."""
        return self.per_shard_batch * self.local_world_size

    def set_epoch(self, epoch: int) -> None:
        """The fix for the reference's missing ``sampler.set_epoch`` call."""
        self._epoch = epoch

    def epoch_index_batches(
        self, epoch: Optional[int] = None
    ) -> Iterator[tuple]:
        """Yield (idx, mask) per step — the sampler half of the loader,
        separated so a prefetcher can pipeline the gather half."""
        epoch = self._epoch if epoch is None else epoch
        eff_epoch = epoch if self.reshuffle_each_epoch else 0
        shards = shard_indices(
            len(self.images),
            self.world_size,
            shuffle=self.shuffle,
            seed=self.seed,
            epoch=eff_epoch,
        )  # (ws, per_shard)
        per_shard = shards.shape[1]
        n = len(self.images)
        # positions >= n in the padded order are sampler wrap-pad duplicates
        # (mirrors the reshape in shard_indices)
        total = per_shard * self.world_size
        is_real = (np.arange(total) < n).reshape(per_shard, self.world_size).T
        bs = self.per_shard_batch
        for step in range(self.steps_per_epoch):
            lo, hi = step * bs, min((step + 1) * bs, per_shard)
            chunk = shards[:, lo:hi]  # (ws, <=bs)
            real = is_real[:, lo:hi]
            valid = hi - lo
            if valid < bs:  # wrap-pad the short final batch; mask it out
                deficit = bs - valid
                reps = -(-deficit // per_shard)  # ceil: shard may be shorter
                pad = np.tile(shards, (1, reps))[:, :deficit]
                chunk = np.concatenate([chunk, pad], axis=1)
            mask = np.zeros((self.world_size, bs), bool)
            mask[:, :valid] = True
            if self.exclude_sampler_pad:
                mask[:, :valid] &= real
            # Shard-major layout: device d's rows are chunk[d]; host h owns
            # the contiguous device block [h*lws, (h+1)*lws), so its local
            # slice of the global batch is the matching row block.
            lo_r = self.process_index * self.local_world_size
            hi_r = lo_r + self.local_world_size
            yield chunk[lo_r:hi_r].reshape(-1), mask[lo_r:hi_r].reshape(-1)

    # -- the staged pipeline body (one method per named stage, so the
    # -- microbenchmark times exactly the code the live path runs) ------

    def _run_stage(self, stage: str, fn, *args):
        """Time one stage: ``data/<stage>`` span + observer report.
        Stage cost is measured here (not in the observer) so the span
        and the health-window number can never disagree — and the
        observer's entry seam (in-flight write + chaos stall hook) is
        INSIDE the measured region, so an injected slow stage shows the
        same ballooned seconds in the span, the report, and the DAT001
        busy-rate window."""
        obs = self.observer
        t0 = time.perf_counter()
        with self.telemetry.span(f"data/{stage}"):
            if obs is not None:
                obs.stage_enter(stage)
            out = fn(*args)
        if obs is not None:
            obs.stage_exit(stage, time.perf_counter() - t0, _out_nbytes(out))
        return out

    def _stage_index(self, it) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        return next(it, None)

    def _stage_gather(self, idx: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        return _gather(self.images, idx), _gather(self.labels, idx)

    def _stage_augment(
        self, images: np.ndarray, labels: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray]:
        if self.host_augment is None:
            return images, labels
        return self.host_augment(images, labels)

    def _stage_collate(
        self, images: np.ndarray, labels: np.ndarray, mask: np.ndarray
    ) -> Dict[str, np.ndarray]:
        return {self.keys[0]: images, self.keys[1]: labels, "mask": mask}

    def _stage_shard(self, batch: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        # device-layout prep: contiguous C-order rows for the h2d copy.
        # A no-op (same array back, no value change) when the gather
        # already produced contiguous output — yields stay bit-identical.
        return {k: np.ascontiguousarray(v) for k, v in batch.items()}

    def epoch_batches(self, epoch: Optional[int] = None) -> Iterator[Dict[str, np.ndarray]]:
        it = self.epoch_index_batches(epoch)
        while True:
            pair = self._run_stage("index", self._stage_index, it)
            if pair is None:
                return
            idx, mask = pair
            images, labels = self._run_stage("gather", self._stage_gather, idx)
            images, labels = self._run_stage(
                "augment", self._stage_augment, images, labels
            )
            batch = self._run_stage(
                "collate", self._stage_collate, images, labels, mask
            )
            batch = self._run_stage("shard", self._stage_shard, batch)
            self.telemetry.count("loader/batches")
            yield batch

    def __iter__(self):
        return self.epoch_batches()

    def __len__(self):
        return self.steps_per_epoch
