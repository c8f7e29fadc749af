"""Traffic generation: the training set a cell trains on, made from ``--seed``.

One general generator reads the ``dataset`` block of a traffic file
(``chipbench/traffic/<mix>.json``). The only kind today is
``class_gaussians``: CIFAR-10-shaped float32 images drawn as class-conditional
Gaussians (a class centre per channel plus N(0, 0.3) noise), the distribution
of the program's ``tpu_ddp.data.cifar10.synthetic_cifar10`` (copied here so the
yardstick does not move when the program's generator does; the original is
listed under Open questions in PERF.md).

The same seed gives the same arrays on every platform: everything is drawn on
the host with numpy's PCG64, in bulk, in float32.
"""

from __future__ import annotations

import numpy as np

#: numpy seeds and the program's ``TrainConfig.seed`` take any whole number;
#: the fold keeps a driver seed of a little over 2**31 inside 32 bits, which every consumer of the seed takes, before
#: the loader adds the epoch to it
SEED_MODULUS = 2**32


def fold_seed(seed: int) -> int:
    if seed < 0:
        raise ValueError(f"--seed must be a whole number >= 0, got {seed}")
    return int(seed) % SEED_MODULUS


def class_gaussians(size: int, image_size: int, channels: int,
                    num_classes: int, seed: int):
    """(images float32 (size, H, W, C), labels int32 (size,))."""
    rng = np.random.default_rng([fold_seed(seed), 0xC1FA])
    labels = rng.integers(0, num_classes, size=size).astype(np.int32)
    centers = rng.standard_normal(
        (num_classes, 1, 1, channels), dtype=np.float32)
    images = rng.standard_normal(
        (size, image_size, image_size, channels), dtype=np.float32)
    images *= np.float32(0.3)
    images += centers[labels]
    return images, labels


KINDS = {"class_gaussians": class_gaussians}


def make_dataset(spec: dict, seed: int):
    """``spec`` is a traffic file's ``dataset`` block."""
    kind = spec["kind"]
    if kind not in KINDS:
        raise ValueError(
            f"unknown dataset kind {kind!r}; known: {sorted(KINDS)}")
    return KINDS[kind](
        int(spec["size"]), int(spec["image_size"]),
        int(spec.get("channels", 3)), int(spec["num_classes"]), seed)


def real_images_per_step(size: int, shards: int, per_shard_batch: int):
    """Unmasked images of each step of one epoch, by the sampler's own
    arithmetic (DistributedSampler semantics: every shard holds
    ceil(size/shards) rows, wrap-padded duplicates are trained on and count;
    only the short last batch of the epoch is padded and masked)."""
    per_shard = -(-size // shards)
    steps = -(-per_shard // per_shard_batch)
    full = [per_shard_batch * shards] * (steps - 1)
    last = (per_shard - (steps - 1) * per_shard_batch) * shards
    return full + [last]
