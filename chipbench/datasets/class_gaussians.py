"""Dataset kind ``class_gaussians``: CIFAR-10-shaped float32 images drawn as
class-conditional Gaussians (a class centre per channel plus N(0, 0.3) noise),
the distribution of the program's ``tpu_ddp.data.cifar10.synthetic_cifar10``
(copied here so the yardstick does not move when the program's generator does;
the original is listed under Open questions in PERF.md).

The same seed gives the same arrays on every platform: everything is drawn on
the host with numpy's PCG64, in bulk, in float32.
"""

import numpy as np


def make(spec, seed):
    """(images float32 (size, H, W, C), labels int32 (size,))."""
    size, image_size = int(spec["size"]), int(spec["image_size"])
    channels, num_classes = int(spec.get("channels", 3)), int(spec["num_classes"])
    rng = np.random.default_rng([seed, 0xC1FA])
    labels = rng.integers(0, num_classes, size=size).astype(np.int32)
    centers = rng.standard_normal(
        (num_classes, 1, 1, channels), dtype=np.float32)
    images = rng.standard_normal(
        (size, image_size, image_size, channels), dtype=np.float32)
    images *= np.float32(0.3)
    images += centers[labels]
    return images, labels
