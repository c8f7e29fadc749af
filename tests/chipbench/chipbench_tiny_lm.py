"""A second throw-away benchmark, of another task and another optimizer: a
tiny decoder (the program's ``models/lm.py``: 2 blocks, hidden 32, vocabulary
64, 16 positions) on integer ``(B, T)`` batches with a mask, next-token loss,
AdamW. Every file of it is new and lives in ``tiny_lm/`` beside this one: its
own benchmark file, configuration (no ``image_size``, no ``num_classes``),
mixes, limits, reference file, token generator and adapter. ``write`` copies
them into a temporary directory, and the shipped harness runs them from there
without a shipped file being touched, which is how a later PR adds such a
configuration."""

import json
import os
import shutil

FILES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny_lm")
CELL = "tiny-lm.t16"
#: the program's own ``make_lm_train_step`` on sequences without padding
PRODUCT_CELL = "tiny-lm.full16"


def write(root, *, fault=None, limits=None):
    """Returns (bench_path, roots). ``fault`` breaks the adapter's timed step
    underneath (``tiny_lm/adapters/lm_probe.py``)."""
    shutil.copytree(FILES, root, dirs_exist_ok=True)
    if fault is not None:
        path = os.path.join(root, "configs", "tiny-lm.json")
        with open(path) as f:
            config = json.load(f)
        with open(path, "w") as f:
            json.dump(dict(config, fault=fault), f)
    if limits is not None:
        with open(os.path.join(root, "limits", CELL + ".json"), "w") as f:
            json.dump({"limits": limits}, f)
    return os.path.join(root, "benchmark.json"), [root]
