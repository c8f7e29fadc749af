"""Roofline-guided auto-tuner: search the layout space, emit the winner.

PRs 5-10 built a complete pre-hoc (``analyze``, ``lint``) / live
(``watch``, ``profile``) / post-hoc (``goodput``, ``registry``)
measurement stack; this package *spends* it on speed. ``tpu-ddp tune``
enumerates the candidate grid — parallelism strategy x mesh shape for
the target chip count x ``--zero1``/``--grad-compress`` overlays x
per-shard batch x ``steps_per_call`` — compiles every candidate
DEVICELESSLY through ``train/strategy.py::build_abstract_step`` and the
shared ``analysis/hlo.py`` compile cache, prices each with
``analysis/roofline.py`` (predicted step time per chip, plus a host
dispatch-overhead term ``steps_per_call`` amortizes), rejects anything
``analysis/lint.py`` flags or anything over the chip's HBM capacity
(``tools/memplan.py``'s peak = args + temp convention), and ranks by
predicted images/sec/chip.

A calibration layer (``calibrate.py``) reads the PR 8 profiler's
measured-over-model evidence — profile bundles, ``analyze --json``
run-dir artifacts, archived validated tune entries in a perf registry —
keyed per chip kind, and scales predictions toward measured reality.
``--validate-top K`` (``validate.py``) runs short measured trials of
the best candidates, joined through the PR 5 run-metadata header, and
re-ranks on measurement.

The winner is emitted as a ready-to-run artifact (a ``TrainConfig``
JSON of the fields ``tpu-ddp train`` takes as flags, plus the
equivalent CLI line); the full ranked table is a schema-versioned
``tune --json`` artifact that ``tpu-ddp registry record`` archives and
``tpu-ddp bench compare`` / ``registry trend`` gate like every other
artifact family. docs/tuning.md is the user guide.
"""

from tpu_ddp.tuner.grid import (  # noqa: F401
    Candidate,
    OVERLAY_STRATEGIES,
    STRATEGY_TOKENS,
    enumerate_grid,
    model_traits,
)
from tpu_ddp.tuner.price import (  # noqa: F401
    TUNE_SCHEMA_VERSION,
    PricedCandidate,
    TuneResult,
    tune,
)
