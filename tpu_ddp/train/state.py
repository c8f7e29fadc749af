"""Train state: the framework's single source of truth for training.

A superset of what the reference persists: it saves only
``model.state_dict()`` (``main.py:45``) and silently drops optimizer state —
lossless there only because plain SGD is stateless. Here
``{step, params, batch_stats, opt_state}`` travel together (SURVEY.md §5.4).

The fused Pallas kernel tier (``--kernels``, docs/kernels.md) reads and
writes this state through the SAME optax layout ``make_optimizer``
builds — the fused update navigates ``opt_state`` in place of running
the chain, it never reshapes it — so checkpoints, opt-slot derivation,
and restores are byte-compatible across the switch in both directions.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import struct


class TrainState(struct.PyTreeNode):
    step: jnp.ndarray
    # Under --zero3 (parallel/zero.py::Zero3Partition) every params leaf
    # is its flat (padded,) update-space row laid out P(data) — the tree
    # STRUCTURE (and so every path-keyed consumer: decay masks, freeze
    # labels, per-layer health) is unchanged; checkpoints always pass
    # through deshard_state back to the original shapes, so the on-disk
    # layout is one and device-count-independent.
    params: Any
    batch_stats: Any
    opt_state: Any
    # --grad-compress error-feedback residual (parallel/compression.py):
    # per-device quantization error carried step-to-step, one
    # (n_shards, padded) f32 leaf per param leaf laid out P(data) — None
    # (an empty subtree) everywhere else, so every existing construction
    # site and checkpoint stays byte-identical without the feature.
    grad_residual: Any = None


def init_model_variables(model, rng, input_shape=(1, 32, 32, 3),
                         example=None) -> tuple:
    """(params, batch_stats) from a dummy-input init — THE init recipe,
    shared by ``create_train_state`` and the ZeRO-1 path (which must defer
    ``tx.init`` so the optimizer state is born scattered; seed-parity
    between the two paths depends on this being one function).

    ``example`` is the dummy input of a model that does not read float32
    images (``train/tasks.py::Task.example_input``: a few token ids). Its
    init is one jitted program: a decoder's forward pass holds kernels and
    sorts, which nobody wants dispatched one by one."""
    if example is not None:
        variables = jax.jit(lambda r, x: model.init(r, x, train=False))(
            rng, example)
    else:
        variables = model.init(
            rng, jnp.zeros(input_shape, jnp.float32), train=False)
    return variables["params"], variables.get("batch_stats", {})


def create_train_state(model, tx, rng, input_shape=(1, 32, 32, 3),
                       example=None) -> TrainState:
    params, batch_stats = init_model_variables(model, rng, input_shape,
                                               example)
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=batch_stats,
        opt_state=tx.init(params),
    )


def param_count(state: TrainState) -> int:
    import numpy as np

    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(state.params))
