"""What a model trains on: what it reads from a batch and which loss it takes.

The ``Trainer``, its loader and the step builders move a training set of two
arrays (first axis the example) and a row ``mask`` (False on the rows that
pad an epoch's short last batch). A ``Task`` names the two arrays in a batch,
says what the model's ``init`` is shown, and turns the model's output and a
batch into the scalar the step differentiates. A model names its task by a
``task`` attribute; one without is an image classifier.

    image classification   {"image", "label", "mask"}        ``loss_fn(logits, label, mask)``
    next-token prediction  {"tokens", "loss_mask", "mask"}   masked next-token NLL

The step builders (``train/steps.py``) take a ``Task`` and know no other
difference between the two: zero1, accumulation, recomputation, health and
the run loop come with the one builder.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Task:
    name: str
    #: batch keys of the training set's two arrays: the model's input, and
    #: what the loss holds it against
    input_key: str
    target_key: str
    #: ``(loss_fn, outputs, batch) -> scalar``: the shard's loss, a mean over
    #: its real targets, in float32
    loss: Callable
    #: does ``masked_accuracy(outputs, batch[target_key], mask)`` mean
    #: anything (a class per row)?
    accuracy: bool

    #: is an example a sequence, whose length no parameter's shape depends on?
    sequence: bool = False
    #: ``(model, size, seed) -> the training set's two arrays`` under
    #: ``--synthetic-data``, sized by the built model; None for an image
    #: classifier, whose synthetic sets ``load_dataset`` has always made
    synthetic: Optional[Callable] = None

    def example_input(self, inputs):
        """What ``model.init`` is shown, from the training set's first array:
        one sequence of zeros cut to a few positions; None for a model that
        reads float32 images, whose init stays the one it always was
        (``train/state.py::init_model_variables``)."""
        if not self.sequence:
            return None
        return jnp.zeros((1, min(inputs.shape[1], 16)), inputs.dtype)


def _classification_loss(loss_fn, logits, batch):
    return loss_fn(logits, batch["label"], batch.get("mask"))


def next_token_loss(logits, tokens, loss_mask, row_mask=None):
    """Mean negative log-likelihood of token t+1 at position t over the real
    targets: positions whose target ``loss_mask`` marks, in rows ``row_mask``
    keeps. Float32 whatever the logits' type."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    w = loss_mask[:, 1:].astype(jnp.float32)
    if row_mask is not None:
        w = w * row_mask[:, None].astype(jnp.float32)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


def _next_token_loss(loss_fn, logits, batch):
    del loss_fn  # a classifier's
    return next_token_loss(logits, batch["tokens"], batch["loss_mask"],
                           batch.get("mask"))


def _synthetic_tokens(model, size, seed):
    from tpu_ddp.data.tokens import synthetic_tokens

    return synthetic_tokens(size, model.spec.vocab_rows, seed)


IMAGE_CLASSIFICATION = Task("image_classification", "image", "label",
                            _classification_loss, accuracy=True)
NEXT_TOKEN = Task("next_token", "tokens", "loss_mask", _next_token_loss,
                  accuracy=False, sequence=True, synthetic=_synthetic_tokens)
TASKS = {t.name: t for t in (IMAGE_CLASSIFICATION, NEXT_TOKEN)}


def task_of(model) -> Task:
    return TASKS[getattr(model, "task", IMAGE_CLASSIFICATION.name)]
