"""What a model trains on: what it reads from a batch and which loss it takes.

The ``Trainer``, its loader and the step builders move a training set of two
arrays (first axis the example) and a row ``mask`` (False on the rows that
pad an epoch's short last batch). A ``Task`` names the two arrays in a batch,
says what the model's ``init`` is shown, and turns the model's output and a
batch into the scalar the step differentiates. A model names its task by a
``task`` attribute; one without is an image classifier.

    image classification   {"image", "label", "mask"}        ``loss_fn(logits, label, mask)``
    next-token prediction  {"tokens", "loss_mask", "mask"}   masked next-token NLL
    ... with a prediction module   (the same keys)           ``L_next + weight * L_mtp``

A loss that is a sum of several terms hands each to the step beside the
sum, by name, and the step reports each in its metrics.

The step builders (``train/steps.py``) take a ``Task`` and know no other
difference between the two: zero1, accumulation, recomputation, health and
the run loop come with the one builder.
"""

from __future__ import annotations

import dataclasses
import functools
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
    #: ``(loss_fn, outputs, batch) -> (scalar, {name: scalar})``: the shard's
    #: loss, a mean over its real targets, in float32, and each term of a
    #: loss of several by the name the step's metrics carry it under ({} for
    #: a loss of one term)
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
    return loss_fn(logits, batch["label"], batch.get("mask")), {}


def next_token_loss(logits, tokens, loss_mask, row_mask=None, ahead=1):
    """Mean negative log-likelihood of token ``t + ahead`` at position ``t``
    over the real targets: positions whose target ``loss_mask`` marks, in
    rows ``row_mask`` keeps. Float32 whatever the logits' type."""
    logp = jax.nn.log_softmax(logits[:, :-ahead].astype(jnp.float32))
    nll = -jnp.take_along_axis(logp, tokens[:, ahead:, None], axis=-1)[..., 0]
    w = loss_mask[:, ahead:].astype(jnp.float32)
    if row_mask is not None:
        w = w * row_mask[:, None].astype(jnp.float32)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


def _next_token_loss(loss_fn, logits, batch):
    del loss_fn  # a classifier's
    return next_token_loss(logits, batch["tokens"], batch["loss_mask"],
                           batch.get("mask")), {}


def _next_token_mtp_loss(weight, loss_fn, outputs, batch):
    """``L_next + weight * L_mtp`` of a model with a multi-token-prediction
    module (arXiv:2412.19437, section 2.2): ``outputs`` is ``(logits,
    mtp_logits)``, and the module's set at position ``i`` predicts token
    ``i + 2``. ``L_mtp`` is the mean NLL over the real targets among those,
    masked as ``L_next`` is; the module runs on every position, and the last
    two have no target."""
    del loss_fn  # a classifier's
    logits, mtp_logits = outputs
    tokens, loss_mask, row_mask = (batch["tokens"], batch["loss_mask"],
                                   batch.get("mask"))
    loss_next = next_token_loss(logits, tokens, loss_mask, row_mask)
    loss_mtp = next_token_loss(mtp_logits, tokens, loss_mask, row_mask,
                               ahead=2)
    return (loss_next + weight * loss_mtp,
            {"loss_next": loss_next, "loss_mtp": loss_mtp})


def _synthetic_tokens(model, size, seed):
    from tpu_ddp.data.tokens import synthetic_tokens

    return synthetic_tokens(size, model.spec.vocab_rows, seed)


IMAGE_CLASSIFICATION = Task("image_classification", "image", "label",
                            _classification_loss, accuracy=True)
NEXT_TOKEN = Task("next_token", "tokens", "loss_mask", _next_token_loss,
                  accuracy=False, sequence=True, synthetic=_synthetic_tokens)
TASKS = {t.name: t for t in (IMAGE_CLASSIFICATION, NEXT_TOKEN)}


def next_token_mtp(weight: float) -> Task:
    """Next-token prediction by a model with a prediction module:
    ``NEXT_TOKEN``'s batch, ``(logits, mtp_logits)`` out, ``L_next + weight
    * L_mtp`` with both terms named."""
    return dataclasses.replace(
        NEXT_TOKEN, name="next_token_mtp",
        loss=functools.partial(_next_token_mtp_loss, weight))


def task_of(model) -> Task:
    name = getattr(model, "task", IMAGE_CLASSIFICATION.name)
    if name == "next_token_mtp":  # the second term's weight is the model's
        return next_token_mtp(model.spec.mtp_weight)
    return TASKS[name]
