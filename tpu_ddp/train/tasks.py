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
    block diffusion                (the same keys)           1/t-weighted masked NLL

A loss that is a sum of several terms hands each to the step beside the
sum, by name, and the step reports each in its metrics.

A task may also **prepare** its batch inside the step (``Task.prepare``):
what happens to a batch between the loader and the model that has to be
drawn anew every step, on the device. ``block_diffusion`` draws its noise
there: for every row and block of ``B`` tokens a level ``t ~ U(t_min, 1]``,
every token of the block masked with probability ``t``, and the model's
input ``[x ‖ x~]``, the clean sequence and the noised copy after it. Its
loss is the cross-entropy of token ``i`` at position ``i`` of the noisy
half (unshifted), at the masked positions only, each weighted ``1 / t``,
over the count of real target positions (BD3-LMs, arXiv:2503.09573).

The step builders (``train/steps.py``) take a ``Task`` and know no other
difference between the two: zero1, accumulation, recomputation, health and
the run loop come with the one builder.
"""

from __future__ import annotations

import dataclasses
import functools
import math
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
    #: ``(key, batch) -> batch``: what the step does to a shard's batch
    #: before the model sees it, under ``tpu_ddp.input``, from a key folded
    #: from the run's seed, the step and the shard (new every step, the same
    #: for the same three); None for a task that feeds what the loader made
    prepare: Optional[Callable] = None
    #: positions of the sequence ``model.init`` is shown; None = the data's,
    #: cut to 16
    example_positions: Optional[int] = None

    def example_input(self, inputs):
        """What ``model.init`` is shown, from the training set's first array:
        one sequence of zeros cut to a few positions; None for a model that
        reads float32 images, whose init stays the one it always was
        (``train/state.py::init_model_variables``)."""
        if not self.sequence:
            return None
        positions = self.example_positions or min(inputs.shape[1], 16)
        return jnp.zeros((1, positions), inputs.dtype)


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


def block_noise(settings, key, batch):
    """A batch of ``block_diffusion`` as the model and the loss take it:
    ``tokens`` becomes ``[x ‖ x~]`` (n, 2L), and beside it the clean
    ``block_targets`` (n, L), ``block_masked`` (n, L) bool and the level of
    each position's block ``block_t`` (n, L) float32. One level a row and
    block, ``1 - u (1 - t_min)`` with ``u ~ U[0, 1)``; a position is masked
    where its own uniform draw lies under its block's level."""
    from tpu_ddp.telemetry.phases import module_scope

    tokens = batch["tokens"]
    n, length = tokens.shape
    if length % settings.block:
        raise ValueError(f"sequences of {length} tokens are not whole "
                         f"blocks of {settings.block}")
    with jax.named_scope(module_scope("block_noise")):
        level_key, mask_key = jax.random.split(key)
        u = jax.random.uniform(level_key, (n, length // settings.block),
                               jnp.float32)
        t = jnp.repeat(1.0 - u * (1.0 - settings.t_min), settings.block,
                       axis=1)
        masked = jax.random.uniform(mask_key, (n, length), jnp.float32) < t
        noisy = jnp.where(masked, jnp.asarray(settings.mask_id, tokens.dtype),
                          tokens)
        return dict(batch, tokens=jnp.concatenate([tokens, noisy], axis=1),
                    block_targets=tokens, block_masked=masked, block_t=t)


def block_diffusion_loss(logits, batch):
    """``sum(m * w * CE(logits_i, x_i) / t) / max(sum(w), 1)`` over a
    prepared batch, ``w`` the real target positions (``loss_mask`` in the
    rows ``mask`` keeps), in float32; and beside it the plain mean NLL at
    the masked positions and the share of real positions that were masked."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    nll = -jnp.take_along_axis(
        logp, batch["block_targets"][..., None], axis=-1)[..., 0]
    w = batch["loss_mask"].astype(jnp.float32)
    if batch.get("mask") is not None:
        w = w * batch["mask"][:, None].astype(jnp.float32)
    masked = w * batch["block_masked"].astype(jnp.float32)
    loss = jnp.sum(masked * nll / batch["block_t"]) / jnp.maximum(
        jnp.sum(w), 1.0)
    return loss, {
        "nll_masked": jnp.sum(masked * nll) / jnp.maximum(
            jnp.sum(masked), 1.0),
        "masked_share": jnp.sum(masked) / jnp.maximum(jnp.sum(w), 1.0)}


def _block_diffusion_loss(loss_fn, logits, batch):
    del loss_fn  # a classifier's
    return block_diffusion_loss(logits, batch)


def _synthetic_tokens(model, size, seed):
    from tpu_ddp.data.tokens import synthetic_tokens

    # a block-diffusion model's data never holds its mask token: the ids
    # under it (the spec puts the mask on the last row held)
    diffusion = getattr(model.spec, "diffusion", None)
    vocab = model.spec.vocab_rows if diffusion is None else diffusion.mask_id
    return synthetic_tokens(size, vocab, seed)


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


def block_diffusion(settings) -> Task:
    """Block-diffusion training by ``settings`` (a model's
    ``DecoderSpec.diffusion``: ``block``, ``mask_id``, ``t_min``):
    ``NEXT_TOKEN``'s batch, noised and doubled in the step; logits of the
    noisy half out; the weighted loss with its two readings named."""
    return dataclasses.replace(
        NEXT_TOKEN, name="block_diffusion", loss=_block_diffusion_loss,
        prepare=functools.partial(block_noise, settings),
        # [clean, noisy] of whole blocks, a half a kernel's tile can hold
        example_positions=2 * math.lcm(settings.block, 8))


def task_of(model) -> Task:
    name = getattr(model, "task", IMAGE_CLASSIFICATION.name)
    if name == "next_token_mtp":  # the second term's weight is the model's
        return next_token_mtp(model.spec.mtp_weight)
    if name == "block_diffusion":  # block, mask and least level: the model's
        return block_diffusion(model.spec.diffusion)
    return TASKS[name]
