"""Training layer (L3): jitted train/eval steps, optimizer factory, Trainer.

Replaces ``train_loop`` (``/root/reference/main.py:26-49``) and the DDP
wrapper (``main.py:63``): the whole forward/loss/backward/allreduce/step
region is ONE jitted SPMD function with ``lax.pmean`` where NCCL sat
(SURVEY.md §3.3).
"""

from tpu_ddp.train.state import TrainState, create_train_state
from tpu_ddp.train.losses import cross_entropy_loss, masked_accuracy
from tpu_ddp.train.steps import (
    make_train_step,
    make_eval_step,
)
from tpu_ddp.train.optim import make_optimizer
from tpu_ddp.train.trainer import Trainer, TrainConfig

__all__ = [
    "TrainState",
    "create_train_state",
    "cross_entropy_loss",
    "masked_accuracy",
    "make_train_step",
    "make_eval_step",
    "make_optimizer",
    "Trainer",
    "TrainConfig",
]
