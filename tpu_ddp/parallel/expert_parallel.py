"""Expert parallelism (EP) — sharding routed-MoE experts over a mesh axis.

Absent from the reference (SURVEY.md §2.3: "Expert parallel (EP / MoE): NO");
built TPU-first on GSPMD: the stacked expert weights of
``tpu_ddp.models.moe.MoEMlp`` (``w_up (E, C, H)`` etc.) are annotated
``P('expert', ...)`` and the XLA partitioner turns the dispatch/combine
einsums into the token all-to-all over ICI — no hand-written
``lax.all_to_all``, and the expert FFN matmuls each device runs are the
large dense (E/ep)-expert blocks the MXU wants.

EP composes with DP on a 2-D ``data x expert`` mesh (batch sharded over
``data``, experts over ``expert``) and with TP by concatenating
``VIT_TP_RULES`` — the step itself is ``make_sharded_train_step``, the same
rule-agnostic GSPMD builder TP and FSDP use; only the layout rules differ.
The MoE load-balance aux loss (sown into the ``aux_loss`` collection) is
handled by that builder's ``aux_weight`` path, mirroring the Switch recipe.

Beside the rules, two pieces of data that say what one chip of a deployment
holds of a layer: ``ExpertShare`` (which routed experts;
``models/moe.py::DroplessMoE`` routes over all and computes its own) and
``HeadShare`` (which heads of an attention or state-space mixer). Neither
adds an exchange: a share run alone hands on its partial result.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import optax
from jax.sharding import Mesh, PartitionSpec as P

from tpu_ddp.parallel.mesh import DATA_AXIS, EXPERT_AXIS
from tpu_ddp.parallel.partitioning import PartitionRule, specs_for_params
from tpu_ddp.train.losses import cross_entropy_loss
from tpu_ddp.train.state import TrainState

@dataclasses.dataclass(frozen=True)
class ExpertShare:
    """Which of a layer's ``num_experts`` routed experts live here: ``held``
    of them, ids ``offset`` and up. Data, not code: the layer that is told
    its share (``tpu_ddp.models.moe.DroplessMoE``) routes over all
    ``num_experts``, computes the part of the result its own experts give for
    the tokens routed to them, and hands that partial result on, whether the
    other shares run on the other positions of an ``expert`` mesh axis (their
    partial results then meet in the exchange between chips) or nowhere (one
    chip of an expert-parallel deployment, run alone)."""

    num_experts: int
    held: int
    offset: int = 0

    def __post_init__(self):
        if not (0 < self.held <= self.num_experts
                and 0 <= self.offset <= self.num_experts - self.held):
            raise ValueError(
                f"experts {self.offset}..{self.offset + self.held - 1} are "
                f"not a share of {self.num_experts}")

    @classmethod
    def of_position(cls, num_experts: int, position: int,
                    positions: int) -> "ExpertShare":
        """The share of one of ``positions`` equal shares (one position of
        an ``expert`` mesh axis)."""
        if num_experts % positions:
            raise ValueError(
                f"{num_experts} experts do not divide over {positions}")
        held = num_experts // positions
        return cls(num_experts, held, position * held)


@dataclasses.dataclass(frozen=True)
class HeadShare:
    """Which of a layer's heads live here: position ``position`` of
    ``positions`` equal shares (one position of a tensor-parallel group).
    ``of(n)`` is ``(held, first)`` of ``n`` published heads or groups: ``n /
    positions`` of them from ``position * held`` up, or, of fewer heads than
    positions (two key-value heads over eight chips), the one head this
    position's query heads read, which its neighbours hold too. Data, not
    code, like ``ExpertShare``: a mixer built with the heads held here
    (``models/hybrid.py``, ``models/decoder.py::GroupedQueryAttention``)
    computes their part of the result and hands it on; the parts of all
    positions add up to the whole mixer's output, in the exchange between
    chips or, on one chip of the deployment run alone, nowhere."""

    positions: int = 1
    position: int = 0

    def __post_init__(self):
        if not 0 <= self.position < self.positions:
            raise ValueError(f"position {self.position} is not one of "
                             f"{self.positions}")

    def of(self, heads: int):
        if heads % self.positions and self.positions % heads:
            raise ValueError(
                f"{heads} heads do not divide over {self.positions}")
        held = max(heads // self.positions, 1)
        return held, self.position * heads // self.positions


# Layout for tpu_ddp.models.moe.MoEMlp (paths like block_1/moe/w_up).
# Router weights stay replicated: every device routes its own tokens.
MOE_EP_RULES = (
    PartitionRule(r"moe/w_up$", P(EXPERT_AXIS, None, None)),
    PartitionRule(r"moe/b_up$", P(EXPERT_AXIS, None)),
    PartitionRule(r"moe/w_down$", P(EXPERT_AXIS, None, None)),
    PartitionRule(r"moe/b_down$", P(EXPERT_AXIS, None)),
)


def make_ep_train_step(
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    state_template: TrainState,
    *,
    rules=MOE_EP_RULES,
    aux_weight: float = 0.01,
    data_axis: str = DATA_AXIS,
    loss_fn: Callable = cross_entropy_loss,
    donate: bool = True,
    remat: bool = False,
    grad_accum_steps: int = 1,
    health=None,
):
    """Expert-parallel (optionally DP x EP) MoE train step.

    Returns ``(step, state_shardings)``; lay the initial state out with
    ``shard_train_state``. ``metrics`` carries both the task loss and the
    load-balance aux loss so balance collapse is observable.
    """
    from tpu_ddp.parallel.tensor_parallel import make_sharded_train_step

    param_specs = specs_for_params(state_template.params, rules)
    build = make_sharded_train_step(
        model, tx, mesh, param_specs,
        data_axis=data_axis, loss_fn=loss_fn, donate=donate,
        aux_weight=aux_weight, remat=remat,
        grad_accum_steps=grad_accum_steps, health=health,
    )
    return build(state_template)
