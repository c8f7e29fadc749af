"""The one vocabulary of step phases, and the scopes that carry it.

Every step builder wraps its work in ``jax.named_scope`` names from this
file, and every Pallas kernel call in ``tpu_ddp.kernel.<name>``. jax writes
the scope path into each instruction's ``op_name`` metadata, the compiler
keeps it through fusion, and :func:`classify` reads it back: an ``op_name``
of the compiled program to ``(phase, module)``. The program map
(``telemetry/program_map.py``) stores that next to each instruction's name,
which is what a device trace prints, so a trace can be split by phase and
by module without guessing from compiler-numbered names.

    jit(shard_step)/tpu_ddp.forward_backward/transpose(jvp(ResNet))/head/dot_general
    `-- program --' `------- scope -------' `--- AD marker, model --' `mod' `primitive'

Phases: ``forward``, ``backward``, ``optimizer``, ``grad_sync`` (everything
that crosses the interconnect, and its codec), ``input`` (in-graph augment
and mixup), ``other`` (metrics, health, whatever no scope reaches).

Stdlib only, like the rest of the package: a trace is read on any machine.
"""

from __future__ import annotations

import functools
import re
from typing import Tuple

FORWARD, BACKWARD, OPTIMIZER = "forward", "backward", "optimizer"
GRAD_SYNC, INPUT, OTHER = "grad_sync", "input", "other"
PHASES = (FORWARD, BACKWARD, OPTIMIZER, GRAD_SYNC, INPUT, OTHER)
#: not a phase: an instruction that only runs other instructions of the map
#: (a ``conditional``'s branch, a ``while``'s body). A device trace shows it
#: as one operation as long as everything it runs, and those beside it, so
#: its time is theirs: whoever sums operations by phase leaves it out
CONTROL = "control"
CONTROL_OPCODES = frozenset(("conditional", "while", "call"))

#: scopes a step builder uses (a new builder or kernel takes these, or adds
#: its own ``tpu_ddp.`` scope to ``SCOPE_PHASES`` below)
SCOPE_PREFIX = "tpu_ddp."
FORWARD_BACKWARD_SCOPE = "tpu_ddp.forward_backward"  # around value_and_grad
FORWARD_SCOPE = "tpu_ddp.forward"                    # eval / predict
LOSS_SCOPE = "tpu_ddp.loss"
GRAD_ACCUM_SCOPE = "tpu_ddp.grad_accum"             # summing microbatches
OPTIMIZER_SCOPE = "tpu_ddp.optimizer_update"
GRAD_SYNC_SCOPE = "tpu_ddp.grad_sync"
GRAD_COMPRESS_SCOPE = "tpu_ddp.grad_compress_ring"
STATS_SYNC_SCOPE = "tpu_ddp.bn_stats_sync"
INPUT_SCOPE = "tpu_ddp.input"
METRICS_SCOPE = "tpu_ddp.metrics"
HEALTH_SCOPE = "tpu_ddp.health"
KERNEL_SCOPE_PREFIX = "tpu_ddp.kernel."
#: a scope inside the model that names a module of its own: the part of a
#: layer a per-layer metric reads (``tpu_ddp.module.moe_route``), where the
#: flax path alone would say ``layer_3`` for router, experts and attention
#: alike. It names the module of everything inside it, a kernel call too
#: (the kernel's own scope stays in the ``op_name``, which is how a roofline
#: reader finds it); it never decides a phase.
MODULE_SCOPE_PREFIX = "tpu_ddp.module."

#: scope (by prefix: the zero3 scopes end in ``/b<k>``) -> phase. The
#: forward scopes are decided by the AD marker instead; a kernel scope
#: names a module and takes the phase of what encloses it.
SCOPE_PHASES = (
    (GRAD_ACCUM_SCOPE, BACKWARD),
    (OPTIMIZER_SCOPE, OPTIMIZER),
    ("tpu_ddp.zero1_shard_update", OPTIMIZER),
    ("tpu_ddp.zero3_shard_update", OPTIMIZER),
    (GRAD_SYNC_SCOPE, GRAD_SYNC),
    (GRAD_COMPRESS_SCOPE, GRAD_SYNC),
    (STATS_SYNC_SCOPE, GRAD_SYNC),
    ("tpu_ddp.zero1_allgather_params", GRAD_SYNC),
    ("tpu_ddp.zero3_prefetch", GRAD_SYNC),
    ("tpu_ddp.zero3_handoff", GRAD_SYNC),
    ("tpu_ddp.zero3_serial_gather", GRAD_SYNC),
    (INPUT_SCOPE, INPUT),
    (METRICS_SCOPE, OTHER),
    (HEALTH_SCOPE, OTHER),
)

#: the backward pass is the transpose of the forward's linearization
BACKWARD_MARKER = "transpose(jvp("

#: jax primitives (the last component of an ``op_name``) and HLO opcodes
#: that cross the interconnect
COLLECTIVE_PRIMITIVES = frozenset((
    "psum", "psum_invariant", "pmax", "pmin", "ppermute", "all_gather",
    "all_gather_invariant", "reduce_scatter", "psum_scatter", "all_to_all",
    "pbroadcast", "pgather",
))
COLLECTIVE_OPCODES = ("all-reduce", "all-gather", "reduce-scatter",
                      "collective-permute", "all-to-all",
                      "collective-broadcast")
COLLECTIVE_MODULE = "collective"

#: path components that are control flow or rematerialization, not a module
_NOT_A_MODULE = frozenset((
    "", "checkpoint", "remat", "rematted_computation", "while", "body",
    "cond", "scan", "shard_map", "closed_call", "core_call", "pjit",
))
_TRANSFORM = re.compile(r"^(?:jvp|transpose|vmap|custom_jvp|custom_vjp)"
                        r"\((.*)\)$")


def kernel_scope(name: str) -> str:
    """The scope a Pallas kernel call is wrapped in."""
    return KERNEL_SCOPE_PREFIX + name


def module_scope(name: str) -> str:
    """The scope a model wraps one named part of a layer in."""
    return MODULE_SCOPE_PREFIX + name


def _unwrap(component: str) -> str:
    """``transpose(jvp(ResNet))`` -> ``ResNet``: AD and batching wrappers
    off, a called function (``jit(log_softmax)``) stays as it is."""
    while True:
        m = _TRANSFORM.match(component)
        if m is None:
            return component
        component = m.group(1)


def _scope_phase(scope: str):
    for prefix, phase in SCOPE_PHASES:
        if scope.startswith(prefix):
            return phase
    return None


@functools.lru_cache(maxsize=8192)  # a program repeats its paths
def _classify_path(path: str, opcode: str) -> Tuple[str, str]:
    names = [_unwrap(c) for c in path.strip().split("/")]
    phase, module, anchor, named = OTHER, "", None, False
    for i, name in enumerate(names):
        if not name.startswith(SCOPE_PREFIX):
            continue
        if name in (FORWARD_BACKWARD_SCOPE, FORWARD_SCOPE):
            anchor = i
            phase = BACKWARD if BACKWARD_MARKER in path else FORWARD
            module, named = "", False
        elif name.startswith(MODULE_SCOPE_PREFIX):
            if not named:  # the outermost names the module
                module, named = name[len(MODULE_SCOPE_PREFIX):], True
        elif name.startswith(KERNEL_SCOPE_PREFIX):
            if not named:
                module = name[len(SCOPE_PREFIX):]
        else:
            known = _scope_phase(name)
            if known is not None:
                # the innermost scope decides: the all-gather of zero1's
                # update sits inside the optimizer scope
                phase, anchor = known, None
            elif anchor is not None:
                continue  # an unknown scope inside the model: not a phase
            module = name[len(SCOPE_PREFIX):]
    if anchor is not None and not module:
        module = _model_module(names, anchor)
    primitive = names[-1] if names else ""
    if primitive in COLLECTIVE_PRIMITIVES or opcode.startswith(
            COLLECTIVE_OPCODES):
        if phase != GRAD_SYNC:
            module = COLLECTIVE_MODULE
        phase = GRAD_SYNC
    return phase, module


def _model_module(names, anchor: int) -> str:
    """The first flax path component under the model: the component after
    the forward scope is the model itself (``ResNet``), the next one its
    child (``stem_conv``, ``_Bottleneck_0``, ``head``). The last component
    of a path is the primitive, never a module."""
    inner = [n for n in names[anchor + 1:-1] if n not in _NOT_A_MODULE]
    if not inner:
        return ""
    if inner[0].startswith(SCOPE_PREFIX):      # tpu_ddp.loss
        return inner[0][len(SCOPE_PREFIX):]
    if len(inner) > 1 and "(" not in inner[1]:
        return inner[1]
    return inner[0]  # the model's own work, or a function it calls


def classify(op_name: str, opcode: str = "") -> Tuple[str, str]:
    """``(phase, module)`` of one instruction from its ``op_name`` (and,
    where the caller has it, its HLO opcode: a collective is ``grad_sync``
    whatever scope it sits in; a ``conditional`` or a ``while`` is
    ``CONTROL``, no phase, whatever it runs).

    An ``op_name`` that joins several paths with ``;`` takes the phase
    they agree on, else the first path's; :func:`is_mixed` says which it
    was."""
    if opcode in CONTROL_OPCODES:
        return CONTROL, ""
    paths = [p for p in (op_name or "").split(";") if p.strip()]
    if not paths:
        if opcode.startswith(COLLECTIVE_OPCODES):
            return GRAD_SYNC, COLLECTIVE_MODULE
        return OTHER, ""
    return _classify_path(paths[0], opcode)


def is_mixed(*op_names: str) -> bool:
    """Do these ``op_name``s (each may join paths with ``;``) hold work of
    more than one phase? The program map asks this of a fusion's own
    ``op_name`` and those of the instructions fused into it.

    ``forward`` beside ``backward`` does not count: the forward-marked
    instructions inside a backward fusion are the linearization's residual
    computations (a ReLU's mask, a BatchNorm's ``rsqrt``), which the
    compiler schedules where they are consumed, so they do run in the
    backward pass. What counts is work of another scope in the same fusion:
    a weight-gradient convolution with the optimizer's update as its
    epilogue is ``backward`` by the ``op_name`` the compiler left on it,
    and ``device_optimizer_ms`` never sees that update. Work that no scope
    reaches (``other``) does not count either."""
    found = {_classify_path(path, "")[0] for op_name in op_names
             for path in (op_name or "").split(";") if path.strip()}
    if BACKWARD in found:
        found.discard(FORWARD)
    return len(found - {OTHER}) > 1
