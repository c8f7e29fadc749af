"""Required FLOPs from shapes.

Walks the jaxpr of a forward pass (the plain reference's, so the count is the
benchmark's own and does not move with the program) and sums the
multiply-accumulates of every ``conv_general_dilated`` and ``dot_general``.
Training needs the forward pass once and, for the backward pass, each
contraction twice more (the gradient by its input and by its weight), so a
step is three forward passes of contractions; a MAC is two FLOPs. Nothing is
taken from a compiler's cost model and recomputation is not counted.
Element-wise work (BN, ReLU, the optimizer) is left out: it is not what the
matrix unit's peak measures.
"""

from __future__ import annotations

import math

import jax

TRAIN_PASSES = 3  # forward + backward-by-input + backward-by-weight
FLOPS_PER_MAC = 2


def _conv_macs(eqn) -> int:
    lhs, rhs = (v.aval.shape for v in eqn.invars[:2])
    out = eqn.outvars[0].aval.shape
    dn = eqn.params["dimension_numbers"]
    groups = eqn.params.get("feature_group_count", 1)
    kernel_spatial = math.prod(rhs[i] for i in dn.rhs_spec[2:])
    in_ch = lhs[dn.lhs_spec[1]]
    # every output element contracts kernel window x (input channels / groups)
    return math.prod(out) * kernel_spatial * in_ch // groups


def _dot_macs(eqn) -> int:
    lhs = eqn.invars[0].aval.shape
    (lhs_contract, _), _ = eqn.params["dimension_numbers"]
    contract = math.prod(lhs[i] for i in lhs_contract)
    return math.prod(eqn.outvars[0].aval.shape) * contract


def _subjaxprs(eqn):
    for value in eqn.params.values():
        for item in value if isinstance(value, (list, tuple)) else (value,):
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                yield inner


def jaxpr_macs(jaxpr) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "conv_general_dilated":
            total += _conv_macs(eqn)
        elif name == "dot_general":
            total += _dot_macs(eqn)
        else:
            inner = sum(jaxpr_macs(j) for j in _subjaxprs(eqn))
            if name == "scan":
                inner *= eqn.params["length"]
            elif inner and name in ("while", "cond"):
                raise ValueError(
                    f"contractions inside a {name!r}: the shapes do not say "
                    "how often they run")
            total += inner
    return total


def forward_macs(fn, *args) -> int:
    """MACs of one call of ``fn`` on arguments of these shapes (arrays or
    ``jax.ShapeDtypeStruct``s); nothing runs."""
    return jaxpr_macs(jax.make_jaxpr(fn)(*args).jaxpr)


def train_flops_per_image(forward_macs_per_image: float) -> float:
    return forward_macs_per_image * TRAIN_PASSES * FLOPS_PER_MAC
