"""Single-pass fused optimizer update: clip + moments + param update + EMA.

The ZeRO-1 update tail (``parallel/zero.py::sharded_update``) and the
replicated DP update both materialize the optax chain as separate XLA
passes over every leaf: clip-scale reads the grads once, the moment
update reads grads + moments and writes moments, the bias-corrected
update reads them again, weight decay reads the params, the lr scale
rewrites the updates, ``apply_updates`` reads params + updates, and the
EMA shadow reads params + updates once more. All of it is elementwise —
pure HBM traffic. This module fuses the whole tail into ONE Pallas pass
per leaf: read grads/params/moments(/EMA) once, write
updates/params/moments(/EMA) once.

Bit-parity contract
-------------------
The kernel must be a drop-in for the optax chain ``make_optimizer``
builds — params, opt_state (counts, moments, EMA) and the returned
update tree must be BIT-identical to the XLA path, step after step
(pinned by ``tests/test_fused_kernels.py``, down to its slow
trainer-step parity case). That means every expression here mirrors the
optax 0.2.3 / in-repo source form exactly:

* clip:   ``select(g_norm < max_norm, t, (t / g_norm.astype(t.dtype)) *
  max_norm)`` with the replicated norm from ``optax.global_norm`` and
  the zero1 norm from ``clip_by_global_norm_sharded``'s
  psum-of-f32-squares (the two differ — each is mirrored separately);
* sgd:    coupled decay ``g + wd * p`` (masked), trace ``g + m * t``;
* adamw:  ``mu = (1-b1)*g + b1*mu``; ``nu = (1-b2)*(g*g) + b2*nu``;
  bias correction ``t / (1 - b**count_inc).astype(t.dtype)``;
  ``mu_hat / (sqrt(nu_hat + 0.0) + eps)``; decoupled decay
  ``u + wd * p`` (masked);
* scale:  ``-lr * u`` (python-float constant) or the schedule's
  ``jnp.array(step, dtype=u.dtype) * u`` with
  ``step = -1 * sched(count)``;
* ema:    ``decay * e + (1.0 - decay) * (p + u)`` on the UNMASKED
  updates (``mask_pad`` runs after the transform in the reference);
* zero1 pad mask: ``where(global_idx < leaf_size, u, 0)``.

Frozen leaves (``multi_transform`` + ``set_to_zero``) never enter a
kernel: their update is zeros and their moment slots are ``MaskedNode``
(zero-leaf pytree nodes) — the surviving moment leaves align 1:1 with
the trainable grad leaves in DFS order, which is how ``FusedUpdate``
navigates the optax state tuple without ever re-deriving it.

Scalar prologue (norms, bias corrections, schedule step) runs as plain
jnp OUTSIDE the kernel — those are O(leaves) scalars, not HBM traffic —
and is fed to the kernel through SMEM.

Interpret-mode semantics (deliberately NOT ``flash_attention.py``'s):
``interpret=None`` compiles via Mosaic on TPU and runs the jnp mirror —
``_reference_leaf``, the SAME ``_update_math`` expressions — off-TPU,
rather than the Pallas interpreter. The interpreter is arithmetically
faithful, but it changes the *shape of the program* XLA:CPU compiles,
and XLA:CPU freely FMA-contracts mul+add chains per fusion: the
interpreter-shaped program duplicates the moment expressions into
different fusions with different contraction choices, and the update
drifts one ulp off the optax chain (no flag or
``lax.optimization_barrier`` placement prevents the duplication — it
happens below the HLO the barrier pins). The mirror compiles to the
same program shape as the optax chain and is bit-exact against it in
every configuration, which is what the parity gate demands. Passing
``interpret=True`` explicitly forces the real Pallas interpreter — the
kernel-machinery path unit tests and ``ops bench`` exercise (asserting
allclose everywhere and bitwise where the program shape permits:
moments, fresh-state steps, quantization). On TPU the compiled kernel's
proof is statistical, not bitwise: ``curves --against`` the XLA path.
Under shard_map the interpreter cannot run (vma-carrying avals), so
``interpret=True`` also falls back to the mirror there.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_ddp.ops.flash_attention import (
    _interpreted_under_shard_map,
    _resolve_interpret,
    _sds,
)
from tpu_ddp.parallel.runtime import is_tpu_device
from tpu_ddp.telemetry.phases import kernel_scope

LANE = 128
_SUBLANES = 8
#: rows of the (rows, 128) leaf layout processed per grid step
_MAX_ROW_BLOCK = 256


@dataclasses.dataclass
class UpdateRecipe:
    """Static description of the optimizer chain ``make_optimizer`` built
    — everything ``FusedUpdate`` needs to mirror it expression-for-
    expression. ``lr`` is the resolved learning rate: a python float or
    the optax schedule callable."""

    optimizer: str                       # "sgd" | "adamw"
    lr: Any
    momentum: float = 0.0
    weight_decay: float = 0.0
    decay_mask: Any = None               # callable or per-leaf bool pytree
    grad_clip_norm: float = 0.0
    zero1_axis: Optional[str] = None
    labeler: Optional[Callable] = None   # params -> "trainable"/"frozen" tree
    ema_decay: float = 0.0
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8


def _update_math(g, p, m, v, e, *, kind, momentum, wd, wd_apply, has_clip,
                 max_norm, step_const, ema_decay, b1, b2, eps,
                 g_norm, step, bc1, bc2):
    """THE update arithmetic — shared verbatim by the Pallas kernel body
    and the jnp reference/fallback path, so the two cannot drift.
    Returns ``(u_unmasked, m_new, v_new, e_new)``; ``u`` is pre-pad-mask
    (the EMA must see it unmasked, exactly like the optax chain)."""
    m_new = v_new = e_new = None
    if has_clip:
        # optax clip_by_global_norm / clip_by_global_norm_sharded leaf op
        g = jnp.where(g_norm < max_norm, g,
                      (g / g_norm.astype(g.dtype)) * max_norm)
    if kind == "adamw":
        mu = (1 - b1) * g + b1 * m
        nu = (1 - b2) * (g * g) + b2 * v
        m_new, v_new = mu, nu
        mu_hat = mu / bc1.astype(mu.dtype)
        nu_hat = nu / bc2.astype(nu.dtype)
        u = mu_hat / (jnp.sqrt(nu_hat + 0.0) + eps)   # eps_root == 0.0
        if wd_apply:
            u = u + wd * p                            # decoupled decay
    else:
        if wd_apply:
            g = g + wd * p                            # coupled decay
        if momentum > 0:
            u = g + momentum * m                      # optax trace
            m_new = u
        else:
            u = g
    if step_const is not None:
        u = step_const * u                            # scale(-lr)
    else:
        u = step.astype(u.dtype) * u                  # scale_by_schedule
    if ema_decay:
        e_new = ema_decay * e + (1.0 - ema_decay) * (p + u)
    return u, m_new, v_new, e_new


def _tile_plan(n: int):
    """(rows_per_step, padded_rows) for an n-element leaf laid out as
    (rows, 128): rows per grid step padded to the f32 sublane multiple,
    total rows padded so the 1-D grid divides evenly."""
    rows = max(1, -(-n // LANE))
    br = min(_MAX_ROW_BLOCK,
             ((rows + _SUBLANES - 1) // _SUBLANES) * _SUBLANES)
    rows_pad = ((rows + br - 1) // br) * br
    return br, rows_pad


def _build_kernel(*, kind, momentum, wd, wd_apply, has_clip, max_norm,
                  step_const, ema_decay, b1, b2, eps, mask_size, br):
    """Pallas kernel closure for one leaf configuration. Ref order:
    smem(1,4 f32), [start(1,1 i32)], g, p, [m], [v], [e] ->
    u, p_new, [m_new], [v_new], [e_new]."""
    has_mom = kind == "sgd" and momentum > 0
    is_adam = kind == "adamw"

    def kernel(*refs):
        it = iter(refs)
        smem = next(it)
        start = next(it) if mask_size is not None else None
        g_ref, p_ref = next(it), next(it)
        m_ref = next(it) if (has_mom or is_adam) else None
        v_ref = next(it) if is_adam else None
        e_ref = next(it) if ema_decay else None
        u_ref, pout_ref = next(it), next(it)
        mout_ref = next(it) if (has_mom or is_adam) else None
        vout_ref = next(it) if is_adam else None
        eout_ref = next(it) if ema_decay else None

        g = g_ref[...]
        p = p_ref[...]
        u, m_new, v_new, e_new = _update_math(
            g, p,
            m_ref[...] if m_ref is not None else None,
            v_ref[...] if v_ref is not None else None,
            e_ref[...] if e_ref is not None else None,
            kind=kind, momentum=momentum, wd=wd, wd_apply=wd_apply,
            has_clip=has_clip, max_norm=max_norm, step_const=step_const,
            ema_decay=ema_decay, b1=b1, b2=b2, eps=eps,
            g_norm=smem[0, 0], step=smem[0, 1],
            bc1=smem[0, 2], bc2=smem[0, 3],
        )
        if mout_ref is not None:
            mout_ref[...] = m_new
        if vout_ref is not None:
            vout_ref[...] = v_new
        if eout_ref is not None:
            eout_ref[...] = e_new
        if mask_size is not None:
            base = start[0, 0] + pl.program_id(0) * (br * LANE)
            rows = lax.broadcasted_iota(jnp.int32, g.shape, 0)
            cols = lax.broadcasted_iota(jnp.int32, g.shape, 1)
            gidx = base + rows * LANE + cols
            u = jnp.where(gidx < mask_size, u, jnp.zeros_like(u))
        u_ref[...] = u
        pout_ref[...] = p + u

    return kernel


def _fused_leaf(g, p, m, v, e, smem, start, *, kind, momentum, wd,
                wd_apply, has_clip, max_norm, step_const, ema_decay,
                b1, b2, eps, mask_size, interpret):
    """One leaf through the fused kernel: 1-D operands padded into the
    (rows, 128) layout, one grid pass, outputs sliced back to n."""
    n = g.shape[0]
    br, rows_pad = _tile_plan(n)
    pad_to = rows_pad * LANE

    def lay(x):
        if x is None:
            return None
        if pad_to != n:
            x = jnp.concatenate([x, jnp.zeros((pad_to - n,), x.dtype)])
        return x.reshape(rows_pad, LANE)

    scalar_spec = pl.BlockSpec((1, 4), lambda i: (0, 0),
                               memory_space=pltpu.SMEM)
    tile_spec = lambda: pl.BlockSpec((br, LANE), lambda i: (i, 0))  # noqa: E731
    operands = [smem]
    in_specs = [scalar_spec]
    if mask_size is not None:
        operands.append(start.reshape(1, 1))
        in_specs.append(pl.BlockSpec((1, 1), lambda i: (0, 0),
                                     memory_space=pltpu.SMEM))
    g2, p2, m2, v2, e2 = lay(g), lay(p), lay(m), lay(v), lay(e)
    for x in (g2, p2, m2, v2, e2):
        if x is not None:
            operands.append(x)
            in_specs.append(tile_spec())
    # every output mixes every operand: it varies over their union
    out_shapes = [_sds((rows_pad, LANE), x.dtype, *operands)
                  for x in (g2, p2, m2, v2, e2) if x is not None]
    with jax.named_scope(kernel_scope("fused_update")):
        outs = pl.pallas_call(
            _build_kernel(kind=kind, momentum=momentum, wd=wd,
                          wd_apply=wd_apply, has_clip=has_clip,
                          max_norm=max_norm, step_const=step_const,
                          ema_decay=ema_decay, b1=b1, b2=b2, eps=eps,
                          mask_size=mask_size, br=br),
            grid=(rows_pad // br,),
            in_specs=in_specs,
            out_specs=[tile_spec() for _ in out_shapes],
            out_shape=out_shapes,
            interpret=interpret,
        )(*operands)
    outs = [o.reshape(-1)[:n] for o in outs]
    it = iter(outs)
    u, p_new = next(it), next(it)
    m_new = next(it) if m2 is not None else None
    v_new = next(it) if v2 is not None else None
    e_new = next(it) if e2 is not None else None
    return u, p_new, m_new, v_new, e_new


def _reference_leaf(g, p, m, v, e, *, kind, momentum, wd, wd_apply,
                    has_clip, max_norm, step_const, ema_decay, b1, b2,
                    eps, mask_size, start, g_norm, step, bc1, bc2):
    """The jnp fallback: SAME ``_update_math`` expressions, native
    shapes, pad mask via ``mask_pad``'s arange form."""
    u, m_new, v_new, e_new = _update_math(
        g, p, m, v, e, kind=kind, momentum=momentum, wd=wd,
        wd_apply=wd_apply, has_clip=has_clip, max_norm=max_norm,
        step_const=step_const, ema_decay=ema_decay, b1=b1, b2=b2,
        eps=eps, g_norm=g_norm, step=step, bc1=bc1, bc2=bc2)
    if mask_size is not None:
        gidx = start + jnp.arange(g.shape[0])
        u = jnp.where(gidx < mask_size, u, jnp.zeros_like(u))
    return u, p + u, m_new, v_new, e_new


class FusedUpdate:
    """The fused drop-in for one ``make_optimizer`` chain. ``apply`` is
    the replicated DP form, ``apply_sharded`` the ZeRO-1 shard-space
    form (folds ``mask_pad`` + ``apply_updates`` into the same pass)."""

    def __init__(self, recipe: UpdateRecipe, interpret=None):
        if recipe.optimizer not in ("sgd", "adamw"):
            raise ValueError(
                f"fused update supports sgd/adamw, got {recipe.optimizer!r}")
        self.recipe = recipe
        self.interpret = interpret

    # -- optax state navigation (layout fixed by make_optimizer) --------

    def _unpack(self, opt_state):
        r = self.recipe
        nav = {"ema": None, "part": None, "masked_tr": None, "clip": None,
               "wd": None, "adam": None, "trace": None, "scale": None}
        s = opt_state
        if r.ema_decay:
            s, nav["ema"] = s[0], s[1]
        if r.labeler is not None:
            nav["part"] = s
            nav["masked_tr"] = s.inner_states["trainable"]
            s = nav["masked_tr"].inner_state
        if r.grad_clip_norm > 0:
            nav["clip"], s = s[0], s[1]
        if r.optimizer == "adamw":
            nav["adam"], nav["wd"], nav["scale"] = s
        else:
            if r.weight_decay > 0:
                nav["wd"], s = s[0], s[1]
            nav["trace"], nav["scale"] = s
        return nav

    def _repack(self, nav, *, new_adam=None, new_trace=None,
                new_scale=None, new_ema_tree=None):
        r = self.recipe
        if r.optimizer == "adamw":
            base = (new_adam, nav["wd"], new_scale)
        else:
            pair = (new_trace, new_scale)
            base = (nav["wd"], pair) if r.weight_decay > 0 else pair
        core = (nav["clip"], base) if r.grad_clip_norm > 0 else base
        if r.labeler is not None:
            new_tr = nav["masked_tr"]._replace(inner_state=core)
            core = nav["part"]._replace(inner_states={
                k: (new_tr if k == "trainable" else val)
                for k, val in nav["part"].inner_states.items()
            })
        if r.ema_decay:
            return (core, nav["ema"]._replace(ema=new_ema_tree))
        return core

    # -- per-leaf static flags ------------------------------------------

    def _flags(self, grads):
        r = self.recipe
        g_leaves = jax.tree.leaves(grads)
        n = len(g_leaves)
        if r.labeler is not None:
            labels = jax.tree.leaves(r.labeler(grads))
            trainable = [lbl == "trainable" for lbl in labels]
        else:
            trainable = [True] * n
        if r.weight_decay > 0:
            mtree = (r.decay_mask(grads) if callable(r.decay_mask)
                     else r.decay_mask)
            wd_flags = [bool(x) and t
                        for x, t in zip(jax.tree.leaves(mtree), trainable)]
        else:
            wd_flags = [False] * n
        return trainable, wd_flags

    # -- entry points ----------------------------------------------------

    def apply(self, grads, opt_state, params):
        """Replicated DP update: ``(new_params, updates, new_opt_state)``
        — bit-identical to ``tx.update`` + ``optax.apply_updates``."""
        return self._run(grads, opt_state, params, partition=None)

    def apply_sharded(self, gsh, opt_state, psh, partition):
        """ZeRO-1 shard-space update: ``(new_psh, updates,
        new_opt_state)`` with ``updates`` already pad-masked (the
        ``health_stats`` contract) — bit-identical to ``tx.update`` +
        ``mask_pad`` + ``apply_updates``."""
        return self._run(gsh, opt_state, psh, partition=partition)

    def _run(self, grads, opt_state, params, *, partition):
        r = self.recipe
        g_leaves, tdef = jax.tree.flatten(grads)
        p_leaves = jax.tree.leaves(params)
        trainable, wd_flags = self._flags(grads)
        nav = self._unpack(opt_state)

        # interpret semantics — see the module docstring: off-TPU the
        # default is the jnp mirror (bit-parity), the real interpreter
        # only on explicit interpret=True (kernel-machinery coverage)
        if self.interpret is None:
            interpret = False
            use_ref = not is_tpu_device()
        else:
            interpret = _resolve_interpret(self.interpret)
            use_ref = _interpreted_under_shard_map(g_leaves[0], interpret)

        # moment leaves align with the TRAINABLE grad leaves in DFS
        # order (frozen positions are MaskedNode: zero-leaf nodes)
        mu_leaves = nu_leaves = trace_leaves = None
        mu_tree = nu_tree = trace_tree = None
        if r.optimizer == "adamw":
            mu_tree, nu_tree = nav["adam"].mu, nav["adam"].nu
            mu_leaves = jax.tree.leaves(mu_tree)
            nu_leaves = jax.tree.leaves(nu_tree)
        elif r.momentum > 0:
            trace_tree = nav["trace"].trace
            trace_leaves = jax.tree.leaves(trace_tree)
        ema_leaves = (jax.tree.leaves(nav["ema"].ema)
                      if r.ema_decay else None)

        # ---- scalar prologue (O(leaves) work, fed via SMEM) ----------
        f0, f1 = jnp.float32(0.0), jnp.float32(1.0)
        g_norm = f0
        if r.grad_clip_norm > 0:
            tr = [g for g, t in zip(g_leaves, trainable) if t]
            if partition is not None:
                # clip_by_global_norm_sharded's norm, expression for
                # expression (f32-cast squares, psum over the axis)
                sq = sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                         for x in tr)
                g_norm = jnp.sqrt(lax.psum(sq, partition.axis))
            else:
                g_norm = optax.global_norm(tr)
        step, step_const = f0, None
        new_scale = nav["scale"]
        if callable(r.lr):
            # scale_by_schedule: step = -1 * sched(count), count bumps
            step = -1 * r.lr(nav["scale"].count)
            new_scale = nav["scale"]._replace(
                count=optax.safe_int32_increment(nav["scale"].count))
        else:
            step_const = -1 * r.lr
        bc1, bc2 = f1, f1
        new_adam = nav["adam"]
        if r.optimizer == "adamw":
            count_inc = optax.safe_int32_increment(nav["adam"].count)
            bc1 = 1 - r.b1 ** count_inc
            bc2 = 1 - r.b2 ** count_inc
        smem = jnp.stack(
            [g_norm, jnp.asarray(step, jnp.float32), bc1, bc2]
        ).astype(jnp.float32).reshape(1, 4)

        slots = (jax.tree.leaves(partition.param_slots)
                 if partition is not None else None)
        axis_idx = (lax.axis_index(partition.axis)
                    if partition is not None else None)

        u_out, p_out = [], []
        m_out, v_out, e_out = [], [], []
        ti = 0
        for i, (g, p) in enumerate(zip(g_leaves, p_leaves)):
            e = ema_leaves[i] if r.ema_decay else None
            if not trainable[i]:
                # set_to_zero: frozen updates are zeros; EMA still sees
                # (p + u) with u = zeros, exactly like the reference
                u = jnp.zeros_like(g)
                u_out.append(u)
                p_out.append(p + u)
                if r.ema_decay:
                    e_out.append(
                        r.ema_decay * e + (1.0 - r.ema_decay) * (p + u))
                continue
            m = v = None
            if r.optimizer == "adamw":
                m, v = mu_leaves[ti], nu_leaves[ti]
            elif r.momentum > 0:
                m = trace_leaves[ti]
            mask_size, start = None, None
            if partition is not None:
                slot = slots[i]
                if slot.padded != slot.size:
                    mask_size = slot.size
                    start = axis_idx * (slot.padded // partition.n_shards)
            cfg = dict(kind=r.optimizer, momentum=r.momentum,
                       wd=r.weight_decay, wd_apply=wd_flags[i],
                       has_clip=r.grad_clip_norm > 0,
                       max_norm=r.grad_clip_norm, step_const=step_const,
                       ema_decay=r.ema_decay, b1=r.b1, b2=r.b2,
                       eps=r.eps, mask_size=mask_size)
            if use_ref:
                u, p_new, m_new, v_new, e_new = _reference_leaf(
                    g, p, m, v, e, start=start, g_norm=g_norm,
                    step=step, bc1=bc1, bc2=bc2, **cfg)
            else:
                shp = g.shape
                flat = lambda x: (None if x is None  # noqa: E731
                                  else x.reshape(-1))
                u, p_new, m_new, v_new, e_new = _fused_leaf(
                    flat(g), flat(p), flat(m), flat(v), flat(e), smem,
                    jnp.asarray(start if start is not None else 0,
                                jnp.int32),
                    interpret=interpret, **cfg)
                unflat = lambda x: (None if x is None  # noqa: E731
                                    else x.reshape(shp))
                u, p_new = unflat(u), unflat(p_new)
                m_new, v_new, e_new = (unflat(m_new), unflat(v_new),
                                       unflat(e_new))
            u_out.append(u)
            p_out.append(p_new)
            if m_new is not None:
                m_out.append(m_new)
            if v_new is not None:
                v_out.append(v_new)
            if r.ema_decay:
                e_out.append(e_new)
            ti += 1

        # ---- rebuild trees / opt_state -------------------------------
        updates = jax.tree.unflatten(tdef, u_out)
        new_params = jax.tree.unflatten(tdef, p_out)
        new_trace = nav["trace"]
        if r.optimizer == "adamw":
            new_mu = jax.tree.unflatten(jax.tree.structure(mu_tree), m_out)
            new_nu = jax.tree.unflatten(jax.tree.structure(nu_tree), v_out)
            new_adam = nav["adam"]._replace(
                count=count_inc, mu=new_mu, nu=new_nu)
        elif r.momentum > 0:
            new_trace = nav["trace"]._replace(trace=jax.tree.unflatten(
                jax.tree.structure(trace_tree), m_out))
        new_ema_tree = None
        if r.ema_decay:
            new_ema_tree = jax.tree.unflatten(
                jax.tree.structure(nav["ema"].ema), e_out)
        new_opt_state = self._repack(
            nav, new_adam=new_adam, new_trace=new_trace,
            new_scale=new_scale, new_ema_tree=new_ema_tree)
        return new_params, updates, new_opt_state


class FusedGradientTransformation(NamedTuple):
    """An ``optax.GradientTransformation`` look-alike whose ``init`` /
    ``update`` ARE the reference chain's (checkpoint layout, opt-slot
    derivation and any direct ``tx.update`` caller are untouched), with
    the fused single-pass implementation riding along as ``.fused`` —
    the update paths opt in via ``getattr(tx, "fused", None)``."""

    init: Callable
    update: Callable
    fused: FusedUpdate


def fuse_optimizer(tx, recipe: UpdateRecipe,
                   interpret=None) -> FusedGradientTransformation:
    """Attach a ``FusedUpdate`` mirroring ``recipe`` to reference ``tx``."""
    return FusedGradientTransformation(
        init=tx.init, update=tx.update,
        fused=FusedUpdate(recipe, interpret=interpret))
