"""ZeRO-1 cross-replica weight-update sharding for the data-parallel family.

The DP step builders (``train/steps.py``, ``train/lm_steps.py``, the SP
builders) historically pmean'd full gradients and then had **every replica
apply the identical full update to fully replicated optimizer state** —
N x the HBM for momentum/Adam moments and N x the update FLOPs. Following
"Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training" (arxiv 2004.13336, PAPERS.md), this module replaces that with:

1. **reduce-scatter** the gradients over the ``data`` axis (replacing the
   pmean): each replica receives the *globally averaged* gradient for only
   its 1/N slice of the flattened update space;
2. apply the optimizer to the **local shard** of params + optimizer state
   (optimizer state lives permanently sharded — the 1/N HBM win);
3. **all-gather** the updated params back to replicated for the next
   forward/backward.

The math is identical to the replicated update — reduce-scatter + slice-
update + all-gather computes exactly what pmean + full-update computes,
element for element — pinned by the parity tests in ``tests/test_zero1.py``.

Update space layout
-------------------
Each param leaf is flattened to 1-D and zero-padded to a multiple of the
shard count (the "padded 1-D update space"); shard *i* owns elements
``[i*S, (i+1)*S)`` of every leaf. Sharding is **per leaf** rather than one
concatenated vector on purpose: the param pytree structure (and with it
every structure-aware optax feature — path-keyed freeze labels, per-leaf
decay masks, the EMA shadow) survives flattening, and checkpoint
de-sharding is a pure unpad+reshape per leaf, which is what lets
``--resume`` and ``--zero1`` compose in either direction. XLA's collective
combiner fuses the per-leaf reduce-scatters/all-gathers back into large
transfers.

Optimizer compatibility
-----------------------
Everything elementwise (sgd/momentum, adamw, EMA, freeze masks, weight
decay with a *precomputed* mask tree — see ``make_optimizer(zero1_axis=)``)
shards exactly. Global-norm clipping needs the cross-shard psum this module
provides (``clip_by_global_norm_sharded``). LAMB's per-layer trust ratios
need whole-leaf norms and are rejected at config validation.

Gradient sync: the builders pcast the params to varying first
(``varying``) so AD produces LOCAL gradients without inserting its own
psum, and this module's reduce-scatter IS the sync. The updated shards come
back through ``all_gather_invariant``, whose result shard_map's checker
types as replicated over the gathered axis — what the ``P()`` out_spec of
the params claims.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax._src.lax.parallel import all_gather_invariant
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_ddp.health.stats import assemble_stats, per_layer_sq, tree_nonfinite, tree_sq
from tpu_ddp.parallel.mesh import DATA_AXIS
from tpu_ddp.parallel.partitioning import _path_str
from tpu_ddp.telemetry.phases import GRAD_COMPRESS_SCOPE, GRAD_SYNC_SCOPE


@dataclasses.dataclass(frozen=True)
class _Slot:
    """Static layout of one leaf of the update space (or one opt-state
    leaf). ``sharded=False`` slots (optimizer step counts, schedule state)
    stay replicated."""

    shape: tuple
    size: int
    padded: int
    sharded: bool = True


def _leaf_slot(leaf, n_shards: int) -> _Slot:
    shape = tuple(leaf.shape)
    size = 1
    for d in shape:
        size *= d
    padded = size + ((-size) % n_shards)
    return _Slot(shape=shape, size=size, padded=padded)


_REPLICATED = _Slot(shape=(), size=1, padded=1, sharded=False)


def _is_slot(x) -> bool:
    return isinstance(x, _Slot)


def _flat_leaf(x, slot: _Slot):
    """One leaf into the update space: reshape(-1) + zero-pad to
    ``slot.padded`` — THE padding arithmetic, shared by every flatten
    path (in-step, fresh init, checkpoint re-scatter)."""
    x = jnp.reshape(x, (-1,))
    if slot.padded != slot.size:
        x = jnp.concatenate(
            [x, jnp.zeros((slot.padded - slot.size,), x.dtype)]
        )
    return x


def _unflat_leaf(x, slot: _Slot):
    """Inverse of ``_flat_leaf``: unpad + reshape to the original."""
    return jnp.reshape(x[: slot.size], slot.shape)


class Zero1Partition:
    """Static partition of a param pytree's update space over a mesh axis.

    Built once per (optimizer, model) pair — from concrete params or
    ``ShapeDtypeStruct`` templates (the deviceless-AOT path in
    ``tools/memplan.py`` builds from abstract shapes only).
    """

    def __init__(self, tx, params_template, n_shards: int,
                 axis: str = DATA_AXIS, compress=None):
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        self.tx = tx
        self.axis = axis
        self.n_shards = n_shards
        self.compress = None
        if compress is not None:
            self.set_compression(compress)
        template = jax.eval_shape(lambda p: p, params_template)
        self.param_slots = jax.tree.map(
            lambda leaf: _leaf_slot(leaf, n_shards), template
        )
        # Opt-state layout: init on the FLAT template, then suffix-match
        # each opt leaf's path against the param paths (momentum/mu/nu/ema
        # trees embed the param tree as a subtree — the same observation
        # parallel/partitioning.py::opt_state_specs builds on). Matched
        # leaves live in the update space (sharded); everything else
        # (step counts, schedule state) is replicated.
        flat_template = jax.eval_shape(self.flatten, template)
        self.opt_template = jax.eval_shape(tx.init, flat_template)
        by_suffix = {}
        for path, slot in jax.tree_util.tree_flatten_with_path(
            self.param_slots, is_leaf=_is_slot
        )[0]:
            by_suffix[tuple(_path_str((k,)) for k in path)] = slot

        def pick(path, leaf):
            del leaf
            parts = tuple(_path_str((k,)) for k in path)
            for plen in range(len(parts), 0, -1):
                slot = by_suffix.get(parts[-plen:])
                if slot is not None:
                    return slot
            return _REPLICATED

        self.opt_slots = jax.tree_util.tree_map_with_path(
            pick, self.opt_template
        )
        self.opt_specs = jax.tree.map(
            lambda s: P(axis) if s.sharded else P(),
            self.opt_slots, is_leaf=_is_slot,
        )

    def set_compression(self, compress) -> None:
        """Attach a ``GradCompressor`` (parallel/compression.py): the grad
        reduce-scatter below swaps ``lax.psum_scatter`` for the
        block-scaled quantized ring — wire bytes drop ~4x (int8) / 2x
        (bf16) while the shard update stays f32. The compressor must be
        built from the same params template and shard count (its per-leaf
        padding is the same arithmetic as this partition's)."""
        if compress.n_shards != self.n_shards or compress.axis != self.axis:
            raise ValueError(
                f"GradCompressor layout (n_shards={compress.n_shards}, "
                f"axis={compress.axis!r}) does not match this partition "
                f"(n_shards={self.n_shards}, axis={self.axis!r})"
            )
        self.compress = compress

    # ---- flat update space (host + in-graph) ----------------------------

    def flatten(self, tree):
        """Original-shaped params-treedef tree -> per-leaf (padded,) 1-D."""
        return jax.tree.map(_flat_leaf, tree, self.param_slots)

    def unflatten(self, flat_tree):
        """Per-leaf (padded,) 1-D tree -> original shapes (unpad+reshape).
        Works in-graph and on global (sharded) arrays — outside a jit the
        slice inserts the all-gather."""
        return jax.tree.map(_unflat_leaf, flat_tree, self.param_slots)

    # ---- in-graph (inside shard_map) ------------------------------------

    def reduce_scatter_mean(self, grads, residual=None,
                            with_error: bool = False):
        """Local (unsynced) grad tree -> ``(shards, err_state)``: this
        shard's 1/N slice of the globally AVERAGED gradient — the pmean
        replacement. Same adds in the same order as the all-reduce,
        restricted to the local slice. With a compressor attached
        (``set_compression``) the psum_scatter becomes the block-scaled
        quantized ring instead (same layout, ~4x fewer wire bytes);
        ``residual``/``with_error`` thread the error-feedback state
        through it. ``err_state`` is None on the uncompressed path."""
        if self.compress is not None:
            with jax.named_scope(GRAD_COMPRESS_SCOPE):
                return self.compress.reduce_scatter_mean_flat(
                    self.flatten(grads), residual, with_error=with_error)
        n = self.n_shards

        def rs(g):
            return lax.psum_scatter(
                g, self.axis, scatter_dimension=0, tiled=True
            ) / n

        with jax.named_scope(GRAD_SYNC_SCOPE):
            return jax.tree.map(rs, self.flatten(grads)), None

    def local_shard(self, flat_tree):
        """This shard's slice of a replicated flat tree (params enter the
        step replicated; the slice is free)."""
        idx = lax.axis_index(self.axis)

        def sl(x, slot):
            s = slot.padded // self.n_shards
            return lax.dynamic_slice_in_dim(x, idx * s, s)

        return jax.tree.map(sl, flat_tree, self.param_slots)

    def mask_pad(self, shard_tree):
        """Zero the padding tail of per-shard trees. The pad region is
        provably zero through every supported elementwise transform (zero
        grads stay zero through momentum/adam/decay/clip), but masking
        costs one fused select and keeps the invariant independent of the
        optimizer chain."""
        idx = lax.axis_index(self.axis)

        def mask(x, slot):
            s = slot.padded // self.n_shards
            if slot.padded == slot.size:
                return x
            gidx = idx * s + jnp.arange(s)
            return jnp.where(gidx < slot.size, x, jnp.zeros_like(x))

        return jax.tree.map(mask, shard_tree, self.param_slots)

    def gather_params(self, shard_tree):
        """Per-shard updated params -> full replicated original-shape tree
        (the once-per-step all-gather)."""

        def ag(x):
            return all_gather_invariant(x, self.axis, axis=0, tiled=True)

        return self.unflatten(jax.tree.map(ag, shard_tree))

    def varying(self, params):
        """Params as differentiation input: the replicated params are
        pcast to varying OUTSIDE the grad closure so AD yields LOCAL
        gradients (no automatic psum — the reduce-scatter is the sync)."""
        return jax.tree.map(
            lambda p: lax.pcast(p, (self.axis,), to="varying"), params
        )

    def sharded_update(self, grads, params, opt_state, residual=None,
                       with_error: bool = False):
        """The ZeRO-1 update tail, run INSIDE the compiled step: returns
        ``(new_params, new_opt_state, grad_shards, update_shards,
        err_state)``. ``grads`` are the LOCAL (per-replica, unsynced —
        but already microbatch-averaged if accumulating) gradients;
        ``params`` the replicated originals; ``opt_state`` the local opt
        shard; ``residual``/``with_error`` the --grad-compress
        error-feedback threading (``err_state`` is the new residual, None
        without compression). The optimizer is ``self.tx`` — the one this
        partition derived its opt-state layout from (a different tx here
        could not match ``opt_slots``, so it is not a parameter)."""
        gsh, err_state = self.reduce_scatter_mean(
            grads, residual, with_error=with_error)
        psh = self.local_shard(self.flatten(params))
        with jax.named_scope("tpu_ddp.zero1_shard_update"):
            fused = getattr(self.tx, "fused", None)
            if fused is not None:
                # the single-pass Pallas tail (ops/fused_update.py): one
                # HBM pass per leaf instead of the materialized optax
                # chain; returns updates already pad-masked
                new_psh, updates, new_opt_state = fused.apply_sharded(
                    gsh, opt_state, psh, partition=self)
            else:
                updates, new_opt_state = self.tx.update(gsh, opt_state, psh)
                updates = self.mask_pad(updates)
                new_psh = optax.apply_updates(psh, updates)
        with jax.named_scope("tpu_ddp.zero1_allgather_params"):
            new_params = self.gather_params(new_psh)
        return new_params, new_opt_state, gsh, updates, err_state

    def health_stats(self, *, loss, grad_shards, params, update_shards,
                     per_layer: bool = False, compress_error_sq=None):
        """The flight-recorder schema (health/stats.py) from SHARDED
        grads/updates: shard-local sums psum'd over the data axis — every
        shard reports the identical global number, exactly as the
        replicated path does. ``loss``/``params`` are already global."""
        psum = lambda x: lax.psum(x, self.axis)  # noqa: E731
        pl = None
        if per_layer:
            pl = {
                "grad_norm": {
                    k: jnp.sqrt(psum(v))
                    for k, v in per_layer_sq(grad_shards).items()
                },
                "param_norm": {
                    k: jnp.sqrt(v) for k, v in per_layer_sq(params).items()
                },
            }
        return assemble_stats(
            loss=loss,
            grad_sq=psum(tree_sq(grad_shards)),
            grad_bad=psum(tree_nonfinite(grad_shards)),
            param_sq=tree_sq(params),
            update_sq=psum(tree_sq(update_shards)),
            update_bad=psum(tree_nonfinite(update_shards)),
            per_layer=pl,
            compress_error_sq=compress_error_sq,
        )

    # ---- specs / shardings (shard_map + device layout) ------------------

    def state_specs(self, *, batch_stats_spec: Optional[P] = None):
        """TrainState-shaped PartitionSpec tree for shard_map in/out_specs:
        step/params/batch_stats replicated, opt_state per-slot."""
        from tpu_ddp.train.state import TrainState

        return TrainState(
            step=P(),
            params=P(),
            batch_stats=batch_stats_spec or P(),
            opt_state=self.opt_specs,
        )

    def state_shardings(self, state, mesh: Mesh):
        """NamedSharding tree matching ``state_specs`` — the device layout
        for device_put / out_shardings / AOT abstract states."""
        replicated = NamedSharding(mesh, P())
        return state.replace(
            step=replicated,
            params=jax.tree.map(lambda _: replicated, state.params),
            batch_stats=jax.tree.map(lambda _: replicated, state.batch_stats),
            opt_state=jax.tree.map(
                lambda _, spec: NamedSharding(mesh, spec),
                state.opt_state, self.opt_specs,
            ),
        )

    # ---- checkpoint interop (de-shard <-> shard) ------------------------

    def deshard_opt_state(self, opt_state):
        """Sharded (flat-padded) opt leaves -> the ORIGINAL optax layout a
        replicated run would checkpoint: unpad + reshape each update-space
        leaf. The result is structurally identical to ``tx.init(params)``
        + training, so a --zero1 checkpoint restores into a replicated run
        and vice versa."""
        return jax.tree.map(
            lambda x, slot: _unflat_leaf(x, slot) if slot.sharded else x,
            opt_state, self.opt_slots,
        )

    def shard_opt_state(self, opt_state, mesh: Mesh):
        """Original-layout opt state (fresh init or restored checkpoint)
        -> flat-padded leaves laid out P(axis) on the mesh."""
        shardings = jax.tree.map(
            lambda _, spec: NamedSharding(mesh, spec),
            self.opt_slots, self.opt_specs, is_leaf=_is_slot,
        )
        scatter = self._jitted(
            ("shard_opt", mesh),
            lambda opt: jax.tree.map(
                lambda x, slot: _flat_leaf(x, slot) if slot.sharded else x,
                opt, self.opt_slots,
            ),
            out_shardings=shardings,
        )
        return scatter(opt_state)

    def _jitted(self, key, fn, **jit_kw):
        """Per-partition jit cache: the de/re-shard transforms must run
        under jit on multihost pods (eager slicing of a non-fully-
        addressable global array raises), and re-wrapping per call would
        recompile per checkpoint."""
        cache = self.__dict__.setdefault("_jit_cache", {})
        if key not in cache:
            cache[key] = jax.jit(fn, **jit_kw)
        return cache[key]

    def deshard_state(self, state):
        """Full TrainState -> the layout a replicated run checkpoints."""
        deshard = self._jitted("deshard_opt", self.deshard_opt_state)
        return state.replace(opt_state=deshard(state.opt_state))

    def deshard_params(self, flat_params):
        """Jitted ``unflatten`` for host-side consumers (the EMA shadow at
        eval time): multihost-safe, compiled once."""
        return self._jitted("deshard_params", self.unflatten)(flat_params)

    def shard_state(self, state, mesh: Mesh):
        """Full original-layout TrainState -> training layout (params
        replicated, opt state scattered)."""
        from tpu_ddp.parallel.mesh import replicated_sharding

        rep = replicated_sharding(mesh)
        return state.replace(
            step=jax.device_put(state.step, NamedSharding(mesh, P())),
            params=jax.device_put(state.params, rep),
            batch_stats=jax.device_put(state.batch_stats, rep),
            opt_state=self.shard_opt_state(state.opt_state, mesh),
        )

    def init_opt_state(self, params, mesh: Mesh):
        """Fresh sharded optimizer state WITHOUT ever materializing the
        replicated original: tx.init runs on the flat tree under a jit
        whose out_shardings scatter every update-space leaf."""
        shardings = jax.tree.map(
            lambda _, spec: NamedSharding(mesh, spec),
            self.opt_template, self.opt_specs,
        )
        with mesh:
            return jax.jit(
                lambda p: self.tx.init(self.flatten(p)),
                out_shardings=shardings,
            )(params)

    # ---- accounting (memplan / docs) ------------------------------------

    def accounting(self) -> dict:
        """Static byte accounting for the HBM claim: replicated vs sharded
        per-device optimizer-state bytes — computed from the layout, the
        same numbers the compiler's memory analysis confirms."""
        opt_leaves = list(zip(
            jax.tree.leaves(self.opt_slots, is_leaf=_is_slot),
            jax.tree.leaves(self.opt_template),
        ))
        repl = 0
        shard = 0
        pad_overhead = 0
        for slot, leaf in opt_leaves:
            item = jnp.dtype(leaf.dtype).itemsize
            if slot.sharded:
                repl += slot.size * item
                shard += (slot.padded // self.n_shards) * item
                pad_overhead += (slot.padded - slot.size) * item
            else:
                b = item
                for d in leaf.shape:
                    b *= d
                repl += b
                shard += b
        return {
            "n_shards": self.n_shards,
            "optimizer_state_bytes_replicated": int(repl),
            "optimizer_state_bytes_per_device_sharded": int(shard),
            "padding_overhead_bytes_total": int(pad_overhead),
            "sharding_factor": (
                round(repl / shard, 2) if shard else None
            ),
        }


def param_blocks(params_template) -> tuple:
    """Layer-granular prefetch blocks: param leaves grouped by their
    TOP-LEVEL module key, in tree-flatten order.

    Returns ``(block_names, blocks)`` where ``blocks[k]`` is the list of
    flat-leaf indices belonging to block ``k``. This is THE block
    partitioner — the ZeRO-3 prefetch schedule, its HBM accounting
    (``Zero3Partition.accounting``), the memplan double-buffer row, and
    the COL001 lint pin all derive their block count from this one
    function, so they cannot disagree. It is a pure function of the tree
    STRUCTURE (paths, not shapes/values), which is why the linter can
    recompute it from the abstract state it audits: the flat scattered
    layout preserves the original pytree paths.
    """
    flat = jax.tree_util.tree_flatten_with_path(params_template)[0]
    names: list = []
    blocks: list = []
    index: dict = {}
    for i, (path, _leaf) in enumerate(flat):
        top = _path_str((path[0],)) if path else f"leaf{i}"
        k = index.get(top)
        if k is None:
            k = index[top] = len(blocks)
            names.append(top)
            blocks.append([])
        blocks[k].append(i)
    return names, blocks


class Zero3Partition(Zero1Partition):
    """ZeRO-3 parameter streaming: the endpoint arxiv 2004.13336 points
    at past its weight-update sharding — parameters live PERMANENTLY
    scattered in the same per-leaf flat padded update space the ZeRO-1
    partition defines (1/N param + 1/N optimizer HBM per chip), and the
    forward re-assembles them block by block over a double-buffered
    all-gather prefetch schedule
    (``parallel/collectives.py::prefetched_block_gather``).

    What changes vs :class:`Zero1Partition`:

    * ``TrainState.params`` keeps its pytree STRUCTURE but each leaf is
      the flat ``(padded,)`` 1-D array laid out ``P(axis)`` — exactly the
      layout the update-space opt leaves already use, so the PR 18 fused
      update kernels, the compressed reduce-scatter ring, and the
      checkpoint de-shard path all compose without modification.
    * The step's differentiation input is :meth:`stream_params`'s
      gathered tree. The gather sits OUTSIDE the grad closure: AD never
      sees it, so the backward is re-gather-free — gradients come out
      full-shaped and LOCAL (the all-gather of varying shards is varying
      on check_vma jax), which is precisely what ``reduce_scatter_mean``
      consumes. No transpose collective, no second gather.
    * :meth:`sharded_update` takes params that ARE already the local
      shards and returns the updated shards — the ZeRO-1 tail minus its
      ``local_shard`` slice at the front and minus the per-step
      ``gather_params`` at the back.
    * Checkpoints stay in the ONE de-sharded, device-count-independent
      layout (``deshard_state`` also unflattens params), so ``--resume``
      composes zero3 <-> zero1 <-> replicated and across device counts.
    """

    #: feature probe for the step builders / trainer routing: "params in
    #: TrainState are flat 1/N shards, stream them" (Zero1 reads False
    #: via getattr).
    scattered_params = True

    def __init__(self, tx, params_template, n_shards: int,
                 axis: str = DATA_AXIS, compress=None,
                 prefetch: bool = True):
        super().__init__(tx, params_template, n_shards, axis=axis,
                         compress=compress)
        # dtype-carrying abstract template of the ORIGINAL layout (the
        # slots only keep shapes) — accounting and shard/deshard need it
        self.param_template = jax.eval_shape(lambda p: p, params_template)
        self.prefetch = prefetch
        self.block_names, self.blocks = param_blocks(self.param_template)
        self.param_specs = jax.tree.map(
            lambda _s: P(axis), self.param_slots, is_leaf=_is_slot
        )

    # ---- in-graph (inside shard_map) ------------------------------------

    def stream_params(self, shard_tree, *, prefetch: Optional[bool] = None):
        """This device's flat param shards -> the full original-shape
        tree, gathered block by block on the prefetch schedule: block
        ``k+1``'s all-gather is issued and barrier-tied before block
        ``k``'s leaves reach their first consuming op, so the gather for
        the next layer rides under the current layer's compute with at
        most two blocks live in HBM. ``prefetch=False`` is the serialized
        injection the lint demo trips COL001 with — never the product
        path."""
        from tpu_ddp.parallel.collectives import prefetched_block_gather

        if prefetch is None:
            prefetch = self.prefetch
        leaves = jax.tree.leaves(shard_tree)
        blocks = [[leaves[i] for i in blk] for blk in self.blocks]
        gathered = prefetched_block_gather(blocks, self.axis,
                                           prefetch=prefetch)
        out = list(leaves)
        for blk, g in zip(self.blocks, gathered):
            for i, x in zip(blk, g):
                out[i] = x
        flat = jax.tree.unflatten(jax.tree.structure(shard_tree), out)
        return self.unflatten(flat)

    def sharded_update(self, grads, params, opt_state, residual=None,
                       with_error: bool = False):
        """The ZeRO-3 update tail: ``grads`` are the LOCAL full-shape
        gradients out of the re-gather-free backward; ``params`` the flat
        1/N shards straight from ``TrainState`` (no slice needed — they
        never stopped being shards); the return's ``new_params`` are the
        updated SHARDS (no gather — the next step's prefetch schedule is
        the only place params are ever re-assembled)."""
        gsh, err_state = self.reduce_scatter_mean(
            grads, residual, with_error=with_error)
        psh = params
        with jax.named_scope("tpu_ddp.zero3_shard_update"):
            fused = getattr(self.tx, "fused", None)
            if fused is not None:
                new_psh, updates, new_opt_state = fused.apply_sharded(
                    gsh, opt_state, psh, partition=self)
            else:
                updates, new_opt_state = self.tx.update(gsh, opt_state, psh)
                updates = self.mask_pad(updates)
                new_psh = optax.apply_updates(psh, updates)
        return new_psh, new_opt_state, gsh, updates, err_state

    def health_stats(self, *, loss, grad_shards, params, update_shards,
                     per_layer: bool = False, compress_error_sq=None):
        """Zero1's schema from FULLY scattered state: ``params`` here are
        this device's 1/N flat shards, so their norms psum over the axis
        too (zero1 skips that psum because its params are replicated).
        Every shard still reports the identical global number."""
        psum = lambda x: lax.psum(x, self.axis)  # noqa: E731
        pl = None
        if per_layer:
            pl = {
                "grad_norm": {
                    k: jnp.sqrt(psum(v))
                    for k, v in per_layer_sq(grad_shards).items()
                },
                "param_norm": {
                    k: jnp.sqrt(psum(v))
                    for k, v in per_layer_sq(params).items()
                },
            }
        return assemble_stats(
            loss=loss,
            grad_sq=psum(tree_sq(grad_shards)),
            grad_bad=psum(tree_nonfinite(grad_shards)),
            param_sq=psum(tree_sq(params)),
            update_sq=psum(tree_sq(update_shards)),
            update_bad=psum(tree_nonfinite(update_shards)),
            per_layer=pl,
            compress_error_sq=compress_error_sq,
        )

    # ---- specs / shardings (shard_map + device layout) ------------------

    def state_specs(self, *, batch_stats_spec: Optional[P] = None):
        """Like Zero1's, with params per-leaf ``P(axis)`` — the flat
        scattered layout IS the steady-state training layout."""
        from tpu_ddp.train.state import TrainState

        return TrainState(
            step=P(),
            params=self.param_specs,
            batch_stats=batch_stats_spec or P(),
            opt_state=self.opt_specs,
        )

    def state_shardings(self, state, mesh: Mesh):
        base = super().state_shardings(state, mesh)
        return base.replace(
            params=jax.tree.map(
                lambda _, spec: NamedSharding(mesh, spec),
                state.params, self.param_specs,
            ),
        )

    # ---- checkpoint interop (de-shard <-> shard) ------------------------

    def deshard_state(self, state):
        """Full TrainState -> the ONE de-sharded checkpoint layout: opt
        state via Zero1's path, params unpadded + reshaped back to their
        original shapes. A --zero3 checkpoint restores into a replicated,
        --zero1, or differently-sized --zero3 run byte-for-byte."""
        state = super().deshard_state(state)
        return state.replace(params=self.deshard_params(state.params))

    def shard_params(self, params, mesh: Mesh):
        """Original-layout params (fresh init or restored checkpoint) ->
        flat ``(padded,)`` leaves laid out ``P(axis)``: the permanent
        training layout."""
        shardings = jax.tree.map(
            lambda _s, spec: NamedSharding(mesh, spec),
            self.param_slots, self.param_specs, is_leaf=_is_slot,
        )
        scatter = self._jitted(
            ("shard_params", mesh), self.flatten, out_shardings=shardings,
        )
        return scatter(params)

    def shard_state(self, state, mesh: Mesh):
        """Full original-layout TrainState -> training layout: params AND
        opt state scattered (vs Zero1, which keeps params replicated)."""
        from tpu_ddp.parallel.mesh import replicated_sharding

        rep = replicated_sharding(mesh)
        return state.replace(
            step=jax.device_put(state.step, NamedSharding(mesh, P())),
            params=self.shard_params(state.params, mesh),
            batch_stats=jax.device_put(state.batch_stats, rep),
            opt_state=self.shard_opt_state(state.opt_state, mesh),
        )

    # ---- accounting (memplan / docs) ------------------------------------

    def accounting(self) -> dict:
        """Zero1's optimizer-state table plus the parameter story:
        replicated vs 1/N per-device param bytes, and the prefetch
        double-buffer high-water (the largest adjacent block pair's
        gathered bytes — the bounded live-gathered set the schedule
        guarantees)."""
        acct = super().accounting()
        slots = jax.tree.leaves(self.param_slots, is_leaf=_is_slot)
        leaves = jax.tree.leaves(self.param_template)
        block_of = {}
        for k, blk in enumerate(self.blocks):
            for i in blk:
                block_of[i] = k
        repl = shard = pad = 0
        block_bytes = [0] * len(self.blocks)
        for i, (slot, leaf) in enumerate(zip(slots, leaves)):
            item = jnp.dtype(leaf.dtype).itemsize
            repl += slot.size * item
            shard += (slot.padded // self.n_shards) * item
            pad += (slot.padded - slot.size) * item
            block_bytes[block_of[i]] += slot.padded * item
        if len(block_bytes) > 1:
            prefetch_hw = max(
                block_bytes[k] + block_bytes[k + 1]
                for k in range(len(block_bytes) - 1)
            )
        else:
            prefetch_hw = block_bytes[0] if block_bytes else 0
        acct.update({
            "params_bytes_replicated": int(repl),
            "params_bytes_per_device_sharded": int(shard),
            "params_padding_overhead_bytes_total": int(pad),
            "n_blocks": len(self.blocks),
            "block_names": list(self.block_names),
            "prefetch_buffer_bytes": int(prefetch_hw),
        })
        return acct


def clip_by_global_norm_sharded(
    max_norm: float, axis: str = DATA_AXIS
) -> optax.GradientTransformation:
    """``optax.clip_by_global_norm`` for gradients living as 1/N shards:
    the squared norm is psum'd over ``axis`` before the sqrt so every shard
    clips by the TRUE global norm — the replicated path's semantics
    exactly. Must run inside the shard_map (the psum needs the axis)."""

    def update_fn(updates, state, params=None):
        del params
        sq = sum(
            jnp.sum(jnp.square(x.astype(jnp.float32)))
            for x in jax.tree.leaves(updates)
        )
        g_norm = jnp.sqrt(lax.psum(sq, axis))
        trigger = g_norm < max_norm
        updates = jax.tree.map(
            lambda t: lax.select(
                trigger, t, (t / g_norm.astype(t.dtype)) * max_norm
            ),
            updates,
        )
        return updates, state

    return optax.GradientTransformation(
        lambda params: optax.EmptyState(), update_fn
    )
