"""Quantized gradient collectives: block-scaled wire compression for the
DP-family gradient sync (``--grad-compress``).

The DP family's gradient sync moves full-precision f32 gradients over the
interconnect every step — the bandwidth-bound term at scale, and the whole
cost on cross-slice DCN where ICI-class bandwidth is unavailable. Following
EQuARX (arxiv 2506.17615, PAPERS.md: block-scaled quantized all-reduce
inside XLA is near-lossless), this module compresses the WIRE only:

- **block-scaled int8** — each ``block`` consecutive elements share one
  f32 scale (max-abs / 127); payload is 1 byte/element + 4 bytes/block,
  ~4x fewer wire bytes than f32;
- **bf16** — a cheap truncating cast, 2x fewer wire bytes, no scales;
- **f32** — identity payload: the debug/parity mode that anchors the ring
  schedule itself against ``lax.psum_scatter``/``lax.pmean``.

Accumulation stays f32 ON DEVICE in every mode (each ring hop dequantizes
before adding — an int8 accumulator would overflow immediately), so
compression error enters only where bytes cross the wire, once per hop.

Error feedback (``--grad-compress-error-feedback``): every device keeps a
residual tree holding the quantization error IT introduced (each hop's
``partial - dequant(quant(partial))`` is known to the sender); the
residual is added back into the local gradient the NEXT step, so the
error telescopes instead of accumulating — for a constant gradient the
sum of applied updates plus the final residual equals the true sum
exactly (pinned by tests/test_compression.py). The residual is carried as
extra state (``TrainState.grad_residual``), per-device like the ZeRO-1
optimizer shards — never replicated — and checkpoints carry it.

Non-finite sentinels survive compression BY CONSTRUCTION: a NaN/Inf in a
block drives that block's max-abs scale non-finite, and dequantization
multiplies by the raw scale — so poisoned gradients still dequantize
non-finite and the numerics flight recorder (``health/stats.py``) sees
them exactly as it does uncompressed.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_ddp.parallel.mesh import DATA_AXIS

#: Wire modes the config surface accepts ("none" = feature off).
MODES = ("none", "bf16", "int8")

#: Modes the compressor itself implements ("f32" is the test/parity
#: anchor: same ring schedule, identity payload).
RING_MODES = ("f32", "bf16", "int8")


@dataclasses.dataclass(frozen=True)
class GradCompression:
    """Static wire-compression configuration a step builder compiles in.

    ``mode``: ring payload dtype ("int8" block-scaled / "bf16" cast /
    "f32" identity — the parity anchor). ``block``: elements per int8
    scale block. ``error_feedback``: carry the per-device residual and
    add it back next step. ``kernels``: route the int8 payload ops
    through the fused Pallas quantize / dequantize-accumulate kernels
    (``ops/fused_quant.py`` — bit-identical wire bytes and residuals);
    fails closed to the jnp path on backends without Pallas support
    (``GradCompressor`` probes at build time, lint's KRN001 names the
    fallback)."""

    mode: str = "int8"
    block: int = 256
    error_feedback: bool = False
    kernels: bool = False

    def __post_init__(self):
        if self.mode not in RING_MODES:
            raise ValueError(
                f"unknown grad-compress mode {self.mode!r}; valid ring "
                f"modes: {', '.join(RING_MODES)}"
            )
        if self.block < 1:
            raise ValueError(
                f"grad_compress_block must be >= 1, got {self.block}"
            )


# ---- block-scaled payloads (pure, shape-static) --------------------------


def _n_blocks(size: int, block: int) -> int:
    return -(-size // block)


def quantize_chunk(x, mode: str, block: int) -> dict:
    """1-D f32 chunk -> wire payload dict. int8 payloads are padded up to
    a whole number of blocks (the pad quantizes to exact zeros); the
    ``scale`` leaf carries one f32 per block. NaN/Inf inputs drive the
    block scale non-finite on purpose (sentinel preservation — module
    docstring)."""
    if mode == "f32":
        return {"q": x}
    if mode == "bf16":
        return {"q": x.astype(jnp.bfloat16)}
    size = x.shape[0]
    nb = _n_blocks(size, block)
    pad = nb * block - size
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
    xb = x.reshape(nb, block)
    scale = jnp.max(jnp.abs(xb), axis=1) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(xb / safe[:, None]), -127, 127).astype(jnp.int8)
    return {"q": q.reshape(-1), "scale": scale}


def dequantize_chunk(payload: dict, mode: str, block: int, size: int):
    """Inverse of ``quantize_chunk``: payload -> f32 (size,). Multiplies
    by the RAW scale (not the zero-guarded one) so non-finite blocks
    dequantize non-finite."""
    if mode == "f32":
        return payload["q"]
    if mode == "bf16":
        return payload["q"].astype(jnp.float32)
    nb = _n_blocks(size, block)
    xb = payload["q"].reshape(nb, block).astype(jnp.float32)
    return (xb * payload["scale"][:, None]).reshape(-1)[:size]


def chunk_wire_bytes(size: int, mode: str, block: int) -> int:
    """Static bytes-on-wire for one chunk payload (q + scales)."""
    if mode == "f32":
        return size * 4
    if mode == "bf16":
        return size * 2
    nb = _n_blocks(size, block)
    return nb * block * 1 + nb * 4


# ---- flat update space (same padding arithmetic as parallel/zero.py) -----


@dataclasses.dataclass(frozen=True)
class _Slot:
    shape: tuple
    size: int
    padded: int


def _leaf_slot(leaf, n_shards: int) -> _Slot:
    shape = tuple(leaf.shape)
    size = 1
    for d in shape:
        size *= d
    return _Slot(shape=shape, size=size, padded=size + ((-size) % n_shards))


def _flat_leaf(x, slot: _Slot):
    x = jnp.reshape(x, (-1,))
    if slot.padded != slot.size:
        x = jnp.concatenate([x, jnp.zeros((slot.padded - slot.size,), x.dtype)])
    return x


def _unflat_leaf(x, slot: _Slot):
    return jnp.reshape(x[: slot.size], slot.shape)


class GradCompressor:
    """Static layout + in-graph entry points for one (model, data-axis)
    pair — the compression analogue of ``Zero1Partition``.

    Each param leaf flattens to 1-D zero-padded to a multiple of
    ``n_shards`` (the SAME arithmetic as the ZeRO-1 update space, which is
    what lets the compressed ring drop into
    ``Zero1Partition.reduce_scatter_mean`` leaf-for-leaf); the ring
    collectives then chunk each leaf N-ways and quantize every hop's
    payload. Built from concrete params or ``ShapeDtypeStruct`` templates
    (the deviceless path in ``tools/memplan.py`` is abstract-only).
    """

    def __init__(self, config: GradCompression, params_template,
                 n_shards: int, axis: str = DATA_AXIS):
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        self.config = config
        self.n_shards = n_shards
        self.axis = axis
        # the EFFECTIVE kernel switch: requested AND executable here
        # (fail closed — KRN001 reports when these differ)
        self.kernels = bool(config.kernels)
        if self.kernels:
            from tpu_ddp.ops import kernel_available

            self.kernels = (config.mode == "int8"
                            and kernel_available("fused_quant")
                            and kernel_available("fused_dequant"))
        template = jax.eval_shape(lambda p: p, params_template)
        self.slots = jax.tree.map(
            lambda leaf: _leaf_slot(leaf, n_shards), template
        )

    # ---- flat update space ----------------------------------------------

    def flatten(self, tree):
        return jax.tree.map(_flat_leaf, tree, self.slots,
                            is_leaf=lambda x: isinstance(x, _Slot))

    def unflatten(self, flat_tree):
        return jax.tree.map(_unflat_leaf, flat_tree, self.slots)

    def varying(self, params):
        """Params as differentiation input (same convention as
        ``Zero1Partition.varying``): the replicated params are pcast to
        varying so AD yields LOCAL gradients — the compressed ring IS the
        sync."""
        return jax.tree.map(
            lambda p: lax.pcast(p, (self.axis,), to="varying"), params
        )

    # ---- in-graph (inside shard_map) ------------------------------------

    def _with_residual(self, flat, residual):
        if residual is None:
            return flat
        return jax.tree.map(lambda x, r: x + r[0], flat, residual)

    def all_reduce_mean(self, grads, residual=None, with_error: bool = False):
        """Local grad tree -> globally AVERAGED full tree via the
        compressed ring all-reduce — the drop-in replacement for the
        explicit grad pmean. Returns ``(grads, err_state)`` where
        ``err_state`` (when ``with_error``) is the new residual in state
        layout (leaves ``(1, padded)``) — pass it back in as ``residual``
        next step for error feedback."""
        from tpu_ddp.parallel.collectives import ring_all_reduce

        flat = self._with_residual(self.flatten(grads), residual)
        leaves, treedef = jax.tree.flatten(flat)
        outs, errs = [], []
        for x in leaves:
            out, err = ring_all_reduce(
                x, self.axis, mode=self.config.mode,
                block=self.config.block, with_error=with_error,
                kernels=self.kernels,
            )
            outs.append(out / self.n_shards)
            errs.append(err)
        grads_out = self.unflatten(jax.tree.unflatten(treedef, outs))
        err_state = None
        if with_error:
            err_state = jax.tree.unflatten(
                treedef, [e[None] for e in errs])
        return grads_out, err_state

    def reduce_scatter_mean_flat(self, flat, residual=None,
                                 with_error: bool = False):
        """Already-flattened (padded 1-D) tree -> this shard's 1/N slice
        of the globally averaged gradient via the compressed ring — the
        ZeRO-1 composition point (``Zero1Partition.reduce_scatter_mean``
        delegates here; its per-leaf padding is the same arithmetic)."""
        from tpu_ddp.parallel.collectives import ring_reduce_scatter

        flat = self._with_residual(flat, residual)
        leaves, treedef = jax.tree.flatten(flat)
        outs, errs = [], []
        for x in leaves:
            out, err = ring_reduce_scatter(
                x, self.axis, mode=self.config.mode,
                block=self.config.block, with_error=with_error,
                kernels=self.kernels,
            )
            outs.append(out / self.n_shards)
            errs.append(err)
        shards = jax.tree.unflatten(treedef, outs)
        err_state = None
        if with_error:
            err_state = jax.tree.unflatten(
                treedef, [e[None] for e in errs])
        return shards, err_state

    def error_sq(self, err_state) -> jnp.ndarray:
        """Sum of squares of the freshly-introduced quantization error,
        psum'd over the ring axis — the in-graph scalar behind the
        flight recorder's ``compress_error_norm`` (every shard reports
        the identical global number)."""
        total = jnp.zeros((), jnp.float32)
        for leaf in jax.tree.leaves(err_state):
            total = total + jnp.sum(jnp.square(leaf.astype(jnp.float32)))
        return lax.psum(total, self.axis)

    # ---- residual state (host side) -------------------------------------

    def residual_template(self):
        """Abstract residual tree: one f32 ``(n_shards, padded)`` leaf per
        param leaf — row i is device i's residual (spec ``P(axis)``)."""
        return jax.tree.map(
            lambda slot: jax.ShapeDtypeStruct(
                (self.n_shards, slot.padded), jnp.float32),
            self.slots, is_leaf=lambda x: isinstance(x, _Slot),
        )

    def residual_shardings(self, mesh: Mesh):
        sh = NamedSharding(mesh, P(self.axis))
        return jax.tree.map(lambda _: sh, self.residual_template())

    def deshard_residual(self, residual):
        """State-layout residual -> PARAM-layout tree: the device-count-
        independent form checkpoints persist (docs/resilience.md).

        The per-device rows are summed first: what error feedback
        carries is the TOTAL un-applied quantization error (each device
        adds its own row into its local grads before the ring sums
        them, so the ring folds in exactly the row-sum). The sum — not
        the rows — is the layout-independent quantity, which is what
        lets an 8-device run's residual resume on 4 devices without
        losing carried error."""
        return self.unflatten(jax.tree.map(
            lambda r: jnp.sum(r.astype(jnp.float32), axis=0), residual))

    def shard_residual(self, param_tree, mesh: Mesh):
        """PARAM-layout residual -> this run's state layout
        (``(n_shards, padded)`` rows, ``P(axis)``): the whole carried
        error lands on row 0 and the other rows start at zero — row 0's
        device folds it back on the next sync, so the total error the
        de-shard summed is conserved bit-for-bit across a device-count
        change (re-splitting it across rows would change nothing
        mathematically and cost a reshard broadcast)."""
        flat = self.flatten(jax.tree.map(
            lambda x: jnp.asarray(x, jnp.float32), param_tree))
        shardings = self.residual_shardings(mesh)
        with mesh:
            return jax.jit(
                lambda t: jax.tree.map(
                    lambda x: jnp.concatenate(
                        [x[None],
                         jnp.zeros((self.n_shards - 1,) + x.shape,
                                   jnp.float32)]),
                    t),
                out_shardings=shardings,
            )(flat)

    def init_residual(self, mesh: Mesh):
        """Fresh all-zero residual laid out ``P(axis)`` on the mesh."""
        shardings = self.residual_shardings(mesh)
        with mesh:
            return jax.jit(
                lambda: jax.tree.map(
                    lambda t: jnp.zeros(t.shape, t.dtype),
                    self.residual_template()),
                out_shardings=shardings,
            )()

    # ---- accounting (telemetry / memplan / docs) -------------------------

    def accounting(self) -> dict:
        """Static per-step per-device wire-byte accounting: what the ring
        moves in this mode vs the same ring in f32 — the numbers behind
        the ``comm/grad_bytes_*`` telemetry counters and the docs/PERF.md
        table. ``all_reduce`` covers the plain-DP sync (ring RS + all-
        gather phases); ``reduce_scatter`` the ZeRO-1 composition (the
        params all-gather ZeRO-1 already pays is unchanged and excluded)."""
        n = self.n_shards
        mode, block = self.config.mode, self.config.block
        rs_wire = rs_base = ag_wire = ag_base = 0
        for slot in jax.tree.leaves(
            self.slots, is_leaf=lambda x: isinstance(x, _Slot)
        ):
            chunk = slot.padded // n
            # RS phase: n-1 hops, one chunk payload per hop per device;
            # AG phase (all-reduce only): each device's reduced chunk is
            # relayed around the ring — n-1 chunk payloads per device.
            rs_wire += (n - 1) * chunk_wire_bytes(chunk, mode, block)
            rs_base += (n - 1) * chunk * 4
            ag_wire += (n - 1) * chunk_wire_bytes(chunk, mode, block)
            ag_base += (n - 1) * chunk * 4
        return {
            "mode": mode,
            "block": block,
            "n_shards": n,
            "error_feedback": self.config.error_feedback,
            "all_reduce_bytes_on_wire_per_device": int(rs_wire + ag_wire),
            "all_reduce_bytes_f32_per_device": int(rs_base + ag_base),
            "reduce_scatter_bytes_on_wire_per_device": int(rs_wire),
            "reduce_scatter_bytes_f32_per_device": int(rs_base),
            "compression_ratio": (
                round((rs_base + ag_base) / (rs_wire + ag_wire), 2)
                if rs_wire + ag_wire else None
            ),
        }


def wire_bytes_table(params_template, n_shards: int, *,
                     block: int = 256) -> dict:
    """Static per-step wire-bytes table across every mode x {plain DP,
    ZeRO-1 reduce-scatter} — backs ``tools/memplan.py --grad-compress``
    and the docs/PERF.md table. Pure accounting; no compile, no devices."""
    table: dict = {"n_shards": n_shards, "block": block, "modes": {}}
    for mode in RING_MODES:
        comp = GradCompressor(
            GradCompression(mode=mode, block=block),
            params_template, n_shards,
        )
        acct = comp.accounting()
        table["modes"][mode] = {
            "dp_all_reduce_bytes_per_device": (
                acct["all_reduce_bytes_on_wire_per_device"]),
            "zero1_reduce_scatter_bytes_per_device": (
                acct["reduce_scatter_bytes_on_wire_per_device"]),
        }
    f32 = table["modes"]["f32"]
    for mode, row in table["modes"].items():
        row["dp_ratio_vs_f32"] = round(
            f32["dp_all_reduce_bytes_per_device"]
            / row["dp_all_reduce_bytes_per_device"], 2)
        row["zero1_ratio_vs_f32"] = round(
            f32["zero1_reduce_scatter_bytes_per_device"]
            / row["zero1_reduce_scatter_bytes_per_device"], 2)
    return table
