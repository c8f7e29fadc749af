"""The state-space scan of a Mamba-2 mixer in its chunked form (state space
duality, arXiv:2405.21060), forward and a backward pass written by hand.

For each sequence and each head ``h`` of group ``g`` the recurrence is

    S_t = a_t S_{t-1} + dt_t x_t B_t^T        (P x N, float32)
    y_t = S_t C_t                             a_t = exp(dt_t A_h),  A_h < 0

``ssd_scan(x, dt, A, B, C)`` computes it a chunk of ``chunk`` positions at a
time. Inside a chunk the outputs are one masked product: with ``la`` the
running sum of ``dt A`` from the chunk's start,

    y_i = sum_{j <= i} exp(la_i - la_j) (C_i . B_j) dt_j x_j + exp(la_i) S_0 C_i

and between chunks only the P x N state at each chunk's start is carried,
``S_0`` of the next chunk being ``exp(la_L) S_0 + sum_j exp(la_L - la_j) dt_j
x_j B_j^T``. The products run on the matrix unit in the operands' type with
float32 accumulation; decays, running sums and the carried states are
float32 whatever the operands are.

The backward pass is a ``custom_vjp`` whose residuals are the operands and
the states at the chunk boundaries, (T / chunk) P N floats a head, and not
any chunk's score block: it builds each chunk's block again. The gradient of
``la`` is read off the outputs, ``dla_i = dy_i . y_i - x_i . dx_i`` inside a
chunk plus ``<dS, S>`` at the chunk's end, each in float32 from float32
accumulators, so the cancellation between the two terms stays inside one
chunk.

``jax.numpy`` throughout, under the scopes ``tpu_ddp.kernel.ssd_scan_fwd``
and ``tpu_ddp.kernel.ssd_scan_bwd``. A length that is not whole chunks is
padded with ``dt = 0``: a padded position decays nothing and adds nothing.
``ssd_scan_stepwise`` is the recurrence itself, one position at a time, for
the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from tpu_ddp.telemetry.phases import kernel_scope

CHUNK = 128
_HIGHEST = lax.Precision.HIGHEST


def _dot(spec, a, b, dtype):
    """One einsum on the matrix unit: operands in ``dtype``, accumulated and
    returned in float32."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def _chunked(x, dt, A, B, C, chunk):
    """The operands cut into chunks and groups: ``x`` (b, c, l, g, r, p),
    ``dt`` (b, c, l, g, r) and ``A`` (g, r) in float32, ``B`` and ``C``
    (b, c, l, g, n)."""
    b, t, h, p = x.shape
    g, n = B.shape[2:]
    c = t // chunk
    return (x.reshape(b, c, chunk, g, h // g, p),
            dt.astype(jnp.float32).reshape(b, c, chunk, g, h // g),
            A.astype(jnp.float32).reshape(g, h // g),
            B.reshape(b, c, chunk, g, n), C.reshape(b, c, chunk, g, n))


def _decays(dt, A):
    """``la`` (b, c, g, r, l): the running sum of ``dt A`` from the chunk's
    start; ``within`` (b, c, g, r, i, j) = ``exp(la_i - la_j)`` for ``j <=
    i``, else 0; ``between`` (b, g, r, c, z) = the decay from the end of
    chunk ``z`` to the start of chunk ``c`` for ``z < c``, else 0."""
    la = jnp.cumsum(jnp.moveaxis(dt * A, 2, -1), axis=-1)
    chunk = la.shape[-1]
    seen = jnp.tril(jnp.ones((chunk, chunk), bool))
    within = jnp.exp(jnp.where(
        seen, la[..., :, None] - la[..., None, :], -jnp.inf))
    whole = jnp.moveaxis(la[..., -1], 1, -1)            # (b, g, r, c)
    ends = jnp.cumsum(whole, axis=-1)
    starts = ends - whole
    chunks = whole.shape[-1]
    earlier = jnp.tril(jnp.ones((chunks, chunks), bool), -1)
    between = jnp.exp(jnp.where(
        earlier, starts[..., :, None] - ends[..., None, :], -jnp.inf))
    return la, within, between


def _outputs(x, dt, B, C, la, within, between, dtype):
    """(y float32 (b, c, l, g, r, p), the state at each chunk's start
    (b, c, g, r, p, n) float32)."""
    scores = _dot("bcign,bcjgn->bcgij", C, B, dtype)
    weights = scores[:, :, :, None] * within
    xdt = x.astype(jnp.float32) * dt[..., None]
    y = _dot("bcgrij,bcjgrp->bcigrp", weights, xdt, dtype)
    to_end = jnp.moveaxis(jnp.exp(la[..., -1:] - la), -1, 2)   # (b,c,l,g,r)
    added = _dot("bcjgrp,bcjgn->bcgrpn", xdt * to_end[..., None], B, dtype)
    starts = jnp.einsum("bgrcz,bzgrpn->bcgrpn", between, added,
                        precision=_HIGHEST)
    carried = _dot("bcign,bcgrpn->bcigrp", C, starts, dtype)
    y = y + carried * jnp.moveaxis(jnp.exp(la), -1, 2)[..., None]
    return y, starts, (weights, xdt, to_end, added)


def _forward(x, dt, A, B, C, chunk):
    with jax.named_scope(kernel_scope("ssd_scan_fwd")):
        xs, dts, As, Bs, Cs = _chunked(x, dt, A, B, C, chunk)
        la, within, between = _decays(dts, As)
        y, starts, _ = _outputs(xs, dts, Bs, Cs, la, within, between,
                                x.dtype)
        return y.reshape(x.shape).astype(x.dtype), starts


def _backward(chunk, res, dy):
    x, dt, A, B, C, starts = res
    dtype = x.dtype
    with jax.named_scope(kernel_scope("ssd_scan_bwd")):
        xs, dts, As, Bs, Cs = _chunked(x, dt, A, B, C, chunk)
        dys = dy.reshape(xs.shape)
        la, within, between = _decays(dts, As)
        y, _, (weights, xdt, to_end, added) = _outputs(
            xs, dts, Bs, Cs, la, within, between, dtype)
        from_start = jnp.moveaxis(jnp.exp(la), -1, 2)        # (b,c,l,g,r)
        x32, dy32 = xs.astype(jnp.float32), dys.astype(jnp.float32)

        # what the states at the chunks' ends are worth: each chunk's own
        # use of its start state, carried back through the later chunks
        used = _dot("bcigrp,bcign->bcgrpn", dy32 * from_start[..., None], Cs,
                    dtype)
        d_ends = jnp.einsum("bgrzc,bzgrpn->bcgrpn", between, used,
                            precision=_HIGHEST)
        whole = jnp.exp(la[..., -1])[..., None, None]        # (b,c,g,r,1,1)
        ends = whole * starts + added

        u = (_dot("bcgrij,bcigrp->bcjgrp", weights, dys, dtype)
             + _dot("bcgrpn,bcjgn->bcjgrp", d_ends, Bs, dtype)
             * to_end[..., None])
        dx = dts[..., None] * u
        direct = jnp.sum(x32 * u, axis=-1)       # d dt through dt_j x_j
        pairs = _dot("bcigrp,bcjgrp->bcgrij", dys, xs, dtype) * within
        pairs = jnp.sum(pairs * jnp.moveaxis(dts, 2, -1)[..., None, :],
                        axis=3)                              # (b,c,g,i,j)
        dC = (_dot("bcgij,bcjgn->bcign", pairs, Bs, dtype)
              + _dot("bcgrpn,bcigrp->bcign", starts,
                     dy32 * from_start[..., None], dtype))
        dB = (_dot("bcgij,bcign->bcjgn", pairs, Cs, dtype)
              + _dot("bcgrpn,bcjgrp->bcjgn", d_ends,
                     xdt * to_end[..., None], dtype))

        dla = jnp.sum(dy32 * y, axis=-1) - dts * direct      # (b,c,l,g,r)
        at_end = jnp.sum(d_ends * ends, axis=(-1, -2))       # (b,c,g,r)
        dla = dla.at[:, :, -1].add(at_end)
        later = jnp.flip(jnp.cumsum(jnp.flip(dla, 2), axis=2), 2)
        ddt = direct + As * later
        dA = jnp.sum(dts * later, axis=(0, 1, 2))
        return (dx.reshape(x.shape).astype(x.dtype),
                ddt.reshape(dt.shape).astype(dt.dtype),
                dA.reshape(A.shape).astype(A.dtype),
                dB.reshape(B.shape).astype(B.dtype),
                dC.reshape(C.shape).astype(C.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(x, dt, A, B, C, chunk):
    return _forward(x, dt, A, B, C, chunk)[0]


def _scan_fwd(x, dt, A, B, C, chunk):
    y, starts = _forward(x, dt, A, B, C, chunk)
    return y, (x, dt, A, B, C, starts)


_scan.defvjp(_scan_fwd, _backward)


def ssd_scan(x, dt, A, B, C, chunk: int = CHUNK):
    """``x`` (b, T, H, P) inputs, ``dt`` (b, T, H) step sizes (after their
    softplus), ``A`` (H,) negative decay rates, ``B`` and ``C`` (b, T, G, N)
    with ``H % G == 0``, the heads of a group sharing them. Returns ``y``
    (b, T, H, P) in ``x``'s type: the recurrence's outputs, without the skip
    ``D x``, which is the mixer's."""
    # under ``shard_map`` a parameter (``A``) is the same on every shard
    # and the activations are not: the backward rule's ``dA`` is a shard's
    # own, so ``A`` is marked varying here and AD's transpose of that mark
    # is the sum over shards
    varying = frozenset().union(*(jax.typeof(a).vma for a in (x, dt, B, C)))
    x, dt, A, B, C = (
        lax.pcast(a, tuple(varying - jax.typeof(a).vma), to="varying")
        if varying - jax.typeof(a).vma else a for a in (x, dt, A, B, C))
    t = x.shape[1]
    chunk = min(chunk, t)
    pad = -t % chunk
    if not pad:
        return _scan(x, dt, A, B, C, chunk)
    x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                   for a in (x, dt, B, C))
    return _scan(x, dt, A, B, C, chunk)[:, :t]


def ssd_scan_stepwise(x, dt, A, B, C):
    """The recurrence one position at a time, float32: what ``ssd_scan``
    has to equal, values and gradients."""
    b, t, h, p = x.shape
    g, n = B.shape[2:]
    f32 = lambda a: jnp.moveaxis(a.astype(jnp.float32), 1, 0)  # noqa: E731
    heads = lambda a: jnp.repeat(a, h // g, axis=1)            # noqa: E731

    def step(state, now):
        xt, dtt, Bt, Ct = now
        decay = jnp.exp(dtt * A.astype(jnp.float32))           # (b, h)
        state = (decay[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None]
                 * heads(Bt)[:, :, None, :])
        return state, jnp.sum(state * heads(Ct)[:, :, None, :], axis=-1)

    _, y = lax.scan(step, jnp.zeros((b, h, p, n), jnp.float32),
                    (f32(x), f32(dt), f32(B), f32(C)))
    return jnp.moveaxis(y, 0, 1).astype(x.dtype)
