"""One token of the Mamba-2 recurrence for every live lane, in Pallas,
for TPU: one pass over the live lanes' state and nothing else (the op
is `ssd_step`, ops/ssd_ops.py, whose plain composition is the
reference). The shape of pallas/gated_delta.py, for another rule.

state [S, H, P, N] float32 stays in HBM and is updated in place (the
output aliases it); a lane that takes no part is neither read nor
written. The step is bound by memory: a lane's state is read once and
written once (2 x H x P x N x 4 bytes) for H x P x N x 4 FLOP.

The grid is (head blocks, lanes). The lanes axis walks the LIVE lanes
only: `idx` (scalar-prefetched) lists them first, and every step past
the last live lane names that lane again, so its blocks are neither
fetched nor written back a second time and the body does nothing. A
block holds `hb` heads of one lane, about 1 MB, double-buffered by the
pipeline. Per head, on the vector unit in float32:

    h = a h + (dt x) (x) B;   y = h C

dt x arrives as a column ([P, 1], broadcast along lanes) and a, B, C as
rows ([1, N], broadcast along sublanes; B and C repeated for each head
of their group), laid out so by the caller, where that is a few
megabytes of XLA work beside the half gigabyte of state. The D x term
is the caller's too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ['ssd_step', 'supported', 'heads_per_block']

_BLOCK_BYTES = 1 << 20


def supported(heads, head_dim, groups, state):
    """Shapes the kernel tiles: a head's state is whole [8, 128] tiles."""
    return state % 128 == 0 and head_dim % 8 == 0 and heads % groups == 0


def heads_per_block(heads, head_dim, state):
    """The most heads, a divisor of `heads`, whose state is at most
    _BLOCK_BYTES."""
    per_head = head_dim * state * 4
    return max(hb for hb in range(1, heads + 1)
               if heads % hb == 0 and (hb == 1
                                       or hb * per_head <= _BLOCK_BYTES))


def _kernel(idx_ref, n_ref, cols_ref, rows_ref, s_ref, o_ref, so_ref, *, hb):
    i = pl.program_id(1)
    n = n_ref[0]

    @pl.when(i < n)
    def _():
        for h in range(hb):
            dx = cols_ref[0, 0, :, h:h + 1]                   # [P, 1]
            a = rows_ref[0, 0, h:h + 1, :]                    # [1, N]
            b = rows_ref[0, 0, hb + h:hb + h + 1, :]
            c = rows_ref[0, 0, 2 * hb + h:2 * hb + h + 1, :]
            s = s_ref[0, h] * a + dx * b                      # [P, N]
            so_ref[0, h] = s
            o_ref[0, 0, :, h:h + 1] = jnp.sum(s * c, axis=1, keepdims=True)

    # no live lane at all: the one block a head block visits goes back
    # as it came
    @pl.when(jnp.logical_and(n == 0, i == 0))
    def _():
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=('interpret',))
def ssd_step(state, x, b, c, dt, a, d, live, interpret=False):
    """state [S, H, P, N], x [S, H, P], b, c [S, G, N], dt, a [S, H]
    (a = exp(dt A)), d [H], live [S] bool -> (y [S, H, P], state).
    Lanes with live False keep their state; their rows of y are zero."""
    S, H, P, N = state.shape
    rep = H // b.shape[1]
    hb = heads_per_block(H, P, N)
    G = H // hb
    n = jnp.sum(live.astype(jnp.int32))
    order = jnp.argsort(jnp.logical_not(live), stable=True)
    idx = order[jnp.minimum(jnp.arange(S), jnp.maximum(n - 1, 0))]
    f32 = jnp.float32

    def rows(v):                        # [S, H, N] -> [S, G, hb, N]
        return v.reshape(S, G, hb, N)

    dx = (x * dt[..., None]).astype(f32)                      # [S, H, P]
    cols_in = jnp.swapaxes(dx.reshape(S, G, hb, P), -1, -2)   # [S,G,P,hb]
    rows_in = jnp.concatenate(
        [rows(jnp.broadcast_to(a[..., None], (S, H, N))),
         rows(jnp.repeat(b, rep, axis=1)),
         rows(jnp.repeat(c, rep, axis=1))], axis=-2).astype(f32)

    def lane(shape):
        return pl.BlockSpec((1, 1) + shape,
                            lambda g, i, idx, n: (idx[i], g, 0, 0))

    state_spec = pl.BlockSpec((1, hb, P, N),
                              lambda g, i, idx, n: (idx[i], g, 0, 0))
    o, new = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(G, S),
            in_specs=[lane((P, hb)), lane((3 * hb, N)), state_spec],
            out_specs=[lane((P, hb)), state_spec]),
        out_shape=[jax.ShapeDtypeStruct((S, G, P, hb), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the state is updated where it lies; what no step visits stays
        input_output_aliases={4: 1},
        # in order: a block that is named again stays where it is
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary')),
        interpret=pltpu.InterpretParams() if interpret else False,
        name='ssd_step',
    )(idx.astype(jnp.int32), n.reshape(1), cols_in, rows_in, state)
    y = jnp.swapaxes(o, -1, -2).reshape(S, H, P) + d[:, None] * x
    return jnp.where(live[:, None, None], y, 0.0), new
