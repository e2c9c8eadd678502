"""One token of the delta rule for every live lane, in Pallas, for TPU:
one pass over the live lanes' state and nothing else. Two entry points,
one a decay shape: `gated_delta_step` (ONE decay a head) and, at the
end of the file, `kda_step` (a decay a KEY CHANNEL); the ops of those
names (ops/delta_rule_ops.py) hold each one's plain reference.
state [S, H, dk, dv] float32 stays in HBM and is updated in place (the
output aliases it); a lane that takes no part is neither read nor
written. The step is bound by memory: a lane's state is read once and
written once (2 x H x dk x dv x 4 bytes) for a few hundred kFLOP.

The grid is (head groups, lanes). The lanes axis walks the LIVE lanes
only: `idx` (scalar-prefetched) lists them first, and every step past
the last live lane names that lane again, so its blocks are neither
fetched nor written back a second time and the body does nothing. A
block holds `hb` heads of one lane, about 1 MB, double-buffered by the
pipeline. Per head, on the vector unit in float32:

    S' = alpha S;  u = beta (v - S'^T k);  S = S' + k u^T;  o = S^T q

k and q arrive as columns ([dk, 1], broadcast along lanes) and alpha,
beta, v as rows ([1, dv], broadcast along sublanes; kda_step's alpha is
a third column), laid out so by the caller: kilobytes of XLA work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ['gated_delta_step', 'heads_per_block', 'kda_step']

_BLOCK_BYTES = 1 << 20


def heads_per_block(heads, dk, dv):
    """The most heads, a divisor of `heads`, whose state is at most
    _BLOCK_BYTES in VMEM (where dv is padded to whole 128-lane tiles)."""
    per_head = dk * (-(-dv // 128) * 128) * 4
    return max(hb for hb in range(1, heads + 1)
               if heads % hb == 0 and (hb == 1
                                       or hb * per_head <= _BLOCK_BYTES))


def _kernel(idx_ref, n_ref, cols_ref, rows_ref, s_ref, o_ref, so_ref, *, hb):
    i = pl.program_id(1)
    n = n_ref[0]

    @pl.when(i < n)
    def _():
        for h in range(hb):
            q = cols_ref[0, 0, :, h:h + 1]                    # [dk, 1]
            k = cols_ref[0, 0, :, hb + h:hb + h + 1]
            alpha = rows_ref[0, 0, h:h + 1, :]                # [1, dv]
            beta = rows_ref[0, 0, hb + h:hb + h + 1, :]
            v = rows_ref[0, 0, 2 * hb + h:2 * hb + h + 1, :]
            s = s_ref[0, h] * alpha                           # [dk, dv]
            u = beta * (v - jnp.sum(s * k, axis=0, keepdims=True))
            s = s + k * u
            so_ref[0, h] = s
            o_ref[0, 0, h:h + 1, :] = jnp.sum(s * q, axis=0, keepdims=True)

    # no live lane at all: the one block a head group visits goes back
    # as it came
    @pl.when(jnp.logical_and(n == 0, i == 0))
    def _():
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=('interpret',))
def gated_delta_step(state, q, k, v, beta, alpha, live, interpret=False):
    """state [S, H, dk, dv], q, k [S, H, dk] (normalised), v [S, H, dv],
    beta, alpha [S, H], live [S] bool -> (o [S, H, dv], state). Lanes
    with live False keep their state; their rows of o are zero."""
    S, H, dk, dv = state.shape
    hb = heads_per_block(H, dk, dv)
    G = H // hb
    n = jnp.sum(live.astype(jnp.int32))
    order = jnp.argsort(jnp.logical_not(live), stable=True)
    idx = order[jnp.minimum(jnp.arange(S), jnp.maximum(n - 1, 0))]

    def cols(a):                        # [S, H, dk] -> [S, G, dk, hb]
        return jnp.swapaxes(a.reshape(S, G, hb, dk), -1, -2)

    def rows(a):                        # [S, H] -> [S, G, hb, dv]
        return jnp.broadcast_to(a.reshape(S, G, hb, 1), (S, G, hb, dv))

    cols_in = jnp.concatenate([cols(q), cols(k)], axis=-1)
    rows_in = jnp.concatenate(
        [rows(alpha), rows(beta), v.reshape(S, G, hb, dv)], axis=-2)
    f32 = jnp.float32

    def lane(shape):
        return pl.BlockSpec((1, 1) + shape,
                            lambda g, i, idx, n: (idx[i], g, 0, 0))

    state_spec = pl.BlockSpec((1, hb, dk, dv),
                              lambda g, i, idx, n: (idx[i], g, 0, 0))
    o, new = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(G, S),
            in_specs=[lane((dk, 2 * hb)), lane((3 * hb, dv)), state_spec],
            out_specs=[lane((hb, dv)), state_spec]),
        out_shape=[jax.ShapeDtypeStruct((S, G, hb, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the state is updated where it lies; what no step visits stays
        input_output_aliases={4: 1},
        # in order: a block that is named again stays where it is
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary')),
        interpret=pltpu.InterpretParams() if interpret else False,
        name='gated_delta_step',
    )(idx.astype(jnp.int32), n.reshape(1), cols_in.astype(f32),
      rows_in.astype(f32), state)
    o = jnp.where(live[:, None, None], o.reshape(S, H, dv), 0.0)
    return o, new


# -- a decay a key channel ---------------------------------------------------

def _kda_kernel(idx_ref, n_ref, cols_ref, rows_ref, s_ref, o_ref, so_ref, *,
                hb):
    """_kernel with alpha a column [dk, 1] beside q and k: S' is the
    state with each ROW scaled, still one pass over the block."""
    i = pl.program_id(1)
    n = n_ref[0]

    @pl.when(i < n)
    def _():
        for h in range(hb):
            q = cols_ref[0, 0, :, h:h + 1]                    # [dk, 1]
            k = cols_ref[0, 0, :, hb + h:hb + h + 1]
            alpha = cols_ref[0, 0, :, 2 * hb + h:2 * hb + h + 1]
            beta = rows_ref[0, 0, h:h + 1, :]                 # [1, dv]
            v = rows_ref[0, 0, hb + h:hb + h + 1, :]
            s = s_ref[0, h] * alpha                           # [dk, dv]
            u = beta * (v - jnp.sum(s * k, axis=0, keepdims=True))
            s = s + k * u
            so_ref[0, h] = s
            o_ref[0, 0, h:h + 1, :] = jnp.sum(s * q, axis=0, keepdims=True)

    @pl.when(jnp.logical_and(n == 0, i == 0))
    def _():
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=('interpret',))
def kda_step(state, q, k, v, beta, alpha, live, interpret=False):
    """state [S, H, dk, dv], q, k (normalised), alpha [S, H, dk],
    v [S, H, dv], beta [S, H], live [S] bool -> (o [S, H, dv], state).
    Lanes with live False keep their state; their rows of o are zero."""
    S, H, dk, dv = state.shape
    hb = heads_per_block(H, dk, dv)
    G = H // hb
    n = jnp.sum(live.astype(jnp.int32))
    order = jnp.argsort(jnp.logical_not(live), stable=True)
    idx = order[jnp.minimum(jnp.arange(S), jnp.maximum(n - 1, 0))]

    def cols(a):                        # [S, H, dk] -> [S, G, dk, hb]
        return jnp.swapaxes(a.reshape(S, G, hb, dk), -1, -2)

    cols_in = jnp.concatenate([cols(q), cols(k), cols(alpha)], axis=-1)
    rows_in = jnp.concatenate(
        [jnp.broadcast_to(beta.reshape(S, G, hb, 1), (S, G, hb, dv)),
         v.reshape(S, G, hb, dv)], axis=-2)
    f32 = jnp.float32

    def lane(shape):
        return pl.BlockSpec((1, 1) + shape,
                            lambda g, i, idx, n: (idx[i], g, 0, 0))

    state_spec = pl.BlockSpec((1, hb, dk, dv),
                              lambda g, i, idx, n: (idx[i], g, 0, 0))
    o, new = pl.pallas_call(
        functools.partial(_kda_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(G, S),
            in_specs=[lane((dk, 3 * hb)), lane((2 * hb, dv)), state_spec],
            out_specs=[lane((hb, dv)), state_spec]),
        out_shape=[jax.ShapeDtypeStruct((S, G, hb, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary')),
        interpret=pltpu.InterpretParams() if interpret else False,
        name='kda_step',
    )(idx.astype(jnp.int32), n.reshape(1), cols_in.astype(f32),
      rows_in.astype(f32), state)
    o = jnp.where(live[:, None, None], o.reshape(S, H, dv), 0.0)
    return o, new
