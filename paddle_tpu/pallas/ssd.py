"""One token of the Mamba-2 recurrence for every live lane, in Pallas,
for TPU: one pass over the live lanes' state and nothing else (the op
is `ssd_step`, ops/ssd_ops.py, whose plain composition is the
reference). The shape of pallas/gated_delta.py, for another rule.

state [S, H, P, N] float32 stays in HBM and is updated in place (the
output aliases it); a lane that takes no part is neither read nor
written. The step is bound by memory: a lane's state is read once and
written once (2 x H x P x N x 4 bytes) for H x P x N x 4 FLOP.

The grid is (head blocks, lanes). A block holds as many heads of one
lane as fit VMEM in and out, double-buffered by the pipeline
(`heads_per_block`): the whole lane where it fits, so that a lane is
one step of the grid. The lanes axis walks the LIVE lanes only: `idx`
(scalar-prefetched) lists them first, and every step past the last live
lane names that lane again, so its blocks are neither fetched nor
written back a second time and the body does nothing.

Per head, on the vector unit in float32:

    h = a h + (dt x) (x) B;   y = h C

a [S, H] is read a scalar a head (SMEM), B and C [S, G, N] a row a
GROUP, each broadcast along sublanes for the H / G heads that share it,
and dt x a column a head ([P, 1], broadcast along lanes). The block is
walked a few heads a trip of a loop (`_unroll`), so the body does not
grow with the block. dt x arrives as [.., P, heads of the block], head
by head along the lanes, 32 KB a lane: a trip's columns are moved to a
scratch of their own first (a lane cannot be indexed by the loop's
counter).

y = h C is a sum along lanes for every row of every head. A trip's
products h * C are tiles of [8, 128] (its heads times P / 8): each is
reduced along its lanes alone and its eight sums set into a lane of
their own of one [8, 128] tile (`_lane_sums`: tile i's in lane i), which
is the trip's one dense store. The caller picks those lanes out and puts
y together (`ssd_step`); the D x term is the caller's too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .latent_prefill import _traced_from_nowhere
from .moe_experts import touched_ids

__all__ = ['ssd_step', 'supported', 'heads_per_block']

# the state's blocks in flight (one in, one out, each double-buffered): a
# quarter of the chip's 128 MiB of VMEM
_STATE_VMEM_BYTES = 32 << 20
# the most tiles of [8, 128] a trip of the body's loop holds at once: each
# is one of the 64 vector registers (and each tile's sums take a lane)
_TRIP_TILES = 64


def supported(heads, head_dim, groups, state):
    """Shapes the kernel tiles: a head's state is whole [8, 128] tiles, no
    more of them down its rows than a trip holds."""
    return state % 128 == 0 and head_dim % 8 == 0 \
        and heads % groups == 0 and head_dim // 8 <= _TRIP_TILES


def heads_per_block(heads, head_dim, state, groups=1):
    """The most heads, a divisor of `heads`, whose state fits
    `_STATE_VMEM_BYTES` four times over, and that are whole groups or lie
    inside one."""
    per_head, rep = head_dim * state * 4, heads // groups
    return max(hb for hb in range(1, heads + 1)
               if heads % hb == 0 and (hb % rep == 0 or rep % hb == 0)
               and (hb == 1 or 4 * hb * per_head <= _STATE_VMEM_BYTES))


def _unroll(hb, rep, head_dim):
    """Heads a trip: the most whose tiles are at most `_TRIP_TILES`, that
    divide the block and share one group."""
    return max(hu for hu in range(1, _TRIP_TILES // (head_dim // 8) + 1)
               if hb % hu == 0 and rep % hu == 0)


def _lane_sums(tiles):
    """tiles: at most 128 arrays [8, 128] -> one [8, 128] in which lane i
    of row r holds the sum of tile i's row r."""
    lane = jax.lax.broadcasted_iota(jnp.int32, tiles[0].shape, 1)
    out = jnp.zeros_like(tiles[0])
    for i, tile in enumerate(tiles):
        total = jnp.sum(tile, axis=1, keepdims=True)
        out = jax.lax.select(lane == i, jnp.broadcast_to(total, out.shape),
                             out)
    return out


def _kernel(idx_ref, n_ref, a_ref, cols_ref, b_ref, c_ref, s_ref, o_ref,
            so_ref, cols_scr, *, rep):
    i = pl.program_id(1)
    n = n_ref[0]
    trips, P, hu = cols_scr.shape
    N = s_ref.shape[3]
    many = b_ref.shape[1] > 1       # the block holds more than one group

    def trip(t, carry):
        first = t * hu
        grp = jax.lax.div(first, jnp.int32(rep)) if many else 0
        b, c = b_ref[0, grp], c_ref[0, grp]                   # [1, N]
        tiles = []
        for j in range(hu):
            h = first + j
            dx = cols_scr[t, :, j:j + 1]                      # [P, 1]
            s = s_ref[0, h] * a_ref[0, 0, 0, h] + dx * b      # [P, N]
            so_ref[0, h] = s
            sc = s * c
            rows = sc[:, :128]
            for k in range(1, N // 128):
                rows = rows + sc[:, 128 * k:128 * (k + 1)]
            tiles += [rows[8 * k:8 * (k + 1)] for k in range(P // 8)]
        o_ref[0, 0, t] = _lane_sums(tiles)
        return carry

    @pl.when(i < n)
    def _():
        for t in range(trips):          # static: these are lane offsets
            cols_scr[t] = cols_ref[0, 0, :, t * hu:(t + 1) * hu]
        jax.lax.fori_loop(0, trips, trip, 0)

    # no live lane at all: the one block a head block visits goes back
    # as it came
    @pl.when(jnp.logical_and(n == 0, i == 0))
    def _():
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def _vmem_bytes(hb, hu, P, N, gb):
    """What a step keeps in VMEM: the state's block in and out and the
    small operands (dt x, the block's heads padded to whole lane rows; y,
    a tile a trip; B and C, a group padded to 8 sublanes), all
    double-buffered, the trips' columns (a trip's heads padded to a lane
    row) and a trip's temporaries."""
    lanes = -(-hb // 128) * 128
    trips = hb // hu
    return (4 * hb * P * N * 4 + 2 * P * lanes * 4 + 2 * trips * 8 * 128 * 4
            + 4 * gb * 8 * N * 4 + trips * P * 128 * 4 + 4 * hu * P * N * 4)


@functools.partial(jax.jit, static_argnames=('interpret',))
def ssd_step(state, x, b, c, dt, a, d, live, interpret=False):
    """state [S, H, P, N], x [S, H, P], b, c [S, G, N], dt, a [S, H]
    (a = exp(dt A)), d [H], live [S] bool -> (y [S, H, P], state).
    Lanes with live False keep their state; their rows of y are zero."""
    S, H, P, N = state.shape
    G = b.shape[1]
    rep = H // G
    hb = heads_per_block(H, P, N, G)
    hu = _unroll(hb, rep, P)
    trips, tiles = hb // hu, hu * (P // 8)
    gb = max(1, hb // rep)              # groups a block holds
    # the live lanes first, the tail filled with the last of them: by rank,
    # not by a sort
    idx, n = touched_ids(live)
    f32 = jnp.float32
    # [S, H, P] -> [S, H / hb, P, hb]: a block's heads along the lanes
    cols_in = jnp.swapaxes(
        (x * dt[..., None]).astype(f32).reshape(S, H // hb, hb, P), -1, -2)

    def block(shape, of=lambda g: g, space=pltpu.VMEM):
        """Lane idx[i]'s part of an operand, for head block g."""
        return pl.BlockSpec(
            (1,) + shape,
            lambda g, i, idx, n: (idx[i], of(g)) + (0,) * (len(shape) - 1),
            memory_space=space)

    def group(g):                       # of head block g
        return jax.lax.div(g * hb, jnp.int32(gb * rep))

    row_spec = block((gb, 1, N), group)
    state_spec = block((hb, P, N))
    call = pl.pallas_call(
        functools.partial(_kernel, rep=rep),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(H // hb, S),
            in_specs=[block((1, 1, hb), space=pltpu.SMEM),
                      block((1, P, hb)), row_spec, row_spec, state_spec],
            out_specs=[block((1, trips, 8, 128)), state_spec],
            scratch_shapes=[pltpu.VMEM((trips, P, hu), f32)]),
        out_shape=[jax.ShapeDtypeStruct((S, H // hb, trips, 8, 128), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the state is updated where it lies; what no step visits stays
        input_output_aliases={6: 1},
        # in order: a block that is named again stays where it is
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary'),
            vmem_limit_bytes=min(100 << 20, max(
                32 << 20, _vmem_bytes(hb, hu, P, N, gb) + (8 << 20)))),
        interpret=pltpu.InterpretParams() if interpret else False,
        name='ssd_step')
    with _traced_from_nowhere():
        o, new = call(idx, n, a.astype(f32).reshape(S, H // hb, 1, hb),
                      cols_in, b.astype(f32).reshape(S, G, 1, N),
                      c.astype(f32).reshape(S, G, 1, N), state)
    # [.., trip, row r of a tile, lane] -> the tiles' lanes [.., trip, r,
    # head of the trip, tile k of the head] -> y[head, 8 k + r]
    sums = o[..., :tiles].reshape(S, H // hb, trips, 8, hu, P // 8)
    y = sums.transpose(0, 1, 2, 4, 5, 3).reshape(S, H, P) + d[:, None] * x
    return jnp.where(live[:, None, None], y, 0.0), new
