"""Ops of the Mamba-2 mixer (state-space duality, arXiv:2405.21060):
`ssd_chunk`, `ssd_step` and the grouped gated norm `gated_group_norm`.
The mixer's convolution is `short_conv` (ops/delta_rule_ops.py) with
its optional bias.

The recurrence, for one head of size P with state size N, state h
[P, N] (float32, zero at a stream's start), token t:

    dt_t = softplus(dt~_t + dt_bias);   a_t = exp(dt_t A),  A = -exp(A_log)
    h_t  = a_t h_{t-1} + dt_t x_t (x) B_t;      y_t = h_t C_t + D x_t

x_t [P] is the head's slice of the convolved channels; B_t, C_t [N]
belong to the head's GROUP (H / G heads share one pair). Both ops take
the layer's raw tensors (the convolved xBC channels side by side, the
dt logits) and split and gate them here (`ssd_inputs`), so the chunk
form and the step form cannot drift apart.

The state lives in a scope variable [slots, H, P, N] that the paged
programs update in place, as the delta rule's does. No op here has a
gradient.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..registry import register_op, op_emitter

_HI = jax.lax.Precision.HIGHEST


def ssd_inputs(xbc, dt, a_log, dt_bias, heads, head_dim, groups, state):
    """xbc [..., H P + 2 G N] (x, B, C side by side), dt [..., H] -> x
    [..., H, P], B, C [..., G, N], dt [..., H] (after softplus),
    log a [..., H] (<= 0), all float32."""
    lead = xbc.shape[:-1]
    xbc = xbc.astype(jnp.float32)
    inner, gn = heads * head_dim, groups * state
    x = xbc[..., :inner].reshape(lead + (heads, head_dim))
    b = xbc[..., inner:inner + gn].reshape(lead + (groups, state))
    c = xbc[..., inner + gn:].reshape(lead + (groups, state))
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + dt_bias.astype(jnp.float32))
    return x, b, c, dt, -jnp.exp(a_log.astype(jnp.float32)) * dt


def _per_head(a, heads):
    """[..., G, N] -> [..., H, N]: each group's row for its heads."""
    return jnp.repeat(a, heads // a.shape[-2], axis=-2)


def ssd_step(h, x, b, c, dt, log_a, d):
    """One token on state h [..., H, P, N]: x [..., H, P], b, c
    [..., G, N], dt, log_a [..., H], d [H] -> (y [..., H, P], new h).
    Elementwise float32: no product is rounded."""
    heads = x.shape[-2]
    b, c = _per_head(b, heads), _per_head(c, heads)
    h = h * jnp.exp(log_a)[..., None, None] \
        + (dt[..., None] * x)[..., None] * b[..., None, :]
    y = jnp.sum(h * c[..., None, :], axis=-1) + d[:, None] * x
    return y, h


def ssd_chunk(h0, x, b, c, dt, log_a, d, block):
    """The recurrence over T tokens of one stream from state h0
    [H, P, N], in blocks of `block` tokens (T a multiple of it): x
    [T, H, P], b, c [T, G, N], dt, log_a [T, H] -> (y [T, H, P], state).

    Within a block, with s the running sum of log a inside it
    (arXiv:2405.21060, section 6):
        y  = tril((C B^T) exp(s_i - s_j)) (dt x)  +  e^{s} C h  + D x
        h' = e^{s_last} h + (B e^{s_last - s})^T (dt x)
    C B^T is per GROUP; the decay and the products with x per head.
    Every product runs at precision "highest": they feed float32 state
    that lives for thousands of tokens, and they are a hundredth of the
    layer's projections."""
    t, heads, p = x.shape
    groups, n = b.shape[1:]
    nb = t // block
    rep = heads // groups
    mm = functools.partial(jnp.einsum, precision=_HI)

    def blocks(a):                      # [T, ...] -> [nb, block, ...]
        return a.reshape((nb, block) + a.shape[1:])

    xb, bb, cb = blocks(x), blocks(b), blocks(c)
    s = jnp.cumsum(blocks(log_a), axis=1)                 # [nb, L, H]
    dx = xb * blocks(dt)[..., None]                       # [nb, L, H, P]
    row = jnp.arange(block)
    causal = (row[:, None] >= row[None, :])[None, :, :, None]
    # masked before the exp: above the diagonal s_i - s_j is positive
    # and may overflow
    decay = jnp.exp(jnp.where(causal, s[:, :, None] - s[:, None], -jnp.inf))
    cbt = mm('zigs,zjgs->zijg', cb, bb)                   # [nb, L, L, G]
    attn = jnp.repeat(cbt, rep, axis=-1) * decay          # [nb, L, L, H]
    y_in = mm('zijh,zjhp->zihp', attn, dx)
    c_h, b_h = _per_head(cb, heads), _per_head(bb, heads)  # [nb, L, H, N]
    c_in = c_h * jnp.exp(s)[..., None]
    s_last = s[:, -1]                                     # [nb, H]
    b_out = b_h * jnp.exp(s_last[:, None] - s)[..., None]

    def step(h, xs):
        c_i, b_o, dx_b, sl = xs
        y = mm('lhs,hps->lhp', c_i, h)
        h = h * jnp.exp(sl)[:, None, None] + mm('lhs,lhp->hps', b_o, dx_b)
        return h, y

    h, y_st = jax.lax.scan(step, h0, (c_in, b_out, dx, s_last))
    y = y_in + y_st + d[None, None, :, None] * xb
    return y.reshape(t, heads, p), h


def _ssd_attrs(op):
    return (int(op.attr('heads')), int(op.attr('head_dim')),
            int(op.attr('groups')), int(op.attr('state')))


@op_emitter('ssd_chunk')
def _ssd_chunk_emit(ctx, op):
    """The chunked recurrence over a run of tokens. XBC [B, T, H P +
    2 G N], DT [B, T, H], ALog, DtBias, D [H] -> Out [B, T, H P].
    Without State every row of the batch starts from zero and nothing is
    kept. With State [slots, H, P, N], Slot, Len, Reset [1] (B = 1): the
    chunk starts from the slot's state (zero if Reset) and leaves the
    state after row Len - 1 there; rows from Len on neither decay nor
    write (dt 0, a 1), so a padded tail leaves the state untouched."""
    heads, p, groups, n = _ssd_attrs(op)
    block = int(op.attr('block', 128))
    xbc = ctx.get(op.single_input('XBC'))
    bsz, t = xbc.shape[:2]
    d = ctx.get(op.single_input('D')).astype(jnp.float32)
    x, b, c, dt, log_a = ssd_inputs(
        xbc, ctx.get(op.single_input('DT')),
        ctx.get(op.single_input('ALog')), ctx.get(op.single_input('DtBias')),
        heads, p, groups, n)
    state = slot = None
    h0 = jnp.zeros((bsz, heads, p, n), jnp.float32)
    if op.input('State'):
        state = ctx.get(op.single_input('State'))
        slot = ctx.get(op.single_input('Slot')).astype(jnp.int32).reshape(())
        length = ctx.get(op.single_input('Len')).astype(jnp.int32) \
            .reshape(())
        reset = ctx.get(op.single_input('Reset')).astype(bool).reshape(())
        h0 = jnp.where(reset, 0.0, state[slot])[None]
        live = (jnp.arange(t) < length)[None, :, None]
        dt, log_a = jnp.where(live, dt, 0.0), jnp.where(live, log_a, 0.0)
    block = min(block, -(-t // 8) * 8)
    pad = -t % block
    if pad:                    # whole blocks: the tail neither decays
        x, b, c, dt, log_a = (  # nor writes
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (x, b, c, dt, log_a))
    y, h = jax.vmap(functools.partial(ssd_chunk, d=d, block=block))(
        h0, x, b, c, dt, log_a)
    ctx.set(op.single_output('Out'),
            y[:, :t].reshape(bsz, t, heads * p).astype(xbc.dtype))
    if state is not None:
        ctx.set(op.single_output('StateOut'), state.at[slot].set(h[0]))


def ssd_step_reference(state, x, b, c, dt, log_a, d, live):
    """The step op's plain composition: state [S, H, P, N], one token a
    lane, live [S] bool -> (y [S, H, P], state with the live lanes'
    updated)."""
    y, new = ssd_step(state, x, b, c, dt, log_a, d)
    return y, jnp.where(live[:, None, None, None], new, state)


@op_emitter('ssd_step')
def _ssd_step_emit(ctx, op):
    """One token a lane. XBC [S, 1, H P + 2 G N], DT [S, 1, H], ALog,
    DtBias, D [H], State [S, H, P, N], Live [S] -> Out [S, 1, H P],
    StateOut. A lane with Live 0 keeps its state, and its output row is
    not meant to be read.

    On a TPU (or under FLAGS_pallas_interpret) the Pallas kernel makes
    one pass over the live lanes' state and skips the others
    (pallas/ssd.py); everywhere else the plain composition above runs,
    which is what the CPU tests compare with the reference. Which of the
    two an emission took is counted in ops.ssd_step.kernel /
    ops.ssd_step.fallback."""
    from ..flags import get_flag
    from ..obs import telemetry
    from ..pallas import ssd as _ssd
    heads, p, groups, n = _ssd_attrs(op)
    xbc = ctx.get(op.single_input('XBC'))
    state = ctx.get(op.single_input('State'))
    live = ctx.get(op.single_input('Live')).astype(bool)
    d = ctx.get(op.single_input('D')).astype(jnp.float32)
    x, b, c, dt, log_a = ssd_inputs(
        xbc[:, 0], ctx.get(op.single_input('DT'))[:, 0],
        ctx.get(op.single_input('ALog')), ctx.get(op.single_input('DtBias')),
        heads, p, groups, n)
    on_tpu = jax.default_backend() == 'tpu'
    if (on_tpu or bool(get_flag('pallas_interpret'))) \
            and _ssd.supported(heads, p, groups, n):
        telemetry.counter('ops.ssd_step.kernel').inc()
        y, new = _ssd.ssd_step(state, x, b, c, dt, jnp.exp(log_a), d, live,
                               interpret=not on_tpu)
    else:
        telemetry.counter('ops.ssd_step.fallback').inc()
        y, new = ssd_step_reference(state, x, b, c, dt, log_a, d, live)
    ctx.set(op.single_output('Out'),
            y.reshape(y.shape[0], 1, heads * p).astype(xbc.dtype))
    ctx.set(op.single_output('StateOut'), new)


def _ssd_infer(op, block):
    xbc = block.var_recursive(op.single_input('XBC'))
    out = block.var_recursive(op.single_output('Out'))
    out.shape = tuple(xbc.shape[:-1]) + (
        int(op.attr('heads')) * int(op.attr('head_dim')),)
    out.dtype = xbc.dtype
    if op.output('StateOut'):
        state = block.var_recursive(op.single_input('State'))
        so = block.var_recursive(op.single_output('StateOut'))
        so.shape, so.dtype = state.shape, state.dtype


# -- the grouped gated norm -------------------------------------------------

@op_emitter('gated_group_norm')
def _gated_group_norm_emit(ctx, op):
    """RMSNorm of X * silu(Z) over each of `groups` equal groups of the
    last axis, in float32, times Scale [n] (one gain a channel):
    X, Z [..., n] -> Y [..., n]."""
    x = ctx.get(op.single_input('X'))
    z = ctx.get(op.single_input('Z'))
    scale = ctx.get(op.single_input('Scale')).astype(jnp.float32)
    groups = int(op.attr('groups'))
    y = x.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    g = y.reshape(y.shape[:-1] + (groups, y.shape[-1] // groups))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + op.attr('epsilon', 1e-5))
    ctx.set(op.single_output('Y'),
            (g.reshape(y.shape) * scale).astype(x.dtype))


def _same_as_x_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    y = block.var_recursive(op.single_output('Y'))
    y.shape, y.dtype = x.shape, x.dtype


def _no_backward(op_type):
    def maker(op, block):
        raise NotImplementedError(
            'op %s has no backward: the state-space mixer is built for '
            'serving only' % op_type)
    return maker


register_op('ssd_chunk', infer_shape=_ssd_infer,
            grad=_no_backward('ssd_chunk'))
register_op('ssd_step', infer_shape=_ssd_infer, no_grad=True)
register_op('gated_group_norm', infer_shape=_same_as_x_infer,
            grad=_no_backward('gated_group_norm'))
