"""Ops of the delta-rule blocks (Gated DeltaNet, arXiv:2412.06464; the
chunked form arXiv:2406.06484; Kimi Delta Attention, arXiv:2510.26692)
and of the RMSNorm blocks around them: `rms_norm`, `short_conv`,
`gated_delta_chunk` / `gated_delta_step` (ONE decay a head: the Olmo
hybrid) and, at the end of the file, `kda_chunk` / `kda_step` (a decay
a KEY CHANNEL: models/solar_open2.py). One head, key size dk, value
size dv, state S [dk, dv] (float32, zero at a stream's start), token t:

    S' = alpha_t S_{t-1};  u_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T;  o_t = S_t^T q_t

with q = q / |q| * dk^-1/2, k = k / |k|, beta_t = beta_scale *
sigmoid(b_t) and alpha_t = exp(-exp(A_log) * softplus(a_t + dt_bias)),
a scalar a head (gated_delta_*) or Diag of dk values (kda_*). The ops
take the layer's raw tensors (QKV after the short convolution, the
gate logits) and do the normalisation and the gates themselves, so a
chunk form and its step form cannot drift apart in them.
Recurrent state lives in scope variables that the paged programs
update in place, as they do the K/V pools: the delta state
[slots, H, dk, dv] and the convolution's last K-1 input rows
[slots, K-1, C]; a model whose recurrent layers keep those rows alone
(models/lfm2.py) keeps them BY THE PAGE, in a pool [pages, K-1, C] its
streams reach through their page tables (`short_conv`'s paged forms).
No op here has a gradient: `append_backward` over one
of them raises an error that names it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..registry import register_op, op_emitter, register_vjp_grad

_HI = jax.lax.Precision.HIGHEST


# -- rms_norm ---------------------------------------------------------------

@op_emitter('rms_norm')
def _rms_norm_emit(ctx, op):
    """X [..., n] over its axes from begin_norm_axis on, in float32:
    x / sqrt(mean(x^2) + epsilon) * Scale."""
    x = ctx.get(op.single_input('X'))
    begin = op.attr('begin_norm_axis', 1)
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=tuple(range(begin, x.ndim)), keepdims=True)
    y = xf * jax.lax.rsqrt(ms + op.attr('epsilon', 1e-6))
    if op.input('Scale'):
        y = y * ctx.get(op.single_input('Scale')).reshape(
            [1] * begin + list(x.shape[begin:]))
    ctx.set(op.single_output('Y'), y.astype(x.dtype))


def _rms_norm_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    y = block.var_recursive(op.single_output('Y'))
    y.shape, y.dtype = x.shape, x.dtype


register_op('rms_norm', infer_shape=_rms_norm_infer)
register_vjp_grad('rms_norm', in_slots=('X', 'Scale'), out_slots=('Y',))


# -- short_conv -------------------------------------------------------------

_CONV_ACT = {'silu': jax.nn.silu, 'none': lambda v: v}


def _conv_rows(xx, w, n, bias=None, act='silu'):
    """xx [..., n + K - 1, C], w [K, C] -> `act` (silu, or none) of the
    causal depthwise convolution (plus `bias` [C], where the layer has
    one), [..., n, C]: row t reads xx[t .. t + K - 1], the last of them
    the token's own."""
    k = w.shape[0]
    acc = sum(xx[..., j:j + n, :] * w[j] for j in range(k))
    if bias is not None:
        acc = acc + bias
    return _CONV_ACT[act](acc)


def _paged_conv_chunk(pool, x, w, bias, act, table, positions, n, pt):
    """The paged chunk form: x [1, T, C] at positions[0].., of which n
    rows are live -> (out [1, T, C], pool). The rows before the chunk
    are those of the page that holds the token before its first (zeros
    at position 0); every page the live rows touch takes the K-1 inputs
    before its new fill point, the page's end or the chunk's."""
    k, t = w.shape[0], x.shape[1]
    table = table.reshape(-1)
    start = positions[0]
    last = table.shape[0] - 1
    prev = jnp.where(start > 0,
                     pool[table[jnp.clip((start - 1) // pt, 0, last)]], 0.0)
    xx = jnp.concatenate([prev, x[0].astype(pool.dtype)], axis=0)
    j = start // pt + jnp.arange(-(-t // pt) + 1, dtype=jnp.int32)
    fill = jnp.minimum(start + n, (j + 1) * pt)
    # rows fill - start .. of xx are the inputs fill - (K-1) .. fill - 1
    at = jnp.clip(fill - start, 0, t)[:, None] + jnp.arange(k - 1)[None, :]
    touched = (j * pt < start + n) & (j <= last)
    page = jnp.where(touched, table[jnp.clip(j, 0, last)], pool.shape[0])
    return (_conv_rows(xx, w, t, bias, act)[None],
            pool.at[page].set(xx[at], mode='drop'))


def _paged_conv_step(pool, x, w, bias, act, table, positions, live, pt):
    """The paged step form: x [S, 1, C], one token a lane at positions
    [S]. A lane reads the rows of the page that holds the token before
    its own and leaves its new rows on the page its token lands on (the
    same page, or the next: a lane that enters a page carries its rows
    forward into it); a dead lane writes nothing."""
    lanes = jnp.arange(table.shape[0], dtype=jnp.int32)
    last = table.shape[1] - 1
    prev = jnp.where(
        (positions > 0)[:, None, None],
        pool[table[lanes, jnp.clip((positions - 1) // pt, 0, last)]], 0.0)
    xx = jnp.concatenate([prev, x.astype(pool.dtype)], axis=1)
    page = jnp.where(live, table[lanes, jnp.clip(positions // pt, 0, last)],
                     pool.shape[0])
    return (_conv_rows(xx, w, 1, bias, act),
            pool.at[page].set(xx[:, 1:], mode='drop'))


@op_emitter('short_conv')
def _short_conv_emit(ctx, op):
    """Causal depthwise convolution of kernel K over the sequence, then
    attr `activation` ('silu' where not given; 'none': the LFM2 mixer,
    whose convolution sits between two gates). X [B, T, C], W [K, C],
    optionally Bias [C] (added before the activation); row t is sum_j
    W[j] x[t - (K-1) + j]. Five forms, by the inputs given:

    whole sequence  no State, no Pool: zeros stand before each row of
                    the batch.
    chunk           State [slots, K-1, C], Slot [1], Len [1], Reset [1],
                    X [1, T, C]: the rows before the chunk are the
                    slot's (zeros if Reset), and the slot's rows become
                    the last K-1 inputs before row Len, so a padded tail
                    leaves nothing behind.
    step            State, Live [S], X [S, 1, C]: every lane is its own
                    slot; lanes with Live 0 keep their rows.
    paged chunk     Pool [pages, K-1, C], Table [1, P], Positions [T],
                    Len [1], attr page_tokens, X [1, T, C]: the rows
                    live BY THE PAGE, an entry the K-1 inputs before its
                    page's fill point, found through the stream's page
                    table as its K/V rows are (_paged_conv_chunk).
    paged step      Pool, Table [S, P], Positions [S], Live [S], X
                    [S, 1, C] (_paged_conv_step).
    """
    x = ctx.get(op.single_input('X'))
    w = ctx.get(op.single_input('W')).astype(x.dtype)
    k = w.shape[0]
    t = x.shape[1]
    act = op.attr('activation', 'silu')
    bias = ctx.get(op.single_input('Bias')).astype(x.dtype) \
        if op.input('Bias') else None
    if op.input('Pool'):
        pool = ctx.get(op.single_input('Pool'))
        table = ctx.get(op.single_input('Table')).astype(jnp.int32)
        positions = ctx.get(op.single_input('Positions')).astype(jnp.int32)
        pt = int(op.attr('page_tokens'))
        if op.input('Live'):
            live = ctx.get(op.single_input('Live')).astype(bool)
            out, pool = _paged_conv_step(pool, x, w, bias, act, table,
                                         positions, live, pt)
        else:
            n = ctx.get(op.single_input('Len')).astype(jnp.int32).reshape(())
            out, pool = _paged_conv_chunk(pool, x, w, bias, act, table,
                                          positions, n, pt)
        ctx.set(op.single_output('Out'), out.astype(x.dtype))
        ctx.set(op.single_output('PoolOut'), pool)
        return
    if not op.input('State'):
        xx = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
        ctx.set(op.single_output('Out'), _conv_rows(xx, w, t, bias, act))
        return
    state = ctx.get(op.single_input('State'))
    if op.input('Live'):
        live = ctx.get(op.single_input('Live')).astype(bool)
        xx = jnp.concatenate([state, x.astype(state.dtype)], axis=1)
        ctx.set(op.single_output('Out'), _conv_rows(xx, w, 1, bias, act))
        ctx.set(op.single_output('StateOut'),
                jnp.where(live[:, None, None], xx[:, 1:], state))
        return
    slot = ctx.get(op.single_input('Slot')).astype(jnp.int32).reshape(())
    n = ctx.get(op.single_input('Len')).astype(jnp.int32).reshape(())
    reset = ctx.get(op.single_input('Reset')).astype(bool).reshape(())
    prev = jnp.where(reset, 0.0, state[slot])
    xx = jnp.concatenate([prev, x[0].astype(state.dtype)], axis=0)
    ctx.set(op.single_output('Out'), _conv_rows(xx, w, t, bias, act)[None])
    # rows [n, n + K - 1) of xx are inputs n - (K-1) .. n - 1
    tail = jax.lax.dynamic_slice_in_dim(xx, n, k - 1, axis=0)
    ctx.set(op.single_output('StateOut'), state.at[slot].set(tail))


def _short_conv_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    out = block.var_recursive(op.single_output('Out'))
    out.shape, out.dtype = x.shape, x.dtype
    if op.output('StateOut'):
        state = block.var_recursive(op.single_input('State'))
        so = block.var_recursive(op.single_output('StateOut'))
        so.shape, so.dtype = state.shape, state.dtype
    if op.output('PoolOut'):
        pool = block.var_recursive(op.single_input('Pool'))
        po = block.var_recursive(op.single_output('PoolOut'))
        po.shape, po.dtype = pool.shape, pool.dtype


# -- the gated delta rule ---------------------------------------------------

def delta_inputs(qkv, ba, a_log, dt_bias, heads, dk, dv, beta_scale):
    """The layer's raw tensors -> what the rule runs on, in float32:
    qkv [..., H*(2 dk + dv)] (q, k, v side by side), ba [..., 2H] (the
    write-strength logits, then the decay logits) -> q, k [..., H, dk]
    normalised, v [..., H, dv], beta [..., H], log_alpha [..., H] (<= 0).
    """
    lead = qkv.shape[:-1]
    qkv = qkv.astype(jnp.float32)
    q = qkv[..., :heads * dk].reshape(lead + (heads, dk))
    k = qkv[..., heads * dk:2 * heads * dk].reshape(lead + (heads, dk))
    v = qkv[..., 2 * heads * dk:].reshape(lead + (heads, dv))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
        * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    ba = ba.astype(jnp.float32)
    beta = beta_scale * jax.nn.sigmoid(ba[..., :heads])
    log_alpha = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
        ba[..., heads:] + dt_bias.astype(jnp.float32))
    return q, k, v, beta, log_alpha


def delta_step(s, q, k, v, beta, log_alpha):
    """One token of the rule on state s [..., dk, dv]: q, k [..., dk],
    v [..., dv], beta, log_alpha [...] -> (o [..., dv], new state).
    Elementwise float32: no product is rounded."""
    s = s * jnp.exp(log_alpha)[..., None, None]
    u = beta[..., None] * (v - jnp.sum(s * k[..., None], axis=-2))
    s = s + k[..., None] * u[..., None, :]
    return jnp.sum(s * q[..., None], axis=-2), s


def delta_chunk(s0, q, k, v, beta, g, block):
    """The rule over T tokens of one stream from state s0 [H, dk, dv],
    in blocks of `block` tokens (T a multiple of it): q, k [T, H, dk],
    v [T, H, dv], beta, g = log alpha [T, H] -> (o [T, H, dv], state).

    Within a block (arXiv:2406.06484, with the decay of 2412.06464):
    with c the running sum of g inside the block and L the strictly
    lower part of beta_i (k_i . k_j) exp(c_i - c_j), the block's
    corrections are the solution of (I + L) [W | U] = [beta k e^c |
    beta v]; from the state S entering the block,
        v' = U - W S
        o  = (q e^c) S + tril(q k^T exp(c_i - c_j)) v'
        S  = e^{c_last} S + (k e^{c_last - c})^T v'.
    Every product runs at precision "highest": they feed float32 state
    that lives for thousands of tokens, and they are a hundredth of
    the layer's projections."""
    t, heads, dk = q.shape
    dv = v.shape[-1]
    n = t // block
    mm = functools.partial(jnp.matmul, precision=_HI)

    def blocks(a):                      # [T, H, ...] -> [n, H, block, ...]
        a = a.reshape((n, block) + a.shape[1:])
        return jnp.moveaxis(a, 1, 2)

    q, k, v = blocks(q), blocks(k), blocks(v)       # [n, H, C, d]
    beta, g = blocks(beta), blocks(g)               # [n, H, C]
    c = jnp.cumsum(g, axis=-1)
    decay = jnp.exp(c[..., :, None] - c[..., None, :])        # [n,H,C,C]
    row = jnp.arange(block)
    strict = row[:, None] > row[None, :]
    kb = k * beta[..., None]
    lower = jnp.where(strict, mm(kb, jnp.swapaxes(k, -1, -2)) * decay, 0.0)
    rhs = jnp.concatenate([kb * jnp.exp(c)[..., None],
                           v * beta[..., None]], axis=-1)
    wu = jax.lax.linalg.triangular_solve(
        lower + jnp.eye(block, dtype=lower.dtype), rhs,
        left_side=True, lower=True, unit_diagonal=True)
    w, u = wu[..., :dk], wu[..., dk:]
    attn = jnp.where(row[:, None] >= row[None, :],
                     mm(q, jnp.swapaxes(k, -1, -2)) * decay, 0.0)
    q_in = q * jnp.exp(c)[..., None]
    c_last = c[..., -1:]
    k_out = k * jnp.exp(c_last - c)[..., None]

    def step(s, xs):
        w_b, u_b, attn_b, q_b, k_b, cl = xs
        v_new = u_b - mm(w_b, s)                               # [H, C, dv]
        o = mm(q_b, s) + mm(attn_b, v_new)
        s = s * jnp.exp(cl)[..., None] + mm(jnp.swapaxes(k_b, -1, -2), v_new)
        return s, o

    s, o = jax.lax.scan(step, s0, (w, u, attn, q_in, k_out, c_last))
    return jnp.moveaxis(o, 1, 2).reshape(t, heads, dv), s


def _delta_attrs(op):
    return (int(op.attr('heads')), int(op.attr('key_dim')),
            int(op.attr('value_dim')), float(op.attr('beta_scale', 1.0)))


@op_emitter('gated_delta_chunk')
def _gated_delta_chunk_emit(ctx, op):
    """The chunked rule over a run of tokens. QKV [B, T, H*(2dk+dv)],
    BA [B, T, 2H], ALog, DtBias [H] -> Out [B, T, H*dv]. Without State
    every row of the batch starts from zero and nothing is kept. With
    State [slots, H, dk, dv], Slot, Len, Reset [1] (B = 1): the chunk
    starts from the slot's state (zero if Reset) and leaves the state
    after row Len - 1 there; rows from Len on neither decay nor write
    (alpha 1, beta 0), so a padded tail leaves the state untouched."""
    heads, dk, dv, beta_scale = _delta_attrs(op)
    block = int(op.attr('block', 64))
    qkv = ctx.get(op.single_input('QKV'))
    b, t = qkv.shape[:2]
    q, k, v, beta, g = delta_inputs(
        qkv, ctx.get(op.single_input('BA')),
        ctx.get(op.single_input('ALog')), ctx.get(op.single_input('DtBias')),
        heads, dk, dv, beta_scale)
    state = slot = None
    s0 = jnp.zeros((b, heads, dk, dv), jnp.float32)
    if op.input('State'):
        state = ctx.get(op.single_input('State'))
        slot = ctx.get(op.single_input('Slot')).astype(jnp.int32).reshape(())
        n = ctx.get(op.single_input('Len')).astype(jnp.int32).reshape(())
        reset = ctx.get(op.single_input('Reset')).astype(bool).reshape(())
        s0 = jnp.where(reset, 0.0, state[slot])[None]
        live = (jnp.arange(t) < n)[None, :, None]
        beta, g = jnp.where(live, beta, 0.0), jnp.where(live, g, 0.0)
    pad = -t % block
    if pad:                    # whole blocks: the tail neither decays
        q, k, v, beta, g = (   # nor writes
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, beta, g))
    o, s = jax.vmap(functools.partial(delta_chunk, block=block))(
        s0, q, k, v, beta, g)
    ctx.set(op.single_output('Out'),
            o[:, :t].reshape(b, t, heads * dv).astype(qkv.dtype))
    if state is not None:
        ctx.set(op.single_output('StateOut'), state.at[slot].set(s[0]))


def gated_delta_step_reference(state, q, k, v, beta, g, live):
    """The step op's plain composition: state [S, H, dk, dv], q, k
    [S, H, dk], v [S, H, dv], beta, g [S, H], live [S] bool -> (o
    [S, H, dv], state with the live lanes' updated)."""
    o, new = delta_step(state, q, k, v, beta, g)
    return o, jnp.where(live[:, None, None, None], new, state)


@op_emitter('gated_delta_step')
def _gated_delta_step_emit(ctx, op):
    """One token a lane. QKV [S, 1, H*(2dk+dv)], BA [S, 1, 2H], ALog,
    DtBias [H], State [S, H, dk, dv], Live [S] -> Out [S, 1, H*dv],
    StateOut. A lane with Live 0 keeps its state, and its output row is
    not meant to be read.

    On a TPU (or under FLAGS_pallas_interpret) the Pallas kernel makes
    one pass over the live lanes' state and skips the others
    (pallas/gated_delta.py); everywhere else the plain composition
    above runs, which is what the CPU tests compare with the
    reference."""
    from ..flags import get_flag
    from ..pallas import gated_delta as _gd
    heads, dk, dv, beta_scale = _delta_attrs(op)
    qkv = ctx.get(op.single_input('QKV'))
    state = ctx.get(op.single_input('State'))
    live = ctx.get(op.single_input('Live')).astype(bool)
    q, k, v, beta, g = delta_inputs(
        qkv[:, 0], ctx.get(op.single_input('BA'))[:, 0],
        ctx.get(op.single_input('ALog')), ctx.get(op.single_input('DtBias')),
        heads, dk, dv, beta_scale)
    on_tpu = jax.default_backend() == 'tpu'
    if on_tpu or bool(get_flag('pallas_interpret')):
        o, new = _gd.gated_delta_step(state, q, k, v, beta, jnp.exp(g),
                                      live, interpret=not on_tpu)
    else:
        o, new = gated_delta_step_reference(state, q, k, v, beta, g, live)
    ctx.set(op.single_output('Out'),
            o.reshape(o.shape[0], 1, heads * dv).astype(qkv.dtype))
    ctx.set(op.single_output('StateOut'), new)


def _delta_infer(op, block):
    qkv = block.var_recursive(op.single_input('QKV'))
    out = block.var_recursive(op.single_output('Out'))
    out.shape = tuple(qkv.shape[:-1]) + (
        int(op.attr('heads')) * int(op.attr('value_dim')),)
    out.dtype = qkv.dtype
    if op.output('StateOut'):
        state = block.var_recursive(op.single_input('State'))
        so = block.var_recursive(op.single_output('StateOut'))
        so.shape, so.dtype = state.shape, state.dtype


def _no_backward(op_type):
    def maker(op, block):
        raise NotImplementedError(
            'op %s has no backward: the gated delta rule and its short '
            'convolution are built for serving only' % op_type)
    return maker


for _t, _infer in (('short_conv', _short_conv_infer),
                   ('gated_delta_chunk', _delta_infer)):
    register_op(_t, infer_shape=_infer, grad=_no_backward(_t))
register_op('gated_delta_step', infer_shape=_delta_infer, no_grad=True)


# -- the rule with a decay a key channel (Kimi Delta Attention) --------------
#
# alpha_t is Diag(exp(g_t)), g_t [dk] <= 0: S' = exp(g_t)[:, None] * S.
# Ops of their own beside gated_delta_*, whose programs stay what they
# were: the chunk form below is other algebra (one [C, C] decay matrix
# a head no longer factors out of the products), and an execution of
# either is found in a trace by its own op type.

def kda_inputs(qkv, gate, b, a_log, dt_bias, heads, dk, dv, beta_scale):
    """The layer's raw tensors -> what the rule runs on, in float32:
    qkv [..., H*(2 dk + dv)] (q, k, v side by side, after the short
    convolution), gate [..., H*dk] (the decay logits, a value a key
    channel), b [..., H] (the write-strength logits), a_log [H],
    dt_bias [H*dk] -> q, k [..., H, dk] normalised, v [..., H, dv],
    beta [..., H], g [..., H, dk] (log alpha, <= 0)."""
    lead = qkv.shape[:-1]
    qkv = qkv.astype(jnp.float32)
    q = qkv[..., :heads * dk].reshape(lead + (heads, dk))
    k = qkv[..., heads * dk:2 * heads * dk].reshape(lead + (heads, dk))
    v = qkv[..., 2 * heads * dk:].reshape(lead + (heads, dv))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
        * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    beta = beta_scale * jax.nn.sigmoid(b.astype(jnp.float32))
    g = -jnp.exp(a_log.astype(jnp.float32))[:, None] * jax.nn.softplus(
        (gate.astype(jnp.float32) + dt_bias.astype(jnp.float32))
        .reshape(lead + (heads, dk)))
    return q, k, v, beta, g


def kda_step(s, q, k, v, beta, g):
    """One token of the rule on state s [..., dk, dv]: q, k, g [..., dk],
    v [..., dv], beta [...] -> (o [..., dv], new state). Elementwise
    float32: no product is rounded."""
    s = s * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - jnp.sum(s * k[..., None], axis=-2))
    s = s + k[..., None] * u[..., None, :]
    return jnp.sum(s * q[..., None], axis=-2), s


def _decayed_products(xs, k, c, sub):
    """For each x of `xs` [..., C, dk] the matrix P_ij = sum_d x_id k_jd
    exp(c_id - c_jd) for i >= j (0 above the diagonal), [..., C, C],
    with c [..., C, dk] the running sum of the log decays inside the
    block. Every exponent evaluated is <= 0: inside a sub-block of
    `sub` tokens the differences c_i - c_j (i >= j) themselves; from a
    sub-block to an earlier one through the boundary r in front of
    row i's sub-block, (x_i e^{c_i - c_r}) . (k_j e^{c_r - c_j}) with
    j <= r < i. exp(-c) alone, which overflows float32 after a few
    hundred tokens of a fast channel, is never formed."""
    lead, (n, dk) = c.shape[:-2], c.shape[-2:]
    a = n // sub
    mm = functools.partial(jnp.matmul, precision=_HI)
    row = jnp.arange(sub)
    inside = row[:, None] >= row[None, :]

    def subs(t):                         # [..., C, dk] -> [..., a, sub, dk]
        return t.reshape(lead + (a, sub, dk))

    cs, ks = subs(c), subs(k)
    decay = jnp.exp(jnp.where(
        inside[:, :, None], cs[..., :, None, :] - cs[..., None, :, :],
        -jnp.inf))                                  # [..., a, sub, sub, dk]
    # c at the boundary in front of each sub-block (0: the block's start)
    edge = jnp.concatenate(
        [jnp.zeros_like(cs[..., :1, -1, :]), cs[..., :-1, -1, :]], axis=-2)
    before = jnp.arange(n)[None, :] < (jnp.arange(a) * sub)[:, None]
    k_in = k[..., None, :, :] * jnp.exp(jnp.where(
        before[:, :, None], edge[..., :, None, :] - c[..., None, :, :],
        -jnp.inf))                                  # [..., a, C, dk]
    own = jnp.eye(a, dtype=c.dtype)
    out = []
    for x in xs:
        xs_ = subs(x)
        near = jnp.sum(xs_[..., :, None, :] * ks[..., None, :, :] * decay,
                       axis=-1)                     # [..., a, sub, sub]
        far = mm(xs_ * jnp.exp(cs - edge[..., :, None, :]),
                 jnp.swapaxes(k_in, -1, -2))        # [..., a, sub, C]
        full = far.reshape(lead + (a, sub, a, sub)) \
            + near[..., :, :, None, :] * own[:, None, :, None]
        out.append(full.reshape(lead + (n, n)))
    return out


KDA_BLOCK, KDA_SUB = 64, 16    # the chunk form's block and its sub-blocks


def kda_chunk(s0, q, k, v, beta, g, block=KDA_BLOCK, sub=KDA_SUB):
    """The rule over T tokens of one stream from state s0 [H, dk, dv],
    in blocks of `block` tokens (T a multiple of it, `block` of `sub`):
    q, k, g [T, H, dk], v [T, H, dv], beta [T, H] -> (o [T, H, dv],
    state).

    delta_chunk's block algebra with Diag(e^c) in place of the scalar
    e^c: with c [C, dk] the running sum of g inside the block, L the
    strictly lower part of beta_i sum_d k_id k_jd exp(c_id - c_jd) and
    A the lower part (diagonal included) of the same with q_i,
    (I + L) [W | U] = [beta k e^c | beta v], and from the state S
    entering the block
        v' = U - W S
        o  = (q e^c) S + A v'
        S  = Diag(e^{c_last}) S + (k e^{c_last - c})^T v'.
    L and A come from _decayed_products, which evaluates no positive
    exponent. Every product runs at precision "highest", as in
    delta_chunk and for its reasons."""
    t, heads, dk = q.shape
    dv = v.shape[-1]
    n = t // block
    mm = functools.partial(jnp.matmul, precision=_HI)

    def blocks(a):                      # [T, H, ...] -> [n, H, block, ...]
        a = a.reshape((n, block) + a.shape[1:])
        return jnp.moveaxis(a, 1, 2)

    q, k, v, g = blocks(q), blocks(k), blocks(v), blocks(g)  # [n, H, C, d]
    beta = blocks(beta)                                      # [n, H, C]
    c = jnp.cumsum(g, axis=-2)
    row = jnp.arange(block)
    kk, attn = _decayed_products((k, q), k, c, sub)
    lower = jnp.where(row[:, None] > row[None, :],
                      kk * beta[..., None], 0.0)
    rhs = jnp.concatenate([k * beta[..., None] * jnp.exp(c),
                           v * beta[..., None]], axis=-1)
    wu = jax.lax.linalg.triangular_solve(
        lower + jnp.eye(block, dtype=lower.dtype), rhs,
        left_side=True, lower=True, unit_diagonal=True)
    w, u = wu[..., :dk], wu[..., dk:]
    q_in = q * jnp.exp(c)
    c_last = c[..., -1, :]                                   # [n, H, dk]
    k_out = k * jnp.exp(c_last[..., None, :] - c)

    def step(s, xs):
        w_b, u_b, attn_b, q_b, k_b, cl = xs
        v_new = u_b - mm(w_b, s)                               # [H, C, dv]
        o = mm(q_b, s) + mm(attn_b, v_new)
        s = s * jnp.exp(cl)[..., None] + mm(jnp.swapaxes(k_b, -1, -2), v_new)
        return s, o

    s, o = jax.lax.scan(step, s0, (w, u, attn, q_in, k_out, c_last))
    return jnp.moveaxis(o, 1, 2).reshape(t, heads, dv), s


def _kda_op_inputs(ctx, op, qkv, gate, b):
    heads, dk, dv, beta_scale = _delta_attrs(op)
    return kda_inputs(qkv, gate, b, ctx.get(op.single_input('ALog')),
                      ctx.get(op.single_input('DtBias')), heads, dk, dv,
                      beta_scale)


@op_emitter('kda_chunk')
def _kda_chunk_emit(ctx, op):
    """gated_delta_chunk's three forms (whole sequence from zero state;
    one stream's chunk from and to its slot's state, rows from Len on
    neither decaying nor writing) for a decay a key channel: QKV
    [B, T, H*(2dk+dv)], G [B, T, H*dk], B [B, T, H], ALog [H], DtBias
    [H*dk] -> Out [B, T, H*dv], in blocks of KDA_BLOCK tokens."""
    heads, dk, dv, _ = _delta_attrs(op)
    qkv = ctx.get(op.single_input('QKV'))
    bsz, t = qkv.shape[:2]
    q, k, v, beta, g = _kda_op_inputs(
        ctx, op, qkv, ctx.get(op.single_input('G')),
        ctx.get(op.single_input('B')))
    state = slot = None
    s0 = jnp.zeros((bsz, heads, dk, dv), jnp.float32)
    if op.input('State'):
        state = ctx.get(op.single_input('State'))
        slot = ctx.get(op.single_input('Slot')).astype(jnp.int32).reshape(())
        n = ctx.get(op.single_input('Len')).astype(jnp.int32).reshape(())
        reset = ctx.get(op.single_input('Reset')).astype(bool).reshape(())
        s0 = jnp.where(reset, 0.0, state[slot])[None]
        live = (jnp.arange(t) < n)[None, :, None]
        beta = jnp.where(live, beta, 0.0)
        g = jnp.where(live[..., None], g, 0.0)
    pad = -t % KDA_BLOCK
    if pad:                    # whole blocks: the tail neither decays
        q, k, v, beta, g = (   # nor writes
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, beta, g))
    o, s = jax.vmap(kda_chunk)(s0, q, k, v, beta, g)
    ctx.set(op.single_output('Out'),
            o[:, :t].reshape(bsz, t, heads * dv).astype(qkv.dtype))
    if state is not None:
        ctx.set(op.single_output('StateOut'), state.at[slot].set(s[0]))


def kda_step_reference(state, q, k, v, beta, g, live):
    """The step op's plain composition: state [S, H, dk, dv], q, k, g
    [S, H, dk], v [S, H, dv], beta [S, H], live [S] bool -> (o
    [S, H, dv], state with the live lanes' updated)."""
    o, new = kda_step(state, q, k, v, beta, g)
    return o, jnp.where(live[:, None, None, None], new, state)


@op_emitter('kda_step')
def _kda_step_emit(ctx, op):
    """gated_delta_step for a decay a key channel: one token a lane.
    QKV [S, 1, H*(2dk+dv)], G [S, 1, H*dk], B [S, 1, H], ALog [H],
    DtBias [H*dk], State [S, H, dk, dv], Live [S] -> Out [S, 1, H*dv],
    StateOut. On a TPU (or under FLAGS_pallas_interpret) the Pallas
    kernel, which takes the decay as a column beside k and q
    (pallas/gated_delta.kda_step); the plain composition elsewhere."""
    from ..flags import get_flag
    from ..pallas import gated_delta as _gd
    heads, _, dv, _ = _delta_attrs(op)
    qkv = ctx.get(op.single_input('QKV'))
    state = ctx.get(op.single_input('State'))
    live = ctx.get(op.single_input('Live')).astype(bool)
    q, k, v, beta, g = _kda_op_inputs(
        ctx, op, qkv[:, 0], ctx.get(op.single_input('G'))[:, 0],
        ctx.get(op.single_input('B'))[:, 0])
    on_tpu = jax.default_backend() == 'tpu'
    if on_tpu or bool(get_flag('pallas_interpret')):
        o, new = _gd.kda_step(state, q, k, v, beta, jnp.exp(g), live,
                              interpret=not on_tpu)
    else:
        o, new = kda_step_reference(state, q, k, v, beta, g, live)
    ctx.set(op.single_output('Out'),
            o.reshape(o.shape[0], 1, heads * dv).astype(qkv.dtype))
    ctx.set(op.single_output('StateOut'), new)


register_op('kda_chunk', infer_shape=_delta_infer,
            grad=_no_backward('kda_chunk'))
register_op('kda_step', infer_shape=_delta_infer, no_grad=True)
