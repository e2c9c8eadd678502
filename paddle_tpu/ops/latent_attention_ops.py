"""Ops of multi-head latent attention (MLA: arXiv:2405.04434 section
2.1, arXiv:2412.19437) and of the rotary positions it takes (RoPE with
YaRN's blended frequencies, arXiv:2309.00071, the DeepSeek-V3 form):
`rotary_yarn`, `latent_attention`, `paged_latent_prefill` and
`paged_latent_attention`.

What a token leaves behind in an MLA layer is ONE row for all heads:
the normed latent c_KV [dc] and the shared rotary key k_R [dr],
already rotated by the token's position. Every head's key and value
are products of that row with the layer's up-projection W_UKV
[dc, H (dn + dv)] (head i's columns are [k_C,i | v_i]):

    score_i(t, s) = (q_C,i(t) . k_C,i(s) + q_R,i(t) . k_R(s)) * scale
    out_i(t)      = sum_s softmax_s(score_i(t, .)) v_i(s)

`latent_attention` computes exactly that over a whole sequence (the
form a saved model holds). The two paged ops read the rows out of a
page pool [pages, page_tokens, row] (row = dc + dr, padded to whole
lanes of 128: the chip lays a 576-wide row out as 640 anyway, and a
pool that says so keeps a page the plain matrix the kernel reads) and
never expand them to per-head keys and values in memory: they use the
ABSORBED form, in which W_UK moves to the query and W_UV to the output,

    q~_i = q_C,i W_UK,i^T  [dc];   score_i = (q~_i . c_KV + q_R,i . k_R) * scale
    u_i  = sum_s p_i(s) c_KV(s);   out_i = u_i W_UV,i

so that the H query heads are rows of one product over a block of
latent rows and the second product reads the same block's first dc
columns. No op here has a gradient.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..registry import register_op, op_emitter

_NEG = -1e30


# -- rotary positions ----------------------------------------------------------

def yarn_mscale(factor, mscale):
    """YaRN's attention temperature for a context stretched by
    `factor`: 0.1 mscale ln(factor) + 1 (1 where nothing is stretched)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _correction_dim(rotations, dim, base, original_max):
    """The rotary dimension whose wavelength makes `rotations` turns
    over the original context."""
    return dim * math.log(original_max / (rotations * 2 * math.pi)) \
        / (2 * math.log(base))


def yarn_inv_freq(dim, base=10000.0, factor=1.0, original_max=4096,
                  beta_fast=32.0, beta_slow=1.0):
    """The dim / 2 angular frequencies, float64 [dim / 2]: pair j turns
    at f_j = base^(-2j/dim) where it makes more than beta_fast turns
    over the original context (left as trained), at f_j / factor where
    it makes fewer than beta_slow (interpolated), and at a linear blend
    of the two between. factor 1 is plain RoPE."""
    f = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1:
        return f
    low = max(math.floor(_correction_dim(beta_fast, dim, base,
                                         original_max)), 0)
    high = min(math.ceil(_correction_dim(beta_slow, dim, base,
                                         original_max)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp                       # 1: as trained, 0: interpolated
    return (f / factor) * (1.0 - keep) + f * keep


def rope_table(op):
    """(inv_freq [dim/2] float32, the factor cos and sin are scaled by)
    from a rotary op's attributes."""
    factor = float(op.attr('factor', 1.0))
    inv = yarn_inv_freq(int(op.attr('dim')), float(op.attr('base', 10000.0)),
                        factor, int(op.attr('original_max', 4096)),
                        float(op.attr('beta_fast', 32.0)),
                        float(op.attr('beta_slow', 1.0)))
    amp = yarn_mscale(factor, float(op.attr('mscale', 1.0))) \
        / yarn_mscale(factor, float(op.attr('mscale_all_dim', 0.0)))
    return inv.astype(np.float32), amp


def rotate(x, positions, inv_freq, amp=1.0):
    """x [..., dr] rotated by `positions` (broadcastable to x's leading
    axes): the pairs are (x[j], x[j + dr/2]) (split halves)."""
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


@op_emitter('rotary_yarn')
def _rotary_yarn_emit(ctx, op):
    """X [B, T, H, dr] or [B, T, dr] with each row rotated by its
    position: arange(T) where no Positions are given (a whole sequence
    from its start), Positions [T] with attr per = 'row' (a chunk's
    rows), Positions [B] with per = 'lane' (one token a lane),
    Positions [B, T] with per = 'each' (a block step's rows). With
    attr start, only X[..., start:] turns and the columns before it
    pass as they are. attrs dim (dr), base, factor, original_max,
    beta_fast, beta_slow, mscale, mscale_all_dim: the table is a
    constant of the program."""
    x = ctx.get(op.single_input('X'))
    start = int(op.attr('start', 0))
    inv, amp = rope_table(op)
    if op.input('Positions'):
        pos = ctx.get(op.single_input('Positions')).astype(jnp.int32)
        per = op.attr('per', 'row')
        if per != 'each':
            pos = pos[:, None] if per == 'lane' else pos[None, :]
    else:
        pos = jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]
    if x.ndim == 4:
        pos = pos[..., None]
    out = rotate(x[..., start:], jnp.broadcast_to(pos, x.shape[:-1]), inv,
                 amp)
    ctx.set(op.single_output('Out'), jnp.concatenate(
        [x[..., :start], out], axis=-1) if start else out)


def _same_shape_infer(op, block):
    x = block.var_recursive(op.single_input('X'))
    out = block.var_recursive(op.single_output('Out'))
    out.shape, out.dtype = x.shape, x.dtype


register_op('rotary_yarn', infer_shape=_same_shape_infer, no_grad=True)


# -- the whole sequence, as the equations stand ---------------------------------

def _split_up(w_ukv, heads, dn):
    """W_UKV [dc, H (dn + dv)] -> (W_UK [dc, H, dn], W_UV [dc, H, dv])."""
    w = w_ukv.reshape(w_ukv.shape[0], heads, -1)
    return w[..., :dn], w[..., dn:]


@op_emitter('latent_attention')
def _latent_attention_emit(ctx, op):
    """Causal MLA over whole sequences, unabsorbed: Q [B, T, H, dn + dr]
    (its rotary part rotated), CKV [B, T, dc] (normed), KR [B, T, dr]
    (rotated), WUKV [dc, H (dn + dv)]; attrs nope_dim (dn), sm_scale
    -> Out [B, T, H dv]. Every head's keys and values are made from
    the latent; this is the form a saved model holds and a test's
    reference, not what serving runs."""
    q = ctx.get(op.single_input('Q'))
    ckv = ctx.get(op.single_input('CKV'))
    kr = ctx.get(op.single_input('KR'))
    heads, dn = q.shape[2], int(op.attr('nope_dim'))
    w_uk, w_uv = _split_up(ctx.get(op.single_input('WUKV')), heads, dn)
    k_c = jnp.einsum('btc,chn->bthn', ckv, w_uk)
    v = jnp.einsum('btc,chv->bthv', ckv, w_uv)
    scores = (jnp.einsum('bthn,bshn->bhts', q[..., :dn], k_c)
              + jnp.einsum('bthr,bsr->bhts', q[..., dn:], kr)) \
        * float(op.attr('sm_scale'))
    t = q.shape[1]
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)),
                       scores.astype(jnp.float32), _NEG)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum('bhts,bshv->bthv', probs, v)
    ctx.set(op.single_output('Out'), out.reshape(out.shape[:2] + (-1,)))


def _latent_out_infer(op, block):
    q = block.var_recursive(op.single_input('Q'))
    w = block.var_recursive(op.single_input('WUKV'))
    out = block.var_recursive(op.single_output('Out'))
    dv = int(w.shape[1]) // int(q.shape[2]) - int(op.attr('nope_dim'))
    out.shape = tuple(q.shape[:2]) + (int(q.shape[2]) * dv,)
    out.dtype = q.dtype


register_op('latent_attention', infer_shape=_latent_out_infer, no_grad=True)


# -- through the page pool, absorbed --------------------------------------------

def absorb_query(q, w_uk, row):
    """q [..., H, dn + dr], W_UK [dc, H, dn] -> [..., H, row]: q~ (dc),
    the rotary part (dr) and zeros up to the pool's row."""
    dn = w_uk.shape[-1]
    qa = jnp.einsum('...hn,chn->...hc', q[..., :dn], w_uk)
    pad = row - qa.shape[-1] - (q.shape[-1] - dn)
    parts = [qa, q[..., dn:]]
    if pad:
        parts.append(jnp.zeros(q.shape[:-1] + (pad,), q.dtype))
    return jnp.concatenate(parts, axis=-1)


def _pool_inputs(ctx, op):
    q = ctx.get(op.single_input('Q'))
    pool = ctx.get(op.single_input('Pool'))
    table = ctx.get(op.single_input('Table')).astype(jnp.int32)
    positions = ctx.get(op.single_input('Positions')).astype(jnp.int32)
    w_uk, w_uv = _split_up(ctx.get(op.single_input('WUKV')), q.shape[2],
                           int(op.attr('nope_dim')))
    return q, pool, table, positions, w_uk, w_uv


def decode_reference(qa, pool, table, positions, sm_scale, dc):
    """The absorbed sum with the window gathered: qa [S, H, row], pool
    [N, pt, row], table [S, P], positions [S] -> u [S, H, dc]. What the
    kernel computes, for every backend."""
    win = pool[table].reshape(table.shape[0], -1, pool.shape[-1])
    scores = jnp.einsum('shw,sjw->shj', qa, win) * sm_scale
    j = jnp.arange(win.shape[1], dtype=jnp.int32)
    scores = jnp.where((j[None, :] <= positions[:, None])[:, None, :],
                       scores.astype(jnp.float32), _NEG)
    probs = jax.nn.softmax(scores, axis=-1).astype(qa.dtype)
    return jnp.einsum('shj,sjc->shc', probs, win[..., :dc])


@op_emitter('paged_latent_attention')
def _paged_latent_attention_emit(ctx, op):
    """One decode step's MLA through the page tables, absorbed: Q
    [S, 1, H, dn + dr] (rotary part rotated), Pool [N, pt, row] (a row:
    the normed latent, the rotated key, zeros), Table [S, P], Positions
    [S], WUKV [dc, H (dn + dv)]; attrs nope_dim, sm_scale -> Out
    [S, 1, H dv]. Lane s attends to positions 0..Positions[s], the row
    appended this step among them. On a TPU (or under
    FLAGS_pallas_interpret), for pages the kernel tiles, the sum is the
    Pallas kernel of pallas/paged_attention.py, which reads each lane's
    live pages once for both products; elsewhere the same absorbed sum
    over the gathered window."""
    from ..pallas import paged_attention as _pa
    from ..flags import get_flag
    q, pool, table, positions, w_uk, w_uv = _pool_inputs(ctx, op)
    dc, sm_scale = w_uk.shape[0], float(op.attr('sm_scale'))
    qa = absorb_query(q[:, 0], w_uk, pool.shape[-1])        # [S, H, row]
    on_tpu = jax.default_backend() == 'tpu'
    if _pa.latent_supported(pool.shape[1], pool.shape[2], dc) and (
            on_tpu or bool(get_flag('pallas_interpret'))):
        u = _pa.paged_latent_attention(
            qa, pool, jnp.clip(table, 0, pool.shape[0] - 1), positions,
            sm_scale=sm_scale, value_dim=dc, interpret=not on_tpu)
    else:
        u = decode_reference(qa, pool, table, positions, sm_scale, dc)
    out = jnp.einsum('shc,chv->shv', u, w_uv)
    ctx.set(op.single_output('Out'), out.reshape(out.shape[0], 1, -1))


# tokens of the cached window a prefill chunk folds in at a time: 64
# pages of 16; a chunk of 256 rows x 64 heads against 1024 rows is a
# [16384, 1024] block of scores (64 MB in float32)
_PREFILL_BLOCK_TOKENS = 1024


def prefill_absorbed(q, pool, table, positions, w_uk, w_uv, sm_scale,
                     block_tokens=_PREFILL_BLOCK_TOKENS):
    """A chunk's rows against the stream's cached latent, absorbed and
    folded block by block with an online softmax in plain XLA: q [C, H,
    dn + dr], pool [N, pt, row], table [P], positions [C] (ascending)
    -> [C, H, dv]. Only the blocks up to the chunk's last position are
    read; the window is never gathered whole, nor are keys and values
    made. Since PR 51 this is the reference the kernel of
    pallas/latent_prefill.py is held to and the path of every backend
    but a TPU: its scores, their mask and their exponentials ([C H,
    1024] float32 each) go through HBM several times a block and every
    row of the chunk is multiplied whatever the prompt's length. A chunk
    of 256 rows x 64 heads on a v5e, the op alone (tools/mla_forms.py;
    PERF.md, PR 51): 4.27 ms behind 4 k cached tokens and 6.07 behind
    12 k, whatever is live, where the kernel takes 0.86 and 2.03 with
    138 rows live and 1.39 and 3.47 with all 256. (The other form, a
    block's keys and values made through W_UKV before the products,
    needs three quarters of the multiplies and takes 4.95 and 8.05:
    the tool keeps it.)"""
    C, H = q.shape[:2]
    pt, row = pool.shape[1:]
    dc = w_uk.shape[0]
    bp = max(1, min(table.shape[0], block_tokens // pt))
    pages = -(-table.shape[0] // bp) * bp
    table = jnp.pad(table, (0, pages - table.shape[0]))
    qa = (absorb_query(q, w_uk, row) * sm_scale).reshape(C * H, row)
    pos = jnp.repeat(positions, H)[:, None]                 # [C H, 1]
    n_blocks = (positions[-1] // pt) // bp + 1

    def fold(i, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice(table, (i * bp,), (bp,))
        blk = pool[ids].reshape(bp * pt, row)
        sc = jnp.dot(qa, blk.T, preferred_element_type=jnp.float32)
        j = i * (bp * pt) + jnp.arange(bp * pt, dtype=jnp.int32)
        sc = jnp.where(j[None, :] <= pos, sc, _NEG)
        m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        return (m_new, alpha * l + p.sum(axis=-1, keepdims=True),
                alpha * acc + jnp.dot(p, blk[:, :dc],
                                      preferred_element_type=jnp.float32))

    m, l, acc = jax.lax.fori_loop(
        0, n_blocks, fold,
        (jnp.full((C * H, 1), _NEG, jnp.float32),
         jnp.zeros((C * H, 1), jnp.float32),
         jnp.zeros((C * H, dc), jnp.float32)))
    u = (acc / l).astype(q.dtype).reshape(C, H, dc)
    return jnp.einsum('thc,chv->thv', u, w_uv)


@op_emitter('paged_latent_prefill')
def _paged_latent_prefill_emit(ctx, op):
    """One prefill chunk's MLA against the stream's pages (the chunk's
    own rows already written): Q [1, C, H, dn + dr], Pool [N, pt, row],
    Table [1, P], Positions [C] (absolute, one after the other: row i
    stands at Positions[0] + i and sees the positions up to its own),
    WUKV, and optionally Len [1] (the chunk's live rows; absent: all of
    them); attrs nope_dim, sm_scale -> Out [1, C, H dv]. On a TPU (or
    under FLAGS_pallas_interpret), for pages the kernel tiles and a
    chunk that is a whole number of its tiles, the absorbed sum is the
    Pallas kernel of pallas/latent_prefill.py, which keeps the scores in
    VMEM, does nothing for the tiles past Len and gives zeros for the
    rows from Len on; elsewhere `prefill_absorbed`, which computes every
    row. Which of the two an emission took is counted in
    ops.latent_prefill.kernel / ops.latent_prefill.fallback."""
    from ..pallas import latent_prefill as _lp
    from ..flags import get_flag
    from ..obs import telemetry
    q, pool, table, positions, w_uk, w_uv = _pool_inputs(ctx, op)
    q, table = q[0], jnp.clip(table[0], 0, pool.shape[0] - 1)
    (C, H), (pt, row), dc = q.shape[:2], pool.shape[1:], w_uk.shape[0]
    sm_scale = float(op.attr('sm_scale'))
    on_tpu = jax.default_backend() == 'tpu'
    if _lp.prefill_supported(C, H, pt, row, dc) and (
            on_tpu or bool(get_flag('pallas_interpret'))):
        telemetry.counter('ops.latent_prefill.kernel').inc()
        length = ctx.get(op.single_input('Len')).reshape(-1)[0] \
            if op.input('Len') else C
        u = _lp.paged_latent_prefill(
            absorb_query(q, w_uk, row) * sm_scale, pool, table, positions[0],
            jnp.asarray(length, jnp.int32), value_dim=dc,
            interpret=not on_tpu)
        out = jnp.einsum('thc,chv->thv', u, w_uv)
    else:
        telemetry.counter('ops.latent_prefill.fallback').inc()
        out = prefill_absorbed(q, pool, table, positions, w_uk, w_uv,
                               sm_scale)
    ctx.set(op.single_output('Out'), out.reshape(1, out.shape[0], -1))


register_op('paged_latent_attention', infer_shape=_latent_out_infer,
            no_grad=True)
register_op('paged_latent_prefill', infer_shape=_latent_out_infer,
            no_grad=True)
