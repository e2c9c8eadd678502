"""Plain reference of the A.X-K1 block (`model_type: axk1`): multi-head
latent attention with YaRN rotary positions, a leading dense SwiGLU
layer, then layers of group-limited sigmoid-gated experts beside one
shared expert; pre-norm, untied head. Forward only, in straightforward
jax.numpy: the attention UNABSORBED (every head's keys and values made
from the latent, the whole causal sequence, no cache), the expert layer
as a loop over experts, the choice of experts by sorting, no batching,
no kernels. Weights come from a seed through `tensor()`; a builder
fills the program with the same tensors, and the reference draws its
own again, one layer (and one expert) at a time, so it never holds a
second model. Long sequences are worked in blocks of rows (and a head
at a time in attention) so that 15 k tokens fit beside the program
under test; the sums are the same.

The equations (arXiv:2405.04434 section 2.1, arXiv:2412.19437,
arXiv:2309.00071; each configuration's `assumed` lists what its source
does not state):

  RMSNorm(z) = z / sqrt(mean(z^2) + eps) * w
  x <- x + Attn(RMSNorm(x));  x <- x + FFN(RMSNorm(x))
  Attn (H heads; h the normed input at position t):
    c_Q = RMSNorm(h W_DQ);  [q_C,i | q_R,i] = c_Q W_UQ   (dn | dr a head)
    [c_KV | k_R] = h W_DKV  (dc | dr);  c_KV <- RMSNorm(c_KV)
    [k_C,i | v_i] = c_KV W_UKV  (dn | dv a head)
    q_R,i <- RoPE_t(q_R,i);  k_R <- RoPE_t(k_R)  (one rotary key for all heads)
    score_i(t, s) = (q_C,i . k_C,i,s + q_R,i . k_R,s) * a,  s <= t
    o = concat_i(sum_s softmax_s(score_i) v_i,s) W_O
    a = (dn + dr)^-0.5 m^2,  m = 0.1 mscale_all_dim ln(factor) + 1
  RoPE with YaRN over the dr / 2 pairs (x[j], x[j + dr/2]):
    f_j = base^(-2j/dr);  inv_freq_j = (f_j / factor) (1 - g_j) + f_j g_j
    g_j = 1 - clip((j - low) / (high - low), 0, 1), low and high the
    floor and ceiling of dr ln(L0 / (2 pi beta)) / (2 ln base) at
    beta_fast and beta_slow, L0 the original context; cos and sin
    scaled by yarn_mscale(factor, mscale) / yarn_mscale(factor,
    mscale_all_dim)
  FFN, layers before `dense_layers`:  W_down (silu(W_gate h) * W_up h)
  FFN, the others (E experts in G groups, k a token, share: `held`
  from `offset`):
    s = sigmoid(h W_r) in float32;  a group's score the sum of its two
    largest s + b;  the topk_group best groups;  the k largest s + b
    inside them;  w = scale * s_sel / sum(s_sel)
    y = sum over the selected e with offset <= e < offset + held of
        w_e W2_e (silu(W1_e h) * W3_e h)   + the shared expert (dense form)

`prec` selects the arithmetic, as reference/nemotron_h.py has it:
'float32' (matmuls at "highest": THE reference), 'float32_default' (the
backend's default matmul precision: what a float32 program that sets
no precision gets; the router's scores are at "highest" in every case)
and 'bfloat16' (the bf16-stored control).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .gpt2 import rel_l2, seed_key  # noqa: F401  (shared with builders)

ATTN_ROLES = ('attn_norm', 'q_down', 'q_norm', 'q_up', 'kv_down', 'kv_norm',
              'kv_up', 'proj', 'ffn_norm')
FFN_ROLES = {'dense': ('gate', 'up', 'down'),
             'experts': ('router', 'bias', 'shared_gate', 'shared_up',
                         'shared_down', 'w1', 'w3', 'w2')}
ALL_ROLES = ATTN_ROLES + FFN_ROLES['dense'] + FFN_ROLES['experts']
GLOBAL_ROLES = ('embed', 'final_norm', 'head')
_HI = jax.lax.Precision.HIGHEST
ROWS = 2048          # rows worked on at a time in a long sequence


class Dims(NamedTuple):
    vocab: int
    dim: int
    heads: int
    layers: int
    dense_layers: int
    positions: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    dense_ffn: int
    expert_ffn: int
    shared_ffn: int
    experts: int
    held: int
    offset: int
    top_k: int
    n_group: int
    topk_group: int
    scale: float
    eps: float
    rope_base: float
    rope_factor: float
    rope_original: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float
    std: float

    def ffn_kind(self, i):
        return 'dense' if i < self.dense_layers else 'experts'

    @property
    def sm_scale(self):
        m = yarn_mscale(self.rope_factor, self.mscale_all_dim)
        return (self.nope_dim + self.rope_dim) ** -0.5 * m * m


def dims_of(model):
    """Dims from a configuration file (HF axk1 keys, and the harness's:
    `n_positions`, `initializer_range`, and for the share
    `router_experts` (the published expert count, which the router
    keeps; `n_routed_experts` counts the experts held) and
    `expert_offset`)."""
    held = int(model['n_routed_experts'])
    rs = model.get('rope_scaling') or {}
    if rs and rs.get('type') != 'yarn':
        raise ValueError('the reference knows YaRN rotary scaling only')
    if model.get('scoring_func', 'sigmoid') != 'sigmoid' \
            or not model.get('norm_topk_prob', True):
        raise ValueError('the reference has a sigmoid gate with '
                         'normalised weights')
    return Dims(
        vocab=int(model['vocab_size']), dim=int(model['hidden_size']),
        heads=int(model['num_attention_heads']),
        layers=int(model['num_hidden_layers']),
        dense_layers=int(model['first_k_dense_replace']),
        positions=int(model['n_positions']),
        q_rank=int(model['q_lora_rank']), kv_rank=int(model['kv_lora_rank']),
        nope_dim=int(model['qk_nope_head_dim']),
        rope_dim=int(model['qk_rope_head_dim']),
        v_dim=int(model['v_head_dim']),
        dense_ffn=int(model['intermediate_size']),
        expert_ffn=int(model['moe_intermediate_size']),
        shared_ffn=int(model['moe_intermediate_size'])
        * int(model['n_shared_experts']),
        experts=int(model.get('router_experts', held)), held=held,
        offset=int(model.get('expert_offset', 0)),
        top_k=int(model['num_experts_per_tok']),
        n_group=int(model['n_group']), topk_group=int(model['topk_group']),
        scale=float(model['routed_scaling_factor']),
        eps=float(model['rms_norm_eps']),
        rope_base=float(model['rope_theta']),
        rope_factor=float(rs.get('factor', 1.0)),
        rope_original=int(rs.get('original_max_position_embeddings', 4096)),
        beta_fast=float(rs.get('beta_fast', 32)),
        beta_slow=float(rs.get('beta_slow', 1)),
        mscale=float(rs.get('mscale', 1)),
        mscale_all_dim=float(rs.get('mscale_all_dim', 0)),
        std=float(model.get('initializer_range', 0.02)))


def _shape(role, d):
    head = d.nope_dim + d.rope_dim
    return {'embed': (d.vocab, d.dim), 'head': (d.dim, d.vocab),
            'final_norm': (d.dim,), 'attn_norm': (d.dim,),
            'ffn_norm': (d.dim,), 'q_norm': (d.q_rank,),
            'kv_norm': (d.kv_rank,),
            'q_down': (d.dim, d.q_rank), 'q_up': (d.q_rank, d.heads * head),
            'kv_down': (d.dim, d.kv_rank + d.rope_dim),
            'kv_up': (d.kv_rank, d.heads * (d.nope_dim + d.v_dim)),
            'proj': (d.heads * d.v_dim, d.dim),
            'gate': (d.dim, d.dense_ffn), 'up': (d.dim, d.dense_ffn),
            'down': (d.dense_ffn, d.dim),
            'router': (d.dim, d.experts), 'bias': (d.experts,),
            'shared_gate': (d.dim, d.shared_ffn),
            'shared_up': (d.dim, d.shared_ffn),
            'shared_down': (d.shared_ffn, d.dim),
            'w1': (d.dim, d.expert_ffn), 'w3': (d.dim, d.expert_ffn),
            'w2': (d.expert_ffn, d.dim)}[role]


def tensor(key, role, d):
    """One weight tensor (for 'w1' / 'w3' / 'w2': ONE expert's).
    Projections normal(0, std) (`initializer_range`, 0.02 where the
    file has none; a tiny test model takes more), those that write to
    the residual stream scaled by 1/sqrt(2L); embedding normal(0, 1), so
    that a token's row is of the size of what the layers add to it;
    gains 1 + 0.1 n so that no gain is invisible to the comparison. The
    router's weights normal(0, 1/sqrt(dim)): on normed input its logits
    have a standard deviation near 1, every expert alike, so routing
    comes out balanced over experts and groups; the selection bias b is
    zero."""
    shape = _shape(role, d)
    noise = jax.random.normal(key, shape, jnp.float32)
    if role == 'embed':
        return noise
    if role.endswith('norm'):
        return 1.0 + 0.1 * noise
    if role == 'bias':
        return jnp.zeros(shape, jnp.float32)
    if role == 'router':
        return noise / math.sqrt(d.dim)
    std = d.std
    if role in ('proj', 'down', 'shared_down', 'w2'):
        std /= math.sqrt(2.0 * d.layers)
    return std * noise


def _global_key(base, role):
    return jax.random.fold_in(base, GLOBAL_ROLES.index(role))


def _role_key(base, i, role):
    return jax.random.fold_in(jax.random.fold_in(base, 100 + i),
                              ALL_ROLES.index(role))


def expert_weights(base, i, e, d):
    """(W1, W3, W2) of expert `e` (its number among all d.experts) of
    layer i; e may be traced. A share holds the experts offset..offset
    + held of the same model."""
    return tuple(
        tensor(jax.random.fold_in(_role_key(base, i, r), e), r, d)
        for r in ('w1', 'w3', 'w2'))


def layer_weights(base, i, d, roles):
    return {r: tensor(_role_key(base, i, r), r, d) for r in roles}


@functools.partial(jax.jit, static_argnums=(2, 3))
def layer_tensor(base, i, role, d):
    """What a builder puts in the program's place, a tensor at a time;
    for 'w1' / 'w3' / 'w2' the held experts' stacked [held, ...], drawn
    one expert at a time as the reference's loop draws them (the seed's
    generator gives other numbers under vmap)."""
    if role not in ('w1', 'w3', 'w2'):
        return tensor(_role_key(base, i, role), role, d)
    return jax.lax.map(
        lambda e: tensor(jax.random.fold_in(_role_key(base, i, role), e),
                         role, d), d.offset + jnp.arange(d.held))


def global_tensor(base, role, d):
    return jax.jit(lambda k: tensor(k, role, d))(_global_key(base, role))


# -- rotary positions --------------------------------------------------------

def yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(d):
    """The closed form above, float64 [dr / 2]."""
    dr = d.rope_dim
    j = np.arange(dr // 2, dtype=np.float64)
    f = d.rope_base ** (-2.0 * j / dr)
    if d.rope_factor <= 1:
        return f

    def bound(beta):
        return dr * math.log(d.rope_original / (2 * math.pi * beta)) \
            / (2 * math.log(d.rope_base))
    low = max(math.floor(bound(d.beta_fast)), 0)
    high = min(math.ceil(bound(d.beta_slow)), dr - 1)
    g = 1.0 - np.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f / d.rope_factor) * (1.0 - g) + f * g


def rope(x, d):
    """x [T, ..., dr] rotated, row t by position t."""
    amp = yarn_mscale(d.rope_factor, d.mscale) \
        / yarn_mscale(d.rope_factor, d.mscale_all_dim)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(yarn_inv_freq(d), jnp.float32)[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


# -- arithmetic ------------------------------------------------------------

def _stream_dtype(prec):
    return jnp.bfloat16 if prec == 'bfloat16' else jnp.float32


def _mm(a, b, prec):
    if prec == 'float32':
        return jnp.matmul(a, b, precision=_HI)
    if prec == 'float32_default':
        return jnp.matmul(a, b, precision=jax.lax.Precision.DEFAULT)
    if prec != 'bfloat16':
        raise ValueError('unknown precision %r' % (prec,))
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def _by_rows(fn, x):
    """fn over x [T, ...] a block of ROWS rows at a time (the same
    numbers: fn works on each row alone)."""
    t = x.shape[0]
    if t <= ROWS or t % ROWS:
        return fn(x)
    out = jax.lax.map(fn, x.reshape((t // ROWS, ROWS) + x.shape[1:]))
    return out.reshape((t,) + out.shape[2:])


def attention(u, p, d, prec):
    """u [T, D] normed -> [T, D]: a head at a time, its keys and values
    made from the latent, its query rows a block at a time."""
    st = u.dtype
    t = u.shape[0]
    head = d.nope_dim + d.rope_dim
    cq = _rms(_by_rows(lambda r: _mm(r, p['q_down'], prec).astype(st), u),
              p['q_norm'], d.eps)
    q = _by_rows(lambda r: _mm(r, p['q_up'], prec).astype(st), cq) \
        .reshape(t, d.heads, head)
    ckr = _by_rows(lambda r: _mm(r, p['kv_down'], prec).astype(st), u)
    ckv = _rms(ckr[:, :d.kv_rank], p['kv_norm'], d.eps)
    k_r = rope(ckr[:, d.kv_rank:], d)                         # [T, dr]
    q = jnp.concatenate([q[..., :d.nope_dim],
                         rope(q[..., d.nope_dim:], d)], axis=-1)
    w_up = p['kv_up'].reshape(d.kv_rank, d.heads, d.nope_dim + d.v_dim)
    pos = jnp.arange(t)

    def one_head(args):
        q_i, w_i = args                           # [T, head], [dc, dn + dv]
        kv = _mm(ckv, w_i, prec).astype(st)
        k_i = jnp.concatenate([kv[:, :d.nope_dim], k_r], axis=-1)
        v_i = kv[:, d.nope_dim:]

        def rows(args):
            q_b, pos_b = args
            sc = _mm(q_b, k_i.T, prec).astype(jnp.float32) * d.sm_scale
            sc = jnp.where(pos[None, :] <= pos_b[:, None], sc, -jnp.inf)
            return _mm(jax.nn.softmax(sc, axis=-1).astype(st), v_i,
                       prec).astype(st)

        if t <= ROWS or t % ROWS:
            return rows((q_i, pos))
        out = jax.lax.map(rows, (q_i.reshape(t // ROWS, ROWS, head),
                                 pos.reshape(t // ROWS, ROWS)))
        return out.reshape(t, d.v_dim)

    ctx = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                                 w_up.transpose(1, 0, 2)))  # [H, T, dv]
    ctx = ctx.transpose(1, 0, 2).reshape(t, d.heads * d.v_dim)
    return _by_rows(lambda r: _mm(r, p['proj'], prec).astype(st), ctx)


def gated_mlp(u, gate, up, down, prec):
    st = u.dtype

    def rows(r):
        h = jax.nn.silu(_mm(r, gate, prec).astype(st)) \
            * _mm(r, up, prec).astype(st)
        return _mm(h, down, prec).astype(st)
    return _by_rows(rows, u)


def route(u, p, d):
    """(experts [T, k], weights [T, k]) of each token, over all
    d.experts, by sorting; float32 at "highest" whatever `prec`."""
    s = jax.nn.sigmoid(jnp.matmul(u.astype(jnp.float32), p['router'],
                                  precision=_HI))
    b = s + p['bias']
    if d.n_group > 1:
        g = b.reshape(b.shape[0], d.n_group, -1)
        best2, _ = jax.lax.top_k(g, 2)
        _, groups = jax.lax.top_k(best2.sum(-1), d.topk_group)
        kept = jnp.any(jnp.arange(d.n_group)[None, None, :]
                       == groups[:, :, None], axis=1)          # [T, G]
        b = jnp.where(kept[:, :, None], g, -jnp.inf).reshape(b.shape)
    _, idx = jax.lax.top_k(b, d.top_k)
    sel = jnp.take_along_axis(s, idx, axis=-1)
    return idx, d.scale * sel / jnp.sum(sel, -1, keepdims=True)


def routed_part(u, p, d, prec, experts_of, first=None, count=None):
    """sum over the experts first..first + count (the held ones where
    not given) of w_e W2_e (silu(W1_e u) * W3_e u), [T, D]: a loop over
    those experts, each over every row and weighted by w (0 where the
    row did not choose it). `experts_of(e)` gives expert e's weights."""
    st = u.dtype
    idx, w = route(u, p, d)
    first = d.offset if first is None else first
    count = d.held if count is None else count

    def one(acc, e):
        w1, w3, w2 = experts_of(e)
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)       # [T]
        y = gated_mlp(u, w1, w3, w2, prec)
        return acc + w_e[:, None] * y.astype(jnp.float32), None

    r, _ = jax.lax.scan(one, jnp.zeros(u.shape, jnp.float32),
                        first + jnp.arange(count))
    return r.astype(st)


def shared_part(u, p, d, prec):
    return gated_mlp(u, p['shared_gate'], p['shared_up'], p['shared_down'],
                     prec)


def block(base, i, x, kind, d, prec):
    """Layer i (its FFN of `kind`; i may be traced) on x [T, D]."""
    p = layer_weights(base, i, d, ATTN_ROLES)
    x = x + attention(_rms(x, p['attn_norm'], d.eps), p, d, prec)
    u = _rms(x, p['ffn_norm'], d.eps)
    if kind == 'dense':
        f = layer_weights(base, i, d, FFN_ROLES['dense'])
        return x + gated_mlp(u, f['gate'], f['up'], f['down'], prec)
    f = layer_weights(base, i, d, FFN_ROLES['experts'][:5])
    return x + routed_part(u, f, d, prec,
                           lambda e: expert_weights(base, i, e, d)) \
        + shared_part(u, f, d, prec)


@functools.partial(jax.jit, static_argnums=(2, 3, 5))
def _layer(base, i, kind, d, x, prec):
    return block(base, i, x, kind, d, prec)


@functools.partial(jax.jit, static_argnums=(1, 3))
def _embed(base, d, tokens, prec):
    return tensor(_global_key(base, 'embed'), 'embed', d)[tokens] \
        .astype(_stream_dtype(prec))


@functools.partial(jax.jit, static_argnums=(1, 3))
def _head(base, d, x, prec):
    h = _rms(x, tensor(_global_key(base, 'final_norm'), 'final_norm', d),
             d.eps)
    return _mm(h, tensor(_global_key(base, 'head'), 'head', d), prec) \
        .astype(jnp.float32)


def padded_length(n):
    """The length a sequence of n tokens is padded to: whole blocks of
    ROWS where it is worked in blocks, else a multiple of 128."""
    return -(-n // ROWS) * ROWS if n > ROWS else -(-n // 128) * 128


def logits(base, d, tokens, prec='float32', rows=None):
    """Logits [T, V] (float32) of one sequence tokens [T], or of its
    `rows` (a slice) only. One jitted call a layer: a layer's weights
    live only inside it, and an expert's only inside its turn of the
    loop."""
    x = _embed(base, d, jnp.asarray(tokens, jnp.int32), prec)
    for i in range(d.layers):
        x = _layer(base, i, d.ffn_kind(i), d, x, prec)
    return _head(base, d, x if rows is None else x[rows], prec)
