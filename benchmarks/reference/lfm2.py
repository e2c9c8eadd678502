"""Plain reference of the LFM2 block with routed experts (`model_type:
lfm2_moe`, HF `Lfm2Moe`; LiquidAI/LFM2-8B-A1B): gated short-convolution
layers with a full-attention layer among every few, a dense SwiGLU in
the first layers and sigmoid-routed SwiGLU experts with a selection
bias in the others, a tied head. Forward only, in straightforward
jax.numpy: the convolution as K shifted products over the whole
sequence (no cache, no carried rows), the expert layer as a loop over
experts, full causal attention over the whole sequence a head at a time
(query rows in blocks of ROWS where the sequence is long, so that a 9 k
conversation's scores fit), no batching, no kernels. Weights come from a
seed through `tensor()`; a builder fills the program with the same
tensors, and the reference draws its own again, one layer (and one
expert) at a time, so it never holds a second model.

The equations (the configuration's `assumed` lists what its source does
not state):

  RMSNorm(z) = z / sqrt(mean(z^2) + eps) * w
  x = E[token]
  h = x + Op_i(RMSNorm(x));  y = h + FF_i(RMSNorm(h))
  conv (K taps, no bias, NO activation):
    [B | C | z] = W_in u, three parts of width D;  v = B * z
    c_t = sum_{j < K} w_j v_{t - (K-1) + j}   (zeros before the first
    token);  out = W_out (C * c)
  attention: q (heads), k, v (kv_heads) of head_dim = W_qkv u; q and k
    RMS-normed a head at a time (one gain [head_dim] each), then RoPE
    over the whole head (split halves, theta rope_theta); query head h
    against K/V head h // (heads / kv_heads); causal
    softmax(q k^T / sqrt(head_dim)) v; W_o.
  FF_i, i < dense_layers: W2 (silu(W1 u) * W3 u), width ffn.
  FF_i, later (E experts, all held, k a token):
    s = sigmoid(W_r u) in float32;  the k largest of s + b;
    w = routed_scale * s[chosen] / sum(s[chosen])
    sum over the chosen e of
        w_e W2_e (silu(W1_e u) * W3_e u)
  logits = RMSNorm(x) E^T

`prec` selects the arithmetic:
  'float32'          float32, matmuls at precision "highest": THE
                     reference.
  'float32_default'  float32, matmuls at the backend's default precision
                     (on a TPU one bf16 pass): what a float32 program
                     that sets no precision gets. The convolution has no
                     matmul and is the same in both; the router's
                     scores are at "highest" in every case.
  'bfloat16'         the bf16-stored control: activations, matmul
                     operands and the convolution's rows kept in
                     bfloat16 (float32 accumulation, norm statistics and
                     router).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .gpt2 import rel_l2, seed_key  # noqa: F401  (shared with builders)

MIXER_ROLES = {'conv': ('norm', 'in', 'conv', 'out'),
               'full_attention': ('norm', 'qkv', 'q_norm', 'k_norm', 'proj')}
DENSE_ROLES = ('ffn_norm', 'up', 'down')
EXPERT_ROLES = ('ffn_norm', 'router', 'bias', 'w1', 'w3', 'w2')
ALL_ROLES = tuple(dict.fromkeys(
    MIXER_ROLES['conv'] + MIXER_ROLES['full_attention'] + DENSE_ROLES
    + EXPERT_ROLES))
GLOBAL_ROLES = ('embed', 'final_norm')
ROWS = 512          # query rows a block, where a sequence is longer
_HI = jax.lax.Precision.HIGHEST


class Dims(NamedTuple):
    vocab: int
    dim: int
    heads: int
    kv_heads: int
    head_dim: int
    kinds: tuple            # 'conv' | 'full_attention', the layers run
    positions: int
    conv_kernel: int
    ffn: int
    dense_layers: int
    experts: int
    top_k: int
    expert_ffn: int
    routed_scale: float
    rope_theta: float
    eps: float
    std: float

    @property
    def layers(self):
        return len(self.kinds)


def dims_of(model):
    """Dims from a configuration file (HF lfm2_moe keys, and the
    harness's: `n_positions`, `initializer_range`, `head_dim`). The
    layers run are the first
    `num_hidden_layers` of `layer_types`."""
    kinds = tuple(model['layer_types'][:int(model['num_hidden_layers'])])
    if set(kinds) - set(MIXER_ROLES):
        raise ValueError('layer_types %r' % (sorted(set(kinds)),))
    if model.get('conv_bias'):
        raise ValueError('the reference\'s convolution has no bias')
    if not model.get('norm_topk_prob', True) \
            or not model.get('use_expert_bias', True):
        raise ValueError('the reference normalises the chosen scores and '
                         'chooses with a bias')
    heads = int(model['num_attention_heads'])
    return Dims(
        vocab=int(model['vocab_size']), dim=int(model['hidden_size']),
        heads=heads, kv_heads=int(model['num_key_value_heads']),
        head_dim=int(model.get('head_dim')
                     or int(model['hidden_size']) // heads),
        kinds=kinds, positions=int(model['n_positions']),
        conv_kernel=int(model['conv_L_cache']),
        ffn=int(model['intermediate_size']),
        dense_layers=int(model['num_dense_layers']),
        experts=int(model['num_experts']),
        top_k=int(model['num_experts_per_tok']),
        expert_ffn=int(model['moe_intermediate_size']),
        routed_scale=float(model.get('routed_scaling_factor', 1.0)),
        rope_theta=float(model['rope_theta']),
        eps=float(model['norm_eps']),
        std=float(model.get('initializer_range', 0.02)))


def _shape(role, d):
    return {'embed': (d.vocab, d.dim), 'final_norm': (d.dim,),
            'norm': (d.dim,), 'ffn_norm': (d.dim,),
            'in': (d.dim, 3 * d.dim), 'conv': (d.conv_kernel, d.dim),
            'out': (d.dim, d.dim),
            'qkv': (d.dim, (d.heads + 2 * d.kv_heads) * d.head_dim),
            'q_norm': (d.head_dim,), 'k_norm': (d.head_dim,),
            'proj': (d.heads * d.head_dim, d.dim),
            'up': (d.dim, 2 * d.ffn), 'down': (d.ffn, d.dim),
            'router': (d.dim, d.experts), 'bias': (d.experts,),
            'w1': (d.dim, d.expert_ffn), 'w3': (d.dim, d.expert_ffn),
            'w2': (d.expert_ffn, d.dim)}[role]


def tensor(key, role, d):
    """One weight tensor (for 'w1' / 'w3' / 'w2': ONE expert's). Every
    projection and the embedding normal(0, std) (`initializer_range`;
    the source row gives none, so 0.02 as the other references take,
    listed under `assumed`; a tiny test model takes more, or its narrow
    layers would add nothing a comparison could see). Gains 1 + 0.1 n
    so that no gain is invisible to the comparison. The convolution's
    taps normal(0, 0.5) around (0.3, 0.5, 1.0) a channel, so that all K
    matter and none cancels. The in-projection's B and z parts four
    times the others' (v = B * z is a product of two: at std it would
    add nothing). The router's weights normal(0, 2 / sqrt(dim)): on
    normed input its logits have a standard deviation near 2, so the
    sigmoids spread over (0.1, 0.9); the selection bias normal(0, 0.02),
    wide enough that it changes which experts a row takes (two fifths
    of the rows at the published sizes) and never a weight, and narrow
    enough that the experts' load stays even, as the bias of a trained
    checkpoint makes it: 14 rows touch 26 of 32 experts (27 with no
    bias). At 0.15, where it stood first, the same few experts took
    most rows (19 of 32 touched), which ones and how few was the
    seed's, and a step's bytes and the cell's `tpot_p50_ms` with them
    (2 % over seeds: PERF.md section 6, PR 60)."""
    shape = _shape(role, d)
    noise = jax.random.normal(key, shape, jnp.float32)
    if role.endswith('norm'):
        return 1.0 + 0.1 * noise
    if role == 'conv':
        centre = jnp.linspace(0.3, 1.0, shape[0]) ** 1.5
        return centre[:, None] + 0.5 * noise
    if role == 'router':
        return noise * (2.0 / math.sqrt(d.dim))
    if role == 'bias':
        return 0.02 * noise
    if role == 'in':
        return d.std * noise * jnp.where(
            (jnp.arange(shape[1]) // d.dim) == 1, 1.0, 4.0)
    return d.std * noise


def _global_key(base, role):
    return jax.random.fold_in(base, GLOBAL_ROLES.index(role))


def _role_key(base, i, role):
    return jax.random.fold_in(jax.random.fold_in(base, 100 + i),
                              ALL_ROLES.index(role))


def layer_roles(i, kind, d):
    return MIXER_ROLES[kind] + (DENSE_ROLES if i < d.dense_layers
                                else EXPERT_ROLES)


def expert_weights(base, i, e, d):
    """(W1, W3, W2) of expert `e` (its number among all d.experts) of
    layer i; e may be traced."""
    return tuple(
        tensor(jax.random.fold_in(_role_key(base, i, r), e), r, d)
        for r in ('w1', 'w3', 'w2'))


def layer_weights(base, i, kind, d):
    """Layer i's tensors by role, without the experts' own."""
    return {r: tensor(_role_key(base, i, r), r, d)
            for r in layer_roles(i, kind, d) if r not in ('w1', 'w3', 'w2')}


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def layer_tensors(base, i, kind, d):
    """What a builder puts in the program's place, a layer at a time:
    layer_weights and, for an expert layer, the experts' W1, W3 and W2
    stacked [experts, ...]."""
    out = layer_weights(base, i, kind, d)
    if i >= d.dense_layers:
        # one expert at a time, as the reference's loop draws them: the
        # seed's generator (rbg) gives other numbers under vmap
        out['w1'], out['w3'], out['w2'] = jax.lax.map(
            lambda e: expert_weights(base, i, e, d),
            jnp.arange(d.experts))
    return out


def global_tensor(base, role, d):
    return jax.jit(lambda k: tensor(k, role, d))(_global_key(base, role))


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


def conv_mixer(u, p, d, prec):
    """The gated short convolution on u [T, D]."""
    st = u.dtype
    t, kk = u.shape[0], d.conv_kernel
    bcz = _mm(u, p['in'], prec).astype(st)
    b, c, z = bcz[:, :d.dim], bcz[:, d.dim:2 * d.dim], bcz[:, 2 * d.dim:]
    v = jnp.pad(b * z, ((kk - 1, 0), (0, 0)))
    conv = sum(v[j:j + t] * p['conv'][j].astype(st) for j in range(kk))
    return _mm(c * conv.astype(st), p['out'], prec).astype(st)


def rope(z, d):
    """z [T, H, head_dim] with row t rotated by t: split halves, angle
    t * theta^(-2j / head_dim) for pair j."""
    half = d.head_dim // 2
    inv = d.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(z.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    z1, z2 = z[..., :half].astype(jnp.float32), \
        z[..., half:].astype(jnp.float32)
    return jnp.concatenate([z1 * cos - z2 * sin, z2 * cos + z1 * sin],
                           axis=-1).astype(z.dtype)


def attention_mixer(u, p, d, prec):
    """Full causal attention on u [T, D], a query head at a time, its
    query rows a block at a time where the sequence is long."""
    st = u.dtype
    t = u.shape[0]
    h, kvh, dh = d.heads, d.kv_heads, d.head_dim
    qkv = _mm(u, p['qkv'], prec).astype(st)
    q = qkv[:, :h * dh].reshape(t, h, dh)
    k = qkv[:, h * dh:(h + kvh) * dh].reshape(t, kvh, dh)
    v = qkv[:, (h + kvh) * dh:].reshape(t, kvh, dh)
    q, k = _rms(q, p['q_norm'], d.eps), _rms(k, p['k_norm'], d.eps)
    q, k = rope(q, d), rope(k, d)
    q, k, v = (a.transpose(1, 0, 2) for a in (q, k, v))
    k, v = (jnp.repeat(a, h // kvh, axis=0) for a in (k, v))
    pos = jnp.arange(t)

    def one_head(args):
        q_i, k_i, v_i = args

        def rows(args):
            q_b, pos_b = args
            sc = _mm(q_b, k_i.T, prec).astype(jnp.float32) / math.sqrt(dh)
            sc = jnp.where(pos[None, :] <= pos_b[:, None], sc, -jnp.inf)
            return _mm(jax.nn.softmax(sc, axis=-1).astype(st), v_i,
                       prec).astype(st)

        if t <= ROWS or t % ROWS:
            return rows((q_i, pos))
        return jax.lax.map(rows, (q_i.reshape(t // ROWS, ROWS, dh),
                                  pos.reshape(t // ROWS, ROWS))) \
            .reshape(t, dh)

    ctx = jax.lax.map(one_head, (q, k, v)).transpose(1, 0, 2)
    return _mm(ctx.reshape(t, h * dh), p['proj'], prec).astype(st)


def dense_ff(u, p, d, prec):
    st = u.dtype
    ab = _mm(u, p['up'], prec).astype(st)
    hid = jax.nn.silu(ab[:, :d.ffn]) * ab[:, d.ffn:]
    return _mm(hid, p['down'], prec).astype(st)


def route(u, p, d):
    """(experts [T, k], weights [T, k]) of each token, over all
    d.experts: the k largest of sigmoid + bias, weighted by the
    sigmoids alone over their sum; float32 at "highest" whatever
    `prec`."""
    s = jax.nn.sigmoid(jnp.matmul(u.astype(jnp.float32), p['router'],
                                  precision=_HI))
    _, idx = jax.lax.top_k(s + p['bias'], d.top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, d.routed_scale * chosen / jnp.sum(chosen, -1, keepdims=True)


def routed_part(u, p, d, prec, experts_of):
    """sum over the experts of w_e W2_e (silu(W1_e u) * W3_e u),
    [T, D]: a loop over the experts, each over every row and
    weighted by w (0 where the row did not choose it)."""
    st = u.dtype
    idx, w = route(u, p, d)

    def one(acc, e):
        w1, w3, w2 = experts_of(e)
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)       # [T]
        hid = jax.nn.silu(_mm(u, w1, prec).astype(st)) \
            * _mm(u, w3, prec).astype(st)
        return acc + w_e[:, None] * _mm(hid, w2, prec).astype(jnp.float32), \
            None

    r, _ = jax.lax.scan(one, jnp.zeros(u.shape, jnp.float32),
                        jnp.arange(d.experts))
    return r.astype(st)


def block(base, i, x, kind, d, prec):
    """Layer i on x [T, D]: the mixer, then the feed-forward."""
    p = layer_weights(base, i, kind, d)
    mixer = conv_mixer if kind == 'conv' else attention_mixer
    x = x + mixer(_rms(x, p['norm'], d.eps), p, d, prec)
    u = _rms(x, p['ffn_norm'], d.eps)
    if i < d.dense_layers:
        return x + dense_ff(u, p, d, prec)
    return x + routed_part(u, p, d, prec,
                           lambda e: expert_weights(base, i, e, d))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 5))
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
    e = tensor(_global_key(base, 'embed'), 'embed', d)
    return _mm(h, e.T, prec).astype(jnp.float32)


def padded_length(n):
    """The length a sequence of n tokens is padded to: whole blocks of
    ROWS where attention works in blocks, else a multiple of 128."""
    return -(-n // ROWS) * ROWS if n > ROWS else -(-n // 128) * 128


def logits(base, d, tokens, prec='float32', rows=None):
    """Logits [T, V] (float32) of one sequence tokens [T], or of its
    `rows` (a slice) only. One jitted call a layer: a layer's weights
    live only inside it, and an expert's only inside its turn of the
    loop."""
    x = _embed(base, d, jnp.asarray(tokens, jnp.int32), prec)
    for i in range(d.layers):
        x = _layer(base, i, d.kinds[i], d, x, prec)
    return _head(base, d, x if rows is None else x[rows], prec)
