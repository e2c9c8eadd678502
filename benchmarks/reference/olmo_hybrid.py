"""Plain reference of the Olmo-Hybrid block: gated-delta-rule layers
(Gated DeltaNet, arXiv:2412.06464) between full-attention layers,
RMSNorm on every sublayer's output, SwiGLU, untied head. Forward only,
in straightforward jax.numpy: the delta rule token by token (no
chunking), full causal attention over the whole sequence (no cache), no
batching, no kernels. Weights come from a seed through `tensor()`; a
builder fills the program with the same tensors, and the reference
draws its own again, one layer (and one slice of the vocabulary) at a
time, so it never holds a second model.

The equations (each configuration's `assumed` lists what its source does
not state):

  RMSNorm(z) = z / sqrt(mean(z^2) + eps) * w
  block:  h = x + RMSNorm(Mixer(x));  y = h + RMSNorm(MLP(h))
  MLP(z) = W_down (silu(W_gate z) * W_up z)
  linear_attention (H heads, dk, dv), token t of one stream:
    q~, k~, v~ = x W_qkv (side by side); each channel through a causal
    depthwise convolution of K taps (zeros before the first token), silu
    q = q / sqrt(|q|^2 + 1e-6) * dk^-1/2,  k = k / sqrt(|k|^2 + 1e-6)
    b, a = x W_ba;  beta = beta_scale * sigmoid(b)
    alpha = exp(-exp(A_log) * softplus(a + dt_bias))
    S' = alpha S;  u = beta (v - S'^T k);  S = S' + k u^T;  o = S^T q
    out = W_o [RMSNorm_head(o) * silu(x W_g)]
  full_attention: q = RMSNorm(x W_q), k = RMSNorm(x W_k) over the whole
    width, v = x W_v; causal softmax(q k^T / sqrt(dh)) v; W_o; no
    positional term.
  embedding -> blocks -> RMSNorm -> head.

`prec` selects the arithmetic:
  'float32'          float32, matmuls at precision "highest": THE
                     reference.
  'float32_default'  float32, matmuls at the backend's default precision
                     (on a TPU one bf16 pass): what a float32 program
                     that sets no precision gets. The recurrence itself
                     has no matmul and is the same in both.
  'bfloat16'         the bf16-stored control: activations, matmul
                     operands and the recurrent state kept in bfloat16
                     (float32 accumulation and norm statistics).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .gpt2 import rel_l2, seed_key  # noqa: F401  (shared with builders)

MLP_ROLES = ('mixer_norm', 'gate', 'up', 'down', 'mlp_norm')
ROLES = {
    'linear_attention': ('qkv', 'conv', 'ba', 'a_log', 'dt_bias',
                         'out_gate', 'head_norm', 'out') + MLP_ROLES,
    'full_attention': ('qkv', 'q_norm', 'k_norm', 'proj') + MLP_ROLES,
}
GLOBAL_ROLES = ('embed', 'final_norm', 'head')
VOCAB_BLOCKS = 8


class Dims(NamedTuple):
    vocab: int
    dim: int
    heads: int
    kinds: tuple
    ffn: int
    positions: int
    key_dim: int
    value_dim: int
    conv_kernel: int
    eps: float
    beta_scale: float

    @property
    def layers(self):
        return len(self.kinds)

    @property
    def conv_dim(self):
        return self.heads * (2 * self.key_dim + self.value_dim)


def dims_of(model):
    """Dims from a configuration file (HF olmo_hybrid keys, and the
    harness's `n_positions`). The layers run are the first
    `num_hidden_layers` of `layer_types`."""
    heads = int(model['num_attention_heads'])
    if int(model['linear_num_key_heads']) != heads or \
            int(model['linear_num_value_heads']) != heads or \
            int(model['num_key_value_heads']) != heads:
        raise ValueError('the reference has one head count for q, k, v '
                         'and the delta rule')
    return Dims(
        vocab=int(model['vocab_size']), dim=int(model['hidden_size']),
        heads=heads,
        kinds=tuple(model['layer_types'][:int(model['num_hidden_layers'])]),
        ffn=int(model['intermediate_size']),
        positions=int(model['n_positions']),
        key_dim=int(model['linear_key_head_dim']),
        value_dim=int(model['linear_value_head_dim']),
        conv_kernel=int(model['linear_conv_kernel_dim']),
        eps=float(model['rms_norm_eps']),
        beta_scale=2.0 if model['linear_allow_neg_eigval'] else 1.0)


def _shape(role, kind, d):
    h, dv = d.heads, d.value_dim
    return {'embed': (d.vocab, d.dim), 'head': (d.dim, d.vocab),
            'final_norm': (d.dim,), 'mixer_norm': (d.dim,),
            'mlp_norm': (d.dim,), 'q_norm': (d.dim,), 'k_norm': (d.dim,),
            'head_norm': (dv,), 'a_log': (h,), 'dt_bias': (h,),
            'qkv': (d.dim, d.conv_dim if kind == 'linear_attention'
                    else 3 * d.dim),
            'conv': (d.conv_kernel, d.conv_dim), 'ba': (d.dim, 2 * h),
            'out_gate': (d.dim, h * dv), 'out': (h * dv, d.dim),
            'proj': (d.dim, d.dim), 'gate': (d.dim, d.ffn),
            'up': (d.dim, d.ffn), 'down': (d.ffn, d.dim)}[role]


def vocab_block(key, role, d, b):
    """Block b of VOCAB_BLOCKS of the embedding (rows) or the head
    (columns): the two tensors too large to draw whole beside a model
    that fills the chip. Embedding normal(0, 1), so that a token's row is
    of the size of what the blocks add to it; head normal(0, 0.02)."""
    n = d.vocab // VOCAB_BLOCKS
    k = jax.random.fold_in(key, b)
    if role == 'embed':
        return jax.random.normal(k, (n, d.dim), jnp.float32)
    return 0.02 * jax.random.normal(k, (d.dim, n), jnp.float32)


def tensor(key, role, kind, d):
    """One weight tensor. Projections normal(0, 0.02), the two that
    write to the residual stream scaled by 1/sqrt(2L); gains 1 + 0.1 n
    so that no gain is invisible to the comparison; convolution taps
    normal(0, 0.5). The decay's parameters are drawn so that alpha
    spreads over about 0.9 to 0.999: dt = exp(dt_bias-ish) log-uniform in
    [0.001, 0.1] (dt_bias its inverse softplus), A = exp(A_log) within
    about 0.7 to 1.4, and the decay logits' weights an eighth of the
    others' so that a token moves its decay rate by tens of percent, not
    by orders of magnitude."""
    if role in ('embed', 'head'):
        return jnp.concatenate(
            [vocab_block(key, role, d, b) for b in range(VOCAB_BLOCKS)],
            axis=0 if role == 'embed' else 1)
    shape = _shape(role, kind, d)
    noise = jax.random.normal(key, shape, jnp.float32)
    if role.endswith('norm'):
        return 1.0 + 0.1 * noise
    if role == 'conv':
        return 0.5 * noise
    if role == 'a_log':
        return 0.17 * noise
    if role == 'dt_bias':
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return jnp.log(jnp.expm1(dt))
    std = 0.02
    if role in ('out', 'proj', 'down'):
        std /= math.sqrt(2.0 * d.layers)
    if role == 'ba':
        return std * noise * jnp.where(jnp.arange(shape[1]) < d.heads,
                                       1.0, 0.125)
    return std * noise


def _global_key(base, role):
    return jax.random.fold_in(base, GLOBAL_ROLES.index(role))


def layer_weights(base, i, kind, d):
    """Layer i's tensors by role; i may be traced."""
    k = jax.random.fold_in(base, 100 + i)
    return {r: tensor(jax.random.fold_in(k, j), r, kind, d)
            for j, r in enumerate(ROLES[kind])}


# what a builder puts in the program's place, a layer at a time
layer_tensors = jax.jit(layer_weights, static_argnums=(2, 3))


def global_tensor(base, role, d):
    return jax.jit(lambda k: tensor(k, role, None, d))(
        _global_key(base, role))


# -- arithmetic ------------------------------------------------------------

def _stream_dtype(prec):
    return jnp.bfloat16 if prec == 'bfloat16' else jnp.float32


def _mm(a, b, prec):
    if prec == 'float32':
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
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


def _linear_mixer(x, p, d, prec):
    """The linear-attention mixer on x [T, D], token by token."""
    st = x.dtype
    t = x.shape[0]
    h, dk, dv, kk = d.heads, d.key_dim, d.value_dim, d.conv_kernel
    qkv = _mm(x, p['qkv'], prec).astype(st)
    padded = jnp.pad(qkv, ((kk - 1, 0), (0, 0)))
    conv = sum(padded[j:j + t] * p['conv'][j].astype(st) for j in range(kk))
    conv = jax.nn.silu(conv).astype(jnp.float32)
    q = conv[:, :h * dk].reshape(t, h, dk)
    k = conv[:, h * dk:2 * h * dk].reshape(t, h, dk)
    v = conv[:, 2 * h * dk:].reshape(t, h, dv)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
        * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    ba = _mm(x, p['ba'], prec).astype(jnp.float32)
    beta = d.beta_scale * jax.nn.sigmoid(ba[:, :h])
    alpha = jnp.exp(-jnp.exp(p['a_log'])
                    * jax.nn.softplus(ba[:, h:] + p['dt_bias']))

    def token(s, xs):
        q_t, k_t, v_t, beta_t, alpha_t = xs
        s = s.astype(jnp.float32) * alpha_t[:, None, None]
        u = beta_t[:, None] * (v_t - jnp.sum(s * k_t[:, :, None], axis=1))
        s = s + k_t[:, :, None] * u[:, None, :]
        o = jnp.sum(s * q_t[:, :, None], axis=1)
        return s.astype(st), o                     # the state as stored

    _, o = jax.lax.scan(token, jnp.zeros((h, dk, dv), st),
                        (q, k, v, beta, alpha))    # o [T, H, dv]
    o = _rms(o.astype(st), p['head_norm'], d.eps).reshape(t, h * dv)
    gate = jax.nn.silu(_mm(x, p['out_gate'], prec).astype(st))
    return _mm(o * gate, p['out'], prec).astype(st)


def _full_mixer(x, p, d, prec):
    st = x.dtype
    t, dim = x.shape
    h = d.heads
    dh = dim // h
    qkv = _mm(x, p['qkv'], prec).astype(st)
    q = _rms(qkv[:, :dim], p['q_norm'], d.eps)
    k = _rms(qkv[:, dim:2 * dim], p['k_norm'], d.eps)
    q, k, v = (a.reshape(t, h, dh).transpose(1, 0, 2)
               for a in (q, k, qkv[:, 2 * dim:]))
    scores = _mm(q, k.transpose(0, 2, 1), prec) / math.sqrt(dh)
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask, scores.astype(jnp.float32), -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(st)
    ctx = _mm(probs, v, prec).astype(st).transpose(1, 0, 2).reshape(t, dim)
    return _mm(ctx, p['proj'], prec).astype(st)


def block(x, p, kind, d, prec):
    """One block on x [T, D]."""
    st = x.dtype
    mixer = _linear_mixer if kind == 'linear_attention' else _full_mixer
    x = x + _rms(mixer(x, p, d, prec), p['mixer_norm'], d.eps)
    up = jax.nn.silu(_mm(x, p['gate'], prec).astype(st)) \
        * _mm(x, p['up'], prec).astype(st)
    return x + _rms(_mm(up, p['down'], prec).astype(st), p['mlp_norm'],
                    d.eps)


@functools.partial(jax.jit, static_argnums=(2, 3, 5))
def _layer(base, i, kind, d, x, prec):
    return block(x, layer_weights(base, i, kind, d), kind, d, prec)


@functools.partial(jax.jit, static_argnums=(1, 3))
def _embed(base, d, tokens, prec):
    """Rows of the embedding, a slice of the vocabulary at a time."""
    n = d.vocab // VOCAB_BLOCKS
    key = _global_key(base, 'embed')

    def one(x, b):
        rows = vocab_block(key, 'embed', d, b)
        local = tokens - b * n
        hit = (local >= 0) & (local < n)
        return jnp.where(hit[:, None], rows[jnp.clip(local, 0, n - 1)],
                         x), None

    x, _ = jax.lax.scan(one, jnp.zeros((tokens.shape[0], d.dim)),
                        jnp.arange(VOCAB_BLOCKS))
    return x.astype(_stream_dtype(prec))


@functools.partial(jax.jit, static_argnums=(1, 3))
def _head(base, d, x, prec):
    w = tensor(_global_key(base, 'final_norm'), 'final_norm', None, d)
    h = _rms(x, w, d.eps)
    key = _global_key(base, 'head')
    out = jax.lax.map(
        lambda b: _mm(h, vocab_block(key, 'head', d, b), prec)
        .astype(jnp.float32), jnp.arange(VOCAB_BLOCKS))    # [B, T, V/B]
    return out.transpose(1, 0, 2).reshape(x.shape[0], d.vocab)


def logits(base, d, tokens, prec='float32', rows=None):
    """Logits [T, V] (float32) of one sequence tokens [T], or of its
    `rows` (a slice) only. One jitted call a layer: a layer's weights
    live only inside it."""
    if d.vocab % VOCAB_BLOCKS:
        raise ValueError('vocabulary %d is not %d equal slices'
                         % (d.vocab, VOCAB_BLOCKS))
    x = _embed(base, d, jnp.asarray(tokens, jnp.int32), prec)
    for i in range(d.layers):
        x = _layer(base, i, d.kinds[i], d, x, prec)
    return _head(base, d, x if rows is None else x[rows], prec)
