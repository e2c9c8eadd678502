"""Plain reference of the Solar-Open2 block (`model_type: solar_open2`):
linear attention by the delta rule with a decay a KEY CHANNEL (Kimi
Delta Attention, arXiv:2510.26692) three layers to one of softmax
attention without a positional term and with an output gate
(arXiv:2505.06708), the period starting on the attention layer; every
layer also sigmoid-routed SwiGLU experts beside one shared expert;
pre-norm; an untied head. Forward only, in straightforward jax.numpy:
the rule token by token (no chunks), the expert sublayer as a loop over
experts, full causal attention over the whole sequence a head at a time
(no cache; query rows in blocks of ROWS where the sequence is long), no
batching, no kernels. Weights come from a seed through `tensor()`; a
builder fills the program with the same tensors, and the reference
draws its own again, one layer (and one expert) at a time, so it never
holds a second model.

The equations (the configuration's `assumed` lists what its source does
not state):

  RMSNorm(z) = z / sqrt(mean(z^2) + eps) * w
  x = E[token];  h = x + Mixer_i(RMSNorm(x));  u = RMSNorm(h)
  x <- h + Experts(u) + Shared(u);  logits = W_head RMSNorm(x)
  kda (H heads, key size dk, value size dv, K taps, rank r):
    [q~ | k~ | v~] = silu(conv_causal(W_qkv v; K taps, zeros before the
    first token)), a tap a channel, no bias
    q = q~ / |q~| * dk^-1/2,  k = k~ / |k~|  (a head at a time)
    g = -exp(A_log[h]) * softplus(W_f2 (W_f1 v) + dt_bias)  in R^{H x dk}
    beta = beta_scale * sigmoid(W_b v)  in R^H
    S' = Diag(exp(g_t)) S_{t-1};  w = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t w^T;  o_t = S_t^T q_t        (S [dk, dv] a head)
    out = W_o [RMSNorm_head(o_t) * sigmoid(W_g2 (W_g1 v))]
  attention: q (heads), k, v (kv_heads) of head_dim = W_qkv v; query
    head h against K/V head h // (heads / kv_heads); causal
    softmax(q k^T / sqrt(head_dim)) v; no positional term;
    out = W_o [attn * sigmoid(W_gate v)], elementwise.
  Experts (E experts, k a token, share: `held` from `offset`):
    s = sigmoid(W_r u) in float32;  the k largest of s + b;
    w = scale * s[chosen] / sum(s[chosen])
    sum over the chosen e with offset <= e < offset + held of
        w_e W2_e (silu(W1_e u) * W3_e u)
  Shared: V2 (silu(a) * b), [a | b] = V1 u.

`prec` selects the arithmetic:
  'float32'          float32, matmuls at precision "highest": THE
                     reference.
  'float32_default'  float32, matmuls at the backend's default precision
                     (on a TPU one bf16 pass): what a float32 program
                     that sets no precision gets. The rule has no matmul
                     and is the same in both; the router's scores are at
                     "highest" in every case.
  'bfloat16'         the bf16-stored control: activations, matmul
                     operands and the delta state kept in bfloat16
                     (float32 accumulation, norm statistics and router).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .gpt2 import rel_l2, seed_key  # noqa: F401  (shared with builders)

MIXER_ROLES = {
    'kda': ('norm', 'qkv', 'conv', 'f_down', 'f_up', 'b', 'a_log',
            'dt_bias', 'g_down', 'g_up', 'head_norm', 'proj'),
    'full_attention': ('norm', 'qkv', 'gate', 'proj'),
}
EXPERT_ROLES = ('ffn_norm', 'router', 'bias', 'shared_gate_up',
                'shared_down', 'w1', 'w3', 'w2')
ALL_ROLES = tuple(dict.fromkeys(
    MIXER_ROLES['kda'] + MIXER_ROLES['full_attention'] + EXPERT_ROLES))
GLOBAL_ROLES = ('embed', 'final_norm', 'head')
ROWS = 512          # query rows a block, where a sequence is longer
_HI = jax.lax.Precision.HIGHEST


class Dims(NamedTuple):
    vocab: int
    dim: int
    heads: int
    kv_heads: int
    head_dim: int
    kinds: tuple            # 'kda' | 'full_attention', the layers run
    positions: int
    kda_heads: int
    key_dim: int
    value_dim: int
    rank: int
    conv_kernel: int
    beta_scale: float
    experts: int
    held: int
    offset: int
    top_k: int
    scale: float
    expert_ffn: int
    shared_ffn: int
    eps: float
    std: float

    @property
    def layers(self):
        return len(self.kinds)

    @property
    def conv_dim(self):
        return self.kda_heads * (2 * self.key_dim + self.value_dim)


def kinds_of(model):
    """The layers run, by kind: layer i is softmax attention where
    `gqa_layers` lists it, the delta rule otherwise; the first
    `num_hidden_layers` of them."""
    gqa = set(int(i) for i in model['gqa_layers'])
    return tuple('full_attention' if i in gqa else 'kda'
                 for i in range(int(model['num_hidden_layers'])))


def dims_of(model):
    """Dims from a configuration file (the solar_open2 keys, and the
    harness's: `n_positions`, `initializer_range`, `kda_gate_rank`, and
    for the share `router_experts` (the published expert count, which
    the router keeps; `n_routed_experts` counts the experts held) and
    `expert_offset`)."""
    if model.get('use_rope'):
        raise ValueError('the reference has no positional term')
    if not model.get('use_gqa_gate') or model.get('kda_use_full_proj'):
        raise ValueError('the reference gates its attention and takes '
                         'the low-rank decay and output gates')
    lin = model['linear_attn_config']
    held = int(model['n_routed_experts'])
    dk = int(lin['head_dim'])
    return Dims(
        vocab=int(model['vocab_size']), dim=int(model['hidden_size']),
        heads=int(model['num_attention_heads']),
        kv_heads=int(model['num_key_value_heads']),
        head_dim=int(model['head_dim']), kinds=kinds_of(model),
        positions=int(model['n_positions']),
        kda_heads=int(lin['num_heads']), key_dim=dk, value_dim=dk,
        rank=int(model.get('kda_gate_rank', dk)),
        conv_kernel=int(lin['short_conv_kernel_size']),
        beta_scale=2.0 if model.get('kda_allow_neg_eigval') else 1.0,
        experts=int(model.get('router_experts', held)), held=held,
        offset=int(model.get('expert_offset', 0)),
        top_k=int(model['num_experts_per_tok']),
        scale=float(model.get('routed_scaling_factor', 1.0)),
        expert_ffn=int(model['moe_intermediate_size']),
        shared_ffn=int(model['n_shared_experts'])
        * int(model['moe_intermediate_size']),
        eps=float(model['rms_norm_eps']),
        std=float(model.get('initializer_range', 0.02)))


def _shape(role, d, kind=None):
    """A tensor's shape; 'qkv' and 'proj' are of the layer's `kind`."""
    h, dk, dv = d.kda_heads, d.key_dim, d.value_dim
    kda = kind == 'kda'
    return {'embed': (d.vocab, d.dim), 'final_norm': (d.dim,),
            'head': (d.dim, d.vocab),
            'norm': (d.dim,), 'ffn_norm': (d.dim,),
            'qkv': (d.dim, d.conv_dim if kda
                    else (d.heads + 2 * d.kv_heads) * d.head_dim),
            'proj': (h * dv if kda else d.heads * d.head_dim, d.dim),
            'conv': (d.conv_kernel, d.conv_dim),
            'f_down': (d.dim, d.rank), 'f_up': (d.rank, h * dk),
            'b': (d.dim, h), 'a_log': (h,), 'dt_bias': (h * dk,),
            'g_down': (d.dim, d.rank), 'g_up': (d.rank, h * dv),
            'head_norm': (dv,),
            'gate': (d.dim, d.heads * d.head_dim),
            'router': (d.dim, d.experts), 'bias': (d.experts,),
            'shared_gate_up': (d.dim, 2 * d.shared_ffn),
            'shared_down': (d.shared_ffn, d.dim),
            'w1': (d.dim, d.expert_ffn), 'w3': (d.dim, d.expert_ffn),
            'w2': (d.expert_ffn, d.dim)}[role]


def tensor(key, role, d, kind=None):
    """One weight tensor (for 'w1' / 'w3' / 'w2': ONE expert's). Every
    projection and the embedding normal(0, std) (`initializer_range`;
    the source row gives none, so 0.02 as the other references take; a
    tiny test model takes more, or its narrow layers would add nothing a
    comparison could see), as HF initialises the Glm4Moe block (no depth
    scaling). Gains 1 + 0.1 n so that no gain is invisible to the
    comparison; convolution taps normal(0, 0.5). The decay's parameters
    are drawn so that alpha spreads over about 0.9 to 0.999 AND differs
    between the channels of one head: dt_bias, a value a key channel,
    the inverse softplus of a log-uniform step in [0.001, 0.1], A =
    exp(A_log) within about 0.7 to 1.4 a head; the low-rank factors at
    `std` move a channel's rate by tens of percent a token (their
    product's logits have a standard deviation near 0.3 at the
    published widths) and keep the output gates off their saturations
    (0.35 to 0.65). The router's weights normal(0, 1/sqrt(dim)): on
    normed input its scores spread over about 0.1 to 0.9, every expert
    alike; the selection bias normal(0, 0.1), so that it changes which
    experts a token takes and a program that weighed by s + b, or left
    b out, would be seen."""
    shape = _shape(role, d, kind)
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
    if role == 'bias':
        return 0.1 * noise
    if role == 'router':
        return noise / math.sqrt(d.dim)
    return d.std * noise


def _global_key(base, role):
    return jax.random.fold_in(base, GLOBAL_ROLES.index(role))


def _role_key(base, i, role):
    return jax.random.fold_in(jax.random.fold_in(base, 100 + i),
                              ALL_ROLES.index(role))


def expert_weights(base, i, e, d):
    """(W1, W3, W2) of expert `e` (its number among all d.experts) of
    layer i; e may be traced. A share holds the experts offset..offset +
    held of the same model."""
    return tuple(
        tensor(jax.random.fold_in(_role_key(base, i, r), e), r, d)
        for r in ('w1', 'w3', 'w2'))


def layer_weights(base, i, kind, d):
    """Layer i's tensors by role, without the experts' own."""
    return {r: tensor(_role_key(base, i, r), r, d, kind)
            for r in MIXER_ROLES[kind] + EXPERT_ROLES
            if r not in ('w1', 'w3', 'w2')}


@functools.partial(jax.jit, static_argnums=(2, 3))
def layer_tensors(base, i, kind, d):
    """What a builder puts in the program's place, a layer at a time:
    layer_weights and the held experts' W1, W3 and W2 stacked
    [held, ...]."""
    out = layer_weights(base, i, kind, d)
    # one expert at a time, as the reference's loop draws them: the
    # seed's generator (rbg) gives other numbers under vmap
    out['w1'], out['w3'], out['w2'] = jax.lax.map(
        lambda e: expert_weights(base, i, e, d),
        d.offset + jnp.arange(d.held))
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


def kda_mixer(u, p, d, prec):
    """The linear-attention mixer on u [T, D], token by token."""
    st = u.dtype
    t = u.shape[0]
    h, dk, dv, kk = d.kda_heads, d.key_dim, d.value_dim, d.conv_kernel
    qkv = _mm(u, p['qkv'], prec).astype(st)
    padded = jnp.pad(qkv, ((kk - 1, 0), (0, 0)))
    conv = sum(padded[j:j + t] * p['conv'][j].astype(st) for j in range(kk))
    conv = jax.nn.silu(conv).astype(jnp.float32)
    q = conv[:, :h * dk].reshape(t, h, dk)
    k = conv[:, h * dk:2 * h * dk].reshape(t, h, dk)
    v = conv[:, 2 * h * dk:].reshape(t, h, dv)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
        * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)

    def low_rank(down, up):
        return _mm(_mm(u, p[down], prec).astype(st), p[up], prec) \
            .astype(jnp.float32)

    g = -jnp.exp(p['a_log'])[:, None] * jax.nn.softplus(
        (low_rank('f_down', 'f_up') + p['dt_bias']).reshape(t, h, dk))
    beta = d.beta_scale * jax.nn.sigmoid(
        _mm(u, p['b'], prec).astype(jnp.float32))

    def token(s, xs):
        q_t, k_t, v_t, beta_t, g_t = xs
        s = s.astype(jnp.float32) * jnp.exp(g_t)[:, :, None]
        w = beta_t[:, None] * (v_t - jnp.sum(s * k_t[:, :, None], axis=1))
        s = s + k_t[:, :, None] * w[:, None, :]
        o = jnp.sum(s * q_t[:, :, None], axis=1)
        return s.astype(st), o                     # the state as stored

    _, o = jax.lax.scan(token, jnp.zeros((h, dk, dv), st),
                        (q, k, v, beta, g))        # o [T, H, dv]
    o = _rms(o.astype(st), p['head_norm'], d.eps).reshape(t, h * dv)
    gate = jax.nn.sigmoid(low_rank('g_down', 'g_up')).astype(st)
    return _mm(o * gate, p['proj'], prec).astype(st)


def attention_mixer(u, p, d, prec):
    """Gated causal attention on u [T, D], a query head at a time, its
    query rows a block at a time where the sequence is long."""
    st = u.dtype
    t = u.shape[0]
    h, kvh, dh = d.heads, d.kv_heads, d.head_dim
    qkv = _mm(u, p['qkv'], prec).astype(st)
    q = qkv[:, :h * dh].reshape(t, h, dh).transpose(1, 0, 2)
    k = qkv[:, h * dh:(h + kvh) * dh].reshape(t, kvh, dh).transpose(1, 0, 2)
    v = qkv[:, (h + kvh) * dh:].reshape(t, kvh, dh).transpose(1, 0, 2)
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
    gate = jax.nn.sigmoid(_mm(u, p['gate'], prec).astype(jnp.float32))
    return _mm(ctx.reshape(t, h * dh) * gate.astype(st), p['proj'],
               prec).astype(st)


def route(u, p, d):
    """(experts [T, k], weights [T, k]) of each token, over all
    d.experts, by sorting: the k largest of s + b, weighted by their s
    over its sum; float32 at "highest" whatever `prec`."""
    s = jax.nn.sigmoid(jnp.matmul(u.astype(jnp.float32), p['router'],
                                  precision=_HI))
    _, idx = jax.lax.top_k(s + p['bias'], d.top_k)
    sel = jnp.take_along_axis(s, idx, axis=-1)
    return idx, d.scale * sel / jnp.sum(sel, -1, keepdims=True)


def routed_part(u, p, d, prec, experts_of, first=None, count=None,
                routing=None):
    """sum over the experts first..first + count (the held ones where
    not given) of w_e W2_e (silu(W1_e u) * W3_e u), [T, D]: a loop over
    those experts, each over every row and weighted by w (0 where the
    row did not choose it). `experts_of(e)` gives expert e's (W1, W3,
    W2); `routing` another pass's route() in place of this one's
    (tools/precision_arms_solar2.py)."""
    st = u.dtype
    idx, w = route(u, p, d) if routing is None else routing
    first = d.offset if first is None else first
    count = d.held if count is None else count

    def one(acc, e):
        w1, w3, w2 = experts_of(e)
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)       # [T]
        hid = jax.nn.silu(_mm(u, w1, prec).astype(st)) \
            * _mm(u, w3, prec).astype(st)
        return acc + w_e[:, None] * _mm(hid, w2, prec).astype(jnp.float32), \
            None

    r, _ = jax.lax.scan(one, jnp.zeros(u.shape, jnp.float32),
                        first + jnp.arange(count))
    return r.astype(st)


def shared_part(u, p, d, prec):
    st = u.dtype
    ab = _mm(u, p['shared_gate_up'], prec).astype(st)
    hid = jax.nn.silu(ab[:, :d.shared_ffn]) * ab[:, d.shared_ffn:]
    return _mm(hid, p['shared_down'], prec).astype(st)


def block(base, i, x, kind, d, prec):
    """Layer i on x [T, D]: the mixer, then the expert sublayer."""
    p = layer_weights(base, i, kind, d)
    mixer = kda_mixer if kind == 'kda' else attention_mixer
    x = x + mixer(_rms(x, p['norm'], d.eps), p, d, prec)
    u = _rms(x, p['ffn_norm'], d.eps)
    return x + routed_part(
        u, p, d, prec, lambda e: expert_weights(base, i, e, d)) \
        + shared_part(u, p, d, prec)


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
