"""Plain reference of the Nemotron-H block (`model_type: nemotron_h`):
Mamba-2 layers, latent expert layers beside one shared expert, and
full-attention layers with fewer K/V heads than query heads; pre-norm,
one mixer a layer, untied head. Forward only, in straightforward
jax.numpy: the recurrence token by token (no chunking), the expert layer
as a loop over experts, full causal attention over the whole sequence
(no cache), no batching, no kernels. Weights come from a seed through
`tensor()`; a builder fills the program with the same tensors, and the
reference draws its own again, one layer (and one expert) at a time, so
it never holds a second model.

The equations (each configuration's `assumed` lists what its source does
not state):

  RMSNorm(z) = z / sqrt(mean(z^2) + eps) * w
  x <- x + Mixer_i(RMSNorm_i(x));  embedding -> layers -> RMSNorm -> head
  mamba (H heads of P, G groups of state N, K taps):
    [z | xBC | dt~] = W_in u, widths H P, H P + 2 G N, H
    xBC = silu(conv_causal(xBC; K taps, zeros before the first token) + b)
    x = xBC[: H P] as H heads; B, C = the next G N each, as G groups (a
    group serves H / G consecutive heads)
    dt = softplus(dt~ + dt_bias);  a = exp(-exp(A_log) dt)
    h_t = a_t h_{t-1} + dt_t x_t (x) B_t  (h [P, N] a head);
    y_t = h_t C_t + D x_t
    out = W_out RMSNorm_groups(y * silu(z)), the norm over each of the G
    groups of H P / G values, one gain a channel
  experts (E experts, k a token, latent L, share: `held` from `offset`):
    s = sigmoid(W_r u) in float32;  the k largest of s + b
    w = scale * s_sel / sum(s_sel);  l = W_down u
    r = sum over the selected experts e with offset <= e < offset + held
        of w_e W2_e relu(W1_e l)^2
    out = W_up r + V2 relu(V1 u)^2
  full_attention: q (heads), k, v (kv_heads) of head_dim = W_qkv u; query
    head h against K/V head h // (heads / kv_heads); causal
    softmax(q k^T / sqrt(head_dim)) v; W_o; no positional term.

`prec` selects the arithmetic:
  'float32'          float32, matmuls at precision "highest": THE
                     reference.
  'float32_default'  float32, matmuls at the backend's default precision
                     (on a TPU one bf16 pass): what a float32 program
                     that sets no precision gets. The recurrence has no
                     matmul and is the same in both; the router's scores
                     are at "highest" in every case, as the published
                     gate computes them in float32.
  'bfloat16'         the bf16-stored control: activations, matmul
                     operands and the recurrent state kept in bfloat16
                     (float32 accumulation, norm statistics and router).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .gpt2 import rel_l2, seed_key  # noqa: F401  (shared with builders)

ROLES = {
    'mamba': ('norm', 'in', 'conv', 'conv_bias', 'dt_bias', 'a_log', 'd',
              'gate_norm', 'out'),
    'experts': ('norm', 'router', 'bias', 'down', 'up', 'shared_up',
                'shared_down', 'w1', 'w2'),
    'full_attention': ('norm', 'qkv', 'proj'),
}
GLOBAL_ROLES = ('embed', 'final_norm', 'head')
PATTERN = {'M': 'mamba', 'E': 'experts', '*': 'full_attention'}
_HI = jax.lax.Precision.HIGHEST


class Dims(NamedTuple):
    vocab: int
    dim: int
    heads: int
    kv_heads: int
    head_dim: int
    kinds: tuple
    positions: int
    mamba_heads: int
    mamba_head_dim: int
    groups: int
    state: int
    conv_kernel: int
    chunk: int
    experts: int
    held: int
    offset: int
    top_k: int
    scale: float
    latent: int
    expert_ffn: int
    shared_ffn: int
    eps: float
    dt_min: float
    dt_max: float
    std: float

    @property
    def layers(self):
        return len(self.kinds)

    @property
    def inner(self):
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        return self.inner + 2 * self.groups * self.state


def dims_of(model):
    """Dims from a configuration file (HF nemotron_h keys, and the
    harness's: `n_positions`, `initializer_range`, and for the share `router_experts` (the
    published expert count, which the router keeps; `n_routed_experts`
    counts the experts held) and `expert_offset`). The layers run are
    the first `num_hidden_layers` letters of `hybrid_override_pattern`."""
    pattern = model['hybrid_override_pattern'][
        :int(model['num_hidden_layers'])]
    held = int(model['n_routed_experts'])
    if int(model['n_group']) != 1 or int(model['n_shared_experts']) != 1:
        raise ValueError('the reference has one routing group and one '
                         'shared expert')
    if int(model['mamba_num_heads']) * int(model['mamba_head_dim']) != \
            int(model['expand']) * int(model['hidden_size']):
        raise ValueError('mamba heads x head size is not expand x hidden')
    return Dims(
        vocab=int(model['vocab_size']), dim=int(model['hidden_size']),
        heads=int(model['num_attention_heads']),
        kv_heads=int(model['num_key_value_heads']),
        head_dim=int(model['head_dim']),
        kinds=tuple(PATTERN[c] for c in pattern),
        positions=int(model['n_positions']),
        mamba_heads=int(model['mamba_num_heads']),
        mamba_head_dim=int(model['mamba_head_dim']),
        groups=int(model['n_groups']), state=int(model['ssm_state_size']),
        conv_kernel=int(model['conv_kernel']),
        chunk=int(model['chunk_size']),
        experts=int(model.get('router_experts', held)), held=held,
        offset=int(model.get('expert_offset', 0)),
        top_k=int(model['num_experts_per_tok']),
        scale=float(model['routed_scaling_factor']),
        latent=int(model['moe_latent_size']),
        expert_ffn=int(model['moe_intermediate_size']),
        shared_ffn=int(model['moe_shared_expert_intermediate_size']),
        eps=float(model['layer_norm_epsilon']),
        dt_min=float(model['time_step_min']),
        dt_max=float(model['time_step_max']),
        std=float(model.get('initializer_range', 0.02)))


def _shape(role, d):
    h = d.mamba_heads
    return {'embed': (d.vocab, d.dim), 'head': (d.dim, d.vocab),
            'final_norm': (d.dim,), 'norm': (d.dim,),
            'in': (d.dim, d.inner + d.conv_dim + h),
            'conv': (d.conv_kernel, d.conv_dim), 'conv_bias': (d.conv_dim,),
            'dt_bias': (h,), 'a_log': (h,), 'd': (h,),
            'gate_norm': (d.inner,), 'out': (d.inner, d.dim),
            'router': (d.dim, d.experts), 'bias': (d.experts,),
            'down': (d.dim, d.latent), 'up': (d.latent, d.dim),
            'shared_up': (d.dim, d.shared_ffn),
            'shared_down': (d.shared_ffn, d.dim),
            'w1': (d.latent, d.expert_ffn), 'w2': (d.expert_ffn, d.latent),
            'qkv': (d.dim, (d.heads + 2 * d.kv_heads) * d.head_dim),
            'proj': (d.heads * d.head_dim, d.dim)}[role]


def tensor(key, role, d):
    """One weight tensor (for 'w1' / 'w2': ONE expert's). Projections
    normal(0, std) (`initializer_range`, 0.02 where the file has none; a
    tiny test model takes more, or its narrow layers would add nothing
    a comparison could see), those that write to the residual stream scaled by
    1/sqrt(2L); embedding normal(0, 1), so that a token's row is of the
    size of what the layers add to it; gains 1 + 0.1 n so that no gain
    is invisible to the comparison; convolution taps normal(0, 0.5), its
    bias 0.1 n. dt_bias the inverse softplus of a log-uniform step in
    [dt_min, dt_max], A_log the log of uniform[1, 16], D 1, and the
    step's columns of W_in an eighth of the others' so that a token moves
    its step by tens of percent, not by orders of magnitude. The router's
    weights normal(0, 1/sqrt(dim)): on normed input its logits have a
    standard deviation near 1, every expert alike, so routing is
    balanced as the published aux-loss-free balancing leaves it; the
    selection bias b is zero."""
    shape = _shape(role, d)
    noise = jax.random.normal(key, shape, jnp.float32)
    if role == 'embed':
        return noise
    if role.endswith('norm'):
        return 1.0 + 0.1 * noise
    if role == 'conv':
        return 0.5 * noise
    if role == 'conv_bias':
        return 0.1 * noise
    if role == 'a_log':
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1., 16.))
    if role == 'd':
        return jnp.ones(shape, jnp.float32)
    if role == 'bias':
        return jnp.zeros(shape, jnp.float32)
    if role == 'dt_bias':
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(d.dt_min), math.log(d.dt_max)))
        return jnp.log(jnp.expm1(dt))
    if role == 'router':
        return noise / math.sqrt(d.dim)
    std = d.std
    if role in ('out', 'up', 'shared_down', 'proj'):
        std /= math.sqrt(2.0 * d.layers)
    if role == 'in':
        return std * noise * jnp.where(
            jnp.arange(shape[1]) < d.inner + d.conv_dim, 1.0, 0.125)
    return std * noise


def _global_key(base, role):
    return jax.random.fold_in(base, GLOBAL_ROLES.index(role))


def _role_key(base, i, kind, role):
    return jax.random.fold_in(jax.random.fold_in(base, 100 + i),
                              ROLES[kind].index(role))


def expert_weights(base, i, e, d):
    """(W1, W2) of expert `e` (its number among all d.experts) of layer
    i; e may be traced. A share holds the experts offset..offset + held
    of the same model."""
    return tuple(
        tensor(jax.random.fold_in(_role_key(base, i, 'experts', r), e), r, d)
        for r in ('w1', 'w2'))


def layer_weights(base, i, kind, d):
    """Layer i's tensors by role, without the experts' own."""
    return {r: tensor(_role_key(base, i, kind, r), r, d)
            for r in ROLES[kind] if r not in ('w1', 'w2')}


@functools.partial(jax.jit, static_argnums=(2, 3))
def layer_tensors(base, i, kind, d):
    """What a builder puts in the program's place, a layer at a time:
    layer_weights, and for an expert layer the held experts' W1 and W2
    stacked [held, ...]."""
    out = layer_weights(base, i, kind, d)
    if kind == 'experts':
        # one expert at a time, as the reference's loop draws them: the
        # seed's generator (rbg) gives other numbers under vmap
        out['w1'], out['w2'] = jax.lax.map(
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


def _relu2(v):
    return jnp.square(jax.nn.relu(v))


def mamba_mixer(u, p, d, prec):
    """The state-space mixer on u [T, D], token by token."""
    st = u.dtype
    t = u.shape[0]
    h, hp, g, n, kk = (d.mamba_heads, d.mamba_head_dim, d.groups, d.state,
                       d.conv_kernel)
    zxd = _mm(u, p['in'], prec).astype(st)
    z = zxd[:, :d.inner]
    xbc = zxd[:, d.inner:d.inner + d.conv_dim]
    dt = zxd[:, d.inner + d.conv_dim:].astype(jnp.float32)
    padded = jnp.pad(xbc, ((kk - 1, 0), (0, 0)))
    conv = sum(padded[j:j + t] * p['conv'][j].astype(st) for j in range(kk))
    conv = jax.nn.silu(conv + p['conv_bias'].astype(st)).astype(jnp.float32)
    x = conv[:, :d.inner].reshape(t, h, hp)
    b = conv[:, d.inner:d.inner + g * n].reshape(t, g, n)
    c = conv[:, d.inner + g * n:].reshape(t, g, n)
    dt = jax.nn.softplus(dt + p['dt_bias'])
    a = jnp.exp(-jnp.exp(p['a_log']) * dt)

    def token(s, xs):
        x_t, b_t, c_t, dt_t, a_t = xs
        b_t, c_t = (jnp.repeat(v, h // g, axis=0) for v in (b_t, c_t))
        s = s.astype(jnp.float32) * a_t[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        y = jnp.sum(s * c_t[:, None, :], axis=-1) + p['d'][:, None] * x_t
        return s.astype(st), y                     # the state as stored

    _, y = jax.lax.scan(token, jnp.zeros((h, hp, n), st), (x, b, c, dt, a))
    y = y.reshape(t, d.inner).astype(st)
    gated = (y * jax.nn.silu(z)).reshape(t, g, d.inner // g)
    gated = _rms(gated, p['gate_norm'].reshape(g, -1), d.eps)
    return _mm(gated.reshape(t, d.inner), p['out'], prec).astype(st)


def route(u, p, d):
    """(experts [T, k], weights [T, k]) of each token, over all
    d.experts; float32 at "highest" whatever `prec`."""
    s = jax.nn.sigmoid(jnp.matmul(u.astype(jnp.float32), p['router'],
                                  precision=_HI))
    _, idx = jax.lax.top_k(s + p['bias'], d.top_k)
    sel = jnp.take_along_axis(s, idx, axis=-1)
    return idx, d.scale * sel / jnp.sum(sel, -1, keepdims=True)


def routed_part(u, p, d, prec, experts_of):
    """sum over the held experts of w_e W2_e relu(W1_e l)^2, in the
    latent [T, L]: a loop over the held experts, each over every row and
    weighted by w (0 where the row did not choose it). `experts_of(e)`
    gives expert e's (W1, W2)."""
    st = u.dtype
    idx, w = route(u, p, d)
    lat = _mm(u, p['down'], prec).astype(st)

    def one(acc, e):
        w1, w2 = experts_of(e)
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)       # [T]
        y = _mm(_relu2(_mm(lat, w1, prec).astype(st)), w2, prec)
        return acc + w_e[:, None] * y.astype(jnp.float32), None

    r, _ = jax.lax.scan(one, jnp.zeros(lat.shape, jnp.float32),
                        d.offset + jnp.arange(d.held))
    return r.astype(st)


def shared_part(u, p, d, prec):
    st = u.dtype
    return _mm(_relu2(_mm(u, p['shared_up'], prec).astype(st)),
               p['shared_down'], prec).astype(st)


def experts_mixer(u, p, d, prec, experts_of):
    r = routed_part(u, p, d, prec, experts_of)
    return _mm(r, p['up'], prec).astype(u.dtype) + shared_part(u, p, d, prec)


def attention_mixer(u, p, d, prec):
    st = u.dtype
    t = u.shape[0]
    h, kvh, dh = d.heads, d.kv_heads, d.head_dim
    qkv = _mm(u, p['qkv'], prec).astype(st)
    q = qkv[:, :h * dh].reshape(t, h, dh).transpose(1, 0, 2)
    k = qkv[:, h * dh:(h + kvh) * dh].reshape(t, kvh, dh).transpose(1, 0, 2)
    v = qkv[:, (h + kvh) * dh:].reshape(t, kvh, dh).transpose(1, 0, 2)
    k, v = (jnp.repeat(a, h // kvh, axis=0) for a in (k, v))
    scores = _mm(q, k.transpose(0, 2, 1), prec) / math.sqrt(dh)
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask, scores.astype(jnp.float32), -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(st)
    ctx = _mm(probs, v, prec).astype(st).transpose(1, 0, 2)
    return _mm(ctx.reshape(t, h * dh), p['proj'], prec).astype(st)


def block(base, i, x, kind, d, prec):
    """Layer i on x [T, D]."""
    p = layer_weights(base, i, kind, d)
    u = _rms(x, p['norm'], d.eps)
    if kind == 'mamba':
        return x + mamba_mixer(u, p, d, prec)
    if kind == 'experts':
        return x + experts_mixer(
            u, p, d, prec, lambda e: expert_weights(base, i, e, d))
    return x + attention_mixer(u, p, d, prec)


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


def logits(base, d, tokens, prec='float32', rows=None):
    """Logits [T, V] (float32) of one sequence tokens [T], or of its
    `rows` (a slice) only. One jitted call a layer: a layer's weights
    live only inside it, and an expert's only inside its turn of the
    loop."""
    x = _embed(base, d, jnp.asarray(tokens, jnp.int32), prec)
    for i in range(d.layers):
        x = _layer(base, i, d.kinds[i], d, x, prec)
    return _head(base, d, x if rows is None else x[rows], prec)
