"""Plain reference of the GraniteMoeHybrid block (`model_type:
granitemoehybrid`, HF `GraniteMoeHybrid`; Mamba-2 is arXiv:2405.21060):
every layer a mixer (Mamba-2, or full attention among every few) and an
expert sublayer, each through a scaled residual; softmax-routed SwiGLU
experts beside one shared expert; a tied head. Forward only, in
straightforward jax.numpy: the recurrence token by token (no chunking),
the expert sublayer as a loop over experts, full causal attention over
the whole sequence a head at a time (no cache; query rows in blocks of
ROWS where the sequence is long, so that a 5 k conversation's scores
fit), no batching, no kernels. Weights come from a seed through
`tensor()`; a builder fills the program with the same tensors, and the
reference draws its own again, one layer (and one expert) at a time, so
it never holds a second model.

The equations (the configuration's `assumed` lists what its source does
not state):

  RMSNorm(z) = z / sqrt(mean(z^2) + eps) * w
  x  = embedding_multiplier * E[token]
  x <- x + residual_multiplier * Mixer_i(RMSNorm(x))
  u  = RMSNorm(x);  x <- x + residual_multiplier * (Experts(u) + Shared(u))
  logits = RMSNorm(x) E^T / logits_scaling
  mamba (H heads of P, G groups of state N, K taps):
    [z | xBC | dt~] = W_in v, widths H P, H P + 2 G N, H
    xBC = silu(conv_causal(xBC; K taps, zeros before the first token) + b)
    x = xBC[: H P] as H heads; B, C = the next G N each, as G groups (a
    group serves H / G consecutive heads; G = 1 as published)
    dt = softplus(dt~ + dt_bias);  a = exp(-exp(A_log) dt)
    h_t = a_t h_{t-1} + dt_t x_t (x) B_t  (h [P, N] a head);
    y_t = h_t C_t + D x_t
    out = W_out RMSNorm_groups(y * silu(z)), the norm over each of the G
    groups of H P / G values, one gain a channel
  attention: q (heads), k, v (kv_heads) of head_dim = W_qkv v; query head
    h against K/V head h // (heads / kv_heads); causal
    softmax(q k^T * attention_multiplier) v; W_o; no positional term.
  Experts (E experts, k a token, share: `held` from `offset`):
    l = W_r u in float32;  the k largest of l;  g = softmax(l[chosen])
    sum over the chosen e with offset <= e < offset + held of
        g_e W2_e (silu(W1_e u) * W3_e u)
  Shared: V2 (silu(a) * b), [a | b] = V1 u.

`prec` selects the arithmetic:
  'float32'          float32, matmuls at precision "highest": THE
                     reference.
  'float32_default'  float32, matmuls at the backend's default precision
                     (on a TPU one bf16 pass): what a float32 program
                     that sets no precision gets. The recurrence has no
                     matmul and is the same in both; the router's logits
                     are at "highest" in every case.
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

MIXER_ROLES = {
    'mamba': ('norm', 'in', 'conv', 'conv_bias', 'dt_bias', 'a_log', 'd',
              'gate_norm', 'out'),
    'attention': ('norm', 'qkv', 'proj'),
}
EXPERT_ROLES = ('ffn_norm', 'router', 'shared_up', 'shared_down',
                'w1', 'w3', 'w2')
ALL_ROLES = tuple(dict.fromkeys(
    MIXER_ROLES['mamba'] + MIXER_ROLES['attention'] + EXPERT_ROLES))
GLOBAL_ROLES = ('embed', 'final_norm')
ROWS = 512          # query rows a block, where a sequence is longer
_HI = jax.lax.Precision.HIGHEST


class Dims(NamedTuple):
    vocab: int
    dim: int
    heads: int
    kv_heads: int
    head_dim: int
    kinds: tuple            # 'mamba' | 'attention', the layers run
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
    expert_ffn: int
    shared_ffn: int
    eps: float
    emb_mult: float
    res_mult: float
    attn_mult: float
    logits_scaling: float
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
    """Dims from a configuration file (HF granitemoehybrid keys, and the
    harness's: `n_positions`, `initializer_range`, `time_step_min` /
    `time_step_max`, and for the share `router_experts` (the published
    expert count, which the router keeps; `num_local_experts` counts the
    experts held) and `expert_offset`). The layers run are the first
    `num_hidden_layers` of `layer_types`."""
    kinds = tuple(model['layer_types'][:int(model['num_hidden_layers'])])
    if set(kinds) - set(MIXER_ROLES):
        raise ValueError('layer_types %r' % (sorted(set(kinds)),))
    held = int(model['num_local_experts'])
    if int(model['mamba_n_heads']) * int(model['mamba_d_head']) != \
            int(model['mamba_expand']) * int(model['hidden_size']):
        raise ValueError('mamba heads x head size is not expand x hidden')
    if model.get('position_embedding_type', 'nope') != 'nope':
        raise ValueError('the reference has no positional term')
    heads = int(model['num_attention_heads'])
    return Dims(
        vocab=int(model['vocab_size']), dim=int(model['hidden_size']),
        heads=heads, kv_heads=int(model['num_key_value_heads']),
        head_dim=int(model.get('head_dim')
                     or int(model['hidden_size']) // heads),
        kinds=kinds, positions=int(model['n_positions']),
        mamba_heads=int(model['mamba_n_heads']),
        mamba_head_dim=int(model['mamba_d_head']),
        groups=int(model['mamba_n_groups']),
        state=int(model['mamba_d_state']),
        conv_kernel=int(model['mamba_d_conv']),
        chunk=int(model['mamba_chunk_size']),
        experts=int(model.get('router_experts', held)), held=held,
        offset=int(model.get('expert_offset', 0)),
        top_k=int(model['num_experts_per_tok']),
        expert_ffn=int(model['intermediate_size']),
        shared_ffn=int(model['shared_intermediate_size']),
        eps=float(model['rms_norm_eps']),
        emb_mult=float(model['embedding_multiplier']),
        res_mult=float(model['residual_multiplier']),
        attn_mult=float(model['attention_multiplier']),
        logits_scaling=float(model['logits_scaling']),
        dt_min=float(model.get('time_step_min', 0.001)),
        dt_max=float(model.get('time_step_max', 0.1)),
        std=float(model.get('initializer_range', 0.02)))


def _shape(role, d):
    h = d.mamba_heads
    return {'embed': (d.vocab, d.dim), 'final_norm': (d.dim,),
            'norm': (d.dim,), 'ffn_norm': (d.dim,),
            'in': (d.dim, d.inner + d.conv_dim + h),
            'conv': (d.conv_kernel, d.conv_dim), 'conv_bias': (d.conv_dim,),
            'dt_bias': (h,), 'a_log': (h,), 'd': (h,),
            'gate_norm': (d.inner,), 'out': (d.inner, d.dim),
            'router': (d.dim, d.experts),
            'shared_up': (d.dim, 2 * d.shared_ffn),
            'shared_down': (d.shared_ffn, d.dim),
            'w1': (d.dim, d.expert_ffn), 'w3': (d.dim, d.expert_ffn),
            'w2': (d.expert_ffn, d.dim),
            'qkv': (d.dim, (d.heads + 2 * d.kv_heads) * d.head_dim),
            'proj': (d.heads * d.head_dim, d.dim)}[role]


def tensor(key, role, d):
    """One weight tensor (for 'w1' / 'w3' / 'w2': ONE expert's). As HF
    GraniteMoeHybrid initialises: every projection and the embedding
    normal(0, std) (`initializer_range`; the source row gives none, so
    0.02 as the other references take, listed under `assumed`; a tiny
    test model takes more, or its narrow layers would add nothing a
    comparison could see). Gains 1 + 0.1 n so that no gain is invisible
    to the comparison; convolution taps normal(0, 0.5), its bias 0.1 n.
    dt_bias the inverse softplus of a log-uniform step in [dt_min,
    dt_max], A_log the log of uniform[1, 16], D 1, and the step's
    columns of W_in an eighth of the others' so that a token moves its
    step by tens of percent, not by orders of magnitude. The router's
    weights normal(0, 1/sqrt(dim)): on normed input its logits have a
    standard deviation near 1, every expert alike, so routing comes out
    balanced and the softmax over the chosen is not flat."""
    shape = _shape(role, d)
    noise = jax.random.normal(key, shape, jnp.float32)
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
    if role == 'dt_bias':
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(d.dt_min), math.log(d.dt_max)))
        return jnp.log(jnp.expm1(dt))
    if role == 'router':
        return noise / math.sqrt(d.dim)
    if role == 'in':
        return d.std * noise * jnp.where(
            jnp.arange(shape[1]) < d.inner + d.conv_dim, 1.0, 0.125)
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
    return {r: tensor(_role_key(base, i, r), r, d)
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


def attention_mixer(u, p, d, prec):
    """Full causal attention on u [T, D], a query head at a time, its
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
            sc = _mm(q_b, k_i.T, prec).astype(jnp.float32) * d.attn_mult
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


def route(u, p, d):
    """(experts [T, k], weights [T, k]) of each token, over all
    d.experts: the k largest logits and the softmax over them; float32
    at "highest" whatever `prec`."""
    logit = jnp.matmul(u.astype(jnp.float32), p['router'], precision=_HI)
    top, idx = jax.lax.top_k(logit, d.top_k)
    return idx, jax.nn.softmax(top, axis=-1)


def routed_part(u, p, d, prec, experts_of):
    """sum over the held experts of g_e W2_e (silu(W1_e u) * W3_e u),
    [T, D]: a loop over the held experts, each over every row and
    weighted by g (0 where the row did not choose it). `experts_of(e)`
    gives expert e's (W1, W3, W2)."""
    st = u.dtype
    idx, g = route(u, p, d)

    def one(acc, e):
        w1, w3, w2 = experts_of(e)
        g_e = jnp.sum(jnp.where(idx == e, g, 0.0), axis=-1)       # [T]
        hid = jax.nn.silu(_mm(u, w1, prec).astype(st)) \
            * _mm(u, w3, prec).astype(st)
        return acc + g_e[:, None] * _mm(hid, w2, prec).astype(jnp.float32), \
            None

    r, _ = jax.lax.scan(one, jnp.zeros(u.shape, jnp.float32),
                        d.offset + jnp.arange(d.held))
    return r.astype(st)


def shared_part(u, p, d, prec):
    st = u.dtype
    ab = _mm(u, p['shared_up'], prec).astype(st)
    hid = jax.nn.silu(ab[:, :d.shared_ffn]) * ab[:, d.shared_ffn:]
    return _mm(hid, p['shared_down'], prec).astype(st)


def block(base, i, x, kind, d, prec):
    """Layer i on x [T, D]: the mixer, then the expert sublayer."""
    p = layer_weights(base, i, kind, d)
    mixer = mamba_mixer if kind == 'mamba' else attention_mixer
    res = jnp.asarray(d.res_mult, x.dtype)
    x = x + res * mixer(_rms(x, p['norm'], d.eps), p, d, prec)
    u = _rms(x, p['ffn_norm'], d.eps)
    return x + res * (
        routed_part(u, p, d, prec, lambda e: expert_weights(base, i, e, d))
        + shared_part(u, p, d, prec))


@functools.partial(jax.jit, static_argnums=(2, 3, 5))
def _layer(base, i, kind, d, x, prec):
    return block(base, i, x, kind, d, prec)


@functools.partial(jax.jit, static_argnums=(1, 3))
def _embed(base, d, tokens, prec):
    st = _stream_dtype(prec)
    return (d.emb_mult * tensor(_global_key(base, 'embed'), 'embed', d)
            [tokens]).astype(st)


@functools.partial(jax.jit, static_argnums=(1, 3))
def _head(base, d, x, prec):
    h = _rms(x, tensor(_global_key(base, 'final_norm'), 'final_norm', d),
             d.eps)
    e = tensor(_global_key(base, 'embed'), 'embed', d)
    return _mm(h, e.T, prec).astype(jnp.float32) / d.logits_scaling


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
