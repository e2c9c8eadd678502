"""Plain reference of the SmallThinker block (`model_type: smallthinker`,
HF `modeling_smallthinker`; arXiv:2507.20984): sliding-window layers
with rotary positions among global layers with no positional term,
grouped K/V heads, and in every layer ReGLU experts chosen by a router
that reads the ATTENTION's normed input. Forward only, in
straightforward jax.numpy: the whole sequence at once with a dense band
mask, a query head at a time (query rows in blocks of ROWS where the
sequence is long, so that a 16 k context's scores fit), the expert
sublayer as a loop over experts, no cache, no batching, no kernels.
Weights come from a seed through `tensor()`; a builder fills the
program with the same tensors, and the reference draws its own again,
one layer (and one expert) at a time, so it never holds a second model.

The equations, for layer l (0-based), x the residual stream, no bias
anywhere (the configuration's `assumed` lists what its source does not
state):

  RMSNorm(z) = z / sqrt(mean(z^2) + eps) * w
  u  = RMSNorm_in(x)
  r  = W_r u                     router logits [E], float32 at "highest"
  q, k, v = W_q u, W_k u, W_v u  heads, kv_heads, kv_heads of head_dim
  if rope_layout[l]:  q, k = RoPE(q), RoPE(k)
        theta rope_theta over the whole head, split halves: pair j is
        (z[j], z[j + head_dim / 2]), angle pos * theta^(-2j / head_dim)
  a  = softmax(q k^T / sqrt(head_dim) + mask_l) v
        query head h reads K/V head h // (heads / kv_heads); mask_l
        causal and, if sliding_window_layout[l], key j visible to query
        i iff i - window < j <= i
  x  = x + W_o a
  h  = RMSNorm_post(x)
  S  = the k largest of r (ties to the lower index);  g = softmax(r[S])
  x  = x + sum_{e in S} g_e W2_e (relu(W1_e h) * W3_e h)
  logits = W_head RMSNorm_f(x)   untied

`prec` selects the arithmetic:
  'float32'          float32, matmuls at precision "highest": THE
                     reference.
  'float32_default'  float32, matmuls at the backend's default precision
                     (on a TPU one bf16 pass): what a float32 program
                     that sets no precision gets. The router's logits
                     are at "highest" in every case.
  'bfloat16'         the bf16-stored control: activations and matmul
                     operands kept in bfloat16 (float32 accumulation,
                     norm statistics, rotary angles and router).
`full_window` (a control, never the reference): the sliding layers
attend to the whole causal history, the window ignored.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .gpt2 import rel_l2, seed_key  # noqa: F401  (shared with builders)

LAYER_ROLES = ('norm', 'qkv', 'proj', 'ffn_norm', 'router')
EXPERT_ROLES = ('w1', 'w3', 'w2')
ALL_ROLES = LAYER_ROLES + EXPERT_ROLES
GLOBAL_ROLES = ('embed', 'final_norm', 'head')
ROWS = 512          # query rows a block, where a sequence is longer
_HI = jax.lax.Precision.HIGHEST


class Dims(NamedTuple):
    vocab: int
    dim: int
    heads: int
    kv_heads: int
    head_dim: int
    sliding: tuple          # 0 | 1 a layer run: a window layer?
    rope: tuple             # 0 | 1 a layer run: rotary positions?
    window: int
    rope_theta: float
    positions: int
    experts: int
    held: int
    offset: int
    top_k: int
    expert_ffn: int
    eps: float
    std: float

    @property
    def layers(self):
        return len(self.sliding)


def dims_of(model):
    """Dims from a configuration file (HF smallthinker keys, and the
    harness's: `n_positions`, `initializer_range`, and for a share
    `experts_held` / `expert_offset`: this configuration holds every
    expert). The layers run are the first `num_hidden_layers` entries
    of the published layouts."""
    n = int(model['num_hidden_layers'])
    if not model.get('moe_primary_router_apply_softmax', True):
        raise ValueError('the reference routes by a softmax over the chosen')
    if model.get('rope_scaling') or model.get('tie_word_embeddings'):
        raise ValueError('the reference has plain RoPE and an untied head')
    experts = int(model['moe_num_primary_experts'])
    return Dims(
        vocab=int(model['vocab_size']), dim=int(model['hidden_size']),
        heads=int(model['num_attention_heads']),
        kv_heads=int(model['num_key_value_heads']),
        head_dim=int(model['head_dim']),
        sliding=tuple(int(v) for v in model['sliding_window_layout'][:n]),
        rope=tuple(int(v) for v in model['rope_layout'][:n]),
        window=int(model['sliding_window_size']),
        rope_theta=float(model['rope_theta']),
        positions=int(model['n_positions']),
        experts=experts, held=int(model.get('experts_held', experts)),
        offset=int(model.get('expert_offset', 0)),
        top_k=int(model['moe_num_active_primary_experts']),
        expert_ffn=int(model['moe_ffn_hidden_size']),
        eps=float(model['rms_norm_eps']),
        std=float(model.get('initializer_range', 0.02)))


def _shape(role, d):
    return {'embed': (d.vocab, d.dim), 'final_norm': (d.dim,),
            'head': (d.dim, d.vocab), 'norm': (d.dim,),
            'ffn_norm': (d.dim,), 'router': (d.dim, d.experts),
            'w1': (d.dim, d.expert_ffn), 'w3': (d.dim, d.expert_ffn),
            'w2': (d.expert_ffn, d.dim),
            'qkv': (d.dim, (d.heads + 2 * d.kv_heads) * d.head_dim),
            'proj': (d.heads * d.head_dim, d.dim)}[role]


def tensor(key, role, d):
    """One weight tensor (for 'w1' / 'w3' / 'w2': ONE expert's). Every
    projection, the embedding and the head normal(0, std)
    (`initializer_range`; the source row gives none, so 0.02 as the
    other references take, listed under `assumed`; a tiny test model
    takes more, or its narrow layers would add nothing a comparison
    could see). Gains 1 + 0.1 n so that no gain is invisible to the
    comparison. The router's weights normal(0, 1/sqrt(dim)): on normed
    input its logits have a standard deviation near 1, every expert
    alike, so routing comes out balanced and the softmax over the
    chosen is not flat."""
    noise = jax.random.normal(key, _shape(role, d), jnp.float32)
    if role.endswith('norm'):
        return 1.0 + 0.1 * noise
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
    layer i; e may be traced."""
    return tuple(
        tensor(jax.random.fold_in(_role_key(base, i, r), e), r, d)
        for r in EXPERT_ROLES)


def layer_weights(base, i, d):
    """Layer i's tensors by role, without the experts' own."""
    return {r: tensor(_role_key(base, i, r), r, d) for r in LAYER_ROLES}


@functools.partial(jax.jit, static_argnums=(2,))
def layer_tensors(base, i, d):
    """What a builder puts in the program's place, a layer at a time:
    layer_weights and the held experts' W1, W3 and W2 stacked
    [held, ...]."""
    out = layer_weights(base, i, d)
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


def attention(u, p, d, i, prec, full_window=False):
    """Layer i's attention on u [T, D]: the whole sequence against a
    dense mask (causal, a band of d.window where the layer slides), a
    query head at a time, its rows a block at a time where the sequence
    is long."""
    st = u.dtype
    t = u.shape[0]
    h, kvh, dh = d.heads, d.kv_heads, d.head_dim
    qkv = _mm(u, p['qkv'], prec).astype(st)
    q = qkv[:, :h * dh].reshape(t, h, dh)
    k = qkv[:, h * dh:(h + kvh) * dh].reshape(t, kvh, dh)
    v = qkv[:, (h + kvh) * dh:].reshape(t, kvh, dh)
    if d.rope[i]:
        q, k = rope(q, d), rope(k, d)
    q, k, v = (a.transpose(1, 0, 2) for a in (q, k, v))
    k, v = (jnp.repeat(a, h // kvh, axis=0) for a in (k, v))
    pos = jnp.arange(t)
    band = d.window if d.sliding[i] and not full_window else t

    def one_head(args):
        q_i, k_i, v_i = args

        def rows(args):
            q_b, pos_b = args
            sc = _mm(q_b, k_i.T, prec).astype(jnp.float32) / math.sqrt(dh)
            seen = (pos[None, :] <= pos_b[:, None]) \
                & (pos[None, :] > pos_b[:, None] - band)
            sc = jnp.where(seen, sc, -jnp.inf)
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
    """(experts [T, k], weights [T, k]) of each token, scored on u (the
    attention's normed input) over all d.experts: the k largest logits
    and the softmax over them; float32 at "highest" whatever `prec`."""
    logit = jnp.matmul(u.astype(jnp.float32), p['router'], precision=_HI)
    top, idx = jax.lax.top_k(logit, d.top_k)
    return idx, jax.nn.softmax(top, axis=-1)


def routed_part(u, hid_in, p, d, prec, experts_of):
    """sum over the held experts of g_e W2_e (relu(W1_e h) * W3_e h),
    [T, D]: routed on u, computed on `hid_in` (h); a loop over the held
    experts, each over every row and weighted by g (0 where the row did
    not choose it). `experts_of(e)` gives expert e's (W1, W3, W2)."""
    st = hid_in.dtype
    idx, g = route(u, p, d)

    def one(acc, e):
        w1, w3, w2 = experts_of(e)
        g_e = jnp.sum(jnp.where(idx == e, g, 0.0), axis=-1)       # [T]
        hid = jax.nn.relu(_mm(hid_in, w1, prec).astype(st)) \
            * _mm(hid_in, w3, prec).astype(st)
        return acc + g_e[:, None] * _mm(hid, w2, prec).astype(jnp.float32), \
            None

    r, _ = jax.lax.scan(one, jnp.zeros(hid_in.shape, jnp.float32),
                        d.offset + jnp.arange(d.held))
    return r.astype(st)


def block(base, i, x, d, prec, full_window=False):
    """Layer i on x [T, D]: attention, then the experts the router
    chose on the attention's input."""
    p = layer_weights(base, i, d)
    u = _rms(x, p['norm'], d.eps)
    x = x + attention(u, p, d, i, prec, full_window)
    h = _rms(x, p['ffn_norm'], d.eps)
    return x + routed_part(u, h, p, d, prec,
                           lambda e: expert_weights(base, i, e, d))


@functools.partial(jax.jit, static_argnums=(1, 2, 4, 5))
def _layer(base, i, d, x, prec, full_window):
    return block(base, i, x, d, prec, full_window)


@functools.partial(jax.jit, static_argnums=(1, 3))
def _embed(base, d, tokens, prec):
    return tensor(_global_key(base, 'embed'), 'embed', d)[tokens] \
        .astype(_stream_dtype(prec))


@functools.partial(jax.jit, static_argnums=(1, 3))
def _head(base, d, x, prec):
    h = _rms(x, tensor(_global_key(base, 'final_norm'), 'final_norm', d),
             d.eps)
    return _mm(h, tensor(_global_key(base, 'head'), 'head', d),
               prec).astype(jnp.float32)


def padded_length(n):
    """The length a sequence of n tokens is padded to: whole blocks of
    ROWS where attention works in blocks, else a multiple of 128."""
    return -(-n // ROWS) * ROWS if n > ROWS else -(-n // 128) * 128


def logits(base, d, tokens, prec='float32', rows=None, full_window=False):
    """Logits [T, V] (float32) of one sequence tokens [T], or of its
    `rows` (a slice) only. One jitted call a layer: a layer's weights
    live only inside it, and an expert's only inside its turn of the
    loop."""
    x = _embed(base, d, jnp.asarray(tokens, jnp.int32), prec)
    for i in range(d.layers):
        x = _layer(base, i, d, x, prec, bool(full_window))
    return _head(base, d, x if rows is None else x[rows], prec)
