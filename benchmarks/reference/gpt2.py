"""Plain reference of the GPT-2 block (LayerNorm, learned positions, full
causal attention, tanh-GELU MLP, untied head): forward, loss and
gradients in straightforward jax.numpy. No kernels, no cache, no
batching. Weights come from a seed through `tensor()` below; the
builders fill the program with the same tensors, and the reference
makes its own again from the seed, one layer at a time, so it needs
nothing the program has made and never holds a second copy of a model.

`prec` selects the arithmetic:
  'float32'   float32 everywhere, matmuls at precision "highest": THE
              reference.
  'float32_default'  float32 everywhere, matmuls at the backend's default
              precision: on a TPU one bf16 pass with float32 accumulation,
              which is what a float32 program that sets no precision gets
              (the serving path). Same storage, same operand rounding as
              such a program, so what is left between the two is the
              order of accumulation.
  'bfloat16'  activations and matmul operands in bfloat16 (float32
              accumulation, LayerNorm/softmax/loss statistics in
              float32): the control for a float32 configuration.
  'float8'    the bf16 recipe with every matmul operand rounded through
              float8_e4m3fn first: the control for a bf16 configuration.

Departures from the published model are listed in each configuration's
`assumed`: jax.nn.gelu's tanh form (what the program's `gelu` op is) and
a head that is not tied to the embedding.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

LAYER_ROLES = ('ln1_g', 'ln1_b', 'qkv_w', 'qkv_b', 'proj_w', 'proj_b',
               'ln2_g', 'ln2_b', 'up_w', 'up_b', 'down_w', 'down_b')
GLOBAL_ROLES = ('wte', 'wpe', 'lnf_g', 'lnf_b', 'head_w', 'head_b')


class Dims(NamedTuple):
    vocab: int
    dim: int
    heads: int
    layers: int
    ffn: int
    positions: int


def dims_of(model):
    """Dims from a configuration file's `model` group (HF GPT-2 keys)."""
    return Dims(vocab=int(model['vocab_size']), dim=int(model['n_embd']),
                heads=int(model['n_head']), layers=int(model['n_layer']),
                ffn=int(model['n_inner']),
                positions=int(model['n_positions']))


def seed_key(seed):
    """A key from any whole number a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7fffffff, impl='rbg'),
                              seed >> 31)


def _shape(role, d):
    return {'wte': (d.vocab, d.dim), 'wpe': (d.positions, d.dim),
            'lnf_g': (d.dim,), 'lnf_b': (d.dim,),
            'head_w': (d.dim, d.vocab), 'head_b': (d.vocab,),
            'ln1_g': (d.dim,), 'ln1_b': (d.dim,),
            'qkv_w': (d.dim, 3 * d.dim), 'qkv_b': (3 * d.dim,),
            'proj_w': (d.dim, d.dim), 'proj_b': (d.dim,),
            'ln2_g': (d.dim,), 'ln2_b': (d.dim,),
            'up_w': (d.dim, d.ffn), 'up_b': (d.ffn,),
            'down_w': (d.ffn, d.dim), 'down_b': (d.dim,)}[role]


def tensor(key, role, d):
    """One weight tensor. GPT-2's initialisation (normal, std 0.02, the
    two residual projections scaled by 1/sqrt(2L)), except that gains
    and biases are not left at 1 and 0: a comparison on exact ones and
    zeros would not see a wrong gain or a dropped bias."""
    noise = jax.random.normal(key, _shape(role, d), jnp.float32)
    if role.endswith('_g'):
        return 1.0 + 0.1 * noise
    if role.endswith('_b'):
        return 0.02 * noise
    std = 0.02
    if role in ('proj_w', 'down_w'):
        std /= math.sqrt(2.0 * d.layers)
    return std * noise


def global_weights(base, d):
    return {r: tensor(jax.random.fold_in(base, j), r, d)
            for j, r in enumerate(GLOBAL_ROLES)}


def layer_weights(base, i, d):
    """Layer i's twelve tensors; i may be traced (inside a scan)."""
    k = jax.random.fold_in(base, 100 + i)
    return {r: tensor(jax.random.fold_in(k, j), r, d)
            for j, r in enumerate(LAYER_ROLES)}


def role_table(d):
    """(role, layer or None) in the order models/transformer.py creates
    its parameters, which is how a builder maps names to roles."""
    out = [('wte', None), ('wpe', None)]
    for i in range(d.layers):
        out += [(r, i) for r in LAYER_ROLES]
    out += [('lnf_g', None), ('lnf_b', None), ('head_w', None),
            ('head_b', None)]
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def all_weights(base, d):
    """Every tensor of the model, in role_table order, in one call."""
    g = global_weights(base, d)
    layers = [layer_weights(base, i, d) for i in range(d.layers)]
    return [g[r] if i is None else layers[i][r] for r, i in role_table(d)]


# -- arithmetic ------------------------------------------------------------

def _stream_dtype(prec):
    return jnp.float32 if prec.startswith('float32') else jnp.bfloat16


@jax.custom_vjp
def _fp8(a):
    """The usual fp8 recipe: values rounded through e4m3 on the way
    forward, cotangents through e5m2 on the way back."""
    return a.astype(jnp.float8_e4m3fn).astype(a.dtype)


_fp8.defvjp(lambda a: (_fp8(a), None),
            lambda _, ct: (ct.astype(jnp.float8_e5m2).astype(ct.dtype),))


def _mm(a, b, prec):
    if prec == 'float32':
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if prec == 'float32_default':
        return jnp.matmul(a, b, precision=jax.lax.Precision.DEFAULT)
    if prec == 'float8':
        a, b = _fp8(a), _fp8(b)
    elif prec != 'bfloat16':
        raise ValueError('unknown precision %r' % (prec,))
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _ln(x, g, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mean) * jax.lax.rsqrt(var + eps) * g + b).astype(x.dtype)


def block(x, p, heads, prec):
    """One GPT-2 block on x [T, D]."""
    t, dim = x.shape
    dh = dim // heads
    st = x.dtype
    h = _ln(x, p['ln1_g'], p['ln1_b'])
    qkv = (_mm(h, p['qkv_w'], prec) + p['qkv_b']).astype(st)
    q, k, v = (qkv[:, j * dim:(j + 1) * dim].reshape(t, heads, dh)
               .transpose(1, 0, 2) for j in range(3))         # [H, T, dh]
    scores = _mm(q, k.transpose(0, 2, 1), prec) / math.sqrt(dh)
    mask = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(mask, scores.astype(jnp.float32), -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(st)
    ctx = _mm(probs, v, prec).astype(st).transpose(1, 0, 2).reshape(t, dim)
    x = x + (_mm(ctx, p['proj_w'], prec) + p['proj_b']).astype(st)
    h = _ln(x, p['ln2_g'], p['ln2_b'])
    up = jax.nn.gelu((_mm(h, p['up_w'], prec) + p['up_b']).astype(st))
    return x + (_mm(up, p['down_w'], prec) + p['down_b']).astype(st)


def logits_fn(base, d, tokens, prec='float32', deltas=None, remat=False):
    """Logits [T, V] (float32) of one sequence tokens [T]. `deltas` maps
    (role, layer or None) to an array that is ADDED to that tensor: the
    gradient with respect to a zero delta is the gradient with respect
    to the weight, without the weight ever being an input."""
    deltas = deltas or {}
    st = _stream_dtype(prec)
    g = global_weights(base, d)
    for (role, layer), dv in deltas.items():
        if layer is None:
            g[role] = g[role] + dv
    t = tokens.shape[0]
    x = (g['wte'][tokens] + g['wpe'][:t]).astype(st)

    def body(x, i):
        p = layer_weights(base, i, d)
        for (role, layer), dv in deltas.items():
            if layer is not None:
                p[role] = p[role] + jnp.where(i == layer, 1.0, 0.0) * dv
        return block(x, p, d.heads, prec), None

    x, _ = jax.lax.scan(jax.checkpoint(body) if remat else body, x,
                        jnp.arange(d.layers))
    h = _ln(x, g['lnf_g'], g['lnf_b'])
    return (_mm(h, g['head_w'], prec) + g['head_b']).astype(jnp.float32)


def loss_fn(base, d, tokens, labels, prec='float32', deltas=None):
    """Mean next-token cross entropy of one sequence."""
    logits = logits_fn(base, d, tokens, prec, deltas, remat=True)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


@functools.partial(jax.jit, static_argnums=(1, 4, 5))
def loss_and_grads(base, d, tokens, labels, wanted, prec='float32'):
    """(loss, {(role, layer): gradient}) for the tensors in `wanted`, a
    tuple of (role, layer or None)."""
    zeros = {w: jnp.zeros(_shape(w[0], d), jnp.float32) for w in wanted}
    return jax.value_and_grad(
        lambda dl: loss_fn(base, d, tokens, labels, prec, dl))(zeros)


@functools.partial(jax.jit, static_argnums=(1, 3))
def logits(base, d, tokens, prec='float32'):
    return logits_fn(base, d, tokens, prec)


def rel_l2(got, want):
    """|got - want| / |want| over all entries, in float32."""
    got = jnp.asarray(got, jnp.float32).reshape(-1)
    want = jnp.asarray(want, jnp.float32).reshape(-1)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
