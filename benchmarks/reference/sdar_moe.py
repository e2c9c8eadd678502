"""Plain reference of the SDAR block with routed experts (`model_type:
sdar_moe`, HF `modeling_sdar_moe`, which derives from the Qwen3-MoE
block; arXiv:2510.06303; block diffusion: arXiv:2503.09573): attention
that is causal over blocks of B tokens and bidirectional inside one, q
and k normed a head at a time before the rotation, grouped K/V heads,
SiLU-gated experts chosen by a softmax top-k router in every layer, a
head whose row i scores the token AT position i. Forward only, in
straightforward jax.numpy: the whole sequence at once against the dense
mask, a query head at a time (query rows in blocks of ROWS where the
sequence is long), the expert sublayer as a loop over experts, no
cache, no batching, no kernels. `block_diffusion_generate` is the
family's published decoding routine in the same plain style: it
recomputes the whole forward at every pass. Weights come from a seed
through `tensor()`; a builder fills the program with the same tensors,
and the reference draws its own again, one layer (and one expert) at a
time, so it never holds a second model.

The equations, for layer l (0-based), x the residual stream, no bias
anywhere, B the block length (the configuration's `assumed` lists what
its source does not state):

  RMSNorm(z) = z / sqrt(mean(z^2) + eps) * w
  u  = RMSNorm_in(x)
  q, k, v = W_q u, W_k u, W_v u  heads, kv_heads, kv_heads of head_dim
  q, k = RMSNorm_q(q), RMSNorm_k(k)
        a head at a time over its head_dim values, one gain [head_dim]
  q, k = RoPE(q), RoPE(k)
        theta rope_theta over the whole head, split halves: pair j is
        (z[j], z[j + head_dim / 2]), angle pos * theta^(-2j / head_dim)
  a  = softmax(q k^T / sqrt(head_dim) + M) v
        query head h reads K/V head h // (heads / kv_heads); key j is
        visible to query i iff j // B <= i // B
  x  = x + W_o a
  h  = RMSNorm_post(x)
  r  = W_r h                     router logits [E], float32 at "highest"
  S  = the k largest of r (ties to the lower index);  g = softmax(r[S])
  x  = x + sum_{e in S} g_e W2_e (silu(W1_e h) * W3_e h)
  logits = W_head RMSNorm_f(x)   untied; row i for position i

`prec` selects the arithmetic:
  'float32'          float32, matmuls at precision "highest": THE
                     reference.
  'float32_default'  float32, matmuls at the backend's default precision
                     (on a TPU one bf16 pass): what a float32 program
                     that sets no precision gets. The router's logits
                     are at "highest" in every case.
  'bfloat16'         the bf16-stored control: activations (so keys and
                     values too) and matmul operands kept in bfloat16
                     (float32 accumulation, norm statistics, rotary
                     angles and router).
`mask` selects what a row sees (controls, never the reference, but for
'block'):
  'block'            the model's: j // B <= i // B
  'causal'           j <= i: causal inside a block too
  ('misaligned', n)  the model's, but the rows before n see no key from
                     n on: what a prefix adopted at a boundary n that is
                     no multiple of B holds (its last tokens' K/V were
                     computed without the rest of their block)
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .gpt2 import rel_l2, seed_key  # noqa: F401  (shared with builders)

LAYER_ROLES = ('norm', 'qkv', 'q_norm', 'k_norm', 'proj', 'ffn_norm',
               'router')
EXPERT_ROLES = ('w1', 'w3', 'w2')
ALL_ROLES = LAYER_ROLES + EXPERT_ROLES
GLOBAL_ROLES = ('embed', 'final_norm', 'head')
RULES = ('low_confidence_static', 'sequential', 'low_confidence_dynamic')
ROWS = 512          # query rows a block, where a sequence is longer
_HI = jax.lax.Precision.HIGHEST


class Dims(NamedTuple):
    vocab: int
    dim: int
    heads: int
    kv_heads: int
    head_dim: int
    layers: int
    rope_theta: float
    positions: int
    experts: int
    held: int
    offset: int
    top_k: int
    expert_ffn: int
    eps: float
    std: float
    block: int              # block length B
    steps: int              # denoising steps T
    rule: str
    threshold: float
    mask_id: int


def dims_of(model):
    """Dims from a configuration file (HF sdar_moe keys, and the
    harness's: `n_positions`, `initializer_range`, the group
    `generation`, `mask_token_id`, and for a share `router_experts`
    (the published expert count, which the router keeps; `num_experts`
    then counts the experts held) and `expert_offset`)."""
    if model.get('rope_scaling') or model.get('tie_word_embeddings'):
        raise ValueError('the reference has plain RoPE and an untied head')
    if not model.get('norm_topk_prob', True):
        raise ValueError('the reference routes by a softmax over the chosen')
    if int(model.get('decoder_sparse_step', 1)) != 1 \
            or model.get('mlp_only_layers'):
        raise ValueError('the reference has experts in every layer')
    held = int(model['num_experts'])
    vocab = int(model['vocab_size'])
    gen = model.get('generation', {})
    return Dims(
        vocab=vocab, dim=int(model['hidden_size']),
        heads=int(model['num_attention_heads']),
        kv_heads=int(model['num_key_value_heads']),
        head_dim=int(model['head_dim']),
        layers=int(model['num_hidden_layers']),
        rope_theta=float(model['rope_theta']),
        positions=int(model['n_positions']),
        experts=int(model.get('router_experts', held)), held=held,
        offset=int(model.get('expert_offset', 0)),
        top_k=int(model['num_experts_per_tok']),
        expert_ffn=int(model['moe_intermediate_size']),
        eps=float(model['rms_norm_eps']),
        std=float(model.get('initializer_range', 0.02)),
        block=int(gen.get('block_length', 4)),
        steps=int(gen.get('denoising_steps', 4)),
        rule=str(gen.get('remasking', 'low_confidence_static')),
        threshold=float(gen.get('threshold', 0.9)),
        mask_id=int(model.get('mask_token_id', vocab - 1)))


def _shape(role, d):
    return {'embed': (d.vocab, d.dim), 'final_norm': (d.dim,),
            'head': (d.dim, d.vocab), 'norm': (d.dim,),
            'q_norm': (d.head_dim,), 'k_norm': (d.head_dim,),
            'ffn_norm': (d.dim,), 'router': (d.dim, d.experts),
            'w1': (d.dim, d.expert_ffn), 'w3': (d.dim, d.expert_ffn),
            'w2': (d.expert_ffn, d.dim),
            'qkv': (d.dim, (d.heads + 2 * d.kv_heads) * d.head_dim),
            'proj': (d.heads * d.head_dim, d.dim)}[role]


def tensor(key, role, d):
    """One weight tensor (for 'w1' / 'w3' / 'w2': ONE expert's). Every
    projection, the embedding and the head normal(0, std)
    (`initializer_range` 0.02, the published value; a tiny test model
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


def seen(pos_q, pos_k, d, mask='block'):
    """[Q, K] bool: the keys at `pos_k` a query at `pos_q` sees (the
    module docstring's `mask`)."""
    q, k = pos_q[:, None], pos_k[None, :]
    if mask == 'causal':
        return k <= q
    out = k // d.block <= q // d.block
    if mask == 'block':
        return out
    kind, n = mask
    if kind != 'misaligned':
        raise ValueError('unknown mask %r' % (mask,))
    return out & ~((q < n) & (k >= n))


def attention(u, p, d, prec, mask='block'):
    """A layer's attention on u [T, D]: the whole sequence against the
    dense mask, a query head at a time, its rows a block at a time
    where the sequence is long."""
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
            sc = jnp.where(seen(pos_b, pos, d, mask), sc, -jnp.inf)
            return _mm(jax.nn.softmax(sc, axis=-1).astype(st), v_i,
                       prec).astype(st)

        if t <= ROWS or t % ROWS:
            return rows((q_i, pos))
        return jax.lax.map(rows, (q_i.reshape(t // ROWS, ROWS, dh),
                                  pos.reshape(t // ROWS, ROWS))) \
            .reshape(t, dh)

    ctx = jax.lax.map(one_head, (q, k, v)).transpose(1, 0, 2)
    return _mm(ctx.reshape(t, h * dh), p['proj'], prec).astype(st)


def route(h, p, d):
    """(experts [T, k], weights [T, k]) of each token, scored on h over
    all d.experts: the k largest logits and the softmax over them;
    float32 at "highest" whatever `prec`."""
    logit = jnp.matmul(h.astype(jnp.float32), p['router'], precision=_HI)
    top, idx = jax.lax.top_k(logit, d.top_k)
    return idx, jax.nn.softmax(top, axis=-1)


def routed_part(h, p, d, prec, experts_of, experts=None):
    """sum over the held experts (`experts`: other numbers, for a
    share) of g_e W2_e (silu(W1_e h) * W3_e h), [T, D]: a loop over the
    experts, each over every row and weighted by g (0 where the row did
    not choose it). `experts_of(e)` gives expert e's (W1, W3, W2)."""
    st = h.dtype
    idx, g = route(h, p, d)

    def one(acc, e):
        w1, w3, w2 = experts_of(e)
        g_e = jnp.sum(jnp.where(idx == e, g, 0.0), axis=-1)       # [T]
        hid = jax.nn.silu(_mm(h, w1, prec).astype(st)) \
            * _mm(h, w3, prec).astype(st)
        return acc + g_e[:, None] * _mm(hid, w2, prec).astype(jnp.float32), \
            None

    r, _ = jax.lax.scan(
        one, jnp.zeros(h.shape, jnp.float32),
        d.offset + jnp.arange(d.held) if experts is None else experts)
    return r.astype(st)


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
    ROWS where attention works in blocks, else a multiple of 128 (which
    whole blocks of the model's divide: what lies behind a row's block
    it never sees)."""
    return -(-n // ROWS) * ROWS if n > ROWS else -(-n // 128) * 128


@functools.partial(jax.jit, static_argnums=(2, 4, 5))
def _layer_many(base, i, d, x, prec, mask):
    """Layer i (traced: every layer is the same program, compiled once a
    shape) on several sequences x [N, T, D] of one length: the attention
    a sequence at a time, the experts over all rows at once (a row's
    experts do not know its sequence), so that a layer's weights are
    drawn once for all of them."""
    p = layer_weights(base, i, d)
    x = x + jax.lax.map(
        lambda one: attention(_rms(one, p['norm'], d.eps), p, d, prec, mask),
        x)
    h = _rms(x, p['ffn_norm'], d.eps)
    return x + routed_part(
        h.reshape(-1, d.dim), p, d, prec,
        lambda e: expert_weights(base, i, e, d)).reshape(x.shape)


def _trunk(base, d, tokens, prec, mask):
    """The stream behind the last layer of sequences tokens [N, T]. One
    jitted call a layer: a layer's weights live only inside it, and an
    expert's only inside its turn of the loop."""
    x = _embed(base, d, jnp.asarray(tokens, jnp.int32), prec)
    for i in range(d.layers):
        x = _layer_many(base, jnp.int32(i), d, x, prec, mask)
    return x


def logits(base, d, tokens, prec='float32', rows=None, mask='block'):
    """Logits [T, V] (float32) of one sequence tokens [T], or of its
    `rows` (a slice or an index array) only; row i scores position i."""
    x = _trunk(base, d, jnp.asarray(tokens)[None], prec, mask)[0]
    return _head(base, d, x if rows is None else x[rows], prec)


def logits_rows(base, d, tokens, rows, prec='float32', mask='block'):
    """Logits [N, R, V] of the rows `rows` [N, R] of N sequences tokens
    [N, T] of one length: `logits` for each, a layer's weights drawn
    once. What a comparison of many passes over one stream reads: every
    pass is a sequence of its own (the ids as they stood at that pass)."""
    rows = jnp.asarray(rows, jnp.int32)
    x = _trunk(base, d, tokens, prec, mask)
    picked = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    return _head(base, d, picked.reshape(-1, d.dim), prec) \
        .reshape(rows.shape + (d.vocab,))


# -- generation ---------------------------------------------------------------

def schedule(d):
    """Rows a denoising step unmasks, step by step: B // T each, the
    first B % T steps one more."""
    return [d.block // d.steps + (i < d.block % d.steps)
            for i in range(d.steps)]


def unmask(lg, ids, n, d):
    """One lane's block between two passes: lg [B, V] (numpy), ids [B];
    up to n of the rows still masked take their candidate. A row's
    candidate x0 is the argmax of its logits over every id but the
    mask's (ties to the lower id), its confidence c = softmax(lg)[x0].
    Returns the new ids (a list)."""
    lg = np.asarray(lg, np.float64)
    ids = [int(t) for t in ids]
    masked = [r for r, t in enumerate(ids) if t == d.mask_id]
    n = min(int(n), len(masked))
    cand = lg.copy()
    cand[:, d.mask_id] = -np.inf
    x0 = cand.argmax(axis=-1)
    top = lg.max(axis=-1, keepdims=True)
    conf = np.exp(cand.max(axis=-1) - top[:, 0]) \
        / np.exp(lg - top).sum(axis=-1)
    if d.rule == 'sequential':
        take = masked[:n]
    else:
        by_conf = sorted(masked, key=lambda r: (-conf[r], r))
        take = by_conf[:n]
        if d.rule == 'low_confidence_dynamic' and n:
            over = [r for r in masked if conf[r] > d.threshold]
            if len(over) >= n:
                take = over
    for r in take:
        ids[r] = int(x0[r])
    return ids


def block_diffusion_generate(base, d, prompt, max_new_tokens, eos_id=None,
                             prec='float32', on_pass=None):
    """The family's published decoding routine at temperature 0, plain:
    the prompt's whole blocks stand; its last len % B tokens open the
    first generated block as fixed tokens, the mask id behind them; a
    block is passed over until no row is masked (each pass hands
    `schedule` rows, never more than are masked, their candidates by
    `d.rule`) and once more, the commit; every pass is the WHOLE forward
    over everything so far, of which the block's rows are read. A
    stream ends at eos_id or at the budget, which may fall inside a
    block: the block is decoded whole, the tokens past the end are
    dropped. `on_pass(ids so far [T], block start, logits [B, V],
    commit)` sees every pass. Returns the generated tokens."""
    prompt = [int(t) for t in prompt]
    b = d.block
    whole = len(prompt) - len(prompt) % b
    seq, tail = prompt[:whole], prompt[whole:]
    out = []
    width = padded_length(len(prompt) + max_new_tokens + b)
    plan = schedule(d)
    while True:
        ids = tail + [d.mask_id] * (b - len(tail))
        fixed, passes = len(tail), 0
        while True:
            padded = np.zeros((width,), np.int32)
            padded[:len(seq) + b] = seq + ids
            lg = np.asarray(logits(base, d, padded, prec,
                                   slice(len(seq), len(seq) + b)))
            commit = d.mask_id not in ids
            if on_pass is not None:
                on_pass(seq + ids, len(seq), lg, commit)
            if commit:
                break
            ids = unmask(lg, ids, plan[min(passes, len(plan) - 1)], d)
            passes += 1
        seq, tail = seq + ids, []
        for tok in ids[fixed:]:
            out.append(tok)
            if len(out) >= max_new_tokens or tok == eos_id:
                return out
