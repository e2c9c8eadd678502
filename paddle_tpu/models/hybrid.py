"""Hybrid language model: gated-delta-rule layers between full-attention
layers (the Olmo-Hybrid block; Gated DeltaNet, arXiv:2412.06464).

Beside models/transformer.py, whose named-fc helpers, page-pool
variables and paged attention it shares. One block wiring for both
layer kinds, post-norm on each sublayer's output, no bias anywhere:

    h = x + RMSNorm(Mixer(x));  y = h + RMSNorm(MLP(h))
    MLP(z) = W_down (silu(W_gate z) * W_up z)

`linear_attention` mixer (H heads, key size dk, value size dv): q~, k~,
v~ = x W_qkv side by side; a causal depthwise convolution of K taps and
silu on every channel (op short_conv); the gated delta rule with its
per-head gates from x W_ba (ops gated_delta_chunk / gated_delta_step,
ops/delta_rule_ops.py); then W_o [RMSNorm_head(o) * silu(x W_g)].
`full_attention` mixer: q = RMSNorm(x W_q), k = RMSNorm(x W_k) over the
whole width, v = x W_v, causal softmax attention, W_o; no positional
term. Embedding -> blocks -> RMSNorm -> untied head.

Three programs come from the one block code:

  language_model_logits   the whole-sequence program that
                          save_inference_model writes and the
                          DecodeTranspiler reads;
  build_paged_prefill_program / build_paged_decode_program
                          the paged serving pair. K/V pools exist for
                          the full-attention layers only; each
                          linear-attention layer keeps, per slot, its
                          delta state [slots, H, dk, dv] and the
                          convolution's last K-1 input rows
                          [slots, K-1, C] as scope variables that both
                          programs update in place, like the pools.
"""
from __future__ import annotations

import numpy as np

from .. import layers as L
from .transformer import (PAGED_DECODE_FEEDS, DecodeSpec, _block_op,
                          _create_pool_vars, _named_attr, _named_fc,
                          _paged_decode_attention, _paged_decode_tokens,
                          _paged_prefill_attention, _qkv_parts, _tmp_var)

KINDS = ('linear_attention', 'full_attention')


class HybridConfig(object):
    def __init__(self, vocab=512, dim=64, heads=2, layer_types=KINDS,
                 ffn=128, max_len=64, key_dim=8, value_dim=16,
                 conv_kernel=4, eps=1e-6, neg_eigval=True):
        self.vocab, self.dim, self.heads = vocab, dim, heads
        self.layer_types = tuple(layer_types)
        self.ffn, self.max_len = ffn, max_len
        self.key_dim, self.value_dim = key_dim, value_dim
        self.conv_kernel, self.eps = conv_kernel, eps
        self.neg_eigval = neg_eigval


class HybridDecodeSpec(DecodeSpec):
    """DecodeSpec of the hybrid block. blocks[i] holds parameter names
    by role: both kinds 'mixer_norm', 'mlp_norm', 'gate', 'up', 'down';
    full_attention 'qkv', 'q_norm', 'k_norm', 'proj'; linear_attention
    'qkv', 'conv', 'ba', 'a_log', 'dt_bias', 'out_gate', 'head_norm',
    'out'. Weights are (name, None) pairs as the named-fc helpers take
    them, norms and the per-head scalars plain names."""

    recurrent_kinds = ('linear_attention',)

    def __init__(self, vocab, dim, heads, ffn, max_len, kinds, key_dim,
                 value_dim, conv_kernel, eps, beta_scale, emb_w, blocks,
                 final_norm, head):
        DecodeSpec.__init__(
            self, vocab=vocab, dim=dim, heads=heads, layers=len(kinds),
            ffn=ffn, max_len=max_len, pos_len=0, emb_w=emb_w, pos_w=None,
            blocks=blocks, final_ln=(final_norm, None), head=head,
            kinds=kinds)
        for kind in kinds:
            if kind not in KINDS:
                raise ValueError('layer kind %r is not one of %s'
                                 % (kind, KINDS))
        self.key_dim, self.value_dim = key_dim, value_dim
        self.conv_kernel, self.eps = conv_kernel, eps
        self.beta_scale = beta_scale
        self.conv_dim = heads * (2 * key_dim + value_dim)

    @property
    def pool_heads(self):
        """Whole sublane tiles of heads a page: 30 heads would be laid
        out as 32 in HBM anyway, and a pool that says so keeps a page
        the plain [page_tokens * heads, dh] matrix the paged_attention
        kernel reads (no relayout of the pool before every call). The
        heads added hold zeros and their outputs are cut off."""
        return -(-self.heads // 8) * 8

    def state_names(self, layer=None):
        """(delta state, convolution rows) var names of the recurrent
        layers; shared by the paged pair."""
        if layer is not None:
            return ('gdn_state.layer%d.s' % layer,
                    'gdn_state.layer%d.conv' % layer)
        out = []
        for i in self.recurrent_layers:
            out.extend(self.state_names(i))
        return out

    def state_shapes(self, slots):
        return ((slots, self.heads, self.key_dim, self.value_dim),
                (slots, self.conv_kernel - 1, self.conv_dim))

    def param_names(self):
        names = [self.emb_w, self.final_ln[0], self.head[0]]
        for blk in self.blocks:
            for v in blk.values():
                names.append(v[0] if isinstance(v, tuple) else v)
        return names

    def build_paged_programs(self, slots, chunk, num_pages, page_tokens,
                             pages_per_slot):
        return build_paged_prefill_program(
            self, slots, chunk, num_pages, page_tokens, pages_per_slot) + \
            build_paged_decode_program(
                self, slots, num_pages, page_tokens, pages_per_slot)


def spec_from_config(cfg):
    """The spec of a model built here, with names of its own."""
    blocks = []
    for i, kind in enumerate(cfg.layer_types):
        def w(role, i=i):
            return ('layer%d.%s.w' % (i, role), None)
        blk = {'qkv': w('qkv')}
        if kind == 'linear_attention':
            blk.update(conv='layer%d.conv.w' % i, ba=w('ba'),
                       a_log='layer%d.a_log' % i,
                       dt_bias='layer%d.dt_bias' % i,
                       out_gate=w('out_gate'),
                       head_norm='layer%d.head_norm.w' % i, out=w('out'))
        else:
            blk.update(q_norm='layer%d.q_norm.w' % i,
                       k_norm='layer%d.k_norm.w' % i, proj=w('proj'))
        blk.update(mixer_norm='layer%d.mixer_norm.w' % i, gate=w('gate'),
                   up=w('up'), down=w('down'),
                   mlp_norm='layer%d.mlp_norm.w' % i)
        blocks.append(blk)
    return HybridDecodeSpec(
        vocab=cfg.vocab, dim=cfg.dim, heads=cfg.heads, ffn=cfg.ffn,
        max_len=cfg.max_len, kinds=cfg.layer_types, key_dim=cfg.key_dim,
        value_dim=cfg.value_dim, conv_kernel=cfg.conv_kernel, eps=cfg.eps,
        beta_scale=2.0 if cfg.neg_eigval else 1.0, emb_w='embed.w',
        blocks=blocks, final_norm='final_norm.w', head=('lm_head.w', None))


# -- the block ---------------------------------------------------------------

def _rms(x, spec, name, axis=2):
    return L.rms_norm(x, begin_norm_axis=axis, epsilon=spec.eps,
                      param_attr=_named_attr(name))


def _param(name, shape):
    from ..layer_helper import LayerHelper
    helper = LayerHelper('hybrid', param_attr=_named_attr(name))
    return helper.create_parameter(attr=helper.param_attr, shape=shape,
                                   dtype='float32')


def _linear_mixer(x, spec, blk, t, delta_type, state=None, at=None):
    """The linear-attention mixer around its two stateful ops. `state` is
    the layer's (delta state, convolution rows) pair, which both ops
    read and write in place, and `at` the inputs that say where and how
    (Slot/Len/Reset for a chunk, Live for a step); neither for the
    whole-sequence form."""
    h, dk, dv = spec.heads, spec.key_dim, spec.value_dim

    def stateful(var):
        if state is None:
            return {}, {}
        return dict(at, State=[var]), {'StateOut': [var]}

    qkv = _named_fc(x, spec.conv_dim, blk['qkv'])
    ba = _named_fc(x, 2 * h, blk['ba'])
    gate = _named_fc(x, h * dv, blk['out_gate'], act='swish')
    conv = _tmp_var()
    ins, outs = stateful(state and state[1])
    _block_op('short_conv',
              inputs=dict(ins, X=[qkv], W=[_param(
                  blk['conv'], [spec.conv_kernel, spec.conv_dim])]),
              outputs=dict(outs, Out=[conv]))
    o = _tmp_var()
    ins, outs = stateful(state and state[0])
    _block_op(delta_type,
              inputs=dict(ins, QKV=[conv], BA=[ba],
                          ALog=[_param(blk['a_log'], [h])],
                          DtBias=[_param(blk['dt_bias'], [h])]),
              outputs=dict(outs, Out=[o]),
              attrs={'heads': h, 'key_dim': dk, 'value_dim': dv,
                     'beta_scale': float(spec.beta_scale)})
    o = _rms(L.reshape(o, shape=[-1, t, h, dv]), spec, blk['head_norm'],
             axis=3)
    o = L.elementwise_mul(L.reshape(o, shape=[-1, t, h * dv]), gate)
    return _named_fc(o, spec.dim, blk['out'])


def _qk_norm(spec, blk):
    """The full-attention layer's norm of q and of k, each over all its
    values, for _qkv_parts."""
    return lambda part, which: _rms(part, spec, blk[which + '_norm'])


def _full_attention(x, spec, blk):
    """Whole-sequence causal attention (the source program's form)."""
    t = spec.max_len
    q4, k4, v4 = _qkv_parts(x, spec, blk, t, _qk_norm(spec, blk))
    q, k, v = (L.transpose(a, perm=[0, 2, 1, 3]) for a in (q4, k4, v4))
    scores = L.matmul(q, k, transpose_y=True, alpha=1.0 / np.sqrt(spec.dh))
    ctx = L.matmul(L.softmax(L.causal_mask_bias(scores)), v)
    ctx = L.reshape(L.transpose(ctx, perm=[0, 2, 1, 3]),
                    shape=[-1, t, spec.dim])
    return _named_fc(ctx, spec.dim, blk['proj'])


def _block(x, spec, i, mixer):
    blk = spec.blocks[i]
    x = L.elementwise_add(x, _rms(mixer(x, spec, blk), spec,
                                  blk['mixer_norm']))
    mlp = L.elementwise_mul(
        _named_fc(x, spec.ffn, blk['gate'], act='swish'),
        _named_fc(x, spec.ffn, blk['up']))
    mlp = _named_fc(mlp, spec.dim, blk['down'])
    return L.elementwise_add(x, _rms(mlp, spec, blk['mlp_norm']))


def _model(tokens, spec, mixers, last=None):
    """Embedding -> blocks -> final norm -> head. `mixers` maps a layer
    kind to its mixer; `last` gathers one row a sequence before the
    head (the prefill's logits)."""
    x = L.embedding(tokens, size=[spec.vocab, spec.dim],
                    param_attr=_named_attr(spec.emb_w))
    for i, kind in enumerate(spec.kinds):
        x = _block(x, spec, i,
                   lambda h, sp, blk, _i=i, _k=kind: mixers[_k](h, sp, blk,
                                                                _i))
    x = _rms(x, spec, spec.final_ln[0])
    if last is None:
        return _named_fc(x, spec.vocab, spec.head)
    gathered = _tmp_var()
    _block_op('gather_time', inputs={'X': [x], 'Index': [last]},
              outputs={'Out': [gathered]})
    return _named_fc(gathered, spec.vocab, spec.head, num_flatten_dims=1)


def language_model_logits(tokens, cfg):
    """tokens [B, T, 1] int64 (T = cfg.max_len) -> logits [B, T, vocab],
    every sequence from zero state."""
    spec = spec_from_config(cfg)
    return _model(tokens, spec, {
        'linear_attention': lambda x, sp, blk, i: _linear_mixer(
            x, sp, blk, sp.max_len, 'gated_delta_chunk'),
        'full_attention': lambda x, sp, blk, i: _full_attention(x, sp, blk)})


# -- the paged pair ------------------------------------------------------------

def _create_state_vars(spec, slots):
    """{layer: (delta state, convolution rows)} of the recurrent layers:
    persistable, donated and updated in place like the page pools, and
    never checkpointed."""
    from ..framework import default_main_program
    block = default_main_program().global_block()
    return {i: tuple(
        block.create_var(name=n, shape=shape, dtype='float32',
                         persistable=True, stop_gradient=True,
                         is_cache=True)
        for n, shape in zip(spec.state_names(i), spec.state_shapes(slots)))
        for i in spec.recurrent_layers}


def _data(name, shape, dtype='int32'):
    return L.data(name, shape, append_batch_size=False, dtype=dtype)


def build_paged_prefill_program(spec, slots, chunk, num_pages, page_tokens,
                                pages_per_slot):
    """One prefill chunk of one stream: models/transformer.py's paged
    prefill feeds, and two more for the recurrent layers:
    prefill_state_slot [1] (the slot whose state the chunk starts from
    and leaves behind) and prefill_state_reset [1] (1 on a stream's
    first chunk: start from zero state, whatever the slot held). Rows
    from prefill_len on leave no trace in either kind of state.
    Returns (program, feed_names, fetch_vars[logits, ids])."""
    from ..framework import Program, program_guard
    prog, startup = Program(), Program()
    prog._is_test = True
    with program_guard(prog, startup):
        tokens = _data('prefill_tokens', [1, chunk, 1], 'int64')
        positions = _data('prefill_positions', [chunk])
        length = _data('prefill_len', [1])
        last = _data('prefill_last', [1])
        table = _data('prefill_page_table', [1, pages_per_slot])
        cow_src = _data('prefill_cow_src', [1])
        cow_dst = _data('prefill_cow_dst', [1])
        slot = _data('prefill_state_slot', [1])
        reset = _data('prefill_state_reset', [1])
        pools = _create_pool_vars(spec, num_pages, page_tokens)
        states = _create_state_vars(spec, slots)

        def linear(x, sp, blk, i):
            return _linear_mixer(
                x, sp, blk, chunk, 'gated_delta_chunk', states[i],
                {'Slot': [slot], 'Len': [length], 'Reset': [reset]})

        logits = _model(tokens, spec, {
            'linear_attention': linear,
            'full_attention': lambda x, sp, blk, i: _paged_prefill_attention(
                x, sp, blk, pools[i], table, positions, length, cow_src,
                cow_dst, chunk, _qk_norm(sp, blk))}, last=last)
        ids = L.argmax(logits, axis=-1)
    return prog, ['prefill_tokens', 'prefill_positions', 'prefill_len',
                  'prefill_last', 'prefill_page_table', 'prefill_cow_src',
                  'prefill_cow_dst', 'prefill_state_slot',
                  'prefill_state_reset'], [logits, ids]


def build_paged_decode_program(spec, slots, num_pages, page_tokens,
                               pages_per_slot):
    """One token a lane over the whole slot pool:
    models/transformer.py's paged decode feeds (the carried token's
    pair among them; no copy-on-write pair: the program copies no
    page), and decode_state_live
    [slots] (1 for the lanes that take part: the others' recurrent state
    stays as it was, as their K/V writes land on the null page).
    Returns (program, feed_names, fetch_vars[logits, ids])."""
    from ..framework import Program, program_guard
    prog, startup = Program(), Program()
    prog._is_test = True
    with program_guard(prog, startup):
        tokens = _paged_decode_tokens(slots)
        step_idx = _data('decode_step_idx', [slots])
        table = _data('decode_page_table', [slots, pages_per_slot])
        live = _data('decode_state_live', [slots])
        pools = _create_pool_vars(spec, num_pages, page_tokens)
        states = _create_state_vars(spec, slots)

        def linear(x, sp, blk, i):
            return _linear_mixer(x, sp, blk, 1, 'gated_delta_step',
                                 states[i], {'Live': [live]})

        logits3 = _model(tokens, spec, {
            'linear_attention': linear,
            'full_attention': lambda x, sp, blk, i: _paged_decode_attention(
                x, sp, blk, pools[i], table, step_idx, _qk_norm(sp, blk))})
        logits = L.reshape(logits3, shape=[-1, spec.vocab])
        ids = L.argmax(logits, axis=-1)
    return prog, PAGED_DECODE_FEEDS + ['decode_state_live'], [logits, ids]
