"""Hybrid language model: gated-delta-rule layers between full-attention
layers (the Olmo-Hybrid block; Gated DeltaNet, arXiv:2412.06464). The
rule here has ONE decay a head (ops gated_delta_*) and the block is
post-norm with a dense MLP; the pre-norm block whose rule decays a key
channel at a time (ops kda_*), with gated attention and an expert
sublayer in every layer, is models/solar_open2.py, which takes this
file's `_param` and `_rms`.

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

Three programs come from the one block walk (_model), which asks the
value it is handed where K/V and state come from:

  language_model_logits   the whole-sequence program that
                          save_inference_model writes, with the
                          description the DecodeTranspiler reads the
                          model from;
  HybridDecodeSpec.paged_logits
                          the walk of the paged serving pair
                          (models/transformer.build_paged_prefill_program
                          and build_paged_decode_program). K/V pools
                          exist for the full-attention layers only; each
                          linear-attention layer keeps, per slot, its
                          delta state [slots, H, dk, dv] and the
                          convolution's last K-1 input rows
                          [slots, K-1, C] as scope variables that both
                          programs update in place, like the pools.
"""
from __future__ import annotations

import numpy as np

from .. import layers as L
from . import describe_served_model
from .transformer import (DecodeSpec, _block_op, _logits_head, _named_attr,
                          _named_fc, _paged_attention, _qkv_parts, _state_io,
                          _tmp_var)

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


Config = HybridConfig


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

    def paged_logits(self, tokens, at):
        return _model(tokens, self, at)


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


def _linear_mixer(x, spec, blk, i, at=None):
    """The linear-attention mixer around its two stateful ops, which
    read and write layer i's (delta state, convolution rows) in place
    where `at` says (a chunk's slot, or the live lanes of a step); the
    whole sequence from zero state without one."""
    h, dk, dv = spec.heads, spec.key_dim, spec.value_dim
    t = at.rows if at else spec.max_len
    qkv = _named_fc(x, spec.conv_dim, blk['qkv'])
    ba = _named_fc(x, 2 * h, blk['ba'])
    gate = _named_fc(x, h * dv, blk['out_gate'], act='swish')
    conv = _tmp_var()
    ins, outs = _state_io(at, i, 1)
    _block_op('short_conv',
              inputs=dict(ins, X=[qkv], W=[_param(
                  blk['conv'], [spec.conv_kernel, spec.conv_dim])]),
              outputs=dict(outs, Out=[conv]))
    o = _tmp_var()
    ins, outs = _state_io(at, i, 0)
    step = at is not None and at.decode
    _block_op('gated_delta_step' if step else 'gated_delta_chunk',
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


def _attention(x, spec, blk, i, at=None):
    """Causal attention with q and k normed: over layer i's pages, or
    over the whole sequence (the source program's form)."""
    if at is not None:
        return _paged_attention(x, spec, blk, i, at, _qk_norm(spec, blk))
    t = spec.max_len
    q4, k4, v4 = _qkv_parts(x, spec, blk, t, _qk_norm(spec, blk))
    q, k, v = (L.transpose(a, perm=[0, 2, 1, 3]) for a in (q4, k4, v4))
    scores = L.matmul(q, k, transpose_y=True, alpha=1.0 / np.sqrt(spec.dh))
    ctx = L.matmul(L.softmax(L.causal_mask_bias(scores)), v)
    ctx = L.reshape(L.transpose(ctx, perm=[0, 2, 1, 3]),
                    shape=[-1, t, spec.dim])
    return _named_fc(ctx, spec.dim, blk['proj'])


_MIXERS = {'linear_attention': _linear_mixer, 'full_attention': _attention}


def _block(x, spec, i, at):
    blk = spec.blocks[i]
    mixed = _MIXERS[spec.kinds[i]](x, spec, blk, i, at)
    x = L.elementwise_add(x, _rms(mixed, spec, blk['mixer_norm']))
    mlp = L.elementwise_mul(
        _named_fc(x, spec.ffn, blk['gate'], act='swish'),
        _named_fc(x, spec.ffn, blk['up']))
    mlp = _named_fc(mlp, spec.dim, blk['down'])
    return L.elementwise_add(x, _rms(mlp, spec, blk['mlp_norm']))


def _model(tokens, spec, at=None):
    """Embedding -> blocks -> final norm -> head: the whole sequence
    from zero state, or one paged program's rows (`at`: PagedStep)."""
    x = L.embedding(tokens, size=[spec.vocab, spec.dim],
                    param_attr=_named_attr(spec.emb_w))
    for i in range(spec.layers):
        x = _block(x, spec, i, at)
    return _logits_head(_rms(x, spec, spec.final_ln[0]), spec, at)


def language_model_logits(tokens, cfg):
    """tokens [B, T, 1] int64 (T = cfg.max_len) -> logits [B, T, vocab],
    every sequence from zero state."""
    describe_served_model(tokens.block.program, 'hybrid', cfg)
    return _model(tokens, spec_from_config(cfg))
