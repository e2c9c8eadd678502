"""Hybrid language model whose recurrent layers are gated SHORT
CONVOLUTIONS (the LFM2 block with routed experts, `model_type:
lfm2_moe`, HF `Lfm2Moe`): most layers mix tokens through a depthwise
causal convolution of a few taps between two elementwise gates, a few
through attention with fewer K/V heads than query heads on narrow heads
(64), q and k normed a head at a time before a rotation over the whole
head; the first layers' feed-forward is a dense SwiGLU, every later
one's a layer of sigmoid-routed SwiGLU experts with a selection bias;
the head is the embedding.

Beside models/sdar_moe.py and models/granite_h.py, whose shapes it
follows, on models/transformer.py's named-fc helpers, page pools and
paged attention. Layer i, x the residual stream, no bias anywhere:

    h = x + Op_i(RMSNorm(x));  y = h + FF_i(RMSNorm(h))

`conv` (K taps, width D):
    [B | C | z] = u W_in          three parts of width D
    v = B * z;  c_t = sum_j w_j v_{t - (K-1) + j}
                                  depthwise, causal, NO activation (op
                                  short_conv, activation 'none')
    out = (C * c) W_out
`full_attention`: q, k, v = u W_qkv with `heads` query heads on
    `kv_heads` K/V heads of `head_dim`; q, k RMS-normed a head at a time
    (one gain [head_dim] each), then RoPE over the whole head (split
    halves, op rotary_yarn at factor 1); causal softmax(q k^T /
    sqrt(head_dim)) v; W_o.
FF, i < dense_layers: W2 (silu(W1 u) * W3 u) of width `ffn`.
FF, later: s = sigmoid(u W_r) over all E experts; the top_k largest of
    s + b (b chooses only); w = routed_scale * s_chosen / sum(s_chosen);
    op moe_experts gives sum_e w_e W2_e (silu(W1_e u) * W3_e u) over
    all E experts: every one is held here. No shared expert.
logits = RMSNorm(x) E^T.

What a stream leaves behind in a `conv` layer is the last K-1 rows of
v: 2 x 2048 values at the published sizes, less than a K/V page. So
this family keeps no per-slot state at all: the rows lie BY THE PAGE, in
one more pool a conv layer, [pages, K-1, D], indexed by the same page
table as the K/V pools, whose entry for a page holds the rows at that
page's fill point (op short_conv's paged forms;
DecodeSpec.page_state_layers). A page that is full, or registered as a
tail, is frozen, so the prefix cache hands out ANY resident page
boundary with its state, copy-on-write forks the rows with the page,
and save_stream / restore_stream carry them: `state_names` is empty,
`snapshot_rows` stays 0 and no snapshot or adopt program exists for
this family (serving/paged.py).

The K/V pools hold two heads of 64 side by side in one lane row
(`head_pack` 2: [pages, page_tokens, kv_heads / 2, 128]); the decode
step's op paged_attention reads them through the kernel of
pallas/paged_attention.py as pairs (paged_attention_d64).

Three programs from the one block walk (_model): language_model_logits
and, through Lfm2DecodeSpec.paged_logits, the paged serving pair. Each
program of the pair returns what its expert layers counted as a third
fetch.
"""
from __future__ import annotations

from .. import layers as L
from . import describe_served_model
from .hybrid import HybridDecodeSpec, _param, _rms
from .sdar_moe import _head_norm
from .smallthinker import _rotary
from .transformer import (DecodeSpec, _block_op, _expert_io, _logits_head,
                          _named_attr, _named_fc, _page_state_io,
                          _paged_attention, _qkv_parts, _tmp_var)

KINDS = ('conv', 'full_attention')
LANES = 128     # a lane row of the pool


class Lfm2Config(object):
    def __init__(self, vocab=512, dim=64, heads=4, kv_heads=2, head_dim=16,
                 layer_types=KINDS, max_len=64, conv_kernel=3, ffn=128,
                 dense_layers=1, experts=8, top_k=2, expert_ffn=48,
                 routed_scale=1.0,
                 rope_theta=1e6, eps=1e-5):
        self.vocab, self.dim, self.max_len = vocab, dim, max_len
        self.heads, self.kv_heads, self.head_dim = heads, kv_heads, head_dim
        self.layer_types = tuple(layer_types)
        self.conv_kernel, self.ffn = int(conv_kernel), ffn
        self.dense_layers = int(dense_layers)
        self.experts = experts
        self.top_k, self.expert_ffn = top_k, expert_ffn
        self.routed_scale = float(routed_scale)
        self.rope_theta, self.eps = float(rope_theta), eps


Config = Lfm2Config


class Lfm2DecodeSpec(DecodeSpec):
    """DecodeSpec of the block. blocks[i] holds parameter names by role:
    'norm', 'ffn_norm'; conv 'in', 'conv', 'out'; full_attention 'qkv',
    'q_norm', 'k_norm', 'proj'; a dense layer 'up' (W1 | W3 side by
    side), 'down'; an expert layer 'router', 'bias', 'w1', 'w3', 'w2'.
    Weights of the named-fc helpers are (name, None) pairs, everything
    else plain names. The head is the embedding."""

    page_state_kinds = ('conv',)

    def __init__(self, cfg, emb_w, blocks, final_norm):
        kinds = tuple(cfg.layer_types)
        for kind in kinds:
            if kind not in KINDS:
                raise ValueError('layer kind %r is not one of %s'
                                 % (kind, KINDS))
        if cfg.conv_kernel < 2:
            raise ValueError('a short convolution of %d taps keeps no rows'
                             % cfg.conv_kernel)
        DecodeSpec.__init__(
            self, vocab=cfg.vocab, dim=cfg.dim, heads=cfg.heads,
            layers=len(kinds), ffn=cfg.ffn, max_len=cfg.max_len, pos_len=0,
            emb_w=emb_w, pos_w=None, blocks=blocks,
            final_ln=(final_norm, None), head=(emb_w, None), kinds=kinds,
            kv_heads=cfg.kv_heads, head_dim=cfg.head_dim)
        self.cfg, self.eps = cfg, cfg.eps
        self.expert_layers = list(range(min(cfg.dense_layers, len(kinds)),
                                        len(kinds)))

    @property
    def head_pack(self):
        """Heads narrower than a lane row share one, where they fill it
        in whole numbers: two heads of 64 (the published size)."""
        pack = LANES // self.dh if self.dh < LANES \
            and LANES % self.dh == 0 else 1
        return pack if self.kv_heads % pack == 0 else 1

    def page_state_shape(self, num_pages):
        """A conv layer's pool: the K-1 rows of v at each page's fill
        point."""
        return (num_pages, self.cfg.conv_kernel - 1, self.dim)

    def param_names(self):
        # the tied head is the embedding: named once
        return HybridDecodeSpec.param_names(self)[1:]

    def paged_logits(self, tokens, at):
        return _model(tokens, self, at)


_ROLES = {'conv': (('in', True), ('conv', False), ('out', True)),
          'full_attention': (('qkv', True), ('q_norm', False),
                             ('k_norm', False), ('proj', True))}
_DENSE_ROLES = (('up', True), ('down', True))
_EXPERT_ROLES = (('router', False), ('bias', False), ('w1', False),
                 ('w3', False), ('w2', False))


def spec_from_config(cfg):
    """The spec of a model built here, with names of its own."""
    blocks = []
    for i, kind in enumerate(cfg.layer_types):
        blk = {'norm': 'layer%d.norm.w' % i,
               'ffn_norm': 'layer%d.ffn_norm.w' % i}
        ff = _DENSE_ROLES if i < cfg.dense_layers else _EXPERT_ROLES
        for role, fc in _ROLES[kind] + ff:
            name = 'layer%d.%s.w' % (i, role)
            blk[role] = (name, None) if fc else name
        blocks.append(blk)
    return Lfm2DecodeSpec(cfg, emb_w='embed.w', blocks=blocks,
                          final_norm='final_norm.w')


# -- the block ---------------------------------------------------------------

def _conv_mixer(x, spec, blk, i, at=None):
    """The gated short convolution around its one stateful op, which
    reads and writes layer i's rows by the page where `at` says (a
    chunk's table, or every live lane's); the whole sequence from zeros
    without one."""
    d = spec.dim
    bcz = _named_fc(x, 3 * d, blk['in'])
    b, c, z = (L.slice(bcz, axes=[2], starts=[k * d], ends=[(k + 1) * d])
               for k in range(3))
    conv = _tmp_var()
    ins, outs, attrs = _page_state_io(at, i)
    _block_op('short_conv',
              inputs=dict(ins, X=[L.elementwise_mul(b, z)],
                          W=[_param(blk['conv'], [spec.cfg.conv_kernel, d])]),
              outputs=dict(outs, Out=[conv]),
              attrs=dict(attrs, activation='none'))
    return _named_fc(L.elementwise_mul(c, conv), d, blk['out'])


def _attention(x, spec, blk, i, at=None):
    """Layer i's attention over its pages, or over the whole sequence
    (the source program's form): the query heads of one K/V head are
    rows of one product."""
    rotary, norm = _rotary(spec, at), _head_norm(spec, blk)
    if at is not None:
        return _paged_attention(x, spec, blk, i, at, rotary=rotary,
                                head_norm=norm)
    t, h, kvh, dh = spec.max_len, spec.heads, spec.kv_heads, spec.dh
    rep = h // kvh
    q4, k4, v4 = _qkv_parts(x, spec, blk, t, rotary=rotary, head_norm=norm)
    q, k, v = (L.transpose(a, perm=[0, 2, 1, 3]) for a in (q4, k4, v4))
    q = L.reshape(q, shape=[-1, kvh, rep * t, dh])
    scores = L.matmul(q, k, transpose_y=True, alpha=spec.sm_scale)
    scores = L.reshape(scores, shape=[-1, h, t, t])
    probs = L.softmax(L.causal_mask_bias(scores))
    ctx = L.matmul(L.reshape(probs, shape=[-1, kvh, rep * t, t]), v)
    ctx = L.transpose(L.reshape(ctx, shape=[-1, h, t, dh]),
                      perm=[0, 2, 1, 3])
    return _named_fc(L.reshape(ctx, shape=[-1, t, h * dh]), spec.dim,
                     blk['proj'])


def _dense(u, spec, blk):
    """A leading layer's feed-forward: W2 (silu(a) * b), [a | b] = u
    [W1 | W3]."""
    f = spec.ffn
    ab = _named_fc(u, 2 * f, blk['up'])
    hid = L.elementwise_mul(
        L.swish(L.slice(ab, axes=[2], starts=[0], ends=[f])),
        L.slice(ab, axes=[2], starts=[f], ends=[2 * f]))
    return _named_fc(hid, spec.dim, blk['down'])


def _experts(u, spec, blk, at=None):
    """The expert layer: op moe_experts scores and works on `u` (gate
    sigmoid with the selection bias); it passes over the dead rows and
    counts the others where `at` says which those are."""
    c = spec.cfg
    ins, outs = _expert_io(at)
    held = [c.experts, spec.dim, c.expert_ffn]
    routed = _tmp_var()
    _block_op('moe_experts',
              inputs=dict(
                  ins, X=[u], Lat=[u],
                  RouterW=[_param(blk['router'], [spec.dim, c.experts])],
                  Bias=[_param(blk['bias'], [c.experts])],
                  W1=[_param(blk['w1'], held)], W3=[_param(blk['w3'], held)],
                  W2=[_param(blk['w2'], [held[0], held[2], held[1]])]),
              outputs=dict(outs, Out=[routed]),
              attrs={'top_k': c.top_k, 'scale': c.routed_scale,
                     'gate': 'sigmoid', 'act': 'silu',
                     'expert_offset': 0})
    return routed


_MIXERS = {'conv': _conv_mixer, 'full_attention': _attention}


def _model(tokens, spec, at=None):
    """Embedding -> layers of two sublayers -> final norm -> the
    embedding again as the head: the whole sequence from zero rows, or
    one paged program's rows (`at`: PagedStep)."""
    x = L.embedding(tokens, size=[spec.vocab, spec.dim],
                    param_attr=_named_attr(spec.emb_w))
    for i, kind in enumerate(spec.kinds):
        blk = spec.blocks[i]
        x = L.elementwise_add(
            x, _MIXERS[kind](_rms(x, spec, blk['norm']), spec, blk, i, at))
        u = _rms(x, spec, blk['ffn_norm'])
        x = L.elementwise_add(
            x, _experts(u, spec, blk, at) if i in spec.expert_layers
            else _dense(u, spec, blk))
    return _logits_head(
        _rms(x, spec, spec.final_ln[0]), spec, at,
        lambda h, _: L.matmul(h, _param(spec.emb_w, [spec.vocab, spec.dim]),
                              transpose_y=True))


def language_model_logits(tokens, cfg):
    """tokens [B, T, 1] int64 (T = cfg.max_len) -> logits [B, T, vocab],
    every sequence from zero rows."""
    describe_served_model(tokens.block.program, 'lfm2', cfg)
    return _model(tokens, spec_from_config(cfg))
