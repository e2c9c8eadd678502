"""Language model of attention layers of two kinds with routed experts in
every layer (the SmallThinker block, `model_type: smallthinker`,
arXiv:2507.20984): sliding-window layers that take rotary positions
among global layers that take no positional term at all, fewer K/V
heads than query heads, ReGLU experts chosen by a router that reads the
ATTENTION's input, not the expert sublayer's.

Beside models/granite_h.py, whose shape it follows, and on
models/transformer.py's named-fc helpers, page pools and paged
attention. For layer l, x the residual stream, no bias anywhere:

    u  = RMSNorm_in(x)
    r  = W_r u                      router logits [E], float32 at
                                    "highest"; read HERE
    q, k, v = W_q u, W_k u, W_v u   `heads` query heads on `kv_heads`
                                    K/V heads of `head_dim`
    if rope_layout[l]:  q, k = RoPE(q), RoPE(k)
                                    theta `rope_theta`, the whole head,
                                    split halves (op rotary_yarn at
                                    factor 1)
    a  = softmax(q k^T / sqrt(head_dim) + mask_l) v
                                    query head h reads K/V head
                                    h // (heads / kv_heads); mask_l
                                    causal and, if
                                    sliding_window_layout[l], key j
                                    visible to query i iff
                                    i - window < j <= i
    x  = x + W_o a
    h  = RMSNorm_post(x)
    S  = the top_k largest of r;  g = softmax(r[S])
    x  = x + sum_{e in S} g_e W2_e (relu(W1_e h) * W3_e h)
                                    no shared expert
    logits = W_head RMSNorm_f(x)    untied

The expert op (moe_experts, gate 'softmax', act 'relu') scores X = u and
works on Lat = h: a router ahead of its mixer needs no op of its own.

Three programs from the one block walk (_model), as in
models/granite_h.py: language_model_logits and, through
SmallThinkerDecodeSpec.paged_logits, the paged serving pair. A layer's
kind says how it keeps a stream: 'full_attention' K/V pages for every
token, 'sliding_attention' K/V pages of which a row reads the last
`window` tokens, in a pool of their own behind a table that gives up
what lies behind the window (models/transformer.DecodeSpec,
serving/paging.py). A pool holds keys as the attention reads them:
rotated, where the layer rotates. Each program of the pair returns what
its expert sublayers counted as a third fetch.
"""
from __future__ import annotations

from .. import layers as L
from . import describe_served_model
from .hybrid import HybridDecodeSpec, _param, _rms
from .transformer import (DecodeSpec, _block_op, _expert_io, _logits_head,
                          _named_attr, _named_fc, _paged_attention,
                          _qkv_parts, _tmp_var)

KINDS = ('full_attention', 'sliding_attention')


class SmallThinkerConfig(object):
    def __init__(self, vocab=512, dim=64, heads=4, kv_heads=2, head_dim=16,
                 layers=4, sliding_window_layout=(0, 1, 1, 1),
                 rope_layout=(0, 1, 1, 1), window=8, rope_theta=1.5e6,
                 max_len=64, experts=16, experts_held=None, expert_offset=0,
                 top_k=4, expert_ffn=48, eps=1e-6):
        self.vocab, self.dim, self.max_len = vocab, dim, max_len
        self.heads, self.kv_heads, self.head_dim = heads, kv_heads, head_dim
        self.layers = int(layers)
        # the published layouts may be longer than the layers run: the
        # first `layers` entries count
        self.sliding_window_layout = tuple(
            int(v) for v in sliding_window_layout)[:self.layers]
        self.rope_layout = tuple(int(v) for v in rope_layout)[:self.layers]
        self.window, self.rope_theta = int(window), float(rope_theta)
        self.experts = experts
        self.experts_held = experts if experts_held is None else experts_held
        self.expert_offset = expert_offset
        self.top_k, self.expert_ffn, self.eps = top_k, expert_ffn, eps


Config = SmallThinkerConfig


class SmallThinkerDecodeSpec(DecodeSpec):
    """DecodeSpec of the block. blocks[i] holds parameter names by role:
    'norm', 'qkv', 'proj', 'ffn_norm', 'router', 'w1', 'w3', 'w2'.
    Weights of the named-fc helpers are (name, None) pairs, everything
    else plain names."""

    def __init__(self, cfg, emb_w, blocks, final_norm, head):
        for what in ('sliding_window_layout', 'rope_layout'):
            if len(getattr(cfg, what)) != cfg.layers:
                raise ValueError('%s has %d entries for %d layers' % (
                    what, len(getattr(cfg, what)), cfg.layers))
        kinds = tuple(KINDS[bool(s)] for s in cfg.sliding_window_layout)
        self.window = cfg.window
        DecodeSpec.__init__(
            self, vocab=cfg.vocab, dim=cfg.dim, heads=cfg.heads,
            layers=cfg.layers, ffn=cfg.expert_ffn, max_len=cfg.max_len,
            pos_len=0, emb_w=emb_w, pos_w=None, blocks=blocks,
            final_ln=(final_norm, None), head=head, kinds=kinds,
            kv_heads=cfg.kv_heads, head_dim=cfg.head_dim)
        if not 0 <= cfg.expert_offset <= cfg.experts - cfg.experts_held:
            raise ValueError('experts %d..%d are not among %d' % (
                cfg.expert_offset, cfg.expert_offset + cfg.experts_held,
                cfg.experts))
        self.cfg, self.eps = cfg, cfg.eps
        self.expert_layers = list(range(cfg.layers))

    param_names = HybridDecodeSpec.param_names

    def paged_logits(self, tokens, at):
        return _model(tokens, self, at)


_ROLES = (('norm', False), ('qkv', True), ('proj', True), ('ffn_norm', False),
          ('router', False), ('w1', False), ('w3', False), ('w2', False))


def spec_from_config(cfg):
    """The spec of a model built here, with names of its own."""
    blocks = []
    for i in range(cfg.layers):
        blk = {}
        for role, fc in _ROLES:
            name = 'layer%d.%s.w' % (i, role)
            blk[role] = (name, None) if fc else name
        blocks.append(blk)
    return SmallThinkerDecodeSpec(cfg, emb_w='embed.w', blocks=blocks,
                                  final_norm='final_norm.w',
                                  head=('lm_head.w', None))


# -- the block ---------------------------------------------------------------

def _rotary(spec, at):
    """The rotation a rotary layer gives q and k [-1, t, heads, dh] (op
    rotary_yarn at factor 1: plain RoPE over the whole head, split
    halves), by the rows' absolute positions: a whole sequence's from
    its start, a chunk's rows', a step's lanes'."""
    c = spec.cfg

    def rotated(x):
        out = _tmp_var()
        ins, per = {'X': [x]}, 'row'
        if at is not None:
            ins['Positions'] = [at.positions]
            per = 'lane' if at.decode else 'row'
        _block_op('rotary_yarn', inputs=ins, outputs={'Out': [out]},
                  attrs={'dim': spec.dh, 'base': c.rope_theta,
                         'factor': 1.0, 'per': per, 'start': 0})
        return out
    return rotated


def _attention(x, spec, blk, i, at=None):
    """Layer i's attention over its pages (through the table of its
    kind), or over the whole sequence with a dense band mask (the source
    program's form)."""
    rotary = _rotary(spec, at) if spec.cfg.rope_layout[i] else None
    if at is not None:
        return _paged_attention(x, spec, blk, i, at, rotary=rotary)
    t, h, kvh, dh = spec.max_len, spec.heads, spec.kv_heads, spec.dh
    rep = h // kvh
    q4, k4, v4 = _qkv_parts(x, spec, blk, t, rotary=rotary)
    q, k, v = (L.transpose(a, perm=[0, 2, 1, 3]) for a in (q4, k4, v4))
    q = L.reshape(q, shape=[-1, kvh, rep * t, dh])
    scores = L.matmul(q, k, transpose_y=True, alpha=spec.sm_scale)
    scores = L.reshape(scores, shape=[-1, h, t, t])
    masked = _tmp_var()
    _block_op('paged_prefill_mask',
              inputs={'X': [scores]}, outputs={'Out': [masked]},
              attrs={'window': spec.window if i in spec.window_layers else 0})
    probs = L.softmax(masked)
    ctx = L.matmul(L.reshape(probs, shape=[-1, kvh, rep * t, t]), v)
    ctx = L.transpose(L.reshape(ctx, shape=[-1, h, t, dh]),
                      perm=[0, 2, 1, 3])
    return _named_fc(L.reshape(ctx, shape=[-1, t, h * dh]), spec.dim,
                     blk['proj'])


def _experts(u, h, spec, blk, at=None):
    """The expert sublayer: op moe_experts scores `u` (this layer's
    attention input) and works on `h` (the normed stream behind the
    attention); it passes over the dead rows and counts the others
    where `at` says which those are."""
    c = spec.cfg
    ins, outs = _expert_io(at)
    held = [c.experts_held, spec.dim, c.expert_ffn]
    routed = _tmp_var()
    _block_op('moe_experts',
              inputs=dict(
                  ins, X=[u], Lat=[h],
                  RouterW=[_param(blk['router'], [spec.dim, c.experts])],
                  W1=[_param(blk['w1'], held)], W3=[_param(blk['w3'], held)],
                  W2=[_param(blk['w2'], [held[0], held[2], held[1]])]),
              outputs=dict(outs, Out=[routed]),
              attrs={'top_k': c.top_k, 'scale': 1.0, 'gate': 'softmax',
                     'act': 'relu', 'expert_offset': c.expert_offset})
    return routed


def _model(tokens, spec, at=None):
    """Embedding -> layers of two sublayers -> final norm -> head: the
    whole sequence, or one paged program's rows (`at`: PagedStep)."""
    x = L.embedding(tokens, size=[spec.vocab, spec.dim],
                    param_attr=_named_attr(spec.emb_w))
    for i, blk in enumerate(spec.blocks):
        u = _rms(x, spec, blk['norm'])
        x = L.elementwise_add(x, _attention(u, spec, blk, i, at))
        x = L.elementwise_add(x, _experts(
            u, _rms(x, spec, blk['ffn_norm']), spec, blk, at))
    return _logits_head(_rms(x, spec, spec.final_ln[0]), spec, at)


def language_model_logits(tokens, cfg):
    """tokens [B, T, 1] int64 (T = cfg.max_len) -> logits [B, T, vocab]."""
    describe_served_model(tokens.block.program, 'smallthinker', cfg)
    return _model(tokens, spec_from_config(cfg))
