"""Hybrid language model of the Solar-Open2 block (`model_type:
solar_open2`): three linear-attention layers by the delta rule with a
decay a KEY CHANNEL (Kimi Delta Attention, arXiv:2510.26692) to one
softmax-attention layer without a positional term and with an output
gate (arXiv:2505.06708), the period starting on the attention layer,
and in EVERY layer a sigmoid-routed expert sublayer beside one shared
expert. Pre-norm (the Glm4Moe block's wiring), no bias anywhere:

    h = x + Mixer_i(RMSNorm(x));  y = h + Experts_i(u) + Shared_i(u),
                                                      u = RMSNorm(h)

A file of its own beside models/hybrid.py, whose linear layers differ
in kind and not in numbers (ONE decay a head through ops
gated_delta_*, post-norm, a dense MLP): a block file states a wiring,
and this one shares with the others what is the same in them: the
named-fc helpers, the paged attention of models/transformer.py, the
whole-sequence grouped attention of models/nemotron_h.py, the gated
expert sublayer of models/axk1.py (one group: no group limit).

`kda` mixer (H heads, key size dk, value size dv): q~, k~, v~ = u W_qkv
side by side; a causal depthwise convolution of K taps and silu on
every channel (op short_conv); log-decays g = -exp(A_log[h]) *
softplus(W_f2 (W_f1 u) + dt_bias), one a key channel, and beta =
beta_scale * sigmoid(u W_b), one a head, inside ops kda_chunk /
kda_step (ops/delta_rule_ops.py), which normalise q and k; then
W_o [RMSNorm_head(o) * sigmoid(W_g2 (W_g1 u))].
`full_attention` mixer: `heads` query heads on `kv_heads` K/V heads of
`head_dim`, causal softmax(q k^T / sqrt(head_dim)) v, no positional
term, no q/k norm; W_o [attn * sigmoid(u W_gate)].
Experts: s = sigmoid(u W_r) (float32 at "highest"); the top_k largest
of s + b; w = routed_scale * s_chosen / sum(s_chosen); op moe_experts
gives the part of the sum that the experts HELD here add
(`experts_held` of E from `expert_offset`), W2 (silu(W1 u) * W3 u);
the shared expert the same as plain matmuls.

Three programs from the one block walk (_model): language_model_logits
and, through SolarOpen2DecodeSpec.paged_logits, the paged serving pair.
K/V pools for the attention layers only; every kda layer keeps, per
slot, its delta state [slots, H, dk, dv] and the convolution's last
K-1 input rows as scope variables that both programs update in place
and the snapshot rows copy (serving/paged.py). Each program of the
pair returns what its expert sublayers counted as a third fetch.
"""
from __future__ import annotations

from .. import layers as L
from . import describe_served_model
from .axk1 import _experts_ffn
from .hybrid import HybridDecodeSpec, _param, _rms
from .nemotron_h import _attention as _grouped_attention
from .transformer import (DecodeSpec, _block_op, _logits_head, _named_attr,
                          _named_fc, _state_io, _tmp_var)

KINDS = ('full_attention', 'kda', 'kda', 'kda')


class SolarOpen2Config(object):
    # the gate of models/axk1._experts_ffn with one group: every row
    # chooses among all experts (no field: nothing a saved model states)
    n_group = topk_group = 1

    def __init__(self, vocab=512, dim=64, heads=4, kv_heads=2, head_dim=16,
                 layer_types=KINDS, max_len=64, kda_heads=2, key_dim=16,
                 value_dim=16, gate_rank=16, conv_kernel=4,
                 neg_eigval=True, experts=16,
                 experts_held=None, expert_offset=0, top_k=4,
                 routed_scale=1.0, expert_ffn=48, shared_ffn=48, eps=1e-5):
        self.vocab, self.dim, self.max_len = vocab, dim, max_len
        self.heads, self.kv_heads, self.head_dim = heads, kv_heads, head_dim
        self.layer_types = tuple(layer_types)
        self.kda_heads, self.key_dim = kda_heads, key_dim
        self.value_dim, self.gate_rank = value_dim, gate_rank
        self.conv_kernel, self.neg_eigval = conv_kernel, neg_eigval
        self.experts = experts
        self.experts_held = experts if experts_held is None else experts_held
        self.expert_offset = expert_offset
        self.top_k, self.routed_scale = top_k, routed_scale
        self.expert_ffn, self.shared_ffn, self.eps = expert_ffn, shared_ffn, eps


Config = SolarOpen2Config


class SolarOpen2DecodeSpec(DecodeSpec):
    """DecodeSpec of the block. blocks[i] holds parameter names by role:
    every layer 'norm', 'qkv', 'proj' and the expert sublayer's
    'ffn_norm', 'router', 'bias', 'w1', 'w3', 'w2', 'shared_gate_up',
    'shared_down'; kda 'conv', 'f_down', 'f_up', 'b', 'a_log',
    'dt_bias', 'g_down', 'g_up', 'head_norm'; full_attention 'gate'.
    Weights of the named-fc helpers are (name, None) pairs, everything
    else plain names."""

    recurrent_kinds = ('kda',)
    state_family = 'kda'

    def __init__(self, cfg, emb_w, blocks, final_norm, head):
        kinds = tuple(cfg.layer_types)
        for kind in kinds:
            if kind not in KINDS:
                raise ValueError('layer kind %r is not one of %s'
                                 % (kind, sorted(set(KINDS))))
        DecodeSpec.__init__(
            self, vocab=cfg.vocab, dim=cfg.dim, heads=cfg.heads,
            layers=len(kinds), ffn=cfg.shared_ffn, max_len=cfg.max_len,
            pos_len=0, emb_w=emb_w, pos_w=None, blocks=blocks,
            final_ln=(final_norm, None), head=head, kinds=kinds,
            kv_heads=cfg.kv_heads, head_dim=cfg.head_dim)
        if not 0 <= cfg.expert_offset <= cfg.experts - cfg.experts_held:
            raise ValueError('experts %d..%d are not among %d' % (
                cfg.expert_offset, cfg.expert_offset + cfg.experts_held,
                cfg.experts))
        self.cfg, self.eps = cfg, cfg.eps
        self.expert_layers = list(range(len(kinds)))
        self.conv_dim = cfg.kda_heads * (2 * cfg.key_dim + cfg.value_dim)

    def state_names(self, layer=None):
        """(delta state, convolution rows) var names of the kda layers;
        shared by the paged pair."""
        if layer is not None:
            return ('kda_state.layer%d.s' % layer,
                    'kda_state.layer%d.conv' % layer)
        return [n for i in self.recurrent_layers
                for n in self.state_names(i)]

    def state_shapes(self, slots):
        c = self.cfg
        return ((slots, c.kda_heads, c.key_dim, c.value_dim),
                (slots, c.conv_kernel - 1, self.conv_dim))

    # every name in blocks, whatever the roles: the hybrid spec's walk
    param_names = HybridDecodeSpec.param_names

    def paged_logits(self, tokens, at):
        return _model(tokens, self, at)


_ROLES = {
    'kda': (('qkv', True), ('conv', False), ('f_down', True),
            ('f_up', True), ('b', True), ('a_log', False),
            ('dt_bias', False), ('g_down', True), ('g_up', True),
            ('head_norm', False), ('proj', True)),
    'full_attention': (('qkv', True), ('gate', True), ('proj', True)),
}
_EXPERT_ROLES = (('ffn_norm', False), ('router', False), ('bias', False),
                 ('w1', False), ('w3', False), ('w2', False),
                 ('shared_gate_up', True), ('shared_down', True))


def spec_from_config(cfg):
    """The spec of a model built here, with names of its own."""
    blocks = []
    for i, kind in enumerate(cfg.layer_types):
        blk = {'norm': 'layer%d.norm.w' % i}
        for role, fc in _ROLES[kind] + _EXPERT_ROLES:
            name = 'layer%d.%s.w' % (i, role)
            blk[role] = (name, None) if fc else name
        blocks.append(blk)
    return SolarOpen2DecodeSpec(cfg, emb_w='embed.w', blocks=blocks,
                                final_norm='final_norm.w',
                                head=('lm_head.w', None))


# -- the block ---------------------------------------------------------------

def _low_rank(x, width, down, up, rank):
    return _named_fc(_named_fc(x, rank, down), width, up)


def _kda_mixer(x, spec, blk, i, at=None):
    """The linear-attention mixer around its two stateful ops, which
    read and write layer i's (delta state, convolution rows) in place
    where `at` says (a chunk's slot, or the live lanes of a step); the
    whole sequence from zero state without one."""
    c = spec.cfg
    h, dk, dv = c.kda_heads, c.key_dim, c.value_dim
    t = at.rows if at else spec.max_len
    qkv = _named_fc(x, spec.conv_dim, blk['qkv'])
    decay = _low_rank(x, h * dk, blk['f_down'], blk['f_up'], c.gate_rank)
    beta = _named_fc(x, h, blk['b'])
    gate = L.sigmoid(_low_rank(x, h * dv, blk['g_down'], blk['g_up'],
                               c.gate_rank))
    conv = _tmp_var()
    ins, outs = _state_io(at, i, 1)
    _block_op('short_conv',
              inputs=dict(ins, X=[qkv], W=[_param(
                  blk['conv'], [c.conv_kernel, spec.conv_dim])]),
              outputs=dict(outs, Out=[conv]))
    o = _tmp_var()
    ins, outs = _state_io(at, i, 0)
    _block_op('kda_step' if at is not None and at.decode else 'kda_chunk',
              inputs=dict(ins, QKV=[conv], G=[decay], B=[beta],
                          ALog=[_param(blk['a_log'], [h])],
                          DtBias=[_param(blk['dt_bias'], [h * dk])]),
              outputs=dict(outs, Out=[o]),
              attrs={'heads': h, 'key_dim': dk, 'value_dim': dv,
                     'beta_scale': 2.0 if c.neg_eigval else 1.0})
    o = _rms(L.reshape(o, shape=[-1, t, h, dv]), spec, blk['head_norm'],
             axis=3)
    o = L.elementwise_mul(L.reshape(o, shape=[-1, t, h * dv]), gate)
    return _named_fc(o, spec.dim, blk['proj'])


def _gated_attention(x, spec, blk, i, at=None):
    """Causal attention over layer i's pages, or over the whole
    sequence, its heads' outputs times sigmoid(u W_gate) elementwise
    in front of the output projection."""
    gate = L.sigmoid(_named_fc(x, spec.heads * spec.dh, blk['gate']))
    return _grouped_attention(x, spec, blk, i, at, out_gate=gate)


_MIXERS = {'kda': _kda_mixer, 'full_attention': _gated_attention}


def _model(tokens, spec, at=None):
    """Embedding -> layers of two sublayers -> final norm -> head: the
    whole sequence from zero state, or one paged program's rows (`at`:
    PagedStep)."""
    x = L.embedding(tokens, size=[spec.vocab, spec.dim],
                    param_attr=_named_attr(spec.emb_w))
    for i, kind in enumerate(spec.kinds):
        blk = spec.blocks[i]
        x = L.elementwise_add(
            x, _MIXERS[kind](_rms(x, spec, blk['norm']), spec, blk, i, at))
        x = L.elementwise_add(
            x, _experts_ffn(_rms(x, spec, blk['ffn_norm']), spec, blk, at))
    return _logits_head(_rms(x, spec, spec.final_ln[0]), spec, at)


def language_model_logits(tokens, cfg):
    """tokens [B, T, 1] int64 (T = cfg.max_len) -> logits [B, T, vocab],
    every sequence from zero state."""
    describe_served_model(tokens.block.program, 'solar_open2', cfg)
    return _model(tokens, spec_from_config(cfg))
