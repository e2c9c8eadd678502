"""Hybrid language model whose every layer is TWO sublayers, a mixer and
an expert sublayer, each added to the stream through a scaled residual
(the GraniteMoeHybrid block, `model_type: granitemoehybrid`): Mamba-2
state-space mixers with a full-attention mixer among every few, softmax-
routed SwiGLU experts at the full width beside one shared expert, and
four multipliers in place of the usual scalings.

A file of its own beside models/nemotron_h.py, whose state-space mixer
(`_mamba_mixer`: the same ops with the same attributes, so one Pallas
step kernel and one set of cost functions serve both) and whose scope
variables it takes as they are. What differs is the wiring around the
mixers, which is what a block file states: read as nemotron_h's block
with a pattern of twice the letters, this block would still need the
four multipliers, the tied head, a second norm a layer and experts
without the latent projections, each as an option of that file that its
own model never sets.

    x  = embedding_multiplier * E[token]
    x <- x + residual_multiplier * Mixer_i(RMSNorm(x))
    x <- x + residual_multiplier * (Experts_i(u) + Shared_i(u)),
                                                      u = RMSNorm(x)
    logits = RMSNorm(x) E^T / logits_scaling          (the head is E)

`mamba` as in models/nemotron_h.py (ONE group of B and C for all heads
at the published sizes, which the ops take as an attribute).
`full_attention`: `heads` query heads on `kv_heads` K/V heads of
`head_dim`, causal softmax(q k^T * attention_multiplier) v, no
positional term, no bias.
Experts: l = u W_r (float32 at "highest"); the top_k largest of l;
g = softmax over the chosen logits; op moe_experts (gate 'softmax', W3
beside W1: an expert is W2 (silu(W1 u) * W3 u)) gives the part of the
sum that the experts HELD here add (`experts_held` of E from
`expert_offset`). Shared: V2 (silu(a) * b), [a | b] = u V1.

Three programs from the one block walk (_model), as in
models/nemotron_h.py: language_model_logits and, through
GraniteHDecodeSpec.paged_logits, the paged serving pair. K/V pools for
the full-attention layers only; every mamba layer keeps its state and
its convolution rows a slot. Each program of the pair returns what its
expert sublayers counted as a third fetch.
"""
from __future__ import annotations

from .. import layers as L
from . import describe_served_model
from .hybrid import _param, _rms
from .nemotron_h import NemotronHDecodeSpec, _attention, _mamba_mixer
from .transformer import (DecodeSpec, _block_op, _expert_io, _logits_head,
                          _named_attr, _named_fc, _tmp_var)

KINDS = ('mamba', 'full_attention')
# layer_types' words
LAYER_TYPES = {'mamba': 'mamba', 'attention': 'full_attention',
               'full_attention': 'full_attention'}


class GraniteHConfig(object):
    def __init__(self, vocab=512, dim=64, heads=4, kv_heads=2, head_dim=16,
                 layer_types=KINDS, max_len=64, mamba_heads=4,
                 mamba_head_dim=8, groups=1, state=16, conv_kernel=4,
                 chunk=256, experts=16, experts_held=None, expert_offset=0,
                 top_k=4, expert_ffn=48, shared_ffn=96, eps=1e-5,
                 embedding_multiplier=12.0, residual_multiplier=0.22,
                 attention_multiplier=0.0078125, logits_scaling=16.0):
        self.vocab, self.dim, self.max_len = vocab, dim, max_len
        self.heads, self.kv_heads, self.head_dim = heads, kv_heads, head_dim
        self.layer_types = tuple(LAYER_TYPES[k] for k in layer_types)
        self.mamba_heads, self.mamba_head_dim = mamba_heads, mamba_head_dim
        self.groups, self.state = groups, state
        self.conv_kernel, self.chunk = conv_kernel, chunk
        self.experts = experts
        self.experts_held = experts if experts_held is None else experts_held
        self.expert_offset = expert_offset
        self.top_k = top_k
        self.expert_ffn, self.shared_ffn, self.eps = expert_ffn, shared_ffn, eps
        self.embedding_multiplier = float(embedding_multiplier)
        self.residual_multiplier = float(residual_multiplier)
        self.attention_multiplier = float(attention_multiplier)
        self.logits_scaling = float(logits_scaling)


Config = GraniteHConfig


class GraniteHDecodeSpec(NemotronHDecodeSpec):
    """DecodeSpec of the block. blocks[i] holds parameter names by role:
    'norm' and the mixer's own (mamba: 'in', 'conv', 'conv_bias',
    'dt_bias', 'a_log', 'd', 'gate_norm', 'out'; full_attention: 'qkv',
    'proj'), then the expert sublayer's: 'ffn_norm', 'router', 'w1',
    'w3', 'w2', 'shared_up', 'shared_down'. The head is the embedding."""

    def __init__(self, cfg, emb_w, blocks, final_norm):
        kinds = tuple(cfg.layer_types)
        DecodeSpec.__init__(
            self, vocab=cfg.vocab, dim=cfg.dim, heads=cfg.heads,
            layers=len(kinds), ffn=cfg.shared_ffn, max_len=cfg.max_len,
            pos_len=0, emb_w=emb_w, pos_w=None, blocks=blocks,
            final_ln=(final_norm, None), head=(emb_w, None), kinds=kinds,
            kv_heads=cfg.kv_heads, head_dim=cfg.head_dim)
        if not 0 <= cfg.expert_offset <= cfg.experts - cfg.experts_held:
            raise ValueError('experts %d..%d are not among %d' % (
                cfg.expert_offset, cfg.expert_offset + cfg.experts_held,
                cfg.experts))
        self.cfg = cfg
        self.eps = cfg.eps
        self.expert_layers = list(range(len(kinds)))
        self.inner = cfg.mamba_heads * cfg.mamba_head_dim
        self.conv_dim = self.inner + 2 * cfg.groups * cfg.state

    @property
    def sm_scale(self):
        return self.cfg.attention_multiplier

    def param_names(self):
        # the tied head is the embedding: named once
        return NemotronHDecodeSpec.param_names(self)[1:]

    def paged_logits(self, tokens, at):
        return _model(tokens, self, at)


_ROLES = {
    'mamba': (('in', True), ('conv', False), ('conv_bias', False),
              ('dt_bias', False), ('a_log', False), ('d', False),
              ('gate_norm', False), ('out', True)),
    'full_attention': (('qkv', True), ('proj', True)),
}
_EXPERT_ROLES = (('ffn_norm', False), ('router', False), ('w1', False),
                 ('w3', False), ('w2', False), ('shared_up', True),
                 ('shared_down', True))


def spec_from_config(cfg):
    """The spec of a model built here, with names of its own."""
    blocks = []
    for i, kind in enumerate(cfg.layer_types):
        blk = {'norm': 'layer%d.norm.w' % i}
        for role, fc in _ROLES[kind] + _EXPERT_ROLES:
            name = 'layer%d.%s.w' % (i, role)
            blk[role] = (name, None) if fc else name
        blocks.append(blk)
    return GraniteHDecodeSpec(cfg, emb_w='embed.w', blocks=blocks,
                              final_norm='final_norm.w')


# -- the block ---------------------------------------------------------------

def _experts(u, spec, blk, at=None):
    """The expert sublayer on the normed stream u: op moe_experts on u
    itself (it passes over the dead rows and counts the others where
    `at` says which those are) and the shared expert as plain
    matmuls."""
    c = spec.cfg
    ins, outs = _expert_io(at)
    held = [c.experts_held, spec.dim, c.expert_ffn]
    routed = _tmp_var()
    _block_op('moe_experts',
              inputs=dict(
                  ins, X=[u], Lat=[u],
                  RouterW=[_param(blk['router'], [spec.dim, c.experts])],
                  W1=[_param(blk['w1'], held)], W3=[_param(blk['w3'], held)],
                  W2=[_param(blk['w2'], [held[0], held[2], held[1]])]),
              outputs=dict(outs, Out=[routed]),
              attrs={'top_k': c.top_k, 'scale': 1.0, 'gate': 'softmax',
                     'expert_offset': c.expert_offset})
    gu = _named_fc(u, 2 * c.shared_ffn, blk['shared_up'])
    shared = L.elementwise_mul(
        L.swish(L.slice(gu, axes=[2], starts=[0], ends=[c.shared_ffn])),
        L.slice(gu, axes=[2], starts=[c.shared_ffn],
                ends=[2 * c.shared_ffn]))
    return L.elementwise_add(
        routed, _named_fc(shared, spec.dim, blk['shared_down']))


_MIXERS = {'mamba': _mamba_mixer, 'full_attention': _attention}


def _model(tokens, spec, at=None):
    """Embedding -> layers of two sublayers -> final norm -> the
    embedding again as the head: the whole sequence from zero state, or
    one paged program's rows (`at`: PagedStep)."""
    c = spec.cfg
    x = L.scale(L.embedding(tokens, size=[spec.vocab, spec.dim],
                            param_attr=_named_attr(spec.emb_w)),
                scale=c.embedding_multiplier)
    for i, kind in enumerate(spec.kinds):
        blk = spec.blocks[i]
        x = L.elementwise_add(x, L.scale(
            _MIXERS[kind](_rms(x, spec, blk['norm']), spec, blk, i, at),
            scale=c.residual_multiplier))
        x = L.elementwise_add(x, L.scale(
            _experts(_rms(x, spec, blk['ffn_norm']), spec, blk, at),
            scale=c.residual_multiplier))
    return _logits_head(
        _rms(x, spec, spec.final_ln[0]), spec, at,
        lambda h, _: L.matmul(h, _param(spec.emb_w, [spec.vocab, spec.dim]),
                              transpose_y=True, alpha=1.0 / c.logits_scaling))


def language_model_logits(tokens, cfg):
    """tokens [B, T, 1] int64 (T = cfg.max_len) -> logits [B, T, vocab],
    every sequence from zero state."""
    describe_served_model(tokens.block.program, 'granite_h', cfg)
    return _model(tokens, spec_from_config(cfg))
