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

Three programs from the one block code, as in models/nemotron_h.py:
language_model_logits and the paged serving pair. K/V pools for the
full-attention layers only; every mamba layer keeps its state and its
convolution rows a slot. The pair's decode program copies no page
(models/transformer.build_page_copy_program), and each program returns
what its expert sublayers counted as a third fetch.
"""
from __future__ import annotations

from .. import layers as L
from .hybrid import _create_state_vars, _data, _param, _rms
from .nemotron_h import NemotronHDecodeSpec, _fetches, _mamba_mixer
from .transformer import (PAGED_DECODE_FEEDS, DecodeSpec, _block_op,
                          _create_pool_vars, _named_attr, _named_fc,
                          _paged_decode_attention, _paged_decode_tokens,
                          _paged_prefill_attention, _qkv_parts, _tmp_var)

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

    def build_paged_programs(self, slots, chunk, num_pages, page_tokens,
                             pages_per_slot):
        return build_paged_prefill_program(
            self, slots, chunk, num_pages, page_tokens, pages_per_slot) + \
            build_paged_decode_program(
                self, slots, num_pages, page_tokens, pages_per_slot)


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

def _experts(u, spec, blk, stats=None, at=None):
    """The expert sublayer on the normed stream u: op moe_experts on u
    itself and the shared expert as plain matmuls. `stats` is the list
    the sublayer's counts are appended to and `at` the input that marks
    dead rows (Live or Len); neither for the whole-sequence form."""
    c = spec.cfg
    outs = {}
    if stats is not None:
        stats.append(_tmp_var('int32'))
        outs['Stats'] = [stats[-1]]
    held = [c.experts_held, spec.dim, c.expert_ffn]
    routed = _tmp_var()
    _block_op('moe_experts',
              inputs=dict(
                  at or {}, X=[u], Lat=[u],
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


def _full_attention(x, spec, blk):
    """Whole-sequence causal attention (the source program's form): the
    query heads of one K/V head are rows of one product."""
    t, h, kvh, dh = spec.max_len, spec.heads, spec.kv_heads, spec.dh
    rep = h // kvh
    q4, k4, v4 = _qkv_parts(x, spec, blk, t)
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


def _model(tokens, spec, mixers, experts, last=None):
    """Embedding -> layers of two sublayers -> final norm -> the
    embedding again as the head. `mixers` maps a layer kind to its
    mixer and `experts(u, spec, blk)` is the program's form of the
    expert sublayer; `last` gathers one row a sequence before the
    head (the prefill's logits)."""
    c = spec.cfg
    x = L.scale(L.embedding(tokens, size=[spec.vocab, spec.dim],
                            param_attr=_named_attr(spec.emb_w)),
                scale=c.embedding_multiplier)
    for i, kind in enumerate(spec.kinds):
        blk = spec.blocks[i]
        x = L.elementwise_add(x, L.scale(
            mixers[kind](_rms(x, spec, blk['norm']), spec, blk, i),
            scale=c.residual_multiplier))
        x = L.elementwise_add(x, L.scale(
            experts(_rms(x, spec, blk['ffn_norm']), spec, blk),
            scale=c.residual_multiplier))
    x = _rms(x, spec, spec.final_ln[0])
    if last is not None:
        gathered = _tmp_var()
        _block_op('gather_time', inputs={'X': [x], 'Index': [last]},
                  outputs={'Out': [gathered]})
        x = gathered
    return L.matmul(x, _param(spec.emb_w, [spec.vocab, spec.dim]),
                    transpose_y=True, alpha=1.0 / c.logits_scaling)


def language_model_logits(tokens, cfg):
    """tokens [B, T, 1] int64 (T = cfg.max_len) -> logits [B, T, vocab],
    every sequence from zero state."""
    spec = spec_from_config(cfg)
    return _model(tokens, spec, {
        'mamba': lambda x, sp, blk, i: _mamba_mixer(
            x, sp, blk, sp.max_len, 'ssd_chunk'),
        'full_attention': lambda x, sp, blk, i: _full_attention(x, sp, blk)},
        _experts)


# -- the paged pair ------------------------------------------------------------

def build_paged_prefill_program(spec, slots, chunk, num_pages, page_tokens,
                                pages_per_slot):
    """One prefill chunk of one stream: models/nemotron_h.py's paged
    prefill feeds and fetches.
    Returns (program, feed_names, fetch_vars[logits, ids, counts])."""
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
        stats = []

        logits = _model(tokens, spec, {
            'mamba': lambda x, sp, blk, i: _mamba_mixer(
                x, sp, blk, chunk, 'ssd_chunk', states[i],
                {'Slot': [slot], 'Len': [length], 'Reset': [reset]}),
            'full_attention': lambda x, sp, blk, i: _paged_prefill_attention(
                x, sp, blk, pools[i], table, positions, length, cow_src,
                cow_dst, chunk)},
            lambda u, sp, blk: _experts(u, sp, blk, stats,
                                        {'Len': [length]}), last=last)
        fetches = _fetches(logits, L.argmax(logits, axis=-1), stats)
    return prog, ['prefill_tokens', 'prefill_positions', 'prefill_len',
                  'prefill_last', 'prefill_page_table', 'prefill_cow_src',
                  'prefill_cow_dst', 'prefill_state_slot',
                  'prefill_state_reset'], fetches


def build_paged_decode_program(spec, slots, num_pages, page_tokens,
                               pages_per_slot):
    """One token a lane over the whole slot pool: models/nemotron_h.py's
    paged decode feeds and fetches (no copy-on-write pair: the program
    copies no page).
    Returns (program, feed_names, fetch_vars[logits, ids, counts])."""
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
        stats = []

        logits3 = _model(tokens, spec, {
            'mamba': lambda x, sp, blk, i: _mamba_mixer(
                x, sp, blk, 1, 'ssd_step', states[i], {'Live': [live]}),
            'full_attention': lambda x, sp, blk, i: _paged_decode_attention(
                x, sp, blk, pools[i], table, step_idx)},
            lambda u, sp, blk: _experts(u, sp, blk, stats, {'Live': [live]}))
        logits = L.reshape(logits3, shape=[-1, spec.vocab])
        fetches = _fetches(logits, L.argmax(logits, axis=-1), stats)
    return prog, PAGED_DECODE_FEEDS + ['decode_state_live'], fetches
