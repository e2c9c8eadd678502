"""Hybrid language model of three layer kinds, one mixer a layer (the
Nemotron-H block, `model_type: nemotron_h`): Mamba-2 state-space
layers, expert layers whose experts work in a latent space beside one
shared expert, and a few full-attention layers with fewer K/V heads
than query heads.

Beside models/hybrid.py, whose shape it follows, and on
models/transformer.py's named-fc helpers, page pools and paged
attention. One wiring for every kind, pre-norm, no bias but the
convolution's:

    x <- x + Mixer_i(RMSNorm_i(x));   embedding -> layers -> RMSNorm -> head

`mamba` (H heads of size P, G groups of state size N): [z | xBC | dt]
= x W_in (widths H P, H P + 2 G N, H); xBC through a causal depthwise
convolution of K taps with a bias, then silu (op short_conv); the
recurrence of ops/ssd_ops.py (ops ssd_chunk / ssd_step) over x, B, C
and dt; then W_out RMSNorm_groups(y * silu(z)) (op gated_group_norm).
`experts`: the router scores x over all E experts; l = x W_down (the
latent, width L); op moe_experts gives the part of sum_e w_e W2_e
relu(W1_e l)^2 that the experts HELD here add (`experts_held` of E from
`expert_offset`: one chip's share under expert parallelism; all of
them where experts_held = E); out = r W_up + V2 relu(V1 x)^2, the
shared expert at the full width.
`full_attention`: q, k, v = x W_qkv with `heads` query heads and
`kv_heads` K/V heads of `head_dim`, causal softmax attention, W_o; no
positional term.

Three programs come from the one block walk (_model), as in
models/hybrid.py: language_model_logits (what save_inference_model
writes, with the description the DecodeTranspiler reads the model from)
and, through NemotronHDecodeSpec.paged_logits, the paged serving pair
(models/transformer.build_paged_prefill_program and
build_paged_decode_program). K/V pools exist for the full-attention
layers only, [pages, page_tokens, kv_heads, head_dim]; each mamba layer
keeps, per slot, its state [slots, H, P, N] and the convolution's last
K-1 input rows [slots, K-1, H P + 2 G N] as scope variables that both
programs update in place; an expert layer keeps nothing for a stream.
Each program of the pair also returns, as a third fetch, what its
expert layers counted in the call ([4] int32: pairs of token and held
expert, held experts with a pair, pairs not computed, layers):
PagedDecodePredictor leaves it on the device and sums it when asked
(moe_counters()).
"""
from __future__ import annotations

from .. import layers as L
from . import describe_served_model
from .hybrid import HybridDecodeSpec, _param, _rms
from .transformer import (DecodeSpec, _block_op, _expert_io, _logits_head,
                          _named_attr, _named_fc, _paged_attention,
                          _qkv_parts, _state_io, _tmp_var)

KINDS = ('mamba', 'experts', 'full_attention')
# hybrid_override_pattern's letters
PATTERN = {'M': 'mamba', 'E': 'experts', '*': 'full_attention'}


class NemotronHConfig(object):
    def __init__(self, vocab=512, dim=64, heads=4, kv_heads=2, head_dim=16,
                 layer_types=KINDS, max_len=64, mamba_heads=4,
                 mamba_head_dim=8, groups=2, state=16, conv_kernel=4,
                 chunk=128, experts=16, experts_held=None, expert_offset=0,
                 top_k=4, routed_scale=1.0, latent=32, expert_ffn=48,
                 shared_ffn=96, eps=1e-5):
        self.vocab, self.dim, self.max_len = vocab, dim, max_len
        self.heads, self.kv_heads, self.head_dim = heads, kv_heads, head_dim
        self.layer_types = tuple(layer_types)
        self.mamba_heads, self.mamba_head_dim = mamba_heads, mamba_head_dim
        self.groups, self.state = groups, state
        self.conv_kernel, self.chunk = conv_kernel, chunk
        self.experts = experts
        self.experts_held = experts if experts_held is None else experts_held
        self.expert_offset = expert_offset
        self.top_k, self.routed_scale = top_k, routed_scale
        self.latent, self.expert_ffn = latent, expert_ffn
        self.shared_ffn, self.eps = shared_ffn, eps


Config = NemotronHConfig


class NemotronHDecodeSpec(DecodeSpec):
    """DecodeSpec of the block. blocks[i] holds parameter names by role:
    every kind 'norm'; mamba 'in', 'conv', 'conv_bias', 'dt_bias',
    'a_log', 'd', 'gate_norm', 'out'; experts 'router', 'bias', 'down',
    'w1', 'w2', 'up', 'shared_up', 'shared_down'; full_attention 'qkv',
    'proj'. Weights of the named-fc helpers are (name, None) pairs,
    everything else plain names."""

    recurrent_kinds = ('mamba',)
    state_family = 'ssm'

    def __init__(self, cfg, emb_w, blocks, final_norm, head):
        kinds = tuple(cfg.layer_types)
        for kind in kinds:
            if kind not in KINDS:
                raise ValueError('layer kind %r is not one of %s'
                                 % (kind, KINDS))
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
        self.cfg = cfg
        self.eps = cfg.eps
        self.expert_layers = [i for i, k in enumerate(kinds)
                              if k == 'experts']
        self.inner = cfg.mamba_heads * cfg.mamba_head_dim
        self.conv_dim = self.inner + 2 * cfg.groups * cfg.state

    def state_names(self, layer=None):
        """(state, convolution rows) var names of the mamba layers;
        shared by the paged pair."""
        if layer is not None:
            return ('ssm_state.layer%d.h' % layer,
                    'ssm_state.layer%d.conv' % layer)
        out = []
        for i in self.recurrent_layers:
            out.extend(self.state_names(i))
        return out

    def state_shapes(self, slots):
        c = self.cfg
        return ((slots, c.mamba_heads, c.mamba_head_dim, c.state),
                (slots, c.conv_kernel - 1, self.conv_dim))

    # every name in blocks, whatever the roles: the hybrid spec's walk
    param_names = HybridDecodeSpec.param_names

    def paged_logits(self, tokens, at):
        return _model(tokens, self, at)


_ROLES = {
    'mamba': (('in', True), ('conv', False), ('conv_bias', False),
              ('dt_bias', False), ('a_log', False), ('d', False),
              ('gate_norm', False), ('out', True)),
    'experts': (('router', False), ('bias', False), ('down', True),
                ('w1', False), ('w2', False), ('up', True),
                ('shared_up', True), ('shared_down', True)),
    'full_attention': (('qkv', True), ('proj', True)),
}


def spec_from_config(cfg):
    """The spec of a model built here, with names of its own."""
    blocks = []
    for i, kind in enumerate(cfg.layer_types):
        blk = {'norm': 'layer%d.norm.w' % i}
        for role, fc in _ROLES[kind]:
            name = 'layer%d.%s.w' % (i, role)
            blk[role] = (name, None) if fc else name
        blocks.append(blk)
    return NemotronHDecodeSpec(cfg, emb_w='embed.w', blocks=blocks,
                               final_norm='final_norm.w',
                               head=('lm_head.w', None))


# -- the block ---------------------------------------------------------------

def _mamba_mixer(x, spec, blk, i, at=None):
    """The state-space mixer around its two stateful ops, which read and
    write layer i's (state, convolution rows) in place where `at` says
    (a chunk's slot, or the live lanes of a step); the whole sequence
    from zero state without one."""
    c = spec.cfg
    h, inner, conv_dim = c.mamba_heads, spec.inner, spec.conv_dim
    zxd = _named_fc(x, 2 * inner + 2 * c.groups * c.state + h, blk['in'])
    z = L.slice(zxd, axes=[2], starts=[0], ends=[inner])
    xbc = L.slice(zxd, axes=[2], starts=[inner], ends=[inner + conv_dim])
    dt = L.slice(zxd, axes=[2], starts=[inner + conv_dim],
                 ends=[inner + conv_dim + h])
    conv = _tmp_var()
    ins, outs = _state_io(at, i, 1)
    _block_op('short_conv',
              inputs=dict(ins, X=[xbc],
                          W=[_param(blk['conv'], [c.conv_kernel, conv_dim])],
                          Bias=[_param(blk['conv_bias'], [conv_dim])]),
              outputs=dict(outs, Out=[conv]))
    y = _tmp_var()
    ins, outs = _state_io(at, i, 0)
    _block_op('ssd_step' if at and at.decode else 'ssd_chunk',
              inputs=dict(ins, XBC=[conv], DT=[dt],
                          ALog=[_param(blk['a_log'], [h])],
                          DtBias=[_param(blk['dt_bias'], [h])],
                          D=[_param(blk['d'], [h])]),
              outputs=dict(outs, Out=[y]),
              attrs={'heads': h, 'head_dim': c.mamba_head_dim,
                     'groups': c.groups, 'state': c.state,
                     'block': c.chunk})
    gated = _tmp_var()
    _block_op('gated_group_norm',
              inputs={'X': [y], 'Z': [z],
                      'Scale': [_param(blk['gate_norm'], [inner])]},
              outputs={'Y': [gated]},
              attrs={'groups': c.groups, 'epsilon': float(spec.eps)})
    return _named_fc(gated, spec.dim, blk['out'])


def _experts_mixer(x, spec, blk, i, at=None):
    """The expert layer: the latent projections and the shared expert
    as plain matmuls around op moe_experts, which passes over the dead
    rows and counts the others where `at` says which those are."""
    c = spec.cfg
    lat = _named_fc(x, c.latent, blk['down'])
    ins, outs = _expert_io(at)
    routed = _tmp_var()
    _block_op('moe_experts',
              inputs=dict(
                  ins, X=[x], Lat=[lat],
                  RouterW=[_param(blk['router'], [spec.dim, c.experts])],
                  Bias=[_param(blk['bias'], [c.experts])],
                  W1=[_param(blk['w1'],
                             [c.experts_held, c.latent, c.expert_ffn])],
                  W2=[_param(blk['w2'],
                             [c.experts_held, c.expert_ffn, c.latent])]),
              outputs=dict(outs, Out=[routed]),
              attrs={'top_k': c.top_k, 'scale': float(c.routed_scale),
                     'expert_offset': c.expert_offset})
    routed = _named_fc(routed, spec.dim, blk['up'])
    shared = L.square(L.relu(_named_fc(x, c.shared_ffn, blk['shared_up'])))
    return L.elementwise_add(
        routed, _named_fc(shared, spec.dim, blk['shared_down']))


def _attention(x, spec, blk, i, at=None, out_gate=None):
    """Causal attention over layer i's pages, or over the whole
    sequence (the source program's form): the query heads of one K/V
    head are rows of one product. `out_gate` [B, t, heads * dh], where
    the block has one (models/solar_open2.py), multiplies the heads'
    outputs in front of the output projection."""
    if at is not None:
        return _paged_attention(x, spec, blk, i, at, out_gate=out_gate)
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
    ctx = L.reshape(ctx, shape=[-1, t, h * dh])
    if out_gate is not None:
        ctx = L.elementwise_mul(ctx, out_gate)
    return _named_fc(ctx, spec.dim, blk['proj'])


_MIXERS = {'mamba': _mamba_mixer, 'experts': _experts_mixer,
           'full_attention': _attention}


def _model(tokens, spec, at=None):
    """Embedding -> layers -> final norm -> head: the whole sequence
    from zero state, or one paged program's rows (`at`: PagedStep)."""
    x = L.embedding(tokens, size=[spec.vocab, spec.dim],
                    param_attr=_named_attr(spec.emb_w))
    for i, kind in enumerate(spec.kinds):
        blk = spec.blocks[i]
        x = L.elementwise_add(
            x, _MIXERS[kind](_rms(x, spec, blk['norm']), spec, blk, i, at))
    return _logits_head(_rms(x, spec, spec.final_ln[0]), spec, at)


def language_model_logits(tokens, cfg):
    """tokens [B, T, 1] int64 (T = cfg.max_len) -> logits [B, T, vocab],
    every sequence from zero state."""
    describe_served_model(tokens.block.program, 'nemotron_h', cfg)
    return _model(tokens, spec_from_config(cfg))
