"""Language model of latent-attention layers with group-limited gated
experts (the A.X-K1 block, `model_type: axk1`; the DeepSeek-V3 layer
equations, arXiv:2405.04434 section 2.1 and arXiv:2412.19437).

Beside models/nemotron_h.py, whose shape it follows, and on
models/transformer.py's named-fc helpers and page pools. Pre-norm, no
bias, two sublayers a layer:

    x <- x + Attn_i(RMSNorm(x));  x <- x + FFN_i(RMSNorm(x))
    embedding -> layers -> RMSNorm -> head (untied)

Attn (multi-head latent attention, H heads): c_Q = RMSNorm(h W_DQ);
[q_C | q_R] = c_Q W_UQ (dn | dr a head); [c_KV | k_R] = h W_DKV (dc |
dr); c_KV <- RMSNorm(c_KV); q_R and k_R rotated by the token's position
(op rotary_yarn: RoPE with YaRN's blended frequencies); every head's
key and value are c_KV W_UKV; causal softmax with scale (dn + dr)^-0.5
m^2, m YaRN's temperature; W_O. The ops are in
ops/latent_attention_ops.py.
FFN: the first `dense_layers` layers W_down (silu(W_gate h) * W_up h);
the others op moe_experts over x itself (no latent: Lat = X) with
group-limited choice and experts of three matrices, `experts_held` of
E from `expert_offset` (one chip's share), plus one shared expert of
the dense form. Gate and up of a dense FFN are one weight [D, 2 F].

Three programs come from the one block walk (_model):
language_model_logits (what save_inference_model writes, with the
description the DecodeTranspiler reads the model from: op
latent_attention over the whole sequence, the equations as they stand)
and, through AXK1DecodeSpec.paged_logits, the paged serving pair
(models/transformer.build_paged_prefill_program and
build_paged_decode_program). A layer keeps ONE page pool,
[pages, page_tokens, row]: a token's normed latent and its ALREADY
ROTATED key side by side (dc + dr values for all heads), padded with
zeros to whole lanes of 128; there is no V pool and no recurrent state,
so the prefix cache hands out pages for this block. Decode attends
through op paged_latent_attention, prefill through
paged_latent_prefill, both in the absorbed form that never expands the
rows to per-head keys and values. Each program of the pair also
returns the expert layers' counts as a third fetch, as
models/nemotron_h.py's do.
"""
from __future__ import annotations

from .. import layers as L
from ..ops.latent_attention_ops import yarn_mscale
from . import describe_served_model
from .hybrid import HybridDecodeSpec, _param, _rms
from .transformer import (DecodeSpec, _block_op, _expert_io, _logits_head,
                          _named_attr, _named_fc, _tmp_var)

KIND = 'latent_attention'
ROPE_KEYS = ('base', 'factor', 'original_max', 'beta_fast', 'beta_slow',
             'mscale', 'mscale_all_dim')


class AXK1Config(object):
    def __init__(self, vocab=512, dim=64, heads=4, layers=3, dense_layers=1,
                 max_len=64, q_rank=48, kv_rank=32, nope_dim=16, rope_dim=8,
                 v_dim=16, dense_ffn=96, expert_ffn=32, shared_ffn=32,
                 experts=16, experts_held=None, expert_offset=0, top_k=4,
                 n_group=4, topk_group=2, routed_scale=2.5, eps=1e-6,
                 rope=None):
        self.vocab, self.dim, self.heads = vocab, dim, heads
        self.layers, self.dense_layers = layers, dense_layers
        self.max_len = max_len
        self.q_rank, self.kv_rank = q_rank, kv_rank
        self.nope_dim, self.rope_dim, self.v_dim = nope_dim, rope_dim, v_dim
        self.dense_ffn, self.expert_ffn = dense_ffn, expert_ffn
        self.shared_ffn = shared_ffn
        self.experts = experts
        self.experts_held = experts if experts_held is None else experts_held
        self.expert_offset = expert_offset
        self.top_k, self.n_group, self.topk_group = top_k, n_group, topk_group
        self.routed_scale, self.eps = routed_scale, eps
        # rotary_yarn's attributes; factor 1 is plain RoPE
        rope = dict({'base': 10000.0, 'factor': 1.0, 'original_max': 4096,
                     'beta_fast': 32.0, 'beta_slow': 1.0, 'mscale': 1.0,
                     'mscale_all_dim': 0.0}, **(rope or {}))
        self.rope = {k: int(v) if k == 'original_max' else float(v)
                     for k, v in rope.items()}

    @property
    def sm_scale(self):
        m = yarn_mscale(self.rope['factor'], self.rope['mscale_all_dim'])
        return (self.nope_dim + self.rope_dim) ** -0.5 * m * m


Config = AXK1Config


class AXK1DecodeSpec(DecodeSpec):
    """DecodeSpec of the block. blocks[i] holds parameter names by role:
    every layer 'attn_norm', 'q_down', 'q_norm', 'q_up', 'kv_down',
    'kv_norm', 'kv_up', 'proj', 'ffn_norm'; a dense layer 'gate_up',
    'down'; an expert layer 'router', 'bias', 'w1', 'w3', 'w2',
    'shared_gate_up', 'shared_down'. Weights of the named-fc helpers are
    (name, None) pairs, everything else plain names. Every layer keeps
    a latent page (`page_kind`), none recurrent state."""

    page_kind = 'latent'
    LANES = 128

    def __init__(self, cfg, emb_w, blocks, final_norm, head):
        DecodeSpec.__init__(
            self, vocab=cfg.vocab, dim=cfg.dim, heads=cfg.heads,
            layers=cfg.layers, ffn=cfg.dense_ffn, max_len=cfg.max_len,
            pos_len=0, emb_w=emb_w, pos_w=None, blocks=blocks,
            final_ln=(final_norm, None), head=head,
            kinds=(KIND,) * cfg.layers,
            head_dim=cfg.nope_dim + cfg.rope_dim)
        if not 0 <= cfg.expert_offset <= cfg.experts - cfg.experts_held:
            raise ValueError('experts %d..%d are not among %d' % (
                cfg.expert_offset, cfg.expert_offset + cfg.experts_held,
                cfg.experts))
        if cfg.experts % cfg.n_group:
            raise ValueError('%d experts in %d groups'
                             % (cfg.experts, cfg.n_group))
        self.cfg, self.eps = cfg, cfg.eps
        self.kv_layers = list(range(cfg.layers))
        self.expert_layers = list(range(cfg.dense_layers, cfg.layers))
        self.latent_row = cfg.kv_rank + cfg.rope_dim
        # what a token's row takes in the pool: whole lanes
        self.pool_row = -(-self.latent_row // self.LANES) * self.LANES

    def pool_names(self, layer=None):
        """One pool a layer; shared by the paged pair."""
        if layer is not None:
            return ('kv_pool.layer%d.latent' % layer,)
        return [n for i in self.kv_layers for n in self.pool_names(i)]

    def pool_shape(self, num_pages, page_tokens):
        return (num_pages, page_tokens, self.pool_row)

    def pool_spec(self):
        return (None, None, None)

    def latent_row_bytes(self):
        """Bytes one token's rows take in the pools, all layers."""
        return 4 * self.pool_row * len(self.kv_layers)

    param_names = HybridDecodeSpec.param_names

    def paged_logits(self, tokens, at):
        return _model(tokens, self, at)


_ATTN_ROLES = (('attn_norm', False), ('q_down', True), ('q_norm', False),
               ('q_up', True), ('kv_down', True), ('kv_norm', False),
               ('kv_up', False), ('proj', True), ('ffn_norm', False))
_FFN_ROLES = {
    'dense': (('gate_up', True), ('down', True)),
    'experts': (('router', False), ('bias', False), ('w1', False),
                ('w3', False), ('w2', False), ('shared_gate_up', True),
                ('shared_down', True)),
}


def ffn_kind(cfg, i):
    return 'dense' if i < cfg.dense_layers else 'experts'


def spec_from_config(cfg):
    """The spec of a model built here, with names of its own."""
    blocks = []
    for i in range(cfg.layers):
        blk = {}
        for role, fc in _ATTN_ROLES + _FFN_ROLES[ffn_kind(cfg, i)]:
            name = 'layer%d.%s.w' % (i, role)
            blk[role] = (name, None) if fc else name
        blocks.append(blk)
    return AXK1DecodeSpec(cfg, emb_w='embed.w', blocks=blocks,
                          final_norm='final_norm.w',
                          head=('lm_head.w', None))


# -- the block ---------------------------------------------------------------

def _rotated(x, spec, positions=None, per='row', start=0):
    """x with its last axis from `start` on rotated (op rotary_yarn)."""
    c = spec.cfg
    out = _tmp_var()
    ins = {'X': [x]}
    if positions is not None:
        ins['Positions'] = [positions]
    _block_op('rotary_yarn', inputs=ins, outputs={'Out': [out]},
              attrs=dict(c.rope, dim=c.rope_dim, per=per, start=start))
    return out


def _latent_parts(x, spec, blk, t, positions=None, per='row'):
    """(q [B, t, H, dn + dr], c_KV [B, t, dc] normed, k_R [B, t, dr]),
    the rotary parts rotated."""
    c = spec.cfg
    head = c.nope_dim + c.rope_dim
    cq = _rms(_named_fc(x, c.q_rank, blk['q_down']), spec, blk['q_norm'])
    q = L.reshape(_named_fc(cq, c.heads * head, blk['q_up']),
                  shape=[-1, t, c.heads, head])
    ckr = _named_fc(x, c.kv_rank + c.rope_dim, blk['kv_down'])
    ckv = _rms(L.slice(ckr, axes=[2], starts=[0], ends=[c.kv_rank]), spec,
               blk['kv_norm'])
    kr = L.slice(ckr, axes=[2], starts=[c.kv_rank],
                 ends=[c.kv_rank + c.rope_dim])
    return (_rotated(q, spec, positions, per, start=c.nope_dim), ckv,
            _rotated(kr, spec, positions, per))


def _attention_op(op_type, spec, blk, q, t, **inputs):
    c = spec.cfg
    ctx = _tmp_var()
    _block_op(op_type,
              inputs=dict({k: [v] for k, v in inputs.items()}, Q=[q],
                          WUKV=[_param(blk['kv_up'], [
                              c.kv_rank,
                              c.heads * (c.nope_dim + c.v_dim)])]),
              outputs={'Out': [ctx]},
              attrs={'nope_dim': c.nope_dim,
                     'sm_scale': float(c.sm_scale)})
    return _named_fc(ctx, spec.dim, blk['proj'])


def _attention(x, spec, blk, i, at=None):
    """The whole sequence, the equations as they stand; or, over layer
    i's pages: the new rows into their pages (the normed latent and the
    rotated key side by side, zeros up to the pool's row), then the
    absorbed attention through the table, one token a lane in a decode
    step, one stream's chunk of rows (and how many of them are live) in
    a prefill chunk. A chunk copies its forked page first (at.cow); a
    decode step copies none: the host ran the page copy program in front
    of it (models/transformer.build_page_copy_program)."""
    if at is None:
        t = spec.max_len
        q, ckv, kr = _latent_parts(x, spec, blk, t)
        return _attention_op('latent_attention', spec, blk, q, t, CKV=ckv,
                             KR=kr)
    q, ckv, kr = _latent_parts(x, spec, blk, at.rows, at.positions,
                               'lane' if at.decode else 'row')
    row = L.concat([ckv, kr], axis=2)
    if spec.pool_row > spec.latent_row:
        row = L.pad(row, paddings=[0, 0, 0, 0, 0,
                                   spec.pool_row - spec.latent_row])
    pool, = at.pools[i]
    ins = {'Pool': [pool], 'X': [row], 'Table': [at.table],
           'Positions': [at.positions]}
    if not at.decode:
        _block_op('kv_page_cow',
                  inputs={'Pool': [pool], 'Src': [at.cow[0]],
                          'Dst': [at.cow[1]]},
                  outputs={'Out': [pool]})
        ins['Len'] = [at.length]
    _block_op('kv_page_append' if at.decode else 'kv_page_write', inputs=ins,
              outputs={'Out': [pool]})
    if at.decode:
        return _attention_op('paged_latent_attention', spec, blk, q, at.rows,
                             Pool=pool, Table=at.table,
                             Positions=at.positions)
    return _attention_op('paged_latent_prefill', spec, blk, q, at.rows,
                         Pool=pool, Table=at.table, Positions=at.positions,
                         Len=at.length)


def _gated_mlp(x, spec, width, up, down):
    gu = _named_fc(x, 2 * width, up)
    h = L.elementwise_mul(
        L.swish(L.slice(gu, axes=[2], starts=[0], ends=[width])),
        L.slice(gu, axes=[2], starts=[width], ends=[2 * width]))
    return _named_fc(h, spec.dim, down)


def _experts_ffn(x, spec, blk, at=None):
    """The expert layer: op moe_experts on x itself, and the shared
    expert as plain matmuls. `at` as in
    models/nemotron_h._experts_mixer."""
    c = spec.cfg
    ins, outs = _expert_io(at)
    held = [c.experts_held, spec.dim, c.expert_ffn]
    routed = _tmp_var()
    _block_op('moe_experts',
              inputs=dict(
                  ins, X=[x], Lat=[x],
                  RouterW=[_param(blk['router'], [spec.dim, c.experts])],
                  Bias=[_param(blk['bias'], [c.experts])],
                  W1=[_param(blk['w1'], held)], W3=[_param(blk['w3'], held)],
                  W2=[_param(blk['w2'], [held[0], held[2], held[1]])]),
              outputs=dict(outs, Out=[routed]),
              attrs={'top_k': c.top_k, 'scale': float(c.routed_scale),
                     'expert_offset': c.expert_offset,
                     'n_group': c.n_group, 'topk_group': c.topk_group})
    return L.elementwise_add(routed, _gated_mlp(
        x, spec, c.shared_ffn, blk['shared_gate_up'], blk['shared_down']))


def _model(tokens, spec, at=None):
    """Embedding -> layers -> final norm -> head: the whole sequence, or
    one paged program's rows (`at`: PagedStep)."""
    c = spec.cfg
    x = L.embedding(tokens, size=[spec.vocab, spec.dim],
                    param_attr=_named_attr(spec.emb_w))
    for i, blk in enumerate(spec.blocks):
        x = L.elementwise_add(
            x, _attention(_rms(x, spec, blk['attn_norm']), spec, blk, i, at))
        h = _rms(x, spec, blk['ffn_norm'])
        x = L.elementwise_add(x, _experts_ffn(h, spec, blk, at)
                              if ffn_kind(c, i) == 'experts' else
                              _gated_mlp(h, spec, c.dense_ffn,
                                         blk['gate_up'], blk['down']))
    return _logits_head(_rms(x, spec, spec.final_ln[0]), spec, at)


def language_model_logits(tokens, cfg):
    """tokens [B, T, 1] int64 (T = cfg.max_len) -> logits [B, T, vocab]."""
    describe_served_model(tokens.block.program, 'axk1', cfg)
    return _model(tokens, spec_from_config(cfg))
