"""Language model that generates by diffusion over blocks (the SDAR
block with routed experts, `model_type: sdar_moe`, arXiv:2510.06303;
block diffusion: arXiv:2503.09573): attention that is causal over
blocks of `block_length` tokens and bidirectional inside one, q and k
normed a head at a time before a rotation over the whole head, fewer
K/V heads than query heads, SiLU-gated experts chosen by a softmax
top-k router in every layer, and a head whose row i scores the token AT
position i (no shift).

Beside models/smallthinker.py, whose shape it follows, and on
models/transformer.py's named-fc helpers, page pools and paged
attention. For layer l, x the residual stream, no bias anywhere, B the
block length:

    u  = RMSNorm_in(x)
    q, k, v = W_q u, W_k u, W_v u   `heads` query heads on `kv_heads`
                                    K/V heads of `head_dim`
    q, k = RMSNorm_q(q), RMSNorm_k(k)
                                    a head at a time over its head_dim
                                    values, one gain [head_dim] each
    q, k = RoPE(q), RoPE(k)         theta `rope_theta`, the whole head,
                                    split halves (op rotary_yarn at
                                    factor 1)
    a  = softmax(q k^T / sqrt(head_dim) + M) v
                                    query head h reads K/V head
                                    h // (heads / kv_heads); key j is
                                    visible to query i iff
                                    j // B <= i // B
    x  = x + W_o a
    h  = RMSNorm_post(x)
    r  = W_r h;  S = the top_k largest of r;  g = softmax(r[S])
    x  = x + sum_{e in S} g_e W2_e (silu(W1_e h) * W3_e h)
    logits = W_head RMSNorm_f(x)    untied, row i for position i

Three programs from the one block walk (_model): language_model_logits
(the whole sequence under the dense block mask) and, through
SdarMoeDecodeSpec.paged_logits, the paged pair: the prefill chunk (whole
blocks of a prompt, `paged_prefill_mask` with the block length) and, in
the place of the one-token decode step, the BLOCK step
(models/transformer.build_paged_block_program): B rows of every lane at
the block's positions, each row attending over the lane's pages up to
the end of its block, and behind the head op `block_unmask`, which does
on the device what the family's decoding routine does between two
passes. How a block is decoded (denoising steps, which masked rows take
their argmax, the mask id) is part of the Config and rides in the
program's description: serving/paged.py and serving/engine.py read it
from the spec.
"""
from __future__ import annotations

from .. import layers as L
from . import describe_served_model
from .hybrid import HybridDecodeSpec, _param, _rms
from .transformer import (DecodeSpec, _block_op, _expert_io, _logits_head,
                          _named_attr, _named_fc, _paged_attention,
                          _qkv_parts, _tmp_var)

RULES = ('low_confidence_static', 'sequential', 'low_confidence_dynamic')


class SdarMoeConfig(object):
    def __init__(self, vocab=512, dim=64, heads=4, kv_heads=2, head_dim=16,
                 layers=2, rope_theta=1e6, max_len=64, experts=16,
                 experts_held=None, expert_offset=0, top_k=4, expert_ffn=48,
                 eps=1e-6, block_length=4, denoising_steps=4,
                 remasking='low_confidence_static', threshold=0.9,
                 mask_id=None):
        self.vocab, self.dim, self.max_len = vocab, dim, max_len
        self.heads, self.kv_heads, self.head_dim = heads, kv_heads, head_dim
        self.layers, self.rope_theta = int(layers), float(rope_theta)
        self.experts = experts
        self.experts_held = experts if experts_held is None else experts_held
        self.expert_offset = expert_offset
        self.top_k, self.expert_ffn, self.eps = top_k, expert_ffn, eps
        # how a block is decoded (the family's published routine)
        self.block_length = int(block_length)
        self.denoising_steps = int(denoising_steps)
        self.remasking, self.threshold = remasking, float(threshold)
        self.mask_id = vocab - 1 if mask_id is None else int(mask_id)


Config = SdarMoeConfig


class SdarMoeDecodeSpec(DecodeSpec):
    """DecodeSpec of the block. blocks[i] holds parameter names by role:
    'norm', 'qkv', 'q_norm', 'k_norm', 'proj', 'ffn_norm', 'router',
    'w1', 'w3', 'w2'. Weights of the named-fc helpers are (name, None)
    pairs, everything else plain names. `block_tokens` (the block
    length) is what makes the pair's second program a block step."""

    def __init__(self, cfg, emb_w, blocks, final_norm, head):
        if cfg.remasking not in RULES:
            raise ValueError('remasking %r is none of %s'
                             % (cfg.remasking, RULES))
        if cfg.block_length < 1 or cfg.denoising_steps < 1 \
                or cfg.max_len % cfg.block_length:
            raise ValueError(
                'blocks of %d tokens in %d denoising steps over %d positions'
                % (cfg.block_length, cfg.denoising_steps, cfg.max_len))
        if not 0 <= cfg.mask_id < cfg.vocab:
            raise ValueError('mask id %d outside the vocabulary of %d'
                             % (cfg.mask_id, cfg.vocab))
        DecodeSpec.__init__(
            self, vocab=cfg.vocab, dim=cfg.dim, heads=cfg.heads,
            layers=cfg.layers, ffn=cfg.expert_ffn, max_len=cfg.max_len,
            pos_len=0, emb_w=emb_w, pos_w=None, blocks=blocks,
            final_ln=(final_norm, None), head=head, kv_heads=cfg.kv_heads,
            head_dim=cfg.head_dim)
        if not 0 <= cfg.expert_offset <= cfg.experts - cfg.experts_held:
            raise ValueError('experts %d..%d are not among %d' % (
                cfg.expert_offset, cfg.expert_offset + cfg.experts_held,
                cfg.experts))
        self.cfg, self.eps = cfg, cfg.eps
        self.expert_layers = list(range(cfg.layers))
        self.block_tokens = cfg.block_length

    param_names = HybridDecodeSpec.param_names

    def paged_logits(self, tokens, at):
        return _model(tokens, self, at)

    @property
    def block_schedule(self):
        """Rows a denoising step unmasks, step by step: block_length //
        denoising_steps each, the first block_length % denoising_steps
        steps one more."""
        b, t = self.cfg.block_length, self.cfg.denoising_steps
        return [b // t + (i < b % t) for i in range(t)]


_ROLES = (('norm', False), ('qkv', True), ('q_norm', False),
          ('k_norm', False), ('proj', True), ('ffn_norm', False),
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
    return SdarMoeDecodeSpec(cfg, emb_w='embed.w', blocks=blocks,
                             final_norm='final_norm.w',
                             head=('lm_head.w', None))


# -- the block ---------------------------------------------------------------

def _rotary(spec, at):
    """The rotation of q and k [-1, t, heads, dh] (op rotary_yarn at
    factor 1: plain RoPE over the whole head, split halves), by the
    rows' absolute positions: a whole sequence's from its start, a
    chunk's rows', a block step's [lanes, rows]."""
    def rotated(x):
        out = _tmp_var()
        ins, per = {'X': [x]}, 'row'
        if at is not None:
            ins['Positions'] = [at.positions]
            per = 'each' if at.decode else 'row'
        _block_op('rotary_yarn', inputs=ins, outputs={'Out': [out]},
                  attrs={'dim': spec.dh, 'base': spec.cfg.rope_theta,
                         'factor': 1.0, 'per': per, 'start': 0})
        return out
    return rotated


def _head_norm(spec, blk):
    """The norm of q and of k, a head at a time over its head_dim
    values, for _qkv_parts."""
    return lambda part, which: _rms(part, spec, blk[which + '_norm'],
                                    axis=3)


def _attention(x, spec, blk, i, at=None):
    """Layer i's attention over its pages, or over the whole sequence
    with the dense block mask (the source program's form)."""
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
    masked = _tmp_var()
    _block_op('paged_prefill_mask',
              inputs={'X': [scores]}, outputs={'Out': [masked]},
              attrs={'block': spec.block_tokens})
    probs = L.softmax(masked)
    ctx = L.matmul(L.reshape(probs, shape=[-1, kvh, rep * t, t]), v)
    ctx = L.transpose(L.reshape(ctx, shape=[-1, h, t, dh]),
                      perm=[0, 2, 1, 3])
    return _named_fc(L.reshape(ctx, shape=[-1, t, h * dh]), spec.dim,
                     blk['proj'])


def _experts(h, spec, blk, at=None):
    """The expert sublayer: op moe_experts scores and works on `h`; it
    passes over the dead rows and counts the others where `at` says
    which those are (a block step's Live is a lane's flag for all its
    rows)."""
    c = spec.cfg
    ins, outs = _expert_io(at)
    held = [c.experts_held, spec.dim, c.expert_ffn]
    routed = _tmp_var()
    _block_op('moe_experts',
              inputs=dict(
                  ins, X=[h], Lat=[h],
                  RouterW=[_param(blk['router'], [spec.dim, c.experts])],
                  W1=[_param(blk['w1'], held)], W3=[_param(blk['w3'], held)],
                  W2=[_param(blk['w2'], [held[0], held[2], held[1]])]),
              outputs=dict(outs, Out=[routed]),
              attrs={'top_k': c.top_k, 'scale': 1.0, 'gate': 'softmax',
                     'act': 'silu', 'expert_offset': c.expert_offset})
    return routed


def _model(tokens, spec, at=None):
    """Embedding -> layers of two sublayers -> final norm -> head: the
    whole sequence, or one paged program's rows (`at`: PagedStep)."""
    x = L.embedding(tokens, size=[spec.vocab, spec.dim],
                    param_attr=_named_attr(spec.emb_w))
    for i, blk in enumerate(spec.blocks):
        x = L.elementwise_add(x, _attention(
            _rms(x, spec, blk['norm']), spec, blk, i, at))
        x = L.elementwise_add(x, _experts(
            _rms(x, spec, blk['ffn_norm']), spec, blk, at))
    return _logits_head(_rms(x, spec, spec.final_ln[0]), spec, at)


def language_model_logits(tokens, cfg):
    """tokens [B, T, 1] int64 (T = cfg.max_len) -> logits [B, T, vocab],
    row i for position i."""
    describe_served_model(tokens.block.program, 'sdar_moe', cfg)
    return _model(tokens, spec_from_config(cfg))
