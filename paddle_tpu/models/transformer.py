"""Transformer language model — the flagship parallel config.

The reference's Transformer lives in its benchmark suite
(benchmark/fluid/machine_translation.py-era NMT); this is the modern
decoder-only formulation built on the framework's Program IR with the full
parallel-axis treatment (SURVEY.md §2.11 extension):

- dp: batch sharded
- tp: attention heads + FFN features Megatron-split via column/row
  parallel fc (GSPMD inserts the psum pair per block)
- sp: activation time axis sharded between blocks
  (sequence parallelism for norm/elementwise regions)
- ep: optional MoE FFN with experts sharded
"""
from __future__ import annotations

import numpy as np

from .. import layers as L
from ..parallel.layers import (column_parallel_fc, row_parallel_fc,
                               vocab_parallel_embedding, moe_layer,
                               sequence_parallel_scope)
from ..parallel.api import sharding_constraint, pipeline_stage_guard


class TransformerConfig(object):
    def __init__(self, vocab=1000, dim=64, heads=4, layers=2, ffn=128,
                 max_len=64, moe_experts=0, use_tp=True, use_sp=True,
                 pp_stages=0, ring_attention=False,
                 flash_attention=False, remat=None):
        self.vocab, self.dim, self.heads = vocab, dim, heads
        self.layers, self.ffn, self.max_len = layers, ffn, max_len
        self.moe_experts = moe_experts
        self.use_tp, self.use_sp = use_tp, use_sp
        # pp_stages > 0: annotate blocks with pipeline stages (layers
        # must divide evenly); consumed by DistributedStrategy(pp=...)
        self.pp_stages = pp_stages
        # long-context: attention over the sp-sharded sequence via the
        # ppermute ring (parallel/ring_attention.py) — O(T/n) per-device
        # score memory instead of materializing [B, H, T, T]
        self.ring_attention = ring_attention
        # single-device long context: Pallas blockwise attention (no
        # [T, T] scores); composable alternative to the sp ring
        self.flash_attention = flash_attention
        # rematerialization policy: None (save all activations),
        # 'nothing' (save only each block's output — max memory saving),
        # or 'dots' (also keep MXU outputs; less recompute). Applied
        # per transformer block via layers.recompute.
        self.remat = remat


def _attention(x, cfg, prefix):
    """Multi-head self-attention, heads split over tp: qkv is
    column-parallel (head dim sharded), output proj row-parallel."""
    D, H = cfg.dim, cfg.heads
    dh = D // H
    T = cfg.max_len
    if cfg.use_tp:
        qkv = column_parallel_fc(x, 3 * D, name=prefix + '_qkv')
    else:
        qkv = L.fc(input=x, size=3 * D, num_flatten_dims=2,
                   name=prefix + '_qkv')

    def heads(sl_start, sl_end):
        part = L.slice(qkv, axes=[2], starts=[sl_start], ends=[sl_end])
        part = L.reshape(part, shape=[-1, T, H, dh])
        part = L.transpose(part, perm=[0, 2, 1, 3])        # [B, H, T, dh]
        if cfg.use_tp:
            # under ring attention keep T sharded over sp: replicating
            # it here would gather full-length Q/K/V per device, undoing
            # the ring's O(T/n) memory
            t_ax = 'sp' if (cfg.ring_attention and cfg.use_sp) else None
            part = sharding_constraint(part, ('dp', 'tp', t_ax, None))
        return part

    q, k, v = heads(0, D), heads(D, 2 * D), heads(2 * D, 3 * D)
    if cfg.ring_attention:
        from ..parallel.layers import ring_attention
        ctx = ring_attention(q, k, v, causal=True)         # [B, H, T, dh]
    elif cfg.flash_attention:
        # Pallas blockwise kernel — no [T, T] score tensor; the
        # long-context enabler (see pallas/flash_attention.py)
        ctx = L.flash_attention(q, k, v, causal=True)      # [B, H, T, dh]
    else:
        scores = L.matmul(q, k, transpose_y=True, alpha=1.0 / np.sqrt(dh))
        causal = L.causal_mask_bias(scores)                # [B, H, T, T]
        probs = L.softmax(causal)
        ctx = L.matmul(probs, v)                           # [B, H, T, dh]
    ctx = L.transpose(ctx, perm=[0, 2, 1, 3])
    ctx = L.reshape(ctx, shape=[-1, T, D])
    if cfg.use_tp:
        ctx = sharding_constraint(ctx, ('dp', None, 'tp'))
        out = row_parallel_fc(ctx, D, name=prefix + '_proj')
    else:
        out = L.fc(input=ctx, size=D, num_flatten_dims=2,
                   name=prefix + '_proj')
    return out


def _ffn(x, cfg, prefix):
    if cfg.moe_experts:
        return moe_layer(x, cfg.moe_experts, cfg.ffn)
    if cfg.use_tp:
        h = column_parallel_fc(x, cfg.ffn, act='gelu', name=prefix + '_up')
        return row_parallel_fc(h, cfg.dim, name=prefix + '_down')
    h = L.fc(input=x, size=cfg.ffn, act='gelu', num_flatten_dims=2,
             name=prefix + '_up')
    return L.fc(input=h, size=cfg.dim, num_flatten_dims=2,
                name=prefix + '_down')


def _block(x, cfg, i):
    prefix = 'layer%d' % i
    ln1 = L.layer_norm(x, begin_norm_axis=2)
    if cfg.use_sp:
        ln1 = sequence_parallel_scope(ln1)
    attn = _attention(ln1, cfg, prefix)
    x = L.elementwise_add(x, attn)
    ln2 = L.layer_norm(x, begin_norm_axis=2)
    if cfg.use_sp:
        ln2 = sequence_parallel_scope(ln2)
    ffn = _ffn(ln2, cfg, prefix)
    return L.elementwise_add(x, ffn)



def _blocks(x, cfg):
    """All transformer blocks; with cfg.pp_stages set, layers are grouped
    into uniform pipeline stages via pipeline_stage_guard (consumed by
    the pp lowering under DistributedStrategy(pp=...))."""
    if cfg.pp_stages:
        if cfg.layers % cfg.pp_stages:
            raise ValueError('layers %d not divisible by pp_stages %d'
                             % (cfg.layers, cfg.pp_stages))
        for i in range(cfg.layers):
            with pipeline_stage_guard(i * cfg.pp_stages // cfg.layers):
                x = _block(x, cfg, i)
        return x
    for i in range(cfg.layers):
        if cfg.remat:
            policy = 'dots' if cfg.remat == 'dots' else 'nothing'
            x = L.recompute(lambda h, i=i: _block(h, cfg, i), x,
                            policy=policy)
        else:
            x = _block(x, cfg, i)
    return x


def _trunk(tokens, cfg):
    """Shared embed + position + blocks + final norm."""
    if cfg.use_tp:
        emb = vocab_parallel_embedding(tokens, [cfg.vocab, cfg.dim])
    else:
        emb = L.embedding(tokens, size=[cfg.vocab, cfg.dim])
    pos = L.position_embedding(emb, cfg.max_len)
    x = L.elementwise_add(emb, pos)
    x = _blocks(x, cfg)
    return L.layer_norm(x, begin_norm_axis=2)


def language_model_trunk(tokens, cfg):
    """Public trunk (embed + position + blocks + final norm) WITHOUT a
    head — pair with layers.fused_softmax_cross_entropy for the
    logits-free LM loss (the bench path), or project manually."""
    return _trunk(tokens, cfg)


def language_model(tokens, cfg):
    """tokens: [B, T, 1] int64 ids (no lod: fixed T). Returns softmax
    probabilities [B, T, vocab]."""
    return L.fc(input=_trunk(tokens, cfg), size=cfg.vocab,
                num_flatten_dims=2, act='softmax')


def language_model_logits(tokens, cfg):
    """Like language_model but returns raw logits [B, T, vocab] — pair
    with softmax_with_cross_entropy so XLA fuses the softmax into the
    loss (the MXU-dense benchmark path)."""
    return L.fc(input=_trunk(tokens, cfg), size=cfg.vocab,
                num_flatten_dims=2, name='lm_head')


def train_network(tokens, labels, cfg):
    """Full LM training graph: next-token cross entropy."""
    probs = language_model(tokens, cfg)
    cost = L.cross_entropy(input=probs, label=labels)
    avg_cost = L.mean(cost)
    return probs, avg_cost


# ---------------------------------------------------------------------------
# Cached-attention mode (paddle_tpu/serving/): the spec the builders bind
# ---------------------------------------------------------------------------
#
# A loaded language-model program is transpiled
# (transpiler/decode_transpiler.py) into a DecodeSpec — the discovered
# dims plus the exact parameter NAMES of the source program — and the
# paged builders further down emit fresh programs that bind those
# names, so all run against the Predictor's existing weight Scope
# without copying a byte. Their attention reuses the SAME ops as the
# full path (mul, matmul+alpha, set-to--1e9 mask, fp32 softmax) over
# same-length reduction axes, which is what keeps greedy decode equal
# to full-prefix recompute (tests/test_serving.py, tests/test_paged.py).

class DecodeTranspileError(ValueError):
    """The loaded program is not a transpilable decoder-only LM, or what
    is asked of it cannot serve its layer kinds."""


def refuse_recurrent(spec, what):
    """Raise for a spec with layers that hold recurrent state (of
    whatever kind: spec.recurrent_layers) where `what` knows a stream's
    state as pages only."""
    if spec.recurrent_layers:
        kinds = sorted({spec.kinds[i] for i in spec.recurrent_layers})
        raise DecodeTranspileError(
            '%s cannot serve a model with %s layers (layers %s): their '
            'recurrent state is not in the page pool'
            % (what, ' and '.join(kinds),
               ','.join(map(str, spec.recurrent_layers))))


def refuse_latent_pages(spec, what):
    """Raise for a spec whose pages hold one latent row a token
    (spec.page_kind 'latent': models/axk1.py) where `what` reads a page
    as K and V heads."""
    if spec.page_kind == 'latent':
        raise DecodeTranspileError(
            '%s cannot serve a model with latent_attention layers: a '
            'page of theirs holds one latent row a token, not K and V '
            'heads' % (what,))


def refuse_window(spec, what):
    """Raise for a spec with sliding_attention layers (spec.window_layers)
    where `what` knows a stream's pages as one table over one pool."""
    if spec.window_layers:
        raise DecodeTranspileError(
            '%s cannot serve a model with sliding_attention layers (layers '
            '%s): their pages are a second table over a pool of their own'
            % (what, ','.join(map(str, spec.window_layers))))


def refuse_page_state(spec, what):
    """Raise for a spec whose recurrent layers keep their rows by the
    page (spec.page_state_layers: models/lfm2.py) where `what` knows a
    page as the K/V pools' alone."""
    if spec.page_state_layers:
        kinds = sorted({spec.kinds[i] for i in spec.page_state_layers})
        raise DecodeTranspileError(
            '%s cannot serve a model with %s layers (layers %s): their '
            'rows lie in a pool of their own beside the K/V pools, a page '
            'in each' % (what, ' and '.join(kinds),
                         ','.join(map(str, spec.page_state_layers))))


def refuse_blocks(spec, what):
    """Raise for a spec that generates by diffusion over blocks
    (spec.block_tokens: models/sdar_moe.py) where `what` steps a lane
    one token at a time or moves its pages by the token."""
    if spec.block_tokens:
        raise DecodeTranspileError(
            '%s cannot serve a model that generates by diffusion over '
            'blocks of %d tokens (family %s): its step is %d rows a lane '
            'at the same positions pass after pass, and only a commit '
            'pass\'s rows count'
            % (what, spec.block_tokens,
               type(spec).__module__.rsplit('.', 1)[-1], spec.block_tokens))


class DecodeSpec(object):
    """Dims + parameter names extracted from a loaded LM program.

    blocks[i] is a dict with keys ln1/ln2 -> (scale_name, bias_name),
    qkv/proj/up/down -> (w_name, b_name); final_ln is (scale, bias);
    head is (w_name, b_name_or_None). pos_len is the positional TABLE
    length (>= max_len, the sequence length programs are built for).

    param_specs maps weight name -> recovered training PartitionSpec in
    tuple form (None = replicated); the transpiler fills it from
    dist_attr / surviving sharding_constraint ops so mesh serving can
    re-shard the same scope. mesh is the serving mesh spec string
    ('tp=2'; '' = single-chip), stamped by prepare_decoding.

    kinds names each layer's mixer: 'full_attention' (K/V in the cache:
    every layer of this block), or a kind of a spec that extends this
    one (models/hybrid.py, models/nemotron_h.py, models/axk1.py). What
    a layer keeps for a stream decides how it is served, not what it is
    called: K/V pages (kv_layers: the cache variables exist for these
    only; page_kind 'kv'), a latent page (kv_layers too, ONE pool a
    layer of one row a token for all heads; page_kind 'latent'),
    per-slot recurrent state beside the pools (recurrent_layers: the
    kinds the spec's class names in `recurrent_kinds`), or nothing (an
    expert layer). Pages of either kind are what the allocator, the
    prefix cache, save_pages and page shipping move, sized by
    pool_shape; only a program that reads a page as K and V heads
    (speculative verify, the heads-sharded mesh layout) has to refuse
    the latent kind (refuse_latent_pages).

    A second page-holding kind, 'sliding_attention': K/V pages like
    'full_attention', of which a row reads the last `window` tokens
    only (the spec's class or instance gives `window`). Such layers are
    kv_layers too and, apart, window_layers (full_layers: the others):
    their pools are sized apart (the builders' window_pages), a stream
    finds their pages through a second table (PagedStep.window_table)
    that gives up the pages behind the window as it advances
    (serving/paging.py), and what reads a stream's pages as one table
    refuses them (refuse_window). A spec with no such layer has none of
    this: one table, one pool size, the feeds it always had.

    kv_heads is the number of K/V heads a page holds (query head h
    reads K/V head h // (heads / kv_heads)); the model's head count
    where it is not given.

    A layer whose recurrent state is small enough to lie WITH the page
    (page_state_layers: the kinds the spec's class names in
    `page_state_kinds`; models/lfm2.py, whose short convolutions keep
    K-1 rows) is neither of the above: it has one more pool,
    [num_pages, ...] (page_state_names, page_state_shape), indexed by
    the stream's one page table, whose entry for a page holds the state
    at that page's fill point. Such pools are allocated, forked, saved,
    restored and evicted with the K/V page of the same number, so a
    stream's state is still pages, and nothing per-slot exists.

    head_pack (1: none) is how many K/V heads share one lane row of the
    pool, for heads narrower than a lane row (two heads of 64:
    pool_shape's last two axes are then [kv_heads / 2, 128], the same
    bytes in the same order as [kv_heads, 64], which the TPU would lay
    out in rows of 128 lanes half empty).

    block_tokens (0: none) is the block length of a model that
    generates by diffusion over blocks (models/sdar_moe.py): its
    attention is causal over blocks and bidirectional inside one, its
    prefill runs a prompt's whole blocks, and the pair's second program
    is a block step (build_paged_block_program) in the place of the
    one-token decode step. What steps a lane a token at a time refuses
    such a spec (refuse_blocks).
    """

    recurrent_kinds = ()
    page_state_kinds = ()   # kinds whose recurrent rows lie by the page
    head_pack = 1           # K/V heads that share a lane row of the pool
    expert_layers = ()      # the layers whose rows an expert op counts
    state_family = None     # names the gauge serving.<family>.state_bytes
    page_kind = 'kv'        # what a page of kv_layers holds
    page_kinds = ('full_attention', 'sliding_attention')
    window = 0              # tokens a row of a sliding_attention layer sees
    block_tokens = 0        # rows of a block step (0: one token a step)

    def __init__(self, vocab, dim, heads, layers, ffn, max_len, pos_len,
                 emb_w, pos_w, blocks, final_ln, head, use_flash=False,
                 param_specs=None, mesh='', kinds=None, kv_heads=None,
                 head_dim=None):
        self.vocab, self.dim, self.heads = vocab, dim, heads
        self.kv_heads = int(kv_heads or heads)
        if heads % self.kv_heads:
            raise ValueError('%d query heads over %d K/V heads'
                             % (heads, self.kv_heads))
        self.layers, self.ffn = layers, ffn
        self.kinds = tuple(kinds or ('full_attention',) * layers)
        self.kv_layers = [i for i, k in enumerate(self.kinds)
                          if k in self.page_kinds]
        self.window_layers = [i for i, k in enumerate(self.kinds)
                              if k == 'sliding_attention']
        self.recurrent_layers = [i for i, k in enumerate(self.kinds)
                                 if k in self.recurrent_kinds]
        self.page_state_layers = [i for i, k in enumerate(self.kinds)
                                  if k in self.page_state_kinds]
        self.max_len, self.pos_len = max_len, pos_len
        self.dh = int(head_dim or dim // heads)
        self.emb_w, self.pos_w = emb_w, pos_w
        self.blocks = blocks
        self.final_ln = final_ln
        self.head = head
        self.use_flash = use_flash
        self.param_specs = dict(param_specs or {})
        self.mesh = mesh

    def pool_names(self, layer=None):
        """Paged K/V pool var names; shared by the paged pair."""
        if layer is not None:
            return ('kv_pool.layer%d.k' % layer,
                    'kv_pool.layer%d.v' % layer)
        out = []
        for i in self.kv_layers:
            out.extend(self.pool_names(i))
        return out

    def state_names(self):
        """Recurrent-state var names (none: every layer here keeps K/V)."""
        return []

    @property
    def sm_scale(self):
        """What the paged attention multiplies its scores by:
        1/sqrt(head_dim), unless the block states its own
        (models/granite_h.py: attention_multiplier)."""
        return 1.0 / np.sqrt(self.dh)

    @property
    def pool_heads(self):
        """Heads a page holds: the model's K/V heads, except where a
        spec pads them to whole tiles (models/hybrid.py)."""
        return self.kv_heads

    def pool_shape(self, num_pages, page_tokens):
        return (num_pages, page_tokens, self.pool_heads // self.head_pack,
                self.dh * self.head_pack)

    def page_state_names(self, layer=None):
        """Pool var names of the layers that keep their recurrent rows
        by the page (one pool a layer); shared by the paged pair."""
        if layer is not None:
            return ('page_state.layer%d' % layer,)
        return [n for i in self.page_state_layers
                for n in self.page_state_names(i)]

    @property
    def full_layers(self):
        """The page-holding layers that keep a stream's every page."""
        return [i for i in self.kv_layers if i not in self.window_layers]

    def window_table_pages(self, chunk, page_tokens):
        """The width of a stream's table of its sliding layers: the
        pages that window - 1 tokens behind a chunk's first row and the
        chunk itself can lie on (a decode step needs fewer); 0 without
        such layers."""
        if not self.window_layers:
            return 0
        if self.window < 1:
            raise ValueError('sliding_attention layers with window %r'
                             % (self.window,))
        return min(-(-(self.window - 1 + chunk) // page_tokens) + 1,
                   -(-self.max_len // page_tokens))

    def build_paged_programs(self, slots, chunk, num_pages, page_tokens,
                             pages_per_slot, **window):
        """The paged pair that serves this spec's block, as (prefill
        program, feeds, fetches, decode program, feeds, fetches).
        `window`: the builders' window_pages and window_pages_per_slot,
        for a spec with sliding layers."""
        step = build_paged_block_program if self.block_tokens \
            else build_paged_decode_program
        return build_paged_prefill_program(
            self, slots, chunk, num_pages, page_tokens, pages_per_slot,
            **window) + step(self, slots, num_pages, page_tokens,
                             pages_per_slot, **window)

    def paged_logits(self, tokens, at):
        """This block's walk over one paged program (`at`: PagedStep):
        logits of the chunk's last live row [1, vocab], or of every
        lane's one row [slots, 1, vocab]. What a block file overrides
        with its own walk."""
        emb = L.embedding(tokens, size=[self.vocab, self.dim],
                          param_attr=_named_attr(self.emb_w))
        pos = _paged_pos_embedding(self, at.positions)         # [rows, 1, D]
        if not at.decode:
            pos = L.reshape(pos, shape=[-1, at.rows, self.dim])    # [1, C, D]
        x = L.elementwise_add(emb, pos)
        for i in range(self.layers):
            x = _cached_block(
                x, self, i, lambda ln, sp, blk, _i=i: _paged_attention(
                    ln, sp, blk, _i, at))
        return _logits_head(_named_ln(x, self.final_ln), self, at)

    def pool_spec(self):
        """PartitionSpec (tuple form) for the page pools
        [pages, pt, H, dh]: heads axis sharded over tp. Flash-attention
        specs serve replicated: the Pallas kernel is opaque to GSPMD."""
        return (None, None, _tp_ax(self), None)

    def serve_param_specs(self):
        """param_specs filtered to the shardings that keep greedy
        decode BIT-EXACT vs single-chip: only column-style layouts
        (last dim sharded, contraction dim whole) qualify — every
        output element is then fully reduced on one device in the same
        order as the single-chip dot, and the gathers GSPMD inserts
        are pure data movement. Row-parallel weights (dim-0 sharded)
        would shard the contraction -> a psum with a different
        reduction order -> dropped here, i.e. served replicated."""
        out = {}
        for name, spec in self.param_specs.items():
            if not spec or len(spec) < 2:
                continue
            if spec[-1] is not None and \
                    all(s is None for s in spec[:-1]):
                out[name] = tuple(spec)
        return out

    def param_names(self):
        names = [self.emb_w, self.pos_w,
                 self.final_ln[0], self.final_ln[1], self.head[0]]
        if self.head[1]:
            names.append(self.head[1])
        for blk in self.blocks:
            for key in ('ln1', 'ln2', 'qkv', 'proj', 'up', 'down'):
                names.extend(n for n in blk[key] if n)
        return names


def _tp_ax(spec):
    """The model axis the cached programs shard on — None (replicated)
    for flash specs, whose Pallas kernel GSPMD cannot partition."""
    return None if spec.use_flash else 'tp'


def _named_attr(name):
    from ..param_attr import ParamAttr
    return ParamAttr(name=name) if name else False


def _named_fc(x, size, pair, act=None, num_flatten_dims=2):
    return L.fc(input=x, size=size, num_flatten_dims=num_flatten_dims,
                param_attr=_named_attr(pair[0]),
                bias_attr=_named_attr(pair[1]), act=act)


def _named_ln(x, pair):
    return L.layer_norm(x, begin_norm_axis=2,
                        param_attr=_named_attr(pair[0]),
                        bias_attr=_named_attr(pair[1]))


def _block_op(op_type, inputs, outputs, attrs=None):
    from ..framework import default_main_program
    default_main_program().current_block().append_op(
        type=op_type, inputs=inputs, outputs=outputs, attrs=attrs or {})


def _tmp_var(dtype='float32'):
    from ..framework import default_main_program
    from .. import unique_name
    return default_main_program().current_block().create_var(
        name=unique_name.generate('kv_decode.tmp'), dtype=dtype)


def _qkv_parts(x, spec, blk, t, qk_norm=None, rotary=None, head_norm=None):
    """qkv fc + per-part slice/reshape to [-1, t, H, dh] — the full
    path's heads() up to (not including) the transpose, which is the
    cache's storage layout. On a mesh each part is pinned heads-sharded
    (the cache/pool layout), a no-op single-chip; the qkv contraction
    dim stays whole either way, so every element is bit-exact.
    `qk_norm(part, 'q' | 'k')`, where the caller's block norms q and k
    whole before the heads are split; `head_norm(part, 'q' | 'k')`,
    where it norms them a head at a time, behind the split and in front
    of the rotation (models/sdar_moe.py). `rotary(part)`, where the block's
    attention takes a rotary term: q and k [-1, t, heads, dh] rotated
    by their rows' positions BEFORE the cache sees a key, so that a
    page holds rotated keys (as the latent page does: models/axk1.py)."""
    D, KV = spec.heads * spec.dh, spec.kv_heads * spec.dh
    qkv = _named_fc(x, D + 2 * KV, blk['qkv'])

    def part(s, e, heads, which=None):
        p = L.slice(qkv, axes=[2], starts=[s], ends=[e])
        if qk_norm is not None and which:
            p = qk_norm(p, which)
        p = L.reshape(p, shape=[-1, t, heads, spec.dh])
        if head_norm is not None and which:
            p = head_norm(p, which)
        if rotary is not None and which:
            p = rotary(p)
        return sharding_constraint(p, (None, None, _tp_ax(spec), None))

    return (part(0, D, spec.heads, 'q'),
            part(D, D + KV, spec.kv_heads, 'k'),
            part(D + KV, D + 2 * KV, spec.kv_heads))


def _cached_block(x, spec, i, attention):
    blk = spec.blocks[i]
    attn = attention(_named_ln(x, blk['ln1']), spec, blk)
    x = L.elementwise_add(x, attn)
    ffn = _named_fc(_named_ln(x, blk['ln2']), spec.ffn, blk['up'],
                    act='gelu')
    # a column-sharded up weight leaves the gelu output ffn-sharded;
    # gather it whole BEFORE the down contraction so the down dot
    # reduces in single-chip order (bit-exactness) instead of a psum
    ffn = sharding_constraint(ffn, (None, None, None))
    ffn = _named_fc(ffn, spec.dim, blk['down'])
    return L.elementwise_add(x, ffn)


# ---------------------------------------------------------------------------
# Paged-cache mode (paddle_tpu/serving/paged.py): page-table builders
# ---------------------------------------------------------------------------
#
# A vLLM-style page pool: one
# [num_pages, page_tokens, H, dk] pool var per layer per K/V, and a
# per-slot page TABLE fed each step mapping logical position j to
# pool[table[j // pt], j % pt]. Every program stays static-shape (pool
# size, table width, chunk width fixed at build time), so each compiles
# exactly once; allocation, COW and prefix sharing are HOST decisions
# (serving/paging.py) that only ever change feed VALUES (and, for a
# decode step that forks a page, put the page copy program in front of
# the step's own: build_page_copy_program). Physical page
# 0 is the reserved null page — dead rows write there, reads of it are
# always masked. Validity is absolute (j <= position): nothing wraps,
# so running out of pages is a typed host-side error, never a silent
# slide (COVERAGE divergence 8).
#
# Who reads the pool how. The DECODE program, which runs every step
# over every slot, holds one paged_attention op a layer: it reads each
# lane's live pages in place (a Pallas kernel on a TPU; off it, the
# gather composition below as the op's reference lowering). The
# PREFILL and VERIFY programs still gather: kv_page_gather copies the
# table's whole window into a dense [B, P*pt, H, dk] tensor and matmul,
# a paged mask, softmax and matmul run over all of it. A prefill chunk
# gathers one slot's window, a sixteenth of what the decode step
# copied; verify gathers every slot's. The BLOCK program (a model that
# generates by diffusion over blocks: build_paged_block_program, in the
# decode program's place) holds one paged_block_attention op a layer:
# the same kernel, a lane's B rows riding as further query heads.


class PagedStep(object):
    """What a paged program hands its block walk (spec.paged_logits):
    where THIS program's sublayers find K/V pages, recurrent state and
    the rows that count. A prefill chunk (`decode` False) is `rows` rows
    of ONE stream: table [1, P], positions [rows], length [1] (the rows
    from it on are padding), last [1] (the row whose logits are
    wanted), cow (src, dst: the page to copy before the write) and,
    for a model with recurrent state, slot [1] and reset [1]. A decode
    step (`decode` True, `rows` 1) is one row of EVERY lane: table
    [slots, P], positions [slots] (the step index) and, for a model with
    recurrent state or expert layers, live [slots]. A block step
    (`decode` True, `rows` B: a model that generates by diffusion over
    blocks) is B rows of every lane: positions [slots, B] (the block's),
    ends [slots] (the position of each lane's last block row: every row
    of the block sees the lane's pages up to it), live [slots], and
    block_ids [slots, B] and transfer [slots] for the unmasking behind
    the head. pools, states and page_states (the pools of the layers
    that keep their recurrent rows by the page, found through `table`
    like the K/V pools; page_tokens beside them) are {layer: its
    variables};
    stats collects what the expert layers counted. For a model with
    sliding layers, the second table: window_table ([1, W] or
    [slots, W]), window_positions (the same rows' positions counted
    from that table's first row: what its pages are addressed by; a
    rotary term takes `positions`) and, in a chunk, window_cow.
    pages_of(spec, layer) is the triple a layer finds its pages
    through. A sublayer asks this value, never the program's name."""

    length = last = cow = slot = reset = live = None
    ends = block_ids = transfer = None
    window_table = window_positions = window_cow = None
    page_tokens = 0

    def __init__(self, decode, rows):
        self.decode, self.rows = decode, rows
        self.stats = []

    def pages_of(self, spec, layer):
        """(table, positions, cow, window) of `layer`'s pages."""
        if layer in spec.window_layers:
            return (self.window_table, self.window_positions,
                    self.window_cow, spec.window)
        return self.table, self.positions, self.cow, 0


def _state_io(at, layer, which):
    """(inputs, outputs) that make a stateful op read and write
    `layer`'s state variable `which` in place; nothing for the
    whole-sequence form (at None), which starts every sequence from
    zero state and keeps none."""
    if at is None:
        return {}, {}
    var = at.states[layer][which]
    where = {'Live': [at.live]} if at.decode else \
        {'Slot': [at.slot], 'Len': [at.length], 'Reset': [at.reset]}
    return dict(where, State=[var]), {'StateOut': [var]}


def _page_state_io(at, layer):
    """(inputs, outputs, attrs) that make a stateful op read and write
    `layer`'s rows BY THE PAGE, through the stream's page table (a
    chunk's, or every lane's); nothing for the whole-sequence form. A
    chunk copies the page it forks in this pool too, in front of the op
    (the decode step's forks are the page copy program's)."""
    if at is None:
        return {}, {}, {}
    pool = at.page_states[layer]
    if not at.decode:
        _block_op('kv_page_cow',
                  inputs={'Pool': [pool], 'Src': [at.cow[0]],
                          'Dst': [at.cow[1]]},
                  outputs={'Out': [pool]})
    where = {'Live': [at.live]} if at.decode else {'Len': [at.length]}
    return (dict(where, Pool=[pool], Table=[at.table],
                 Positions=[at.positions]),
            {'PoolOut': [pool]}, {'page_tokens': int(at.page_tokens)})


def _expert_io(at):
    """(inputs, outputs) that make an expert op pass over the dead rows
    and count the others; nothing for the whole-sequence form."""
    if at is None:
        return {}, {}
    at.stats.append(_tmp_var('int32'))
    rows = {'Live': [at.live]} if at.decode else {'Len': [at.length]}
    return rows, {'Stats': [at.stats[-1]]}


def _persistable(name, shape):
    from ..framework import default_main_program
    return default_main_program().global_block().create_var(
        name=name, shape=shape, dtype='float32', persistable=True,
        stop_gradient=True, is_cache=True)


def _create_pool_vars(spec, num_pages, page_tokens, window_pages=0):
    """{layer: its page-pool vars} ((K, V), or the one pool of a layer
    that keeps a latent page) of the layers that keep pages:
    persistable (the executor writes them back to the Scope each run —
    and donates them, so the update is in-place on device) but is_cache
    (io.py save/load skip them). A sliding layer's pools hold
    `window_pages` pages."""
    sliding = getattr(spec, 'window_layers', ())
    return {i: tuple(_persistable(n, spec.pool_shape(
        window_pages if i in sliding else num_pages, page_tokens))
        for n in spec.pool_names(i)) for i in spec.kv_layers}


def _create_page_state_vars(spec, num_pages):
    """{layer: its page-state pool var} of the layers that keep their
    recurrent rows by the page: persistable, donated and never
    checkpointed, like the K/V pools."""
    return {i: _persistable(spec.page_state_names(i)[0],
                            spec.page_state_shape(num_pages))
            for i in getattr(spec, 'page_state_layers', ())}


def _create_state_vars(spec, slots):
    """{layer: (the recurrence's state, convolution rows)} of the
    recurrent layers: persistable, donated and updated in place like
    the page pools, and never checkpointed."""
    return {i: tuple(_persistable(n, shape) for n, shape in
                     zip(spec.state_names(i), spec.state_shapes(slots)))
            for i in spec.recurrent_layers}


def _pool_heads(x, spec):
    """x [B, t, H, dh] with zero heads appended up to spec.pool_heads
    (nothing where the pool holds the model's K/V heads as they are:
    padding is for pools with a K/V head a query head)."""
    extra = spec.pool_heads - spec.kv_heads
    if not extra:
        return x
    return L.pad(x, paddings=[0, 0, 0, 0, 0, extra, 0, 0])


def _pool_rows(x, spec, t):
    """K or V [B, t, pool_heads, dh] as the pool holds a token's row:
    as it is, or `head_pack` heads side by side in one lane row."""
    if spec.head_pack == 1:
        return x
    return L.reshape(x, shape=[-1, t, spec.pool_heads // spec.head_pack,
                               spec.dh * spec.head_pack])


def _model_heads(ctx, spec, t):
    """ctx [B, t, heads or pool_heads, dh] -> [B, t, heads * dh]: the
    model's heads."""
    if spec.pool_heads != spec.kv_heads:
        ctx = L.slice(ctx, axes=[2], starts=[0], ends=[spec.heads])
    return L.reshape(ctx, shape=[-1, t, spec.heads * spec.dh])


def _paged_gather(pool_var, table, spec):
    g = _tmp_var()
    _block_op('kv_page_gather',
              inputs={'Pool': [pool_var], 'Table': [table]},
              outputs={'Out': [g]})                    # [B, J, H, dh]
    if spec.head_pack > 1:      # the packed rows as heads again
        g = L.reshape(g, shape=[-1, int(table.shape[1])
                                * int(pool_var.shape[1]),
                                spec.pool_heads, spec.dh])
    return sharding_constraint(L.transpose(g, perm=[0, 2, 1, 3]),
                               (None, _tp_ax(spec), None, None))


def _paged_attention(x, spec, blk, i, at, qk_norm=None, rotary=None,
                     out_gate=None, head_norm=None):
    """Layer i's attention over its K/V pages, in the form `at`'s
    program takes: a prefill chunk's, a decode step's or a block
    step's; through the table of the layer's kind (at.pages_of).
    `out_gate` [B, rows, heads * dh] multiplies the heads' outputs in
    front of the output projection, where the block gates them;
    `head_norm` is _qkv_parts's."""
    if not at.decode:
        form = _paged_prefill_attention
    elif at.rows > 1:
        form = _paged_block_attention
    else:
        form = _paged_decode_attention
    return form(x, spec, blk, at.pools[i], at, at.pages_of(spec, i), qk_norm,
                rotary, out_gate, head_norm)


def _gated_proj(ctx, spec, blk, out_gate):
    if out_gate is not None:
        ctx = L.elementwise_mul(ctx, out_gate)
    return _named_fc(ctx, spec.dim, blk['proj'])


def _paged_prefill_attention(x, spec, blk, pool, at, pages, qk_norm=None,
                             rotary=None, out_gate=None, head_norm=None):
    """One chunk of prefill attention: COW any forked page, scatter the
    chunk's K/V rows through the table, then attend the chunk's queries
    over the WHOLE gathered history (earlier pages + this chunk): the
    whole table of the layer's kind, so a sliding layer gathers its own
    table's width and masks a band. A model that generates by diffusion
    over blocks masks by block (spec.block_tokens: a row sees its whole
    block; its chunks hold whole blocks only)."""
    length, chunk = at.length, at.rows
    table, positions, (cow_src, cow_dst), band = pages
    q4, k4, v4 = (_pool_heads(a, spec)
                  for a in _qkv_parts(x, spec, blk, chunk, qk_norm,
                                      rotary, head_norm))   # [1, C, H, dh]
    for pool_var, new in ((pool[0], _pool_rows(k4, spec, chunk)),
                          (pool[1], _pool_rows(v4, spec, chunk))):
        _block_op('kv_page_cow',
                  inputs={'Pool': [pool_var], 'Src': [cow_src],
                          'Dst': [cow_dst]},
                  outputs={'Out': [pool_var]})
        _block_op('kv_page_write',
                  inputs={'Pool': [pool_var], 'X': [new],
                          'Table': [table], 'Positions': [positions],
                          'Len': [length]},
                  outputs={'Out': [pool_var]})
    q = sharding_constraint(L.transpose(q4, perm=[0, 2, 1, 3]),
                            (None, _tp_ax(spec), None, None))
    kt = _paged_gather(pool[0], table, spec)           # [1, KVH, J, dh]
    vt = _paged_gather(pool[1], table, spec)
    # the query heads of one K/V head are rows of one product with its
    # gathered pages: [1, KVH, rep * C, dh] (nothing to do where every
    # query head has a K/V head of its own)
    rep = spec.heads // spec.kv_heads
    window = int(table.shape[1]) * int(pool[0].shape[1])       # J
    grouped = [-1, spec.kv_heads, rep * chunk, spec.dh]
    if rep > 1:
        q = L.reshape(q, shape=grouped)
    scores = L.matmul(q, kt, transpose_y=True,
                      alpha=spec.sm_scale)
    if rep > 1:
        scores = L.reshape(scores, shape=[-1, spec.heads, chunk, window])
    masked = _tmp_var()                                # [1, H, C, J]
    attrs = {'window': int(band)} if band else {}
    if spec.block_tokens:
        attrs['block'] = int(spec.block_tokens)
    _block_op('paged_prefill_mask',
              inputs={'X': [scores], 'Positions': [positions]},
              outputs={'Out': [masked]}, attrs=attrs or None)
    probs = L.softmax(masked)
    if rep > 1:
        probs = L.reshape(probs, shape=grouped[:3] + [window])
    ctx = L.matmul(probs, vt)                          # [1, H, C, dh]
    if rep > 1:
        ctx = L.reshape(ctx, shape=[-1, spec.heads, chunk, spec.dh])
    ctx = _model_heads(L.transpose(ctx, perm=[0, 2, 1, 3]), spec, chunk)
    ctx = sharding_constraint(ctx, (None, None, None))
    return _gated_proj(ctx, spec, blk, out_gate)


def _paged_decode_attention(x, spec, blk, pool, at, pages, qk_norm=None,
                            rotary=None, out_gate=None, head_norm=None):
    """One decode step's attention: append the new K/V row, then ONE
    paged_attention op that reads each lane's live pages through its
    table (no gathered window; see the op's docstring for its two
    lowerings), the last `window` positions' for a sliding layer. No
    copy-on-write here: a page that forks in a decode step was copied
    before the step's program was dispatched
    (build_page_copy_program)."""
    table, positions, _, band = pages
    q1, k1, v1 = (_pool_heads(a, spec)
                  for a in _qkv_parts(x, spec, blk, 1, qk_norm, rotary,
                                      head_norm))  # [S, 1, H | KVH, dh]
    for pool_var, new in ((pool[0], _pool_rows(k1, spec, 1)),
                          (pool[1], _pool_rows(v1, spec, 1))):
        _block_op('kv_page_append',
                  inputs={'Pool': [pool_var], 'X': [new],
                          'Table': [table], 'Positions': [positions]},
                  outputs={'Out': [pool_var]})
    ctx = _tmp_var()
    _block_op('paged_window_attention' if band else 'paged_attention',
              inputs={'Q': [q1], 'KPool': [pool[0]], 'VPool': [pool[1]],
                      'Table': [table], 'Positions': [positions]},
              outputs={'Out': [ctx]},
              attrs=dict({'sm_scale': float(spec.sm_scale),
                          'head_axis': _tp_ax(spec) or ''},
                         **({'window': int(band)} if band else {})))
    #                                                     [S, 1, H, dh]
    ctx = _model_heads(ctx, spec, 1)
    ctx = sharding_constraint(ctx, (None, None, None))
    return _gated_proj(ctx, spec, blk, out_gate)


def _paged_block_attention(x, spec, blk, pool, at, pages, qk_norm=None,
                           rotary=None, out_gate=None, head_norm=None):
    """One block step's attention: write the block's `at.rows` K/V rows
    of every lane through its table at the block's positions (one
    kv_page_append with 2-D positions: a later pass over the same block
    overwrites them, and whether they ever count is the host's
    bookkeeping), then ONE paged_block_attention op in which every row
    of a lane reads the lane's live pages up to the END of its block
    (at.ends): causal over blocks, bidirectional inside one. As in a
    decode step, a page that forks was copied before the program was
    dispatched."""
    table, positions, _, band = pages
    if band:
        raise DecodeTranspileError('a block step over sliding_attention '
                                   'layers')
    qb, kb, vb = (_pool_heads(a, spec)
                  for a in _qkv_parts(x, spec, blk, at.rows, qk_norm, rotary,
                                      head_norm))  # [S, B, H | KVH, dh]
    for pool_var, new in ((pool[0], kb), (pool[1], vb)):
        _block_op('kv_page_append',
                  inputs={'Pool': [pool_var], 'X': [new],
                          'Table': [table], 'Positions': [positions]},
                  outputs={'Out': [pool_var]})
    ctx = _tmp_var()
    _block_op('paged_block_attention',
              inputs={'Q': [qb], 'KPool': [pool[0]], 'VPool': [pool[1]],
                      'Table': [table], 'Positions': [at.ends]},
              outputs={'Out': [ctx]},
              attrs={'sm_scale': float(spec.sm_scale)})  # [S, B, H, dh]
    ctx = _model_heads(ctx, spec, at.rows)
    return _gated_proj(ctx, spec, blk, out_gate)


def _paged_verify_attention(x, spec, blk, pool, table, positions,
                            cow_src, cow_dst, k1):
    """Speculative verify attention: append K1 = k+1 proposed rows per
    slot through its page table in ONE kv_page_append (2-D positions),
    then attend every row over the gathered history with the per-row
    causal spec_verify_mask. Same ops, same reduction lengths as the
    decode step, so each verify row's output is bit-exact with the
    decode step the target would have run at that position."""
    q4, k4, v4 = _qkv_parts(x, spec, blk, k1)          # [S, K1, H, dh]
    for pool_var, new in ((pool[0], k4), (pool[1], v4)):
        _block_op('kv_page_cow',
                  inputs={'Pool': [pool_var], 'Src': [cow_src],
                          'Dst': [cow_dst]},
                  outputs={'Out': [pool_var]})
        _block_op('kv_page_append',
                  inputs={'Pool': [pool_var], 'X': [new],
                          'Table': [table], 'Positions': [positions]},
                  outputs={'Out': [pool_var]})
    q = sharding_constraint(L.transpose(q4, perm=[0, 2, 1, 3]),
                            (None, _tp_ax(spec), None, None))
    kt = _paged_gather(pool[0], table, spec)           # [S, H, J, dh]
    vt = _paged_gather(pool[1], table, spec)
    scores = L.matmul(q, kt, transpose_y=True,
                      alpha=spec.sm_scale)             # [S, H, K1, J]
    masked = _tmp_var()
    _block_op('spec_verify_mask',
              inputs={'X': [scores], 'Positions': [positions]},
              outputs={'Out': [masked]})
    probs = L.softmax(masked)
    ctx = L.matmul(probs, vt)                          # [S, H, K1, dh]
    ctx = L.transpose(ctx, perm=[0, 2, 1, 3])
    ctx = L.reshape(ctx, shape=[-1, k1, spec.dim])
    ctx = sharding_constraint(ctx, (None, None, None))
    return _named_fc(ctx, spec.dim, blk['proj'])


def _paged_pos_embedding(spec, index):
    """Positional rows gathered by absolute index (paged positions
    never wrap): Index [rows] -> [1, rows, D] / [rows, 1, D]. This
    block's only positional term: a learned row added at the embedding.
    (A block whose attention takes a rotary term rotates q and k by the
    same absolute index before the cache sees k: models/axk1.py.)"""
    from ..layer_helper import LayerHelper
    helper = LayerHelper('position_embedding',
                         param_attr=_named_attr(spec.pos_w))
    pos_var = helper.create_parameter(
        attr=helper.param_attr, shape=[spec.pos_len, spec.dim],
        dtype='float32')
    pos = _tmp_var()
    _block_op('position_embedding_at',
              inputs={'Pos': [pos_var], 'Index': [index]},
              outputs={'Out': [pos]})                  # [rows, 1, D]
    return pos


def _logits_head(x, spec, at, head=None):
    """The normed stream -> logits: of the chunk's last live row
    [1, vocab] (gather_time by at.last), or of every lane's one row
    [slots, 1, vocab]. `head(x, flatten)` where the block's head is
    not a named fc (the tied head of models/granite_h.py)."""
    head = head or (lambda h, flatten: _named_fc(
        h, spec.vocab, spec.head, num_flatten_dims=flatten))
    if at is None or at.decode:
        return head(x, 2)
    gathered = _tmp_var()
    _block_op('gather_time', inputs={'X': [x], 'Index': [at.last]},
              outputs={'Out': [gathered]})                     # [1, D]
    return head(gathered, 1)


def _paged_fetches(spec, at, tokens, slots, num_pages, page_tokens,
                   window_pages=0):
    """The half the builders share: the pools and state variables,
    the block's walk, and its fetches: logits [lanes, vocab], greedy
    ids, and the expert layers' counts summed over the layers where
    there are any. A block step's: logits [lanes * rows, vocab], the
    block's ids [lanes, rows] behind its unmasking, the counts, and last
    each lane's rows still masked."""
    at.pools = _create_pool_vars(spec, num_pages, page_tokens, window_pages)
    at.states = _create_state_vars(spec, slots)
    at.page_states = _create_page_state_vars(spec, num_pages)
    at.page_tokens = page_tokens
    logits = spec.paged_logits(tokens, at)
    masked = None
    if at.block_ids is not None:
        # a block step: the ids behind this pass's unmasking and, last
        # of the fetches, each lane's rows still masked
        ids, masked = _tmp_var('int64'), _tmp_var('int32')
        _block_op('block_unmask',
                  inputs={'Logits': [logits], 'Ids': [at.block_ids],
                          'Transfer': [at.transfer], 'Live': [at.live]},
                  outputs={'Out': [ids], 'Masked': [masked]},
                  attrs={'rule': spec.cfg.remasking,
                         'threshold': float(spec.cfg.threshold),
                         'mask_id': int(spec.cfg.mask_id)})
    if at.decode:
        logits = L.reshape(logits, shape=[-1, spec.vocab])
    fetches = [logits, L.argmax(logits, axis=-1) if masked is None else ids]
    if at.stats:
        total = at.stats[0]
        for one in at.stats[1:]:
            total = L.elementwise_add(total, one)
        fetches.append(total)
    if masked is not None:
        fetches.append(masked)
    return fetches


def _feeds():
    """(declare, names): declare(name, shape, dtype) is a feed variable
    and names the list of those declared, in order."""
    names = []

    def declare(name, shape, dtype='int32'):
        names.append(name)
        return L.data(name, shape, append_batch_size=False, dtype=dtype)
    return declare, names


def build_paged_prefill_program(spec, slots, chunk, num_pages, page_tokens,
                                pages_per_slot, window_pages=0,
                                window_pages_per_slot=0):
    """One prefill CHUNK through one stream's page table, for whatever
    block `spec` is of (its walk: spec.paged_logits).

    Feeds:  prefill_tokens [1, C, 1] int64 (chunk tokens, zero-padded),
            prefill_positions [C] int32 (absolute position per row —
            chunk start + arange, rows >= Len are padding),
            prefill_len [1] int32 (live rows this chunk),
            prefill_last [1] int32 (chunk-local index of the last live
            row, Len - 1 — the gather_time row for the logits),
            prefill_page_table [1, P] int32 (the stream's table; entries
            past the written extent are 0, the null page),
            prefill_cow_src / prefill_cow_dst [1] int32 (page copy to
            apply before the write — (0, 0) when no fork this chunk),
            and for a model with recurrent state (spec.state_names(),
            one set a slot: `slots`) prefill_state_slot [1] (the slot
            whose state the chunk starts from and leaves behind) and
            prefill_state_reset [1] (1 on a stream's first chunk: start
            from zero state, whatever the slot held);
            and for a model with sliding layers (spec.window_layers;
            their pools hold `window_pages` pages)
            prefill_window_table [1, W] (W = window_pages_per_slot: the
            stream's table of those layers as it stands, a sliding
            list), prefill_window_positions [C] (each row's position
            counted from that table's first row) and
            prefill_window_cow_src / prefill_window_cow_dst [1].
    The same program serves chunked prefill AND prefix-hit suffix
    prefill: shared pages arrive pre-populated in the table and the
    chunk simply starts at the first unshared position. Logits are the
    last live row's — only the FINAL chunk's logits mean anything. Rows
    from prefill_len on leave no trace in pages or state and are not
    counted by the expert layers.
    Returns (program, feed_names, fetch_vars[logits, ids(, counts)]).
    """
    from ..framework import Program, program_guard
    prog, startup = Program(), Program()
    prog._is_test = True
    with program_guard(prog, startup):
        feed, names = _feeds()
        at = PagedStep(decode=False, rows=chunk)
        tokens = feed('prefill_tokens', [1, chunk, 1], 'int64')
        at.positions = feed('prefill_positions', [chunk])
        at.length = feed('prefill_len', [1])
        at.last = feed('prefill_last', [1])
        at.table = feed('prefill_page_table', [1, pages_per_slot])
        at.cow = (feed('prefill_cow_src', [1]), feed('prefill_cow_dst', [1]))
        if spec.state_names():
            at.slot = feed('prefill_state_slot', [1])
            at.reset = feed('prefill_state_reset', [1])
        if spec.window_layers:
            at.window_table = feed('prefill_window_table',
                                   [1, window_pages_per_slot])
            at.window_positions = feed('prefill_window_positions', [chunk])
            at.window_cow = (feed('prefill_window_cow_src', [1]),
                             feed('prefill_window_cow_dst', [1]))
        fetches = _paged_fetches(spec, at, tokens, slots, num_pages,
                                 page_tokens, window_pages)
    return prog, names, fetches


def build_paged_decode_program(spec, slots, num_pages, page_tokens,
                               pages_per_slot, window_pages=0,
                               window_pages_per_slot=0):
    """One-token decode step over the whole slot pool, page-indexed, for
    whatever block `spec` is of.

    Feeds:  decode_tokens [slots, 1, 1] int64,
            decode_prev_ids [slots] int64 and decode_carry [slots]
            int32 (a lane with carry set is fed its entry of prev_ids —
            the greedy ids an earlier step left on the device — and not
            its decode_tokens entry: serving/paged.py dispatches a step
            before it has fetched the one before; one select in front
            of the embedding lookup, so the step that carries a token on
            and the one that takes every token from the host are one
            executable),
            decode_step_idx [slots] int32 (absolute position of the
            incoming token: the write lands at
            pool[table[pos // pt], pos % pt], never wrapped),
            decode_page_table [slots, P] int32 (all-zero rows for idle
            or mid-prefill slots: their appends hit the null page),
            and the lanes that take part [slots] int32, which
            serving/paged.py fills under either of its two names:
            decode_state_live for a model with recurrent state (the
            others' state stays as it was, and its expert layers
            neither count nor weigh their rows), decode_live for one
            with expert layers alone;
            and for a model with sliding layers decode_window_table
            [slots, W] and decode_window_step_idx [slots] (the incoming
            token's position counted from the lane's window table's
            first row).
    Admission and page allocation are host decisions that only change
    these feed values — the program compiles exactly once. It copies no
    page: where a lane's append would land on a page it shares, the
    host has run the page copy program (build_page_copy_program) in
    front of this one, and the table already names the copy.
    Returns (program, feed_names, fetch_vars[logits, ids(, counts)]).
    """
    from ..framework import Program, program_guard
    prog, startup = Program(), Program()
    prog._is_test = True
    with program_guard(prog, startup):
        feed, names = _feeds()
        at = PagedStep(decode=True, rows=1)
        tokens = feed('decode_tokens', [slots, 1, 1], 'int64')
        prev = feed('decode_prev_ids', [slots], 'int64')
        carry = feed('decode_carry', [slots])
        tokens = L.where_select(L.cast(carry, 'bool'),
                                L.reshape(prev, shape=[slots, 1, 1]), tokens)
        at.positions = feed('decode_step_idx', [slots])
        at.table = feed('decode_page_table', [slots, pages_per_slot])
        if spec.state_names():
            at.live = feed('decode_state_live', [slots])
        elif spec.expert_layers:
            at.live = feed('decode_live', [slots])
        if spec.window_layers:
            at.window_table = feed('decode_window_table',
                                   [slots, window_pages_per_slot])
            at.window_positions = feed('decode_window_step_idx', [slots])
        fetches = _paged_fetches(spec, at, tokens, slots, num_pages,
                                 page_tokens, window_pages)
    return prog, names, fetches


def build_paged_block_program(spec, slots, num_pages, page_tokens,
                              pages_per_slot):
    """One pass over a block of B = spec.block_tokens tokens of EVERY
    lane, for a model that generates by diffusion over blocks: the
    pair's second program, where the others have the one-token decode
    step. A denoising pass and the pass that commits a block are this
    one program with the same feed shapes: whether a pass's K/V rows
    count is the host's bookkeeping (serving/paged.py block_step).

    Feeds:  block_tokens [slots, B, 1] int64 (the block's ids as the
            host holds them: fixed tokens, the mask id elsewhere),
            block_prev_ids [slots, B] int64 and block_carry [slots]
            int32 (a lane with carry set takes its ids from
            block_prev_ids, what the pass before left on the device
            behind its unmasking, and not from block_tokens: a pass is
            dispatched before the one before has been fetched),
            block_positions [slots, B] int32 (the rows' absolute
            positions, start + arange(B): the rows' K/V land at
            pool[table[pos // pt], pos % pt]; all zero for a lane that
            sits the pass out, whose writes hit the null page),
            block_ends [slots] int32 (the position of the block's last
            row: every row attends to 0..end),
            block_page_table [slots, P] int32,
            block_live [slots] int32 (the lanes that take part: the
            expert layers neither count nor weigh the others' rows, and
            their ids pass through),
            block_transfer [slots] int32 (the masked rows this pass
            unmasks, at most; 0 in a commit pass, which has none).
    It copies no page: a block's pages are grown and its shared frontier
    page forked by the host before the block's first pass (the page copy
    program in front of it).
    Returns (program, feed_names, fetch_vars[logits [slots * B, vocab],
    ids [slots, B] behind the unmasking, counts, masked [slots]]).
    """
    from ..framework import Program, program_guard
    if spec.window_layers or spec.state_names():
        raise DecodeTranspileError(
            'a block step over sliding_attention or recurrent layers')
    if page_tokens % spec.block_tokens:
        raise DecodeTranspileError(
            'pages of %d tokens do not hold whole blocks of %d'
            % (page_tokens, spec.block_tokens))
    rows = spec.block_tokens
    prog, startup = Program(), Program()
    prog._is_test = True
    with program_guard(prog, startup):
        feed, names = _feeds()
        at = PagedStep(decode=True, rows=rows)
        tokens = feed('block_tokens', [slots, rows, 1], 'int64')
        prev = feed('block_prev_ids', [slots, rows], 'int64')
        carry = feed('block_carry', [slots])
        tokens = L.where_select(L.cast(carry, 'bool'),
                                L.reshape(prev, shape=[slots, rows, 1]),
                                tokens)
        at.block_ids = L.reshape(tokens, shape=[slots, rows])
        at.positions = feed('block_positions', [slots, rows])
        at.ends = feed('block_ends', [slots])
        at.table = feed('block_page_table', [slots, pages_per_slot])
        at.live = feed('block_live', [slots])
        at.transfer = feed('block_transfer', [slots])
        fetches = _paged_fetches(spec, at, tokens, slots, num_pages,
                                 page_tokens)
    return prog, names, fetches


def build_page_copy_program(spec, slots, num_pages, page_tokens,
                            window_pages=0):
    """The copy a forking decode step runs in front of its program: one
    kv_page_cow a pool of the pair (the K/V pools and, for a model
    whose recurrent layers keep their rows by the page, those pools
    too), and nothing else.

    Feeds:  page_copy_src / page_copy_dst [slots] int32 (a step forks at
            most one page a lane; (0, 0) for the pairs it does not use).
    The pools are the pair's own vars, donated like a step program's,
    so every layer's copy is in place and one dispatch moves a page in
    all of them (serving/paged.py `_fork_pages`: 2 ms of the host for
    48 pools; a program of one pool run 48 times took 5 ms in bare
    jitted calls and 45 through Executor.run). A few small scatters:
    48 pools compile in half a second, in front of the predictor's
    first decode step.
    The prefill and verify programs keep their own kv_page_cow.
    A model with sliding layers feeds a second pair,
    page_copy_window_src / page_copy_window_dst [slots], for the pages
    of their pools (a page that forks in both is copied by the one
    dispatch).
    Returns (program, feed_names); nothing to fetch.
    """
    from ..framework import Program, program_guard
    prog, startup = Program(), Program()
    prog._is_test = True
    sliding = getattr(spec, 'window_layers', ())
    with program_guard(prog, startup):
        feed, names = _feeds()
        pair = (feed('page_copy_src', [slots]),
                feed('page_copy_dst', [slots]))
        wpair = (feed('page_copy_window_src', [slots]),
                 feed('page_copy_window_dst', [slots])) if sliding else None
        pools = _create_pool_vars(spec, num_pages, page_tokens, window_pages)
        for layer in spec.kv_layers:
            src, dst = wpair if layer in sliding else pair
            for pool in pools[layer]:
                _block_op('kv_page_cow',
                          inputs={'Pool': [pool], 'Src': [src],
                                  'Dst': [dst]},
                          outputs={'Out': [pool]},
                          attrs={'page_rows': True})
        # the rows a layer keeps by the page fork with it
        for pool in _create_page_state_vars(spec, num_pages).values():
            _block_op('kv_page_cow',
                      inputs={'Pool': [pool], 'Src': [pair[0]],
                              'Dst': [pair[1]]},
                      outputs={'Out': [pool]})
    return prog, names


def snapshot_names(spec):
    """Snapshot-row vars, one beside each recurrent-state var of the
    pair and in the same order."""
    return [n + '.snapshot' for n in spec.state_names()]


def build_state_copy_programs(spec, slots, rows):
    """What gives a model with recurrent layers a prefix cache: the two
    programs that move one stream's recurrent state between its slot
    and a snapshot row, on the device.

    Beside each state var [slots, ...] of the pair (spec.state_names())
    lies a snapshot var [rows, ...] (snapshot_names) in the same scope.
    `snapshot` copies row state_copy_from of every state var to row
    state_copy_to of its snapshot var; `adopt` copies the other way.
    One state_row_copy an array: the array written is donated and
    updated where it lies, the one read is left alone, so a copy moves
    the stream's state once each way (38.7 MB at the Granite 4.0-H
    Small widths) whatever `slots` and `rows` are, and the device runs
    it in the order it was dispatched: a snapshot behind the chunk that
    ended the prompt, an adoption in front of the stream's first chunk.
    Returns (snapshot program, adopt program, feed_names); nothing to
    fetch.
    """
    from ..framework import Program, program_guard
    feeds = ['state_copy_from', 'state_copy_to']
    out = []
    for adopt in (False, True):
        prog, startup = Program(), Program()
        prog._is_test = True
        with program_guard(prog, startup):
            at, to = (L.data(n, [1], append_batch_size=False, dtype='int32')
                      for n in feeds)
            block = prog.global_block()
            for layer in spec.recurrent_layers:
                for name, shape in zip(spec.state_names(layer),
                                       spec.state_shapes(slots)):
                    state, snap = (block.create_var(
                        name=n, shape=(lead,) + tuple(shape[1:]),
                        dtype='float32', persistable=True,
                        stop_gradient=True, is_cache=True)
                        for n, lead in ((name, slots),
                                        (name + '.snapshot', rows)))
                    src, dst = (snap, state) if adopt else (state, snap)
                    _block_op('state_row_copy',
                              inputs={'Src': [src], 'Pool': [dst],
                                      'From': [at], 'To': [to]},
                              outputs={'Out': [dst]})
        out.append(prog)
    return out[0], out[1], feeds


def build_verify_program(spec, slots, k1, num_pages, page_tokens,
                         pages_per_slot):
    """Speculative verify: the TARGET model over K1 = k+1 proposed
    positions for every slot in ONE pass — the paged prefill program
    generalized to a batch of slots with a fixed row count.

    Feeds:  verify_tokens [slots, K1, 1] int64 (row 0 is the stream's
            last committed token, rows 1..k the draft proposals),
            verify_positions [slots, K1] int32 (absolute position per
            row — base..base+k for live slots, all zero for idle ones,
            whose writes land in the null page),
            verify_page_table [slots, P] int32,
            verify_cow_src / verify_cow_dst [slots] int32 (at most ONE
            fork per slot per verify: only the shared frontier page can
            COW — pages grown for the proposals are born private).
    Appends all K1 rows per layer per slot, attends with the per-row
    causal spec_verify_mask, and returns logits [slots*K1, vocab] +
    greedy ids [slots, K1]: ids[s, r] is the target's next token AFTER
    verify row r — compare against the draft chain for the longest
    accepted prefix, and ids[s, a] is the free bonus token.
    Returns (program, feed_names, fetch_vars[logits, ids]).
    """
    from ..framework import Program, program_guard
    refuse_recurrent(spec, 'the speculative verify program')
    refuse_latent_pages(spec, 'the speculative verify program')
    refuse_window(spec, 'the speculative verify program')
    refuse_blocks(spec, 'the speculative verify program')
    refuse_page_state(spec, 'the speculative verify program')
    prog, startup = Program(), Program()
    prog._is_test = True
    with program_guard(prog, startup):
        tokens = L.data('verify_tokens', [slots, k1, 1],
                        append_batch_size=False, dtype='int64')
        positions = L.data('verify_positions', [slots, k1],
                           append_batch_size=False, dtype='int32')
        table = L.data('verify_page_table', [slots, pages_per_slot],
                       append_batch_size=False, dtype='int32')
        cow_src = L.data('verify_cow_src', [slots],
                         append_batch_size=False, dtype='int32')
        cow_dst = L.data('verify_cow_dst', [slots],
                         append_batch_size=False, dtype='int32')
        pools = _create_pool_vars(spec, num_pages, page_tokens)
        emb = L.embedding(tokens, size=[spec.vocab, spec.dim],
                          param_attr=_named_attr(spec.emb_w))  # [S, K1, D]
        pos = _paged_pos_embedding(spec, positions)            # [S, K1, D]
        x = L.elementwise_add(emb, pos)
        for i in range(spec.layers):
            x = _cached_block(
                x, spec, i,
                lambda ln, sp, blk, _i=i: _paged_verify_attention(
                    ln, sp, blk, pools[_i], table, positions,
                    cow_src, cow_dst, k1))
        x = _named_ln(x, spec.final_ln)
        logits3 = _named_fc(x, spec.vocab, spec.head)          # [S, K1, V]
        ids = L.argmax(logits3, axis=-1)                       # [S, K1]
        logits = L.reshape(logits3, shape=[-1, spec.vocab])
    return prog, ['verify_tokens', 'verify_positions',
                  'verify_page_table', 'verify_cow_src',
                  'verify_cow_dst'], [logits, ids]
