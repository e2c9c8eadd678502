"""DecodeTranspiler: loaded LM program -> paged prefill + decode pair.

The serving-side analog of the DistributeTranspiler: instead of
rewriting ops in place, it READS the loaded language-model program —
walking the op sequence the models/transformer.py builders emit — to
recover the architecture (dims, head count, layer count, flash or
naive attention) and the exact parameter names, then asks the cached-
attention builders for two fresh programs that bind those names. Both
run against the Predictor's existing weight Scope, so transpilation
moves zero bytes of weights.

Recognized source shape: the decoder-only LM (`language_model_logits`
/ `language_model`, TP-sharded or not) — lookup_table,
position_embedding, per block [layer_norm, qkv mul, proj mul,
layer_norm, up mul, down mul] (+ flash_attention or the
matmul/causal_mask/softmax triple), final layer_norm, lm_head mul.
GSPMD-style TP keeps full LOGICAL weight shapes, so a use_tp=True
program walks identically; its sharding is RECOVERED into
DecodeSpec.param_specs — from dist_attr annotations when the program
is still in memory, else from the sharding_constraint ops that survive
save_inference_model (see _recover_param_specs).

A second recognized shape is the hybrid LM of models/hybrid.py
(`hybrid.language_model_logits`): lookup_table, no position op, per
block one of two mixers, told apart by their marker op, and a gated
MLP, every sublayer's output through an rms_norm —
  linear_attention  [qkv mul, ba mul, out_gate mul, short_conv,
                    gated_delta_chunk, rms_norm (heads), out mul]
  full_attention    [qkv mul, rms_norm (q), rms_norm (k),
                    matmul/causal_mask/softmax, proj mul]
then [rms_norm, gate mul, up mul, down mul, rms_norm], a final
rms_norm and the lm_head mul. Its spec (HybridDecodeSpec) carries the
layer kinds and the delta rule's sizes; its paged pair's programs keep
K/V pools for the full-attention layers and per-slot recurrent state
for the others. Speculative decoding,
page shipping and mesh serving refuse it by the layer kind's name.

A third is the three-kind hybrid of models/nemotron_h.py
(`nemotron_h.language_model_logits`): lookup_table, no position op,
every layer one rms_norm and one mixer, told apart by its marker op —
  mamba           [in mul, short_conv (with Bias), ssd_chunk,
                  gated_group_norm, out mul]
  experts         [down mul, moe_experts, up mul, shared-up mul,
                  shared-down mul]
  full_attention  [qkv mul, matmul/causal_mask/softmax, proj mul]
then a final rms_norm and the lm_head mul. Its spec
(NemotronHDecodeSpec) carries the kinds, the sizes of each and what the
expert op was told it holds (experts_held, expert_offset: attributes
of the model, read back from the op); K/V heads fewer than query heads
are read from the qkv weight's width. The refusals above hold for it
alike: they ask whether a layer holds recurrent state, not its name.

A fourth is the latent-attention block of models/axk1.py
(`axk1.language_model_logits`): lookup_table, no position op, every
layer
  [rms_norm, q-down mul, rms_norm, q-up mul, rotary_yarn, kv-down mul,
   rms_norm, rotary_yarn, latent_attention, proj mul, rms_norm]
then a gated MLP [gate-up mul, down mul] or an expert layer
[moe_experts with W3, shared gate-up mul, shared down mul], a final
rms_norm and the lm_head mul. Its spec (AXK1DecodeSpec) carries the
sizes read from the weights' shapes and the attributes of the
rotary, attention and expert ops; its pages hold ONE latent row a
token a layer (page_kind 'latent'), so the prefix cache and page
shipping serve it, and speculative decoding and mesh serving, which
read a page as K and V heads, refuse it by that name.

A fifth is the two-sublayer hybrid of models/granite_h.py
(`granite_h.language_model_logits`), told from the third by the gate
of its expert op (attr gate 'softmax'): lookup_table and a scale (the
embedding's multiplier), no position op, every layer
  [rms_norm, a mamba or a full_attention mixer as in the third, scale,
   rms_norm, moe_experts with W3, shared gate-up mul, shared down mul,
   scale]
then a final rms_norm and ONE matmul with the embedding transposed (the
tied head; its alpha is 1 / logits_scaling). Its spec
(GraniteHDecodeSpec) reads the multipliers back from the scale ops and
from the attention product's alpha. It holds recurrent state, so the
refusals above hold for it.

Genuinely
unsupported layouts (the training MoE op moe_ffn, whose capacity drops
tokens; ring attention; a
constraint on an axis the serving mesh cannot honor) still raise
DecodeTranspileError naming the offending op/axis — better a loud
refusal at prepare time than a silently wrong cache layout at serve
time.
"""
from __future__ import annotations

import re

from ..models import axk1, granite_h, hybrid, nemotron_h
from ..models.transformer import (DecodeSpec, DecodeTranspileError,
                                  refuse_latent_pages, refuse_recurrent,
                                  build_page_copy_program,
                                  build_state_copy_programs,
                                  build_verify_program, snapshot_names)

__all__ = ['DecodeTranspileError', 'PagedDecodePair', 'SpecDecodePair',
           'DecodeTranspiler', 'extract_decode_spec', 'refuse_recurrent',
           'refuse_latent_pages']


class PagedDecodePair(object):
    """The transpile result: spec + both programs and their ABIs.

    The cache state is per-layer page POOLS ([num_pages, page_tokens,
    H, dk] for K and for V, or one [num_pages, page_tokens, row] of
    latent rows: spec.pool_shape), the prefill program runs one `prefill_chunk`-token chunk
    through one stream's page table, and both programs take the page
    index as a feed (serving/paged.py computes it). Fetch order for
    both programs is [logits, greedy_ids]; pool var names
    (spec.pool_names()) are shared between the two programs, so one
    Scope carries the K/V state from prefill into decode. The decode
    program copies no page: copy_program (feeds copy_feeds, no fetch;
    models/transformer.build_page_copy_program) is what the host runs
    in front of a decode step that forks one. Where the deployment
    gives a model with recurrent layers `snapshot_rows` (0: none, and
    then none of this exists), snapshot_program and adopt_program
    (feeds state_copy_feeds, no fetch;
    models/transformer.build_state_copy_programs) move one slot's
    recurrent state to a row of snapshot_names and back."""

    snapshot_rows = 0
    snapshot_program = adopt_program = state_copy_feeds = None

    def __init__(self, spec, slots, page_tokens, pages_per_slot,
                 num_pages, prefill_chunk,
                 prefill_program, prefill_feeds, prefill_fetches,
                 decode_program, decode_feeds, decode_fetches,
                 copy_program, copy_feeds):
        self.spec = spec
        self.slots = slots
        self.page_tokens = page_tokens
        self.pages_per_slot = pages_per_slot
        self.num_pages = num_pages
        self.prefill_chunk = prefill_chunk
        self.prefill_program = prefill_program
        self.prefill_feeds = prefill_feeds
        self.prefill_fetches = prefill_fetches
        self.decode_program = decode_program
        self.decode_feeds = decode_feeds
        self.decode_fetches = decode_fetches
        self.copy_program = copy_program
        self.copy_feeds = copy_feeds

    @property
    def cache_names(self):
        return self.spec.pool_names()

    @property
    def state_names(self):
        """Recurrent-state vars (delta state, then convolution rows, a
        recurrent layer): per-slot state that is not pages."""
        return self.spec.state_names()

    @property
    def pool_shape(self):
        return self.spec.pool_shape(self.num_pages, self.page_tokens)

    def keep_snapshots(self, rows):
        """Give the pair `rows` snapshot rows and the two programs that
        fill and read them (a model with recurrent layers only)."""
        if not self.state_names:
            raise ValueError('snapshot_rows=%d for a model without '
                             'recurrent state: its prefixes are pages '
                             'alone' % rows)
        self.snapshot_rows = int(rows)
        self.snapshot_names = snapshot_names(self.spec)
        self.snapshot_program, self.adopt_program, self.state_copy_feeds = \
            build_state_copy_programs(self.spec, self.slots, self.snapshot_rows)


class SpecDecodePair(object):
    """Speculative transpile result: the TARGET PagedDecodePair plus a
    verify program over K1 = spec_k + 1 rows per slot, and a DRAFT
    PagedDecodePair — either transpiled from an explicit draft program
    (its own weights) or a self-draft: the target spec truncated to its
    first `draft_layers` blocks, whose parameter names are a subset of
    the target's, so the SAME weight scope serves both models with zero
    extra weight HBM. The verify program binds the target's pool var
    names, so target prefill / decode / verify share one cache scope;
    the draft pair's pools live in the draft predictor's own scope."""

    def __init__(self, target, draft, spec_k, verify_program,
                 verify_feeds, verify_fetches, self_draft):
        self.target = target
        self.draft = draft
        self.spec_k = int(spec_k)
        self.verify_program = verify_program
        self.verify_feeds = verify_feeds
        self.verify_fetches = verify_fetches
        self.self_draft = bool(self_draft)

    @property
    def spec(self):
        return self.target.spec


def _truncate_spec(spec, draft_layers):
    """Self-draft spec: the target's first `draft_layers` blocks with
    the same embedding / final-norm / head names."""
    draft_layers = int(draft_layers)
    if not 1 <= draft_layers <= spec.layers:
        raise DecodeTranspileError(
            'spec_draft_layers %d outside [1, %d] (target layers)'
            % (draft_layers, spec.layers))
    truncated = DecodeSpec(vocab=spec.vocab, dim=spec.dim,
                           heads=spec.heads,
                           layers=draft_layers, ffn=spec.ffn,
                           max_len=spec.max_len, pos_len=spec.pos_len,
                           emb_w=spec.emb_w, pos_w=spec.pos_w,
                           blocks=spec.blocks[:draft_layers],
                           final_ln=spec.final_ln, head=spec.head,
                           use_flash=spec.use_flash)
    # the draft's params are a SUBSET of the target's: carry their
    # recovered shardings so the self-draft shards the same way
    names = set(truncated.param_names())
    truncated.param_specs = {n: s for n, s in spec.param_specs.items()
                             if n in names}
    return truncated


def _fail(msg):
    raise DecodeTranspileError(
        'cannot transpile program for cached decoding: %s (expected a '
        'decoder-only LM from models.transformer.language_model'
        '[_logits])' % msg)


# sharding_constraint specs emitted by parallel/layers.py directly
# after a parallel fc's bias add; the LAST-dim axis tells the weight
# layout (column: output features sharded -> w (None, ax); row: output
# replicated after the psum -> w (ax, None)).
_SERVABLE_AXES = ('dp', 'tp', 'sp', 'ep', 'pp')


def _recover_param_specs(block, spec, muls, add_out_of, act_out_of,
                         constraints):
    """Recover each weight's PartitionSpec (tuple form) for mesh
    serving. Two sources, in preference order:

    1. var.dist_attr — present while the trained program is still in
       memory (shard_tensor wrote it), lost on save/load;
    2. the sharding_constraint ops parallel/layers.py appends right
       after each parallel fc's bias add — these SURVIVE
       save_inference_model, so a loaded TP program is still
       recoverable: a 2-tuple constraint (.., ax) right after a mul's
       add means column-parallel (w sharded (None, ax)); (.., None)
       means row-parallel (w sharded (ax, None), inferred from the
       matching column fc's axis).

    Unannotated weights map to None (replicated). An axis outside the
    canonical mesh axes is a genuinely unsupported layout -> loud
    DecodeTranspileError naming the weight and axis."""
    specs = {}

    def record(name, wspec):
        if wspec is None:
            specs[name] = None
            return
        wspec = tuple(wspec)
        for ax in wspec:
            for a in (ax if isinstance(ax, (tuple, list)) else (ax,)):
                if a is not None and a not in _SERVABLE_AXES:
                    _fail('weight %r is sharded on unknown mesh axis '
                          '%r (valid: %s)' % (name, a, _SERVABLE_AXES))
        specs[name] = wspec

    def infer(name, out_name):
        try:
            var = block.var_recursive(name)
        except KeyError:
            var = None
        dist = getattr(var, 'dist_attr', None)
        if dist is not None:
            record(name, dist)
            return
        out = add_out_of.get(out_name, out_name)
        out = act_out_of.get(out, out)
        cspec = constraints.get(out)
        if cspec is None or len(cspec) < 2:
            specs[name] = None
            return
        ax = cspec[-1]
        if isinstance(ax, (tuple, list)):
            ax = ax[0] if ax else None
        if ax is not None:
            record(name, (None, ax))        # column-parallel
        else:
            # a trailing-None activation constraint right after a mul
            # is the row-parallel signature; the contraction dim was
            # sharded over whichever model axis the net uses (tp)
            record(name, ('tp', None))
    for mul_w, mul_out in muls:
        infer(mul_w, mul_out)
    # embedding: dist_attr only (vocab_parallel_embedding emits no
    # constraint); lost after save/load -> replicated, still correct
    try:
        emb_var = block.var_recursive(spec.emb_w)
    except KeyError:
        emb_var = None
    dist = getattr(emb_var, 'dist_attr', None)
    record(spec.emb_w, tuple(dist) if dist is not None else None)
    spec.param_specs = {n: specs.get(n) for n in spec.param_names()}


def _extract_hybrid_spec(block):
    """The hybrid LM's spec (see the module docstring): weights and norms
    in op order, dealt out to layers by each layer's marker op."""
    emb_w = ids = None
    muls, norms, markers = [], [], []
    conv_w = None
    eps = 1e-6
    for op in block.ops:
        t = op.type
        if t == 'lookup_table' and emb_w is None:
            emb_w, ids = op.single_input('W'), op.single_input('Ids')
        elif t == 'mul':
            muls.append(op.single_input('Y'))
        elif t == 'rms_norm':
            norms.append(op.single_input('Scale'))
            eps = op.attr('epsilon', eps)
        elif t == 'short_conv':
            conv_w = op.single_input('W')
        elif t == 'gated_delta_chunk':
            markers.append(('linear_attention', op, conv_w))
        elif t == 'causal_mask':
            markers.append(('full_attention', op, None))
        elif t in ('layer_norm', 'position_embedding', 'moe_ffn',
                   'flash_attention', 'ring_attention'):
            _fail('op %s inside an rms_norm model: not the hybrid block '
                  'of models/hybrid.py' % t)
    if emb_w is None:
        _fail('no lookup_table op (token embedding)')
    if not markers:
        _fail('no gated_delta_chunk or causal_mask op marks a layer')
    want_muls = sum(7 if k == 'linear_attention' else 5
                    for k, _, _ in markers) + 1
    want_norms = sum(3 if k == 'linear_attention' else 4
                     for k, _, _ in markers) + 1
    if len(muls) != want_muls or len(norms) != want_norms:
        _fail('%d mul and %d rms_norm ops for layer kinds %s (want %d and '
              '%d)' % (len(muls), len(norms), [k for k, _, _ in markers],
                       want_muls, want_norms))
    vocab, dim = (int(d) for d in block.var_recursive(emb_w).shape)
    max_len = int(block.var_recursive(ids).shape[1])
    muls, norms = iter(muls), iter(norms)

    def w():
        return (next(muls), None)
    blocks, heads, sizes = [], None, None
    for i, (kind, op, conv) in enumerate(markers):
        if kind == 'linear_attention':
            if conv is None:
                _fail('layer %d: gated_delta_chunk without a short_conv'
                      % i)
            blk = {'qkv': w(), 'ba': w(), 'out_gate': w(), 'conv': conv,
                   'a_log': op.single_input('ALog'),
                   'dt_bias': op.single_input('DtBias'),
                   'head_norm': next(norms), 'out': w()}
            got = (int(op.attr('heads')), int(op.attr('key_dim')),
                   int(op.attr('value_dim')),
                   float(op.attr('beta_scale', 1.0)),
                   int(block.var_recursive(conv).shape[0]))
            if sizes not in (None, got):
                _fail('layer %d: delta rule sizes %r differ from %r'
                      % (i, got, sizes))
            sizes, heads = got, got[0]
        else:
            blk = {'qkv': w(), 'q_norm': next(norms),
                   'k_norm': next(norms), 'proj': w()}
            if tuple(block.var_recursive(blk['qkv'][0]).shape) != \
                    (dim, 3 * dim):
                _fail('layer %d qkv weight %r is not the full logical '
                      '(%d, %d)' % (i, blk['qkv'][0], dim, 3 * dim))
        blk.update(mixer_norm=next(norms), gate=w(), up=w(), down=w(),
                   mlp_norm=next(norms))
        blocks.append(blk)
    if heads is None:
        # attention only: the head count is the scores' second axis
        heads = int(block.var_recursive(
            markers[0][1].single_input('X')).shape[1])
    if sizes is None:
        sizes = (heads, 0, 0, 1.0, 1)
    if dim % heads:
        _fail('%d heads do not divide dim %d' % (heads, dim))
    ffn = int(block.var_recursive(blocks[0]['gate'][0]).shape[1])
    spec = hybrid.HybridDecodeSpec(
        vocab=vocab, dim=dim, heads=heads, ffn=ffn, max_len=max_len,
        kinds=[k for k, _, _ in markers], key_dim=sizes[1],
        value_dim=sizes[2], conv_kernel=sizes[4], eps=eps,
        beta_scale=sizes[3], emb_w=emb_w, blocks=blocks,
        final_norm=next(norms), head=w())
    spec.param_specs = {n: None for n in spec.param_names()}
    return spec


_NEMOTRON_MARKERS = {'ssd_chunk': 'mamba', 'moe_experts': 'experts',
                     'causal_mask': 'full_attention'}


def _extract_nemotron_spec(block):
    """The three-kind hybrid's spec (see the module docstring): the ops
    between one rms_norm and the next are one layer, whose kind its
    marker op gives and whose sizes the marker's attributes and the
    weights' shapes give."""
    def shape(name):
        return tuple(int(d) for d in block.var_recursive(name).shape)

    emb_w = ids = None
    layers = []                 # [norm scale, [ops until the next norm]]
    eps = 1e-5
    for op in block.ops:
        t = op.type
        if t == 'lookup_table' and emb_w is None:
            emb_w, ids = op.single_input('W'), op.single_input('Ids')
        elif t == 'rms_norm':
            layers.append([op.single_input('Scale'), []])
            eps = op.attr('epsilon', eps)
        elif t in ('layer_norm', 'position_embedding', 'moe_ffn',
                   'gated_delta_chunk', 'flash_attention',
                   'ring_attention'):
            _fail('op %s inside a model with ssd_chunk or moe_experts '
                  'layers: not the block of models/nemotron_h.py' % t)
        elif layers:
            layers[-1][1].append(op)
    if emb_w is None:
        _fail('no lookup_table op (token embedding)')
    if len(layers) < 2:
        _fail('no rms_norm before a mixer and before the head')
    (final_norm, tail), layers = layers[-1], layers[:-1]
    head = [op.single_input('Y') for op in tail if op.type == 'mul']
    if len(head) != 1:
        _fail('%d mul ops after the final rms_norm (want the head)'
              % len(head))
    vocab, dim = shape(emb_w)
    cfg = dict(vocab=vocab, dim=dim, max_len=shape(ids)[1], eps=eps)
    sizes = {}

    def agree(kind, i, got):
        if sizes.setdefault(kind, got) != got:
            _fail('layer %d: %s sizes %r differ from %r'
                  % (i, kind, got, sizes[kind]))
        cfg.update(got)

    blocks, kinds = [], []
    for i, (norm, ops) in enumerate(layers):
        marks = [op for op in ops if op.type in _NEMOTRON_MARKERS]
        if len(marks) != 1:
            _fail('layer %d: %d marker ops (%s) between two rms_norm ops, '
                  'want one' % (i, len(marks), [m.type for m in marks]))
        mark, kind = marks[0], _NEMOTRON_MARKERS[marks[0].type]
        muls = [(op.single_input('Y'), None) for op in ops
                if op.type == 'mul']
        want = {'mamba': 2, 'experts': 4, 'full_attention': 2}[kind]
        if len(muls) != want:
            _fail('layer %d (%s): %d mul ops, want %d'
                  % (i, kind, len(muls), want))
        blk = {'norm': norm}
        if kind == 'mamba':
            roles, got = _mamba_roles(i, ops, mark, muls, shape)
            blk.update(roles)
            agree(kind, i, got)
        elif kind == 'experts':
            blk.update({'down': muls[0], 'up': muls[1],
                        'shared_up': muls[2], 'shared_down': muls[3],
                        'router': mark.single_input('RouterW'),
                        'bias': mark.single_input('Bias'),
                        'w1': mark.single_input('W1'),
                        'w2': mark.single_input('W2')})
            held, latent, ffn = shape(blk['w1'])
            agree(kind, i, dict(
                experts=shape(blk['router'])[1], experts_held=held,
                expert_offset=int(mark.attr('expert_offset', 0)),
                top_k=int(mark.attr('top_k')),
                routed_scale=float(mark.attr('scale', 1.0)),
                latent=latent, expert_ffn=ffn,
                shared_ffn=shape(muls[2][0])[1]))
        else:
            roles, got = _attention_roles(i, block, mark, muls, shape)
            blk.update(roles)
            agree(kind, i, got)
        blocks.append(blk)
        kinds.append(kind)
    spec = nemotron_h.NemotronHDecodeSpec(
        nemotron_h.NemotronHConfig(layer_types=kinds, **cfg),
        emb_w=emb_w, blocks=blocks, final_norm=final_norm,
        head=(head[0], None))
    spec.param_specs = {n: None for n in spec.param_names()}
    return spec


def _mamba_roles(i, ops, mark, muls, shape):
    """(names by role, sizes) of a mamba mixer's ops (those of
    models/nemotron_h._mamba_mixer)."""
    conv = [op for op in ops if op.type == 'short_conv']
    gate = [op for op in ops if op.type == 'gated_group_norm']
    if len(conv) != 1 or not conv[0].input('Bias') or len(gate) != 1:
        _fail('layer %d: ssd_chunk without one short_conv with a '
              'Bias and one gated_group_norm' % i)
    roles = {'in': muls[0], 'out': muls[1],
             'conv': conv[0].single_input('W'),
             'conv_bias': conv[0].single_input('Bias'),
             'a_log': mark.single_input('ALog'),
             'dt_bias': mark.single_input('DtBias'),
             'd': mark.single_input('D'),
             'gate_norm': gate[0].single_input('Scale')}
    return roles, dict(
        mamba_heads=int(mark.attr('heads')),
        mamba_head_dim=int(mark.attr('head_dim')),
        groups=int(mark.attr('groups')), state=int(mark.attr('state')),
        chunk=int(mark.attr('block', 128)),
        conv_kernel=shape(roles['conv'])[0])


def _attention_roles(i, block, mark, muls, shape):
    """(names by role, sizes) of a whole-sequence attention mixer with
    whole K/V heads (models/nemotron_h._full_attention)."""
    heads = int(block.var_recursive(mark.single_input('X')).shape[1])
    width, wide = shape(muls[1][0])[0], shape(muls[0][0])[1]
    if width % heads or (wide - width) % (2 * (width // heads)):
        _fail('layer %d: qkv weight %r and proj weight %r do not '
              'split into %d query heads and whole K/V heads'
              % (i, shape(muls[0][0]), shape(muls[1][0]), heads))
    dh = width // heads
    return {'qkv': muls[0], 'proj': muls[1]}, dict(
        heads=heads, head_dim=dh, kv_heads=(wide - width) // (2 * dh))


def _extract_granite_spec(block):
    """The two-sublayer hybrid's spec (see the module docstring): the
    ops between one rms_norm and the next are one sublayer; sublayers
    come in pairs, a mixer (marker ssd_chunk or causal_mask) and an
    expert sublayer (marker moe_experts)."""
    def shape(name):
        return tuple(int(d) for d in block.var_recursive(name).shape)

    emb_w = ids = None
    emb_scale = 1.0
    subs = []                   # [norm scale, [ops until the next norm]]
    eps = 1e-5
    for op in block.ops:
        t = op.type
        if t == 'lookup_table' and emb_w is None:
            emb_w, ids = op.single_input('W'), op.single_input('Ids')
        elif t == 'rms_norm':
            subs.append([op.single_input('Scale'), []])
            eps = op.attr('epsilon', eps)
        elif t in ('layer_norm', 'position_embedding', 'moe_ffn',
                   'gated_delta_chunk', 'flash_attention',
                   'ring_attention', 'latent_attention'):
            _fail('op %s inside a model whose experts are softmax-gated: '
                  'not the block of models/granite_h.py' % t)
        elif subs:
            subs[-1][1].append(op)
        elif t == 'scale' and emb_w is not None:
            emb_scale = float(op.attr('scale'))
    if emb_w is None:
        _fail('no lookup_table op (token embedding)')
    if len(subs) < 3 or len(subs) % 2 != 1:
        _fail('%d rms_norm ops: want two a layer and one before the head '
              '(models/granite_h.py)' % len(subs))
    (final_norm, tail), subs = subs[-1], subs[:-1]
    head = [op for op in tail if op.type == 'matmul']
    if len(head) != 1 or head[0].single_input('Y') != emb_w \
            or not head[0].attr('transpose_Y'):
        _fail('after the final rms_norm: want one matmul with the '
              'embedding transposed (the tied head)')
    vocab, dim = shape(emb_w)
    cfg = dict(vocab=vocab, dim=dim, max_len=shape(ids)[1], eps=eps,
               embedding_multiplier=emb_scale,
               logits_scaling=1.0 / float(head[0].attr('alpha')))
    sizes = {}

    def agree(kind, i, got):
        if sizes.setdefault(kind, got) != got:
            _fail('layer %d: %s sizes %r differ from %r'
                  % (i, kind, got, sizes[kind]))
        cfg.update(got)

    def parts(i, ops, markers):
        marks = [op for op in ops if op.type in markers]
        scales = [op for op in ops if op.type == 'scale']
        if len(marks) != 1 or len(scales) != 1:
            _fail('layer %d: %d marker ops (%s) and %d scale ops between '
                  'two rms_norm ops, want one of each'
                  % (i, len(marks), [m.type for m in marks], len(scales)))
        agree('residual', i, dict(
            residual_multiplier=float(scales[0].attr('scale'))))
        return marks[0], [(op.single_input('Y'), None) for op in ops
                          if op.type == 'mul']

    blocks, kinds = [], []
    for i in range(len(subs) // 2):
        (norm, ops), (ffn_norm, ffn_ops) = subs[2 * i], subs[2 * i + 1]
        mark, muls = parts(i, ops, ('ssd_chunk', 'causal_mask'))
        if len(muls) != 2:
            _fail('layer %d: %d mul ops in the mixer, want 2'
                  % (i, len(muls)))
        kind = _NEMOTRON_MARKERS[mark.type]
        if kind == 'mamba':
            roles, got = _mamba_roles(i, ops, mark, muls, shape)
        else:
            roles, got = _attention_roles(i, block, mark, muls, shape)
            scores = [op for op in ops if op.type == 'matmul'][0]
            got['attention_multiplier'] = float(scores.attr('alpha'))
        agree(kind, i, got)
        blk = dict(roles, norm=norm, ffn_norm=ffn_norm)
        mark, muls = parts(i, ffn_ops, ('moe_experts',))
        if len(muls) != 2 or not mark.input('W3') \
                or mark.attr('gate', 'sigmoid') != 'softmax' \
                or mark.single_input('X') != mark.single_input('Lat'):
            _fail('layer %d: the expert sublayer of models/granite_h.py is '
                  'a softmax-gated moe_experts with W3 on the normed '
                  'stream itself and two mul ops (the shared expert)' % i)
        blk.update({'router': mark.single_input('RouterW'),
                    'w1': mark.single_input('W1'),
                    'w3': mark.single_input('W3'),
                    'w2': mark.single_input('W2'),
                    'shared_up': muls[0], 'shared_down': muls[1]})
        held, _, ffn = shape(blk['w1'])
        agree('experts', i, dict(
            experts=shape(blk['router'])[1], experts_held=held,
            expert_offset=int(mark.attr('expert_offset', 0)),
            top_k=int(mark.attr('top_k')), expert_ffn=ffn,
            shared_ffn=shape(muls[1][0])[0]))
        blocks.append(blk)
        kinds.append(kind)
    spec = granite_h.GraniteHDecodeSpec(
        granite_h.GraniteHConfig(layer_types=kinds, **cfg),
        emb_w=emb_w, blocks=blocks, final_norm=final_norm)
    spec.param_specs = {n: None for n in spec.param_names()}
    return spec


def _extract_axk1_spec(block):
    """The latent-attention block's spec (see the module docstring).
    The ops that carry a parameter, in order, spell the model: N an
    rms_norm, M a mul, A latent_attention, E moe_experts; a layer is
    N M N M M N A M N then M M (a gated MLP) or E M M (experts beside
    one shared expert), the gated-MLP layers first, and N M ends the
    model."""
    def shape(name):
        return tuple(int(d) for d in block.var_recursive(name).shape)

    emb_w = ids = None
    letters, ops, rotary = [], [], None
    for op in block.ops:
        t = op.type
        if t == 'lookup_table' and emb_w is None:
            emb_w, ids = op.single_input('W'), op.single_input('Ids')
        elif t in ('layer_norm', 'position_embedding', 'moe_ffn', 'ssd_chunk',
                   'gated_delta_chunk', 'flash_attention', 'ring_attention',
                   'causal_mask'):
            _fail('op %s inside a model with latent_attention layers: not '
                  'the block of models/axk1.py' % t)
        elif t == 'rotary_yarn':
            rotary = rotary or op
        elif t in ('rms_norm', 'mul', 'latent_attention', 'moe_experts'):
            letters.append({'rms_norm': 'N', 'mul': 'M',
                            'latent_attention': 'A', 'moe_experts': 'E'}[t])
            ops.append(op)
    if emb_w is None:
        _fail('no lookup_table op (token embedding)')
    spelled = ''.join(letters)
    if not re.match(r'^(NMNMMNAMNMM)*(NMNMMNAMNEMM)*NM$', spelled) \
            or len(spelled) == 2 or rotary is None:
        _fail('parameter ops spell %r: not layers of [norm, q-down, norm, '
              'q-up, kv-down, norm, latent_attention, proj, norm] and a '
              'gated MLP (the first layers) or moe_experts with a shared '
              'expert, then a final norm and the head (models/axk1.py)'
              % spelled)
    vocab, dim = shape(emb_w)
    eps = ops[0].attr('epsilon', 1e-6)
    blocks, sizes = [], {}

    def agree(i, got):
        for k, v in got.items():
            if sizes.setdefault(k, v) != v:
                _fail('layer %d: %s %r differs from %r' % (i, k, v, sizes[k]))

    def w(op):
        return (op.single_input('Y'), None)

    at = 0
    while at + 2 < len(ops):
        i = len(blocks)
        n1, qd, n2, qu, kd, n3, att, proj, n4 = ops[at:at + 9]
        blk = {'attn_norm': n1.single_input('Scale'), 'q_down': w(qd),
               'q_norm': n2.single_input('Scale'), 'q_up': w(qu),
               'kv_down': w(kd), 'kv_norm': n3.single_input('Scale'),
               'kv_up': att.single_input('WUKV'), 'proj': w(proj),
               'ffn_norm': n4.single_input('Scale')}
        heads = int(block.var_recursive(att.single_input('Q')).shape[2])
        dn = int(att.attr('nope_dim'))
        kv_rank, up_w = shape(blk['kv_up'])
        head = shape(blk['q_up'][0])[1] // heads
        agree(i, dict(
            heads=heads, nope_dim=dn, rope_dim=head - dn,
            v_dim=up_w // heads - dn, kv_rank=kv_rank,
            q_rank=shape(blk['q_down'][0])[1],
            sm_scale=float(att.attr('sm_scale'))))
        if shape(blk['kv_down'][0])[1] != kv_rank + head - dn:
            _fail('layer %d: kv-down weight %r is not kv_rank %d + rope_dim '
                  '%d wide' % (i, shape(blk['kv_down'][0]), kv_rank,
                               head - dn))
        at += 9
        if letters[at] == 'E':
            mark, up, down = ops[at:at + 3]
            if not mark.input('W3'):
                _fail('layer %d: moe_experts without W3 beside '
                      'latent_attention: the experts of models/axk1.py are '
                      'gated (three matrices)' % i)
            blk.update({'router': mark.single_input('RouterW'),
                        'bias': mark.single_input('Bias'),
                        'w1': mark.single_input('W1'),
                        'w3': mark.single_input('W3'),
                        'w2': mark.single_input('W2'),
                        'shared_gate_up': w(up), 'shared_down': w(down)})
            held, _, ffn = shape(blk['w1'])
            agree(i, dict(
                experts=shape(blk['router'])[1], experts_held=held,
                expert_offset=int(mark.attr('expert_offset', 0)),
                top_k=int(mark.attr('top_k')),
                n_group=int(mark.attr('n_group', 1)),
                topk_group=int(mark.attr('topk_group', 1)),
                routed_scale=float(mark.attr('scale', 1.0)),
                expert_ffn=ffn, shared_ffn=shape(down.single_input('Y'))[0]))
        else:
            up, down = ops[at:at + 2]
            blk.update({'gate_up': w(up), 'down': w(down)})
            agree(i, dict(dense_ffn=shape(down.single_input('Y'))[0]))
        at += 3 if letters[at] == 'E' else 2
        blocks.append(blk)
    final_norm, head = ops[at].single_input('Scale'), w(ops[at + 1])
    sizes.pop('sm_scale')
    cfg = axk1.AXK1Config(
        vocab=vocab, dim=dim, layers=len(blocks),
        dense_layers=sum('gate_up' in b for b in blocks),
        max_len=shape(ids)[1], eps=eps,
        rope={k: rotary.attr(k) for k in axk1.ROPE_KEYS}, **sizes)
    spec = axk1.AXK1DecodeSpec(cfg, emb_w=emb_w, blocks=blocks,
                               final_norm=final_norm, head=head)
    spec.param_specs = {n: None for n in spec.param_names()}
    return spec


def extract_decode_spec(program):
    """Scan the loaded program and return its DecodeSpec."""
    block = program.global_block()
    if any(op.type == 'latent_attention' for op in block.ops):
        return _extract_axk1_spec(block)
    if any(op.type == 'moe_experts' and op.attr('gate') == 'softmax'
           for op in block.ops):
        return _extract_granite_spec(block)
    if any(op.type in ('ssd_chunk', 'moe_experts') for op in block.ops):
        return _extract_nemotron_spec(block)
    if any(op.type == 'rms_norm' for op in block.ops):
        return _extract_hybrid_spec(block)
    emb_w = pos_w = None
    lns = []          # (scale_name, bias_name) in op order
    muls = []         # (w_name, out_name) in op order
    bias_of = {}      # mul/intermediate out name -> persistable bias name
    add_out_of = {}   # mul out name -> its bias add's out name
    act_out_of = {}   # fc activation's in name -> out name (one hop)
    constraints = {}  # constrained var name -> sharding spec tuple
    reshape4 = None
    use_flash = False

    for op in block.ops:
        t = op.type
        if t == 'lookup_table' and emb_w is None:
            emb_w = op.single_input('W')
        elif t == 'position_embedding' and pos_w is None:
            pos_w = op.single_input('Pos')
        elif t == 'layer_norm':
            lns.append((op.single_input('Scale') if op.input('Scale')
                        else None,
                        op.single_input('Bias') if op.input('Bias')
                        else None))
        elif t == 'mul':
            muls.append((op.single_input('Y'), op.single_output('Out')))
        elif t == 'flash_attention':
            use_flash = True
        elif t == 'moe_ffn':
            _fail('op moe_ffn: the training expert layer drops the pairs '
                  'over its capacity, which a served stream may not; '
                  'the served expert layer is op moe_experts '
                  '(models/nemotron_h.py)')
        elif t == 'ring_attention':
            _fail('op ring_attention: sp-ring attention has no '
                  'cached-decode equivalent (serve with the paged '
                  'cache instead)')
        elif t == 'sharding_constraint':
            spec = op.attr('spec')
            if spec is not None:
                constraints[op.single_input('X')] = tuple(spec)
        elif t in ('gelu', 'relu', 'tanh', 'sigmoid'):
            # fc applies its act AFTER the bias add, so a parallel fc's
            # constraint sits one hop past add_out — record the hop
            act_out_of[op.single_input('X')] = op.single_output('Out')
        elif t == 'reshape2' and reshape4 is None:
            shp = op.attr('shape') or []
            if len(shp) == 4:
                reshape4 = list(shp)
        elif t == 'elementwise_add':
            y = op.single_input('Y')
            try:
                yv = block.var_recursive(y)
            except KeyError:
                continue
            if yv.persistable:
                x = op.single_input('X')
                bias_of[x] = y
                add_out_of[x] = op.single_output('Out')

    if emb_w is None:
        _fail('no lookup_table op (token embedding)')
    if pos_w is None:
        _fail('no position_embedding op')
    if reshape4 is None:
        _fail('no 4-d attention head reshape')
    if len(muls) < 5 or (len(muls) - 1) % 4:
        _fail('%d mul ops do not form 4*layers+1 (qkv/proj/up/down per '
              'block + lm_head)' % len(muls))
    layers = (len(muls) - 1) // 4
    if len(lns) != 2 * layers + 1:
        _fail('%d layer_norms for %d layers (want 2*layers+1)'
              % (len(lns), layers))

    max_len, heads, dh = reshape4[1], reshape4[2], reshape4[3]
    emb_shape = block.var_recursive(emb_w).shape
    if emb_shape is None or len(emb_shape) != 2:
        _fail('embedding table %r has no [vocab, dim] shape' % emb_w)
    vocab, dim = int(emb_shape[0]), int(emb_shape[1])
    if heads * dh != dim:
        _fail('head reshape %r inconsistent with dim %d'
              % (reshape4, dim))
    pos_len = int(block.var_recursive(pos_w).shape[0])
    ffn = int(block.var_recursive(muls[2][0]).shape[1])

    def pair(i):
        w, out = muls[i]
        return (w, bias_of.get(out))

    blocks = []
    for i in range(layers):
        base = 4 * i
        blk = {'ln1': lns[2 * i], 'ln2': lns[2 * i + 1],
               'qkv': pair(base), 'proj': pair(base + 1),
               'up': pair(base + 2), 'down': pair(base + 3)}
        qkv_shape = block.var_recursive(blk['qkv'][0]).shape
        if tuple(qkv_shape) != (dim, 3 * dim):
            _fail('layer %d qkv weight %r is %r, want the full logical '
                  '(%d, %d) — GSPMD keeps logical shapes, so this is '
                  'not a recognizable attention block'
                  % (i, blk['qkv'][0], tuple(qkv_shape), dim, 3 * dim))
        blocks.append(blk)

    spec = DecodeSpec(vocab=vocab, dim=dim, heads=heads, layers=layers,
                      ffn=ffn, max_len=max_len, pos_len=pos_len,
                      emb_w=emb_w, pos_w=pos_w, blocks=blocks,
                      final_ln=lns[-1], head=pair(len(muls) - 1),
                      use_flash=use_flash)
    _recover_param_specs(block, spec, muls, add_out_of, act_out_of,
                         constraints)
    return spec


class DecodeTranspiler(object):
    def transpile(self, program, slots=8, page_tokens=None, kv_pages=None,
                  prefill_chunk=None, snapshot_rows=0):
        """program: a loaded inference Program (AnalysisPredictor's).
        Returns a PagedDecodePair whose cache is a page pool sized by
        page_tokens / kv_pages and whose prefill runs prefill_chunk-
        token chunks (each None defaults from FLAGS_serving_*, kv_pages
        0 auto-sizes to a full window for every slot). snapshot_rows
        (a model with recurrent layers; 0: none) is how many prefix
        boundaries keep their recurrent state on the device. Raises
        DecodeTranspileError if the program is not a recognizable
        decoder-only LM."""
        if slots < 1:
            raise ValueError('slots must be >= 1, got %r' % (slots,))
        pair = self._transpile_paged(extract_decode_spec(program), slots,
                                     page_tokens, kv_pages, prefill_chunk)
        if snapshot_rows:
            pair.keep_snapshots(snapshot_rows)
        return pair

    def transpile_spec(self, program, draft_program=None, slots=8,
                       spec_k=None, draft_layers=None, page_tokens=None,
                       kv_pages=None, prefill_chunk=None):
        """Speculative-decoding transpile: target program (+ optional
        draft program) -> SpecDecodePair. With no draft_program the
        draft is a SELF-draft — the target truncated to its first
        `draft_layers` (default FLAGS_spec_draft_layers) transformer
        blocks, sharing the target's weight scope. spec_k defaults from
        FLAGS_spec_k. The draft pair reuses the target's page geometry
        so both sides price the same window."""
        from ..flags import get_flag
        spec_k = int(spec_k if spec_k is not None else get_flag('spec_k'))
        if spec_k < 1:
            raise ValueError('spec_k must be >= 1, got %r' % spec_k)
        refuse_recurrent(extract_decode_spec(program),
                         'speculative decoding')
        refuse_latent_pages(extract_decode_spec(program),
                            'speculative decoding')
        target = self.transpile(program, slots=slots,
                                page_tokens=page_tokens,
                                kv_pages=kv_pages,
                                prefill_chunk=prefill_chunk)
        spec = target.spec
        if draft_program is not None:
            draft_spec = extract_decode_spec(draft_program)
            if draft_spec.vocab != spec.vocab:
                raise DecodeTranspileError(
                    'draft vocab %d != target vocab %d — proposals '
                    'would not index the target logits'
                    % (draft_spec.vocab, spec.vocab))
            if draft_spec.max_len < spec.max_len:
                raise DecodeTranspileError(
                    'draft max_len %d < target max_len %d — the draft '
                    'cannot cover the target window'
                    % (draft_spec.max_len, spec.max_len))
        else:
            draft_spec = _truncate_spec(
                spec, draft_layers if draft_layers is not None
                else get_flag('spec_draft_layers'))
        draft = self._transpile_paged(draft_spec, target.slots,
                                      target.page_tokens, kv_pages,
                                      prefill_chunk)
        vp, vf, vv = build_verify_program(
            spec, target.slots, spec_k + 1, target.num_pages,
            target.page_tokens, target.pages_per_slot)
        return SpecDecodePair(target, draft, spec_k, vp, vf, vv,
                              self_draft=draft_program is None)

    def _transpile_paged(self, spec, slots, page_tokens, kv_pages,
                         prefill_chunk):
        from ..flags import get_flag
        pt = int(page_tokens or get_flag('serving_page_tokens'))
        if pt < 1:
            raise ValueError('page_tokens must be >= 1, got %r' % pt)
        pages_per_slot = -(-spec.max_len // pt)         # ceil
        num_pages = int(kv_pages if kv_pages is not None
                        else get_flag('serving_kv_pages'))
        if num_pages == 0:
            # every slot can hold a full window, plus the reserved
            # null page
            num_pages = slots * pages_per_slot + 1
        if num_pages < 2:
            raise ValueError('kv_pages must be >= 2 (page 0 is the '
                             'reserved null page), got %d' % num_pages)
        chunk = int(prefill_chunk or get_flag('serving_prefill_chunk'))
        chunk = max(1, min(chunk, spec.max_len))
        return PagedDecodePair(
            spec, slots, pt, pages_per_slot, num_pages, chunk,
            *(spec.build_paged_programs(slots, chunk, num_pages, pt,
                                        pages_per_slot)
              + build_page_copy_program(spec, slots, num_pages, pt)))
