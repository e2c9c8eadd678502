"""DecodeTranspiler: loaded LM program -> paged prefill + decode pair.

The serving-side analog of the DistributeTranspiler: instead of
rewriting ops in place, it READS the loaded language-model program for
its DecodeSpec (dims, layer kinds, the exact parameter names), then
asks the paged builders of models/transformer.py for two fresh programs
that bind those names. Both run against the Predictor's existing weight
Scope, so transpilation moves zero bytes of weights.

Where the spec comes from (extract_decode_spec), told by what the
program holds:

A description. Each block file written for serving (the tuple
models.SERVED_FAMILIES) states its model once: its Config, and
spec_from_config(cfg), from which language_model_logits builds the
whole-sequence program. language_model_logits leaves {'family',
'config'} on the program (Program.served_model), clone and _prune keep
it, and it rides inside the `program` entry that save_inference_model
writes. A program that has one gets spec_from_config(Config(**config))
of its family: the same spec its builder had, never a guess. The file
is input from outside, so the spec is held to it as far as it goes:
every parameter the spec names is a persistable variable of the
program, and the embedding and the attention weights have the shapes
the spec says (_check_weights).

The GPT shape, for a program with no description: the decoder-only LM
of models/transformer.py (`language_model_logits` / `language_model`,
TP-sharded or not), which was written for training, generates its
parameter names, and is read by walking its op sequence — lookup_table,
position_embedding, per block [layer_norm, qkv mul, proj mul,
layer_norm, up mul, down mul] (+ flash_attention or the
matmul/causal_mask/softmax triple), final layer_norm, lm_head mul.
GSPMD-style TP keeps full LOGICAL weight shapes, so a use_tp=True
program walks identically; its sharding is RECOVERED into
DecodeSpec.param_specs — from dist_attr annotations when the program
is still in memory, else from the sharding_constraint ops that survive
save_inference_model (see _recover_param_specs).

What a spec's layers keep for a stream decides what can serve it, not
its family's name: speculative decoding and mesh serving refuse
recurrent state (refuse_recurrent), latent pages
(refuse_latent_pages) and sliding layers' second table (refuse_window), and
both refuse a model whose step is a block of rows (refuse_blocks). Genuinely unsupported layouts (the training MoE
op moe_ffn, whose capacity drops tokens; ring attention; a constraint
on an axis the serving mesh cannot honor) still raise
DecodeTranspileError naming the offending op/axis — better a loud
refusal at prepare time than a silently wrong cache layout at serve
time.
"""
from __future__ import annotations

from .. import models
from ..models.transformer import (DecodeSpec, DecodeTranspileError,
                                  refuse_blocks, refuse_latent_pages,
                                  refuse_recurrent,
                                  refuse_window, build_page_copy_program,
                                  build_state_copy_programs,
                                  build_verify_program, snapshot_names)

__all__ = ['DecodeTranspileError', 'PagedDecodePair', 'SpecDecodePair',
           'DecodeTranspiler', 'extract_decode_spec', 'refuse_recurrent',
           'refuse_latent_pages', 'refuse_window', 'refuse_blocks']


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
    # a model with sliding layers: the pages of their pools and the
    # width of a stream's table of them (0: no such layers)
    window_num_pages = window_pages_per_slot = 0

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
        """Every pool a page number indexes: the K/V (or latent) pools
        and, behind them, the pools of the layers that keep their
        recurrent rows by the page."""
        return self.spec.pool_names() + self.spec.page_state_names()

    @property
    def state_names(self):
        """Recurrent-state vars (delta state, then convolution rows, a
        recurrent layer): per-slot state that is not pages."""
        return self.spec.state_names()

    @property
    def pool_shape(self):
        return self.spec.pool_shape(self.num_pages, self.page_tokens)

    def cache_shapes(self):
        """(pool var name, its shape) of every pool: a sliding layer's
        hold window_num_pages pages, the others num_pages."""
        spec = self.spec
        return [(name, spec.pool_shape(
            self.window_num_pages if i in spec.window_layers
            else self.num_pages, self.page_tokens))
            for i in spec.kv_layers for name in spec.pool_names(i)] + [
            (name, spec.page_state_shape(self.num_pages))
            for name in spec.page_state_names()]

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
        'cannot transpile program for cached decoding: %s (a program is '
        'recognised by the description a served family\'s '
        'language_model_logits leaves on it, models.SERVED_FAMILIES %s, '
        'or, with none, as the decoder-only LM of '
        'models.transformer.language_model[_logits])'
        % (msg, models.SERVED_FAMILIES))


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


def _described_spec(described, block):
    """The spec of a program that carries its description: what its
    family's spec_from_config says of the Config the fields make."""
    family = models.served_family(described.get('family'))
    if family is None:
        _fail('its description names the family %r'
              % (described.get('family'),))
    try:
        spec = family.spec_from_config(family.Config(**described['config']))
    except (KeyError, TypeError, ValueError) as err:
        _fail('the description %r does not make a %s: %s: %s'
              % (described, family.Config.__name__, type(err).__name__, err))
    spec.param_specs = {n: None for n in spec.param_names()}
    _check_weights(block, spec)
    return spec


def _check_weights(block, spec):
    """Hold a described spec to the program it came with: every
    parameter it names is there, and the shapes it states without
    asking its family (the embedding's, the K/V layers' qkv weights,
    from whose width a pool's heads follow) are the variables'."""
    def shape(name):
        try:
            var = block.var_recursive(name)
        except KeyError:
            var = None
        if var is None or not var.persistable:
            _fail('the described model\'s parameter %r is not a persistable '
                  'variable of the program' % name)
        return tuple(int(d) for d in var.shape)

    want = {spec.emb_w: (spec.vocab, spec.dim)}
    if spec.page_kind == 'kv':
        wide = (spec.heads + 2 * spec.kv_heads) * spec.dh
        want.update({spec.blocks[i]['qkv'][0]: (spec.dim, wide)
                     for i in spec.kv_layers})
    for name in spec.param_names():
        got = shape(name)
        if got != want.get(name, got):
            _fail('parameter %r is %r in the program and %r by its '
                  'description' % (name, got, want[name]))


def extract_decode_spec(program):
    """The DecodeSpec of a loaded program: its description's (see the
    module docstring), or the GPT walk's."""
    block = program.global_block()
    if program.served_model is not None:
        return _described_spec(program.served_model, block)
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
                  'the served expert layer is op moe_experts, which '
                  'the block files of models.SERVED_FAMILIES build')
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
                  prefill_chunk=None, snapshot_rows=0, window_pages=None):
        """program: a loaded inference Program (AnalysisPredictor's).
        Returns a PagedDecodePair whose cache is a page pool sized by
        page_tokens / kv_pages and whose prefill runs prefill_chunk-
        token chunks (each None defaults from FLAGS_serving_*, kv_pages
        0 auto-sizes to a full window for every slot). snapshot_rows
        (a model with recurrent layers; 0: none) is how many prefix
        boundaries keep their recurrent state on the device.
        window_pages (a model with sliding layers; None or 0 auto-sizes
        to a full window table for every slot) is how many pages the
        pools of those layers hold, sized apart from kv_pages. Raises
        DecodeTranspileError if the program is not a recognizable
        decoder-only LM."""
        if slots < 1:
            raise ValueError('slots must be >= 1, got %r' % (slots,))
        pair = self._transpile_paged(extract_decode_spec(program), slots,
                                     page_tokens, kv_pages, prefill_chunk,
                                     window_pages)
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
        refuse_window(extract_decode_spec(program), 'speculative decoding')
        refuse_blocks(extract_decode_spec(program), 'speculative decoding')
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
                         prefill_chunk, window_pages=None):
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
        if not spec.window_layers:
            if window_pages:
                raise ValueError('window_pages=%d for a model without '
                                 'sliding_attention layers' % window_pages)
            return PagedDecodePair(
                spec, slots, pt, pages_per_slot, num_pages, chunk,
                *(spec.build_paged_programs(slots, chunk, num_pages, pt,
                                            pages_per_slot)
                  + build_page_copy_program(spec, slots, num_pages, pt)))
        if spec.recurrent_layers:
            raise DecodeTranspileError(
                'a model with sliding_attention layers and recurrent '
                'layers: a prefix of it would be two tables and state')
        # the sliding layers' pools and tables, sized apart
        wide = spec.window_table_pages(chunk, pt)
        window = {'window_pages': int(window_pages or slots * wide + 1),
                  'window_pages_per_slot': wide}
        if window['window_pages'] < 2:
            raise ValueError('window_pages must be >= 2, got %d'
                             % window['window_pages'])
        pair = PagedDecodePair(
            spec, slots, pt, pages_per_slot, num_pages, chunk,
            *(spec.build_paged_programs(slots, chunk, num_pages, pt,
                                        pages_per_slot, **window)
              + build_page_copy_program(spec, slots, num_pages, pt,
                                        window['window_pages'])))
        pair.window_num_pages = window['window_pages']
        pair.window_pages_per_slot = wide
        return pair
