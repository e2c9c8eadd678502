"""Builder for the SmallThinker block (paddle_tpu/models/smallthinker.py):
a configuration file in, the serving system under test out, through the
program's public API and nothing else:

    smallthinker.language_model_logits -> save_inference_model ->
    AnalysisPredictor -> prepare_decoding(paged=True, window_pages=..)
    -> ServingEngine.

The drive, the two warm requests, the pre-rolled load, the step probe
and the document-caching warm-up are those of builders/gpt2.py,
builders/olmo_hybrid.py and builders/axk1.py; what differs is the model
built, where its seeded weights come from (reference/smallthinker.py, a
layer at a time), what the second page table counts, and a check whose
streams cross the window's edge in prefill and in decode and open on a
cached document's pages of BOTH pools.
"""
from __future__ import annotations

import gc
import tempfile

import numpy as np

from builders import axk1, gpt2, olmo_hybrid
from harness import traffic_docs
from reference import smallthinker as ref

# the compared lanes of `correct`, in the order they are opened
LANES = ('short', 'edge', 'parent', 'followup', 'cold')


def _block():
    """models/smallthinker; a program from before the block says so and
    leaves at once, with a message and exit code 1."""
    try:
        from paddle_tpu.models import smallthinker
    except ImportError as e:
        raise SystemExit('this program cannot run the smallthinker block: '
                         '%s' % (e,))
    return smallthinker


def model_config(dims):
    return _block().SmallThinkerConfig(
        vocab=dims.vocab, dim=dims.dim, heads=dims.heads,
        kv_heads=dims.kv_heads, head_dim=dims.head_dim, layers=dims.layers,
        sliding_window_layout=dims.sliding, rope_layout=dims.rope,
        window=dims.window, rope_theta=dims.rope_theta,
        max_len=dims.positions, experts=dims.experts,
        experts_held=dims.held, expert_offset=dims.offset, top_k=dims.top_k,
        expert_ffn=dims.expert_ffn, eps=dims.eps)


def put_seeded_weights(scope, spec, dims, seed):
    """The reference's tensors under the program's parameter names, a
    layer at a time; shapes are checked against what the program made."""
    import jax
    key = ref.seed_key(seed)

    def put(name, value, what):
        name = name[0] if isinstance(name, tuple) else name
        old = scope.find_var(name)
        if old is not None and tuple(old.shape) != tuple(value.shape):
            raise RuntimeError('parameter %s %r is not %s %r'
                               % (name, old.shape, what, value.shape))
        scope.set_var(name, value)

    put(spec.emb_w, ref.global_tensor(key, 'embed', dims), 'embed')
    put(spec.final_ln[0], ref.global_tensor(key, 'final_norm', dims),
        'final_norm')
    put(spec.head, ref.global_tensor(key, 'head', dims), 'head')
    for i in range(dims.layers):
        for role, value in ref.layer_tensors(key, i, dims).items():
            put(spec.blocks[i][role], value, '%s[%d]' % (role, i))
    jax.block_until_ready([scope.find_var(n) for n in spec.param_names()])


def serve_reference(seed, dims, lanes, n_decode, prec=None, **kw):
    """builders/granite_h.serve_reference for this block: for each lane
    the reference's logits at its last `n_decode` + 1 positions (the
    last prompt position and each decoded one). Lanes are padded to one
    length (attention is causal), so a layer compiles once. `kw`: the
    reference's own (full_window, a control)."""
    import jax.numpy as jnp
    key = ref.seed_key(seed)
    width = ref.padded_length(max(len(t) for t in lanes))
    out = []
    for toks, n in zip(lanes, n_decode):
        padded = np.zeros((width,), np.int32)
        padded[:len(toks)] = toks
        rows = slice(len(toks) - n - 1, len(toks))
        out.append(tuple(
            np.asarray(ref.logits(key, dims, jnp.asarray(padded), p, rows,
                                  **kw))
            for p in ((prec,) if prec else ('float32', 'float32_default'))))
    return out


def check_prompts(seed, dims, sv, page_tokens):
    """The compared streams' prompts by name (LANES, the order they are
    opened in): `short` stays inside the window; `edge` ends a few
    tokens short of the window, so that its decode steps cross it;
    `parent` is a fresh document and a question, lengthened until it
    ends half way into a page, and `followup` that whole prompt and
    `followup_tokens` more: it opens on the parent's registered pages
    of both pools, the partly filled last one among them, and forks it
    in both (the prefix cache connects a partly filled page only to a
    prompt that ends inside it); `cold` is the longest, prefilled cold
    through chunk after chunk while every other lane decodes."""
    rng = np.random.default_rng([int(seed), 9])
    out = {}
    for name in ('short', 'edge', 'parent', 'cold'):
        n = int(sv[name + '_tokens'])
        if name == 'parent':
            n += (page_tokens // 2 - n) % page_tokens
        out[name] = rng.integers(1, dims.vocab, size=n)
    out['followup'] = np.concatenate([out['parent'], rng.integers(
        1, dims.vocab, size=int(sv['followup_tokens']))])
    return [out[name] for name in LANES]


def check_decoded(prompts, sv, chunk):
    """How many tokens each compared lane of `correct` decodes: a step
    between any two prefill chunks of every lane opened after it (the
    follow-up opens on its parent's whole prompt: one chunk), then
    `decode_tokens` steps of all together."""
    follow = LANES.index('followup')
    between = [0 if i == follow else -(-len(p) // chunk) - 1
               for i, p in enumerate(prompts)]
    return [sum(between[i + 1:]) + int(sv['decode_tokens'])
            for i in range(len(prompts))]


def comparisons(got, truth, same, limits):
    """builders/axk1.comparisons (medians of rows' relative L2: with 6
    of 64 experts a token a rounding may put a sixth and a seventh
    expert in the other order, and that row reads tens of times the
    others), and beside them the share of such rows: those that read
    over `swapped_row_rel_l2` against the reference at the program's own
    matmul precision, of all compared rows. A program that took other
    experts in many rows, or wrong pages in one lane, passes the
    medians' limits in no lane and this one's nowhere."""
    checks = axk1.comparisons(got, truth, same, limits)
    rows = np.concatenate([
        np.linalg.norm(g - s_, axis=-1) / np.linalg.norm(s_, axis=-1)
        for g, s_ in zip(got, same)])
    checks.append({'name': 'swapped_rows_share',
                   'value': float(np.mean(
                       rows > float(limits['swapped_row_rel_l2']))),
                   'limit': limits['swapped_rows_share']})
    return checks


class ServeSystem(axk1.ServeSystem):
    def __init__(self, config, traffic, devices, seed, phases, rehearse):
        self.config, self.traffic = config, traffic
        self.devices, self.seed = devices, int(seed)
        self.phases, self.rehearse = phases, rehearse
        self.dims = ref.dims_of(config)
        self.window_live_max = 0

    def build(self):
        import jax
        import paddle_tpu as fluid
        from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
        from paddle_tpu.serving import ServingEngine
        block = _block()
        cfg = self.config
        fluid.flags.set_flags(cfg.get('flags', {}))
        mc = model_config(self.dims)
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            tokens = fluid.layers.data(
                'tokens', shape=[1, mc.max_len, 1], dtype='int64',
                append_batch_size=False)
            logits = block.language_model_logits(tokens, mc)
        self.main = main
        self.phases.mark('build')

        exe = fluid.Executor(fluid.TPUPlace())
        with tempfile.TemporaryDirectory(prefix='bench_model_') as tmp:
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                put_seeded_weights(scope, block.spec_from_config(mc),
                                   self.dims, self.seed)
                self.phases.note('seeded_weights')
                fluid.io.save_inference_model(tmp, ['tokens'], [logits],
                                              exe, main_program=main)
            del scope
            gc.collect()
            self.phases.note('save_inference_model')
            pred = AnalysisPredictor(AnalysisConfig(tmp))
            self.phases.note('analysis_predictor')
        sv = cfg['serving']
        self.dec = pred.prepare_decoding(
            slots=int(sv['slots']), paged=True,
            page_tokens=int(sv['page_tokens']),
            kv_pages=int(sv['kv_pages']),
            window_pages=int(sv['window_pages']),
            prefill_chunk=int(sv['prefill_chunk']))
        self.phases.note('prepare_decoding')
        jax.block_until_ready(jax.live_arrays())
        self.phases.note('device_transfers')
        self.probe = gpt2._StepProbe(self.dec, self.slice_s)

        def see_window():
            self.window_live_max = max(
                self.window_live_max,
                self.dec.pool_stats()['window_pages_live'])

        self.probe.on_step.append(see_window)
        self.engine = ServingEngine(self.dec).start()
        self._jax = jax
        self.phases.mark('weights')
        return self

    def counters(self, slice_since=None):
        """olmo_hybrid's (the step probe's among them: the slice's own
        counts for the rooflines, the K/V rows a full layer's and a
        sliding layer's attention had to read in its decode steps, and
        what the expert sublayers counted, gpt2._StepProbe.counters);
        the prefix cache's counters beside the prompt tokens admitted, as
        builders/axk1.py reports them; what the second table counted
        (`window_pages_freed`, `prefix_window_tail_miss`: running
        totals; `window_live_pages_max`, `window_pages_in_use_max`: the
        most since the reading before)."""
        from paddle_tpu.obs import telemetry
        c = olmo_hybrid.ServeSystem.counters(self, slice_since)
        snap = telemetry.snapshot()
        for key in ('prefix_hits', 'prefix_tokens_reused',
                    'prompt_tokens_admitted', 'window_pages_freed'):
            c[key] = snap['counters'].get('serving.' + key, 0)
        c['prefix_window_tail_miss'] = snap['counters'].get(
            'serving.prefix.window_tail_miss', 0)
        c['prefix_window_tail_adopted'] = snap['counters'].get(
            'serving.prefix.window_tail_adopted', 0)
        c['window_live_pages_max'], self.window_live_max = \
            self.window_live_max, 0
        c['window_pages_in_use_max'] = \
            self.dec.pool_stats()['window_pages_in_use']
        return c

    def check(self):
        """The occupancy check of builders/axk1.py over two page tables:
        `filler_streams` streams are opened first, each on a document of
        the corpus that set-up cached and a short question (a prefix
        hit in both pools: the document's full pages and its window
        tail), and stay live; then the compared streams (check_prompts),
        each prefilled chunk by chunk with one decode step of every
        lane already prefilled between any two chunks (so the window
        table gives up pages behind a chunk while other lanes decode,
        and `edge` crosses the window's end in a decode step), then
        `decode_tokens` steps of all TOGETHER. Each compared lane's
        prefill logits and every one of its decode logits against the
        reference's full forward of that stream. The pools are given up
        before the reference runs: it needs their room."""
        self.stop_engine()
        dec, sv = self.dec, self.config['correct']
        for slot in list(dec.slot_tokens()):
            dec.release(slot)
        prompts = check_prompts(self.seed, self.dims, sv, dec.page_tokens)
        slots = [i * dec.slots // len(prompts) for i in range(len(prompts))]
        rng = np.random.default_rng([self.seed, 11])
        lo, hi = sv['filler_tokens']
        corpus = traffic_docs.documents(self.traffic['params'], self.config)
        fillers = [s for s in range(dec.slots) if s not in slots]
        fillers = fillers[:int(sv['filler_streams'])]
        seqs, got = {}, {s: [] for s in slots}
        tokens = np.zeros((dec.slots,), np.int64)
        positions = np.zeros((dec.slots,), np.int32)
        shared = {}
        before = self.dec.pool_stats()

        def decode():
            for slot, seq in seqs.items():
                tokens[slot], positions[slot] = seq[-1], len(seq) - 1
            ids, lg = dec.decode_step(tokens, positions, return_logits=True)
            ids, lg = np.asarray(ids), np.asarray(lg)
            for slot, seq in seqs.items():
                seq.append(int(ids[slot]))
                if slot in got:
                    got[slot].append(lg[slot])

        def prefill(slot, prompt):
            shared[slot] = dec.open_stream(slot, prompt)['shared_tokens']
            while True:
                out = dec.prefill_step(slot, return_logits=True)
                if out is not None:
                    break
                if seqs:
                    decode()
            seqs[slot] = list(prompt) + [int(out[0])]
            if slot in got:
                got[slot].append(np.asarray(out[1]))

        for k, slot in enumerate(fillers):
            prefill(slot, np.concatenate([
                corpus[k % len(corpus)],
                rng.integers(1, self.dims.vocab,
                             size=int(rng.integers(lo, hi + 1)))]))
        for slot, prompt in zip(slots, prompts):
            prefill(slot, prompt)
        for _ in range(int(sv['decode_tokens'])):
            decode()
        stats = self.dec.pool_stats()
        for slot in list(seqs):
            dec.release(slot)
        follow = slots[LANES.index('followup')]
        print('check: fillers opened on %d..%d cached tokens; the follow-up '
              'stream on %d of its %d prompt tokens; window pages live %d '
              '(full pages in use %d -> %d)'
              % (min(shared[s] for s in fillers) if fillers else 0,
                 max(shared[s] for s in fillers) if fillers else 0,
                 shared[follow], len(prompts[LANES.index('followup')]),
                 stats['window_pages_live'], before['pages_in_use'],
                 stats['pages_in_use']))
        dec.reset()
        gc.collect()
        refs = serve_reference(self.seed, self.dims,
                               [seqs[s][:-1] for s in slots],
                               [len(got[s]) - 1 for s in slots])
        checks = comparisons(
            [np.stack(got[s]) for s in slots], [t for t, _ in refs],
            [s_ for _, s_ in refs], sv)
        # a follow-up that opened anywhere but on its document's end did
        # not test the window tail
        want = len(prompts[LANES.index('parent')])
        checks.append({'name': 'followup_tokens_not_on_the_document_end',
                       'value': float(abs(want - shared[follow])),
                       'limit': 0.0})
        return checks


def build_serve(**kw):
    return ServeSystem(**kw).build()
