"""Builder for the A.X-K1 block (paddle_tpu/models/axk1.py): a
configuration file in, the serving system under test out, through the
program's public API and nothing else:

    axk1.language_model_logits -> save_inference_model ->
    AnalysisPredictor -> prepare_decoding(paged=True) -> ServingEngine.

The drive, the pre-rolled load, the step probe and the comparisons are
builders/olmo_hybrid.py's ServeSystem; what differs is the model built,
where its seeded weights come from (reference/axk1.py, a tensor at a
time), a warm-up that caches the traffic's documents, what the expert
layers, the latent pages and the prefix cache count, and a check whose
streams open on cached and on each other's pages.
"""
from __future__ import annotations

import gc
import tempfile
import time

import numpy as np

from builders import gpt2, olmo_hybrid
from harness import traffic_docs
from reference import axk1 as ref


def _block():
    """models/axk1; a program from before the block says so and leaves
    at once, with a message and exit code 1."""
    try:
        from paddle_tpu.models import axk1
    except ImportError as e:
        raise SystemExit('this program cannot run the axk1 block: %s' % (e,))
    return axk1


def model_config(dims):
    return _block().AXK1Config(
        vocab=dims.vocab, dim=dims.dim, heads=dims.heads, layers=dims.layers,
        dense_layers=dims.dense_layers, max_len=dims.positions,
        q_rank=dims.q_rank, kv_rank=dims.kv_rank, nope_dim=dims.nope_dim,
        rope_dim=dims.rope_dim, v_dim=dims.v_dim, dense_ffn=dims.dense_ffn,
        expert_ffn=dims.expert_ffn, shared_ffn=dims.shared_ffn,
        experts=dims.experts, experts_held=dims.held,
        expert_offset=dims.offset, top_k=dims.top_k, n_group=dims.n_group,
        topk_group=dims.topk_group, routed_scale=dims.scale, eps=dims.eps,
        rope={'base': dims.rope_base, 'factor': dims.rope_factor,
              'original_max': dims.rope_original,
              'beta_fast': dims.beta_fast, 'beta_slow': dims.beta_slow,
              'mscale': dims.mscale, 'mscale_all_dim': dims.mscale_all_dim})


# the program's fused weights: role -> the reference's tensors side by side
_FUSED = {'gate_up': ('gate', 'up'),
          'shared_gate_up': ('shared_gate', 'shared_up')}


def put_seeded_weights(scope, spec, dims, seed):
    """The reference's tensors under the program's parameter names, a
    tensor at a time; shapes are checked against what the program made."""
    import jax
    import jax.numpy as jnp
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
    for i, blk in enumerate(spec.blocks):
        for role, name in blk.items():
            parts = [ref.layer_tensor(key, i, r, dims)
                     for r in _FUSED.get(role, (role,))]
            put(name, parts[0] if len(parts) == 1
                else jnp.concatenate(parts, axis=1), '%s[%d]' % (role, i))
    jax.block_until_ready([scope.find_var(n) for n in spec.param_names()])


def serve_reference(seed, dims, lanes, n_decode, prec=None):
    """builders/olmo_hybrid.serve_reference for this block: for each
    lane the reference's logits at the last prompt position and at each
    decoded one; `n_decode` is a count a lane. Lanes are padded to one
    length (attention is causal), so each layer kind compiles once."""
    import jax.numpy as jnp
    key = ref.seed_key(seed)
    width = ref.padded_length(max(len(t) for t in lanes))
    out = []
    for toks, n in zip(lanes, n_decode):
        padded = np.zeros((width,), np.int32)
        padded[:len(toks)] = toks
        rows = slice(len(toks) - n - 1, len(toks))
        out.append(tuple(
            np.asarray(ref.logits(key, dims, jnp.asarray(padded), p, rows))
            for p in ((prec,) if prec else ('float32', 'float32_default'))))
    return out


def check_prompts(seed, dims, sv, page_tokens):
    """The compared streams' prompts: a fresh document of each of
    `document_tokens` and a question behind it, in that order, and
    after the stream `followup_of` a stream whose prompt is that
    stream's whole prompt and `followup_tokens` more: it opens on the
    other's registered pages, its last, partly filled one among them,
    and forks it. The prefix cache connects a partly filled page only
    to a prompt that ends inside it, so the parent's question is
    lengthened until its prompt ends half way into a page."""
    rng = np.random.default_rng([int(seed), 9])
    lo, hi = sv['question_tokens']
    prompts = []
    for i, n in enumerate(sv['document_tokens']):
        n = int(n) + int(rng.integers(lo, hi + 1))
        if i == int(sv['followup_of']):
            n += (page_tokens // 2 - n) % page_tokens
        prompts.append(rng.integers(1, dims.vocab, size=n))
        if i == int(sv['followup_of']):
            prompts.append(np.concatenate([prompts[-1], rng.integers(
                1, dims.vocab, size=int(sv['followup_tokens']))]))
    return prompts


def check_decoded(prompts, sv, chunk):
    """How many tokens each compared lane of `correct` decodes: a step
    between any two prefill chunks of every lane opened after it (the
    follow-up opens on its parent's whole prompt: one chunk), then
    `decode_tokens` steps of all together."""
    follow = int(sv['followup_of']) + 1
    between = [0 if i == follow else -(-len(p) // chunk) - 1
               for i, p in enumerate(prompts)]
    return [sum(between[i + 1:]) + int(sv['decode_tokens'])
            for i in range(len(prompts))]


def comparisons(got, truth, same, limits):
    """got, truth, same: for each lane logits [1 + decoded, vocab], row
    0 the prefill's. Every number is a MEDIAN of rows' relative L2, not
    a norm over whole tensors: where rounding puts a token's 8th and
    9th expert (or its 4th and 5th group) in the other order, program
    and reference take different experts for that token and its row
    reads tens of times the others (0.09 seen for one prefill row,
    0.0235 for a lane of 25 rows, with the other rows at 0.0034); it
    carries a whole tensor's number past the bf16-stored control's and
    leaves a median where it was. The prefill rows (one a lane) have
    the median over lanes; the decode rows the median within each lane,
    worst lane, so that a lane that went wrong alone (a fork, a table)
    is seen; against the reference at the program's own matmul
    precision, and all of a lane's rows against "highest". The whole
    tensors' numbers are printed beside them, lane by lane."""
    def rows(a, b):
        return np.linalg.norm(a - b, axis=-1) / np.linalg.norm(b, axis=-1)
    for i, (g, t, s_) in enumerate(zip(got, truth, same)):
        print('lane %d prefill %.6g decode %.6g to_highest %.6g (whole '
              'tensors); row medians %.6g %.6g'
              % (i, ref.rel_l2(g[:1], s_[:1]), ref.rel_l2(g[1:], s_[1:]),
                 ref.rel_l2(g, t), np.median(rows(g[1:], s_[1:])),
                 np.median(rows(g, t))))
    lanes = list(zip(got, truth, same))
    values = {
        'prefill_row_median_rel_l2': np.median(
            [rows(g[:1], s_[:1])[0] for g, _, s_ in lanes]),
        'decode_row_median_rel_l2': max(
            np.median(rows(g[1:], s_[1:])) for g, _, s_ in lanes),
        'row_median_rel_l2_to_highest': max(
            np.median(rows(g, t)) for g, t, _ in lanes)}
    return [{'name': name, 'value': float(value), 'limit': limits[name]}
            for name, value in values.items()]


class ServeSystem(olmo_hybrid.ServeSystem):
    def __init__(self, config, traffic, devices, seed, phases, rehearse):
        self.config, self.traffic = config, traffic
        self.devices, self.seed = devices, int(seed)
        self.phases, self.rehearse = phases, rehearse
        self.dims = ref.dims_of(config)

    def build(self):
        import jax
        import paddle_tpu as fluid
        from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
        from paddle_tpu.serving import ServingEngine
        axk1 = _block()
        cfg = self.config
        fluid.flags.set_flags(cfg.get('flags', {}))
        mc = model_config(self.dims)
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            tokens = fluid.layers.data(
                'tokens', shape=[1, mc.max_len, 1], dtype='int64',
                append_batch_size=False)
            logits = axk1.language_model_logits(tokens, mc)
        self.main = main
        self.phases.mark('build')

        exe = fluid.Executor(fluid.TPUPlace())
        with tempfile.TemporaryDirectory(prefix='bench_model_') as tmp:
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                put_seeded_weights(scope, axk1.spec_from_config(mc),
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
            prefill_chunk=int(sv['prefill_chunk']))
        self.phases.note('prepare_decoding')
        jax.block_until_ready(jax.live_arrays())
        self.phases.note('device_transfers')
        self.probe = gpt2._StepProbe(self.dec, self.slice_s)
        self.engine = ServingEngine(self.dec).start()
        self._jax = jax
        self.phases.mark('weights')
        return self

    def warm_up(self, plan):
        """gpt2's two warm requests; then every document of the corpus
        once through the engine, one token out, so that its pages are
        registered in the prefix cache (the corpus is the deployment's:
        it comes from the traffic's `params`, not from the plan); then
        `preroll_s` seconds of the cell's own traffic, left running.
        All of it set-up, phase `warm`."""
        gpt2.ServeSystem.warm_up(self, plan)
        params = self.traffic['params']
        t0 = time.perf_counter()
        for doc in traffic_docs.documents(params, self.config):
            self.engine.submit(doc, max_new_tokens=1).result(1100)
        self.phases.detail.append(('documents', time.perf_counter() - t0))
        seconds = float(params.get('preroll_s', 0))
        if seconds > 0:
            self.preroll(seconds)
            self.phases.detail.append(('preroll', seconds))
        self.phases.mark('warm')

    def counters(self, slice_since=None):
        """olmo_hybrid's (the step probe's among them: what the expert
        layers counted, and the slice's latent rows, `slice_latent_rows`
        over `slice_decode_calls`); the prefix cache's counters beside
        the prompt tokens admitted; the bytes the latent pages in use
        hold."""
        from paddle_tpu.obs import telemetry
        c = olmo_hybrid.ServeSystem.counters(self, slice_since)
        snap = telemetry.snapshot()
        for key in ('prefix_hits', 'prefix_tokens_reused',
                    'prompt_tokens_admitted'):
            c[key] = snap['counters'].get('serving.' + key, 0)
        c['latent_rows_read'] = \
            snap['counters'].get('serving.latent.rows_read', 0)
        c['latent_cache_bytes_max'] = \
            snap['gauges'].get('serving.latent.cache_bytes', 0)
        return c

    def check(self):
        """The occupancy check of builders/nemotron_h.py over cached
        documents: `filler_streams` streams are opened first, each on a
        document of the corpus that set-up cached and a short question
        (a prefix hit: one chunk), and stay live; then the compared
        streams (check_prompts: fresh documents of 1 k to 15 k tokens
        and one follow-up that opens on another compared stream's
        registered pages and forks its last), each prefilled chunk by
        chunk with one decode step of every lane already prefilled
        between any two chunks, then `decode_tokens` steps of all
        TOGETHER. Each compared lane's prefill logits and every one of
        its decode logits against the reference's full forward of that
        stream. The pools are given up before the reference runs: it
        needs their room."""
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
        for slot in list(seqs):
            dec.release(slot)
        follow = slots[int(sv['followup_of']) + 1]
        print('check: fillers opened on %d..%d cached tokens; the follow-up '
              'stream on %d of its %d prompt tokens'
              % (min(shared[s] for s in fillers) if fillers else 0,
                 max(shared[s] for s in fillers) if fillers else 0,
                 shared[follow], len(prompts[int(sv['followup_of']) + 1])))
        dec.reset()
        gc.collect()
        refs = serve_reference(self.seed, self.dims,
                               [seqs[s][:-1] for s in slots],
                               [len(got[s]) - 1 for s in slots])
        checks = comparisons(
            [np.stack(got[s]) for s in slots], [t for t, _ in refs],
            [s_ for _, s_ in refs], sv)
        # a follow-up that found no registered pages did not test them
        want = len(prompts[int(sv['followup_of'])])
        checks.append({'name': 'followup_tokens_not_shared',
                       'value': float(want - shared[follow]), 'limit': 0.0})
        return checks


def build_serve(**kw):
    return ServeSystem(**kw).build()
