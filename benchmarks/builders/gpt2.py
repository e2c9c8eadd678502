"""Builders for the GPT-2 block (models/transformer.py): a configuration
file in, the system under test out, through the program's public API
and nothing else. Weights are made on the device from --seed in one
jitted call (reference/gpt2.py) and put in the program's place; the
program's own initialisers decide nothing that is measured or compared.

build_train  Program IR -> Adam under bf16 AMP -> Executor(startup) ->
             ParallelExecutor over the cell's chips, fed by py_reader.
build_serve  save_inference_model -> AnalysisPredictor ->
             prepare_decoding(paged=True) -> ServingEngine.
"""
from __future__ import annotations

import bisect
import collections
import gc
import tempfile
import time

import numpy as np

from harness import spans, trace, traffic as traffic_mod
from reference import gpt2 as ref


def _tfm_config(model, flash):
    from paddle_tpu.models import transformer as tfm
    return tfm.TransformerConfig(
        vocab=int(model['vocab_size']), dim=int(model['n_embd']),
        heads=int(model['n_head']), layers=int(model['n_layer']),
        ffn=int(model['n_inner']), max_len=int(model['n_positions']),
        use_tp=False, use_sp=False, flash_attention=flash)


def _parameters(program):
    from paddle_tpu.framework import Parameter
    return [v for v in program.global_block().vars.values()
            if isinstance(v, Parameter)]


def _seeded_weights(program, dims, seed):
    """{parameter name: device array}: the program's parameters in
    creation order are reference/gpt2.role_table's roles; the shapes are
    checked so a reordering cannot pass unseen."""
    params = _parameters(program)
    values = ref.all_weights(ref.seed_key(seed), dims)
    if len(params) != len(values):
        raise RuntimeError('%d parameters in the program, %d roles in the '
                           'reference' % (len(params), len(values)))
    out = {}
    for p, v, (role, layer) in zip(params, values, ref.role_table(dims)):
        if tuple(p.shape) != tuple(v.shape):
            raise RuntimeError('parameter %s %r is not role %s[%s] %r'
                               % (p.name, p.shape, role, layer, v.shape))
        out[p.name] = v
    return out


def _names_by_role(program, dims):
    return {rl: p.name for p, rl in zip(_parameters(program),
                                        ref.role_table(dims))}


class _System:
    def __init__(self, config, traffic, devices, seed, phases, rehearse):
        self.config, self.traffic = config, traffic
        self.devices, self.seed = devices, int(seed)
        self.phases, self.rehearse = phases, rehearse
        self.dims = ref.dims_of(config)

    def hlo_texts(self):
        """Compiled HLO of every segment the live executors ran: where
        the traced instructions' op types come from."""
        from paddle_tpu import executor
        return executor.all_compiled_hlo_texts()


# -- what `correct` compares (shared with tools/controls.py) ------------------

def train_wanted(dims):
    """Gradients compared with the reference: first and last layer, a
    LayerNorm gain and the head."""
    return (('qkv_w', 0), ('down_w', dims.layers - 1), ('lnf_g', None),
            ('head_w', None))


def train_probe(seed, dims, n):
    """The n distinct seeded sequences [n, T] whose mean loss and
    gradients are compared: a step's whole batch, so that under a dp
    mesh every chip holds other data and a batch split or a gradient
    all-reduce that is wrong or missing changes the result."""
    rng = np.random.default_rng([int(seed), 7])
    return rng.integers(0, dims.vocab, size=(n, dims.positions),
                        dtype=np.int64)


def train_reference(seed, dims, seqs, wanted, prec='float32'):
    """(loss, {(role, layer): gradient}) of the mean next-token loss
    over `seqs`, one sequence at a time through the plain reference."""
    import jax.numpy as jnp
    key = ref.seed_key(seed)
    loss, grads = 0.0, None
    for seq in seqs:
        l, g = ref.loss_and_grads(
            key, dims, jnp.asarray(seq, jnp.int32),
            jnp.asarray(np.roll(seq, -1), jnp.int32), wanted, prec)
        loss += float(l) / len(seqs)
        grads = g if grads is None else {w: grads[w] + g[w] for w in g}
    return loss, {w: g / len(seqs) for w, g in grads.items()}


def train_comparisons(got_loss, got, want_loss, want, limits):
    for w in want:
        print('gradient %s[%s] rel_l2 %.6g'
              % (w[0], w[1], ref.rel_l2(got[w], want[w])))
    return [{'name': 'loss_rel_err',
             'value': abs(float(got_loss) - float(want_loss))
             / abs(float(want_loss)),
             'limit': limits['loss_rel_err']},
            {'name': 'grad_rel_l2_max',
             'value': max(ref.rel_l2(got[w], want[w]) for w in want),
             'limit': limits['grad_rel_l2_max']}]


def serve_probe(seed, dims, lengths):
    """One seeded prompt for each length: the lanes of the check."""
    rng = np.random.default_rng([int(seed), 9])
    return [rng.integers(1, dims.vocab, size=int(n)) for n in lengths]


def serve_reference(seed, dims, lanes, n_decode, prec=None):
    """For each lane (its tokens, prompt then decoded) the reference's
    logits at the last prompt position and at each decoded one,
    [1 + n_decode, vocab]. Without `prec`, a pair for each lane:
    (truth, same_arithmetic), float32 at "highest", and float32 at the
    backend's default matmul precision, which is the arithmetic the
    configuration states (PERF.md section 6: against the truth alone, a
    float32 program that multiplies in one bf16 pass and a bfloat16 one
    are less than 2x apart). Lanes are padded to one length (attention
    is causal, so what follows a row cannot change it): one compile."""
    import jax.numpy as jnp
    key = ref.seed_key(seed)
    width = -(-max(len(t) for t in lanes) // 128) * 128
    out = []
    for toks in lanes:
        padded = np.zeros((width,), np.int32)
        padded[:len(toks)] = toks
        rows = slice(len(toks) - n_decode - 1, len(toks))
        out.append(tuple(
            np.asarray(ref.logits(key, dims, jnp.asarray(padded), p)[rows])
            for p in ((prec,) if prec else ('float32', 'float32_default'))))
    return out


def serve_comparisons(got, truth, same, limits):
    """got, truth, same: for each lane logits [1 + decoded, vocab], row
    0 the prefill's. Each number is the worst lane's."""
    for i, (g, t, s_) in enumerate(zip(got, truth, same)):
        print('lane %d prefill %.6g decode %.6g to_highest %.6g'
              % (i, ref.rel_l2(g[:1], s_[:1]), ref.rel_l2(g[1:], s_[1:]),
                 ref.rel_l2(g, t)))
    lanes = list(zip(got, truth, same))
    return [{'name': 'prefill_logits_rel_l2',
             'value': max(ref.rel_l2(g[:1], s_[:1]) for g, _, s_ in lanes),
             'limit': limits['logits_rel_l2']},
            {'name': 'decode_logits_rel_l2',
             'value': max(ref.rel_l2(g[1:], s_[1:]) for g, _, s_ in lanes),
             'limit': limits['logits_rel_l2']},
            {'name': 'logits_rel_l2_to_highest',
             'value': max(ref.rel_l2(g, t) for g, t, _ in lanes),
             'limit': limits['logits_rel_l2_to_highest']}]


# -- training ----------------------------------------------------------------

class TrainSystem(_System):
    def build(self):
        import jax
        import paddle_tpu as fluid
        from paddle_tpu.models import transformer as tfm
        cfg, tp = self.config, self.traffic['params']
        self._jax = jax
        fluid.flags.set_flags(cfg.get('flags', {}))
        n = len(self.devices)
        t = int(tp['seq_len'])
        if t != self.dims.positions:
            raise ValueError('traffic seq_len %d != n_positions %d: the '
                             'program is built for one length' %
                             (t, self.dims.positions))
        self.batch = int(tp['per_step'])
        if self.batch % n:
            raise ValueError('%d sequences a step do not split over %d '
                             'chips' % (self.batch, n))
        self.items_per_step = self.batch * t
        tc = _tfm_config(cfg, flash=True)
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 1
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            self.reader = fluid.layers.py_reader(
                capacity=4, shapes=[(-1, t, 1), (-1, t, 1)],
                dtypes=['int64', 'int64'], name='bench_reader',
                use_double_buffer=True)
            tokens, labels = fluid.layers.read_file(self.reader)
            trunk = tfm.language_model_trunk(tokens, tc)
            cost = fluid.layers.fused_softmax_cross_entropy(
                trunk, labels, tc.vocab,
                chunk=min(int(cfg['optimizer']['head_chunk']),
                          self.batch * t // n), name='lm_head')
            self.loss = fluid.layers.mean(cost)
            opt = fluid.optimizer.Adam(
                learning_rate=float(cfg['optimizer']['learning_rate']))
            opt = fluid.contrib.mixed_precision.decorate(opt)
            opt.minimize(self.loss)
        self.main = main
        self.phases.mark('build')

        self.scope = fluid.Scope()
        self._guard = fluid.scope_guard(self.scope)
        self._guard.__enter__()
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)                 # Adam's state and the step count
        jax.block_until_ready([self.scope.find_var(p.name)
                               for p in _parameters(main)])
        self.phases.note('startup_program')
        self._put_weights()
        self.phases.note('seeded_weights')
        self.pe = fluid.ParallelExecutor(
            use_cuda=True, loss_name=self.loss.name, main_program=main,
            devices=self.devices)
        self.phases.mark('weights')
        return self

    def _put_weights(self):
        """The seeded weights in the scope's place. What they replace is
        freed first, so the two are never live together and the peak the
        allocator reports is the step's, not this swap's."""
        for p in _parameters(self.main):
            old = self.scope.find_var(p.name)
            if hasattr(old, 'delete'):
                old.delete()
        weights = _seeded_weights(self.main, self.dims, self.seed)
        self._jax.block_until_ready(list(weights.values()))
        for name, value in weights.items():
            self.scope.set_var(name, value)

    def _start_reader(self, batches):
        self.reader.decorate_tensor_provider(lambda: batches)
        self.reader.start()

    def step(self, fetch=None):
        out = self.pe.run(fetch_list=fetch or [self.loss.name],
                          return_numpy=False)
        self._jax.block_until_ready(out)
        return out

    def warm_up(self, plan):
        self._start_reader(traffic_mod.batch_stream(plan))
        self.step()                      # cache load, or the compile
        self.phases.mark('load')
        for _ in range(int(self.traffic['params']['warm_steps'])):
            self.step()
        self.phases.mark('warm')

    def counters(self):
        return {'compiled_segments':
                self.pe.jit_cache_stats()['compiled_segments']}

    def reader_depth(self):
        from paddle_tpu.obs import telemetry
        return telemetry.snapshot()['gauges'].get(
            'reader.device_queue_depth', 0)

    def check(self):
        """Mean loss and four gradients of one step's batch of distinct
        seeded sequences at the seeded weights, against the float32
        reference taken over the same sequences one at a time."""
        limits = self.config['correct']
        self.reader.reset()
        self._put_weights()
        seqs = train_probe(self.seed, self.dims, self.batch)
        toks = seqs[:, :, None]
        batch = [toks, np.roll(toks, -1, axis=1)]

        def once():
            yield batch
        self.reader.decorate_tensor_provider(once)
        self.reader.start()
        names = _names_by_role(self.main, self.dims)
        wanted = train_wanted(self.dims)
        fetch = [self.loss.name] + [names[w] + '@GRAD' for w in wanted]
        out = self.step(fetch)
        got_loss = float(np.asarray(out[0]).reshape(-1)[0])
        got = {w: np.asarray(g) for w, g in zip(wanted, out[1:])}
        self.reader.reset()
        want_loss, want = train_reference(self.seed, self.dims, seqs, wanted)
        return train_comparisons(got_loss, got, want_loss, want, limits)

    def close(self):
        try:
            self.reader.reset()
        finally:
            self._guard.__exit__(None, None, None)


def build_train(**kw):
    return TrainSystem(**kw).build()


# -- serving -----------------------------------------------------------------

DECODE_TABLES, PREFILL_TABLES = 'paged.decode.tables', 'paged.prefill.tables'
# what the program says of a step on its own spans (serving/paged.py)
LANE_ATTRS = ('pages_read', 'state_lanes', 'latent_rows', 'rows_read',
              'window_rows_read')
CHUNK_ATTRS = ('state_tokens',)
MOE_COUNTS = ('pairs', 'experts_touched', 'layer_calls')


def slice_counts(steps, program_spans, since):
    """The slice's own counts: sums over the steps that ended at or after
    `since`. `steps` are the probe's records (t0, t1, lanes, live_tokens,
    chunk_tokens), `program_spans` the program's span dicts. A step is
    named by what it carried, which is what the program itself said of
    it: lanes, if a `paged.decode.tables` span began inside it, a chunk,
    if a `paged.prefill.tables` span did; a step that carried both holds
    both.

      slice_decode_calls, slice_<attr>   the decode spans and their
          attrs' sums (LANE_ATTRS), beside slice_lanes and
          slice_live_tokens, which the probe counted in the same steps
      slice_plain_*    the same over the steps that carried lanes and no
          chunk: the pure decode program's executions
      slice_prefill_calls, slice_state_tokens   the prefill spans and
          their attr's sum, beside slice_prefill_tokens (the probe's)
    """
    tables = sorted((s['t0'], s['name'], s) for s in program_spans
                    if s['name'] in (DECODE_TABLES, PREFILL_TABLES))
    starts = [t for t, _, _ in tables]
    c = dict.fromkeys(
        [pre + key for pre in ('slice_', 'slice_plain_')
         for key in ('decode_calls', 'lanes', 'live_tokens') + LANE_ATTRS]
        + ['slice_prefill_calls', 'slice_prefill_tokens']
        + ['slice_' + a for a in CHUNK_ATTRS], 0)
    for t0, t1, lanes, live, chunk in steps:
        if t1 < since:
            continue
        mine = tables[bisect.bisect_left(starts, t0):
                      bisect.bisect_left(starts, t1)]
        carried = [s for _, name, s in mine if name == DECODE_TABLES]
        chunks = [s for _, name, s in mine if name == PREFILL_TABLES]
        prefixes = () if not carried else ('slice_',) if chunks \
            else ('slice_', 'slice_plain_')
        for pre in prefixes:
            c[pre + 'decode_calls'] += len(carried)
            c[pre + 'lanes'] += lanes
            c[pre + 'live_tokens'] += live
            for a in LANE_ATTRS:
                c[pre + a] += sum(s.get(a, 0) for s in carried)
        if chunks:
            c['slice_prefill_calls'] += len(chunks)
            c['slice_prefill_tokens'] += chunk
            for a in CHUNK_ATTRS:
                c['slice_' + a] += sum(s.get(a, 0) for s in chunks)
    return c


class _StepProbe:
    """Wraps every public callable of the decoder whose name ends in
    `_step` (today `prefill_step` and `decode_step`, the two calls the
    engine's worker makes) on the INSTANCE: a host span `bench.<name>`
    on the profiler's clock around each, and a record of what the step
    carried, read from what the call left behind and not from the
    method's name. A stream that stands inside its prompt (`open_stream`
    tells the prompt's length) and grew carried a chunk's rows; a stream
    past its prompt that grew was a lane. `decode_calls` / `prefill_calls`
    count the steps that carried lanes / a chunk, `decode_s` the host
    time of the first, `prefill_s` that of the steps with a chunk and no
    lane, `live_tokens` the tokens the lanes held after their step,
    `prefill_tokens` the rows the chunks carried. The records of twice the
    last `slice_s` seconds (the traffic's `trace_seconds`) stay, for
    slice_counts(); `on_step` callables run after every step."""

    def __init__(self, dec, slice_s):
        self.decode_calls = self.prefill_calls = 0
        self.decode_s = self.prefill_s = 0.0
        self.prefill_tokens = self.live_tokens = 0
        self.pages_max = self.live_pages_max = 0
        self.on_step = []
        self.steps = collections.deque()
        self.moe_at = collections.deque()   # (when, dec.moe_counters())
        self._dec, self.slice_s = dec, float(slice_s)
        self._prompt = {}                   # slot: its prompt's length
        opened = dec.open_stream

        def open_stream(slot, prompt):
            self._prompt[int(slot)] = len(prompt)
            return opened(slot, prompt)

        dec.open_stream = open_stream
        for name in dir(dec):
            if name.endswith('_step') and not name.startswith('_') \
                    and callable(getattr(dec, name)):
                setattr(dec, name, self._wrapped(name, getattr(dec, name)))

    def _wrapped(self, name, call):
        dec, span = self._dec, 'bench.' + name

        def step(*a, **kw):
            before = dec.slot_tokens()
            t0 = time.perf_counter()
            with trace.span(span):
                out = call(*a, **kw)
            t1 = time.perf_counter()
            self._see(t0, t1, before, dec.slot_tokens())
            return out

        step.__name__ = name
        return step

    def _see(self, t0, t1, before, after):
        lanes = live = chunk = 0
        for slot, n in after.items():
            grew = n - before.get(slot, 0)
            if grew <= 0:
                continue
            if before.get(slot, 0) < self._prompt.get(slot, 0):
                chunk += grew
            else:
                lanes += 1
                live += n
        if lanes:
            self.decode_calls += 1
            self.decode_s += t1 - t0
            self.live_tokens += live
        if chunk:
            self.prefill_calls += 1
            self.prefill_tokens += chunk
            if not lanes:
                self.prefill_s += t1 - t0
        self.steps.append((t0, t1, lanes, live, chunk))
        if not self.moe_at or t1 - self.moe_at[-1][0] >= 0.1:
            # a sample ten times a second: a slice's expert counts are a
            # difference of two, both ends on a step's boundary
            self.moe_at.append((t1, self._dec.moe_counters()))
        while self.steps[0][1] < t1 - 2 * self.slice_s:
            self.steps.popleft()
        while self.moe_at[0][0] < t1 - 2 * self.slice_s:
            self.moe_at.popleft()
        # the pages the pool has handed out (open streams and what the
        # prefix cache keeps of finished prompts), and those that open
        # streams alone hold
        dec = self._dec
        self.pages_max = max(self.pages_max,
                             dec.pool_stats()['pages_in_use'])
        self.live_pages_max = max(self.live_pages_max, sum(
            -(-n // dec.page_tokens) for n in after.values()))
        for call in self.on_step:
            call()

    def counters(self, since=None):
        """Running totals; two `*_max`, the most since the last reading;
        the `slice_*` sums over the steps that ended at or after `since`
        (when the profiler's capture began: the steps a traced slice
        holds), without it over those of the last `slice_s` seconds
        (slice_counts, and what the expert layers counted since the first
        sample of those seconds, decode steps and prefill chunks apart),
        which stand for themselves: a drive takes them as its closing
        reading has them (harness/drives.py)."""
        c = {k: getattr(self, k) for k in (
            'decode_calls', 'prefill_calls', 'decode_s', 'prefill_s',
            'prefill_tokens', 'live_tokens')}
        c['kv_pages_in_use_max'], self.pages_max = self.pages_max, 0
        c['kv_live_pages_max'], self.live_pages_max = self.live_pages_max, 0
        if since is None:
            since = time.perf_counter() - self.slice_s
        c.update(slice_counts(list(self.steps), spans.program_spans(),
                              since))
        moe = self._dec.moe_counters()      # {} without expert layers
        if moe:
            then = next((m for t, m in list(self.moe_at) if t >= since), moe)
            for what in MOE_COUNTS + ('pairs_dropped',):
                c['moe_' + what] = moe.get('decode.' + what, 0)
                c['moe_prefill_' + what] = \
                    moe.get(what, 0) - c['moe_' + what]
            for what in MOE_COUNTS:
                lanes = moe.get('decode.' + what, 0) \
                    - then.get('decode.' + what, 0)
                c['slice_moe_' + what] = lanes
                c['slice_moe_prefill_' + what] = \
                    moe.get(what, 0) - then.get(what, 0) - lanes
        return c


class ServeSystem(_System):
    @property
    def slice_s(self):
        """The seconds a traced run traces before its window closes: the
        slice whose steps the probe's `slice_*` counters sum over."""
        return float(self.traffic['params'].get('trace_seconds', 4))

    def build(self):
        import jax
        import paddle_tpu as fluid
        from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
        from paddle_tpu.models import transformer as tfm
        from paddle_tpu.serving import ServingEngine
        cfg = self.config
        fluid.flags.set_flags(cfg.get('flags', {}))
        tc = _tfm_config(cfg, flash=False)
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            tokens = fluid.layers.data(
                'tokens', shape=[1, tc.max_len, 1], dtype='int64',
                append_batch_size=False)
            logits = tfm.language_model_logits(tokens, tc)
        self.main = main
        self.phases.mark('build')

        # the only way the program builds a predictor is through a saved
        # model: seeded weights -> scope -> disk -> the predictor's scope
        exe = fluid.Executor(fluid.TPUPlace())
        with tempfile.TemporaryDirectory(prefix='bench_model_') as tmp:
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                weights = _seeded_weights(main, self.dims, self.seed)
                jax.block_until_ready(list(weights.values()))
                self.phases.note('seeded_weights')
                for name, value in weights.items():
                    scope.set_var(name, value)
                del weights
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
        # were the weights' and the zeroed page pool's way to the chip
        # still in flight, it would end inside `load`: wait for it here
        # (0.01 s on the chip: prepare_decoding has waited already)
        jax.block_until_ready(jax.live_arrays())
        self.phases.note('device_transfers')
        self.probe = _StepProbe(self.dec, self.slice_s)
        self.engine = ServingEngine(self.dec).start()
        self._jax = jax
        self.phases.mark('weights')
        return self

    def warm_up(self, plan):
        """A fixed list of warm requests: the first touches the prefill
        and the decode program once each (cache load, or the compile),
        the second runs both again warm with a second chunk."""
        rng = np.random.default_rng([self.seed, 8])
        chunk = self.dec.prefill_chunk
        for n_prompt, phase in ((chunk // 2, 'load'),
                                (chunk + chunk // 2, 'warm')):
            prompt = rng.integers(1, self.dims.vocab, size=n_prompt)
            self.engine.submit(prompt, max_new_tokens=4).result(1100)
            self.phases.mark(phase)
            self.phases.detail += [
                (phase + '_prefill_calls', self.probe.prefill_s),
                (phase + '_decode_calls', self.probe.decode_s)]

    def counters(self, slice_since=None):
        """The step probe's (the window's totals, the slice's own counts
        and, for a model with expert layers, what they counted: the one
        place every serving builder takes them from), the executors'
        compiles, preemptions and the lanes fed a decode step.
        `slice_since`: when the traced slice began, on `perf_counter()`
        (the drive's closing reading of a traced run)."""
        from paddle_tpu.obs import telemetry
        snap = telemetry.snapshot()
        c = self.probe.counters(slice_since)
        c['compiled_segments'] = \
            self.dec.jit_cache_stats()['compiled_segments']
        c['preemptions'] = snap['counters'].get('serving.preemptions', 0)
        batch = snap['hists'].get('serving.decode_batch', {})
        c['decode_batch_sum'] = batch.get('sum', 0.0)
        c['decode_batch_count'] = batch.get('count', 0)
        return c

    def stop_engine(self):
        if self.engine is not None:
            # a drain that gives up at once: queued and running requests
            # are cancelled at the next step boundary
            self.engine.stop(drain=True, timeout=0.0)
            self.engine = None

    def check(self):
        """Several streams of different lengths through the paged cache,
        directly on the decoder: each prefilled chunk by chunk, then all
        decoded TOGETHER, one lane each of the same decode steps, at
        their different positions; the longest is near the longest
        context the traffic reaches. Against the reference's full
        forward of each stream: the logits at the last prompt position
        and at each decoded position, lane by lane."""
        self.stop_engine()
        dec, sv = self.dec, self.config['correct']
        for slot in list(dec.slot_tokens()):
            dec.release(slot)
        n_decode = int(sv['decode_tokens'])
        prompts = serve_probe(self.seed, self.dims, sv['prompt_tokens'])
        slots = [i * dec.slots // len(prompts) for i in range(len(prompts))]
        seqs, got = [], []
        for slot, prompt in zip(slots, prompts):
            dec.open_stream(slot, prompt)
            out = None
            while out is None:
                out = dec.prefill_step(slot, return_logits=True)
            seqs.append(list(prompt) + [int(out[0])])
            got.append([np.asarray(out[1])])
        tokens = np.zeros((dec.slots,), np.int64)
        positions = np.zeros((dec.slots,), np.int32)
        for _ in range(n_decode):
            for slot, seq in zip(slots, seqs):
                tokens[slot], positions[slot] = seq[-1], len(seq) - 1
            ids, lg = dec.decode_step(tokens, positions, return_logits=True)
            ids, lg = np.asarray(ids), np.asarray(lg)
            for slot, seq, rows in zip(slots, seqs, got):
                rows.append(lg[slot])
                seq.append(int(ids[slot]))
        for slot in slots:
            dec.release(slot)
        refs = serve_reference(self.seed, self.dims,
                               [seq[:-1] for seq in seqs], n_decode)
        return serve_comparisons([np.stack(g) for g in got],
                                 [t for t, _ in refs], [s_ for _, s_ in refs],
                                 sv)

    def close(self):
        self.stop_engine()


def build_serve(**kw):
    return ServeSystem(**kw).build()
