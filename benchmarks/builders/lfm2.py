"""Builder for the LFM2 block with routed experts
(paddle_tpu/models/lfm2.py): a configuration file in, the serving system
under test out, through the program's public API and nothing else:

    lfm2.language_model_logits -> save_inference_model ->
    AnalysisPredictor -> prepare_decoding(paged=True) -> ServingEngine.

The drive, the two warm requests, the step probe and the comparisons are
those of builders/gpt2.py, the warm-up's shape that of
builders/granite_h.py; what differs is the model built, where its seeded
weights come from (reference/lfm2.py, a layer at a time), what set-up
leaves in the cache before the window (the sessions' plan,
harness/traffic_sessions_replay.py's in traffic_sessions.py's form,
WITHOUT its leading system prompts: no system prompt is ever
prefilled alone, each comes in as the head of a longer prompt), what the
conv pools count, and a check whose compared sessions open on the four
kinds of boundary a page-attached state has.
"""
from __future__ import annotations

import gc
import tempfile
import time

import numpy as np

from builders import gpt2
from reference import lfm2 as ref

# the compared sessions of `correct`, in the order they are opened:
# where each one's last turn finds its pages and conv rows
SESSIONS = ('tail', 'foreign_system', 'cold', 'forked')


def _block():
    """models/lfm2; a program from before the block says so and leaves
    at once, with a message and exit code 1."""
    try:
        from paddle_tpu.models import lfm2
    except ImportError as e:
        raise SystemExit('this program cannot run the lfm2 block: %s' % (e,))
    return lfm2


def model_config(dims):
    return _block().Lfm2Config(
        vocab=dims.vocab, dim=dims.dim, heads=dims.heads,
        kv_heads=dims.kv_heads, head_dim=dims.head_dim,
        layer_types=dims.kinds, max_len=dims.positions,
        conv_kernel=dims.conv_kernel, ffn=dims.ffn,
        dense_layers=dims.dense_layers, experts=dims.experts,
        top_k=dims.top_k, expert_ffn=dims.expert_ffn,
        routed_scale=dims.routed_scale,
        rope_theta=dims.rope_theta, eps=dims.eps)


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
    for i, kind in enumerate(dims.kinds):
        for role, value in ref.layer_tensors(key, i, kind, dims).items():
            put(spec.blocks[i][role], value, '%s[%d]' % (role, i))
    jax.block_until_ready([scope.find_var(n) for n in spec.param_names()])


def serve_reference(seed, dims, lanes, n_decode, prec=None):
    """builders/granite_h.serve_reference for this block: for each lane
    the reference's logits at its last `n_decode` + 1 positions (the
    last prompt position and each decoded one). Lanes are padded to one
    length (every mixer is causal), so each layer kind compiles once."""
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


def check_sessions(seed, dims, sv, page_tokens):
    """The compared sessions, a dict a name of SESSIONS: `last`, the
    last turn's prompt (of `session_tokens`); `earlier`, the prompt that
    is prefilled before it by whoever leaves the pages it opens on (None
    for the cold one); `opens_at`, where the design says the last turn
    opens.

      tail            its own earlier turn, `reopen_tokens` shorter and
                      lengthened until it ends half way into a page: the
                      last turn opens on a registered tail and forks it
      foreign_system  ANOTHER session's first turn: the same system
                      prompt (whole pages) and a message of its own; the
                      last turn opens on the system prompt's last whole
                      page, where no prompt ever ended
      cold            nothing
      forked          an owner's prompt that ends half way into a page,
                      whose owner stays live and has decoded on (so has
                      forked the tail page away): the last turn opens on
                      the page the owner left behind
    """
    rng = np.random.default_rng([int(seed), 9])
    out = {}
    for name, total, more in zip(SESSIONS, sv['session_tokens'],
                                 sv['reopen_tokens']):
        total, more = int(total), int(more)
        first = total - more
        if name in ('tail', 'forked'):
            first += (page_tokens // 2 - first) % page_tokens
        elif name == 'foreign_system':
            first -= first % page_tokens
        toks = rng.integers(1, dims.vocab, size=first + more)
        earlier, opens_at = toks[:first], first
        if name == 'foreign_system':
            earlier = np.concatenate([toks[:first], rng.integers(
                1, dims.vocab, size=int(sv['foreign_message_tokens']))])
        elif name == 'cold':
            earlier, opens_at = None, 0
        out[name] = {'last': toks, 'earlier': earlier, 'opens_at': opens_at}
    return out


def check_decoded(sessions, sv, chunk):
    """How many tokens each compared lane of `correct` decodes, in
    SESSIONS' order: a step between any two prefill chunks of every last
    turn opened after its own (a last turn prefills what follows the
    boundary it opens on), then `decode_tokens` steps of all together."""
    between = [-(-(len(sessions[n]['last']) - sessions[n]['opens_at'])
                 // chunk) - 1 for n in SESSIONS]
    return [sum(between[i + 1:]) + int(sv['decode_tokens'])
            for i in range(len(SESSIONS))]


class ServeSystem(gpt2.ServeSystem):
    def __init__(self, config, traffic, devices, seed, phases, rehearse):
        self.config, self.traffic = config, traffic
        self.devices, self.seed = devices, int(seed)
        self.phases, self.rehearse = phases, rehearse
        self.dims = ref.dims_of(config)
        self.streams_opened = 0

    def build(self):
        import jax
        import paddle_tpu as fluid
        from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
        from paddle_tpu.serving import ServingEngine
        lfm2 = _block()
        cfg = self.config
        fluid.flags.set_flags(cfg.get('flags', {}))
        mc = model_config(self.dims)
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            tokens = fluid.layers.data(
                'tokens', shape=[1, mc.max_len, 1], dtype='int64',
                append_batch_size=False)
            logits = lfm2.language_model_logits(tokens, mc)
        self.main = main
        self.phases.mark('build')

        exe = fluid.Executor(fluid.TPUPlace())
        with tempfile.TemporaryDirectory(prefix='bench_model_') as tmp:
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                put_seeded_weights(scope, lfm2.spec_from_config(mc),
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
        opened = self.dec.open_stream

        def open_stream(slot, prompt):
            self.streams_opened += 1
            return opened(slot, prompt)

        self.dec.open_stream = open_stream
        self.engine = ServingEngine(self.dec).start()
        self._jax = jax
        self.phases.mark('weights')
        return self

    def warm_up(self, plan):
        """gpt2's two warm requests; then the plan's `warm` prompts
        WITHOUT the system prompts that lead the list, once each through
        the engine, one token out (cache only: the conversations that
        are in progress when the pre-roll opens, each a system prompt
        and what its session has said so far; no system prompt alone);
        then the plan's `preroll`, submitted when due and left running.
        All of it set-up, phase `warm`. Without a plan
        (tools/chat_sweep.py, whose windows bring plans of their own)
        the two warm requests alone."""
        gpt2.ServeSystem.warm_up(self, plan)
        if plan is None:
            plan = {'preroll': [], 'warm': []}
        alone = int(self.traffic['params']['n_system_prompts']) \
            if plan['warm'] else 0
        t0 = time.perf_counter()
        for prompt in plan['warm'][alone:]:
            self.engine.submit(prompt, max_new_tokens=1).result(1100)
        self.phases.detail.append(('warm_prompts', time.perf_counter() - t0))
        seconds = float(self.traffic['params'].get('preroll_s', 0)) \
            if plan['preroll'] else 0.0
        t0 = time.perf_counter()
        for r in plan['preroll']:
            time.sleep(max(0.0, t0 + r['due'] - time.perf_counter()))
            self.engine.submit(r['prompt'], max_new_tokens=r['max_new'])
        time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
        self.phases.detail.append(('preroll', seconds))
        self.phases.mark('warm')

    def counters(self, slice_since=None):
        """gpt2's (the step probe's among them: the window's totals, the
        slice's own counts and what the expert layers counted); the
        prefix cache's counters beside the prompt tokens and the streams
        admitted; what the conv pools hold and counted."""
        from paddle_tpu.obs import telemetry
        c = gpt2.ServeSystem.counters(self, slice_since)
        snap = telemetry.snapshot()
        for key in ('prefix_hits', 'prefix_tokens_reused',
                    'prompt_tokens_admitted'):
            c[key] = snap['counters'].get('serving.' + key, 0)
        c['prefix_offprompt_tokens'] = snap['counters'].get(
            'serving.prefix.offprompt_tokens', 0)
        c['page_state_bytes_max'] = \
            snap['gauges'].get('serving.page_state.bytes', 0)
        for key in ('streams_adopted', 'rows_chunk', 'rows_step'):
            c['page_state_' + key] = snap['counters'].get(
                'serving.page_state.' + key, 0)
        c['streams_opened'] = self.streams_opened
        return c

    def check(self):
        """The occupancy check of builders/granite_h.py over sessions
        that open on the four kinds of boundary (check_sessions):
        `filler_streams` short streams are opened first and stay live;
        then the earlier prompts are prefilled (the tail's and the
        foreign system prompt's session are released, which leaves their
        pages and conv rows in the cache; the forked one's owner stays
        live and takes decode steps, so forks its tail page away); then
        each compared last turn opens where it opens and is prefilled
        from there chunk by chunk, with one decode step of every lane
        already prefilled between any two chunks; then `decode_tokens`
        steps of all TOGETHER. Each compared lane's prefill logits and
        every one of its decode logits against the reference's full
        forward over the whole conversation. A last turn that did not
        open exactly where the design says fails the check by name, as
        does a foreign system prompt whose tokens were not counted as
        coming from a boundary where no prompt ended. The pools are
        given up before the reference runs: it needs their room."""
        self.stop_engine()
        dec, sv = self.dec, self.config['correct']
        for slot in list(dec.slot_tokens()):
            dec.release(slot)
        sessions = check_sessions(self.seed, self.dims, sv, dec.page_tokens)
        slots = {name: i * dec.slots // (len(SESSIONS) + 1)
                 for i, name in enumerate(SESSIONS)}
        owner = len(SESSIONS) * dec.slots // (len(SESSIONS) + 1)
        rng = np.random.default_rng([self.seed, 11])
        lo, hi = sv['filler_tokens']
        taken = set(slots.values()) | {owner}
        fillers = [s for s in range(dec.slots) if s not in taken]
        fillers = fillers[:int(sv['filler_streams'])]
        seqs, got = {}, {s: [] for s in slots.values()}
        tokens = np.zeros((dec.slots,), np.int64)
        positions = np.zeros((dec.slots,), np.int32)
        shared, offprompt = {}, {}

        def decode():
            for slot, seq in seqs.items():
                tokens[slot], positions[slot] = seq[-1], len(seq) - 1
            ids, lg = dec.decode_step(tokens, positions, return_logits=True)
            ids, lg = np.asarray(ids), np.asarray(lg)
            for slot, seq in seqs.items():
                seq.append(int(ids[slot]))
                if slot in got:
                    got[slot].append(lg[slot])

        def prefill(slot, prompt, keep=True):
            opened = dec.open_stream(slot, prompt)
            shared[slot] = opened['shared_tokens']
            offprompt[slot] = opened['offprompt_tokens']
            while True:
                out = dec.prefill_step(slot, return_logits=True)
                if out is not None:
                    break
                if seqs:
                    decode()
            if not keep:
                dec.release(slot)
                return
            seqs[slot] = list(prompt) + [int(out[0])]
            if slot in got:
                got[slot].append(np.asarray(out[1]))

        for slot in fillers:
            prefill(slot, rng.integers(1, self.dims.vocab,
                                       size=int(rng.integers(lo, hi + 1))))
        # the earlier prompts, each in the slot another session's last
        # turn will take; the forked one's owner in a slot of its own
        names = list(SESSIONS)
        for name, other in zip(names, names[1:] + names[:1]):
            earlier = sessions[name]['earlier']
            if name == 'forked':
                prefill(owner, earlier)
                decode()            # the owner's append forks its tail page
            elif earlier is not None:
                prefill(slots[other], earlier, keep=False)
        for name in names:
            prefill(slots[name], sessions[name]['last'])
        off = {name: offprompt[slots[name]] for name in names}
        for _ in range(int(sv['decode_tokens'])):
            decode()
        for slot in list(seqs):
            dec.release(slot)
        order = [slots[name] for name in names]
        print('check: last turns opened on %s of %s tokens (%s from a '
              'boundary where no prompt ended)'
              % ([shared[s] for s in order],
                 [len(sessions[n]['last']) for n in names],
                 [off[n] for n in names]))
        dec.reset()
        gc.collect()
        refs = serve_reference(self.seed, self.dims,
                               [seqs[s][:-1] for s in order],
                               [len(got[s]) - 1 for s in order])
        checks = gpt2.serve_comparisons(
            [np.stack(got[s]) for s in order], [t for t, _ in refs],
            [s_ for _, s_ in refs], sv)
        # a last turn that opened elsewhere did not test what it names
        want_off = {n: sessions[n]['opens_at'] if n == 'foreign_system'
                    else 0 for n in names}
        checks.append({
            'name': 'streams_not_opened_where_designed',
            'value': float(sum(
                abs(sessions[n]['opens_at'] - shared[slots[n]])
                + abs(want_off[n] - off[n]) for n in names)),
            'limit': 0.0})
        return checks


def build_serve(**kw):
    return ServeSystem(**kw).build()
