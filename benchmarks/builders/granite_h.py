"""Builder for the GraniteMoeHybrid block
(paddle_tpu/models/granite_h.py): a configuration file in, the serving
system under test out, through the program's public API and nothing
else:

    granite_h.language_model_logits -> save_inference_model ->
    AnalysisPredictor -> prepare_decoding(paged=True, snapshot_rows=..)
    -> ServingEngine.

The drive, the two warm requests, the step probe and the comparisons are
those of builders/gpt2.py and builders/olmo_hybrid.py; what differs is
the model built, where its seeded weights come from
(reference/granite_h.py, a layer at a time), what set-up leaves in the
cache before the window (harness/traffic_sessions.py: the system
prompts, the conversations already in progress, then the pre-roll of
the plan itself), the reference the check compares with, and what the
snapshot rows count.
"""
from __future__ import annotations

import gc
import tempfile
import time

import numpy as np

from builders import gpt2, olmo_hybrid
from harness import traffic_sessions
from reference import granite_h as ref


def _block():
    """models/granite_h; a program from before the block says so and
    leaves at once, with a message and exit code 1."""
    try:
        from paddle_tpu.models import granite_h
    except ImportError as e:
        raise SystemExit('this program cannot run the granite_h block: '
                         '%s' % (e,))
    return granite_h


def model_config(dims):
    return _block().GraniteHConfig(
        vocab=dims.vocab, dim=dims.dim, heads=dims.heads,
        kv_heads=dims.kv_heads, head_dim=dims.head_dim,
        layer_types=dims.kinds, max_len=dims.positions,
        mamba_heads=dims.mamba_heads, mamba_head_dim=dims.mamba_head_dim,
        groups=dims.groups, state=dims.state, conv_kernel=dims.conv_kernel,
        chunk=dims.chunk, experts=dims.experts, experts_held=dims.held,
        expert_offset=dims.offset, top_k=dims.top_k,
        expert_ffn=dims.expert_ffn, shared_ffn=dims.shared_ffn,
        eps=dims.eps, embedding_multiplier=dims.emb_mult,
        residual_multiplier=dims.res_mult,
        attention_multiplier=dims.attn_mult,
        logits_scaling=dims.logits_scaling)


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
    """builders/olmo_hybrid.serve_reference for this block: for each
    lane the reference's logits at its last `n_decode` + 1 positions
    (the last prompt position and each decoded one). Lanes are padded to
    one length (every mixer is causal), so each layer kind compiles
    once."""
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
    """The compared sessions: for each of `session_tokens` (the length
    of its LAST turn's prompt) a pair (earlier turn's prompt, last
    turn's prompt), the second the first and `reopen_tokens` more (a
    scripted answer and a new message). The session `mid_page` is
    lengthened until its earlier prompt ends half way into a page, so
    that its last turn adopts in the middle of a page and forks it; the
    others' earlier prompts end wherever they end."""
    rng = np.random.default_rng([int(seed), 9])
    out = []
    for i, (total, more) in enumerate(zip(sv['session_tokens'],
                                          sv['reopen_tokens'])):
        first = int(total) - int(more)
        if i == int(sv['mid_page']):
            first += (page_tokens // 2 - first) % page_tokens
        toks = rng.integers(1, dims.vocab, size=first + int(more))
        out.append((toks[:first], toks))
    return out


def check_decoded(sessions, sv, chunk):
    """How many tokens each compared lane of `correct` decodes: a step
    between any two prefill chunks of every last turn opened after its
    own (a last turn prefills what follows its snapshot), then
    `decode_tokens` steps of all together."""
    between = [-(-(len(b) - len(a)) // chunk) - 1 for a, b in sessions]
    return [sum(between[i + 1:]) + int(sv['decode_tokens'])
            for i in range(len(sessions))]


class ServeSystem(olmo_hybrid.ServeSystem):
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
        granite_h = _block()
        cfg = self.config
        fluid.flags.set_flags(cfg.get('flags', {}))
        mc = model_config(self.dims)
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            tokens = fluid.layers.data(
                'tokens', shape=[1, mc.max_len, 1], dtype='int64',
                append_batch_size=False)
            logits = granite_h.language_model_logits(tokens, mc)
        self.main = main
        self.phases.mark('build')

        exe = fluid.Executor(fluid.TPUPlace())
        with tempfile.TemporaryDirectory(prefix='bench_model_') as tmp:
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                put_seeded_weights(scope, granite_h.spec_from_config(mc),
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
            prefill_chunk=int(sv['prefill_chunk']),
            snapshot_rows=int(sv['snapshot_rows']))
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
        once each through the engine, one token out (cache only: the
        system prompts, so that each has its snapshot, and the
        conversations that are in progress when the pre-roll opens);
        then the plan's `preroll`, the turns due in the seconds before
        the window, submitted when due and left running. All of it
        set-up, phase `warm`. Without a plan (tools/chat_sweep.py, whose
        windows bring plans of their own) the system prompts alone."""
        gpt2.ServeSystem.warm_up(self, plan)
        if plan is None:
            plan = {'preroll': [], 'warm': traffic_sessions.system_prompts(
                self.traffic['params'], self.config)}
        t0 = time.perf_counter()
        for prompt in plan['warm']:
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
        """olmo_hybrid's (the step probe's among them: the window's
        totals, the slice's own counts for the rooflines and what the
        expert sublayers counted, gpt2._StepProbe.counters); the bytes
        the state-space state holds, as builders/nemotron_h.py reports
        them; the prefix cache's counters beside the prompt tokens and
        the streams admitted; what the snapshot rows counted and hold."""
        from paddle_tpu.obs import telemetry
        c = olmo_hybrid.ServeSystem.counters(self, slice_since)
        snap = telemetry.snapshot()
        c['ssm_state_bytes_max'] = \
            snap['gauges'].get('serving.ssm.state_bytes', 0)
        for key in ('prefix_hits', 'prefix_tokens_reused',
                    'prompt_tokens_admitted'):
            c[key] = snap['counters'].get('serving.' + key, 0)
        for key in ('taken', 'adopted', 'evicted'):
            c['snapshots_' + key] = snap['counters'].get(
                'serving.state.snapshots_' + key, 0)
        c['state_snapshot_bytes_max'] = \
            snap['gauges'].get('serving.state.snapshot_bytes', 0)
        c['streams_opened'] = self.streams_opened
        return c

    def check(self):
        """The occupancy check of builders/nemotron_h.py over sessions
        that reopen: `filler_streams` short streams are opened first and
        stay live; then, for each compared session (check_sessions), its
        EARLIER turn is prefilled and released, which leaves its pages
        and a snapshot of its recurrent state in the cache (in the slot
        that ANOTHER session's last turn will take: a last turn finds
        another conversation's state in its slot, and only the adoption
        makes it right); then its LAST turn opens on that snapshot and
        is prefilled from there chunk by chunk, with one decode step of
        every lane already prefilled between any two chunks; then
        `decode_tokens` steps of all TOGETHER. Each compared lane's prefill logits and every one
        of its decode logits against the reference's full forward over
        the whole conversation. A last turn that did not open on
        exactly its earlier turn's boundary fails the check by name.
        The pools, the state and the snapshot rows are given up before
        the reference runs: it needs their room."""
        self.stop_engine()
        dec, sv = self.dec, self.config['correct']
        for slot in list(dec.slot_tokens()):
            dec.release(slot)
        sessions = check_sessions(self.seed, self.dims, sv, dec.page_tokens)
        slots = [i * dec.slots // len(sessions)
                 for i in range(len(sessions))]
        rng = np.random.default_rng([self.seed, 11])
        lo, hi = sv['filler_tokens']
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

        def prefill(slot, prompt, keep=True):
            shared[slot] = dec.open_stream(slot, prompt)['shared_tokens']
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
        for slot, (first, _) in zip(slots[1:] + slots[:1], sessions):
            prefill(slot, first, keep=False)
        for slot, (_, last) in zip(slots, sessions):
            prefill(slot, last)
        for _ in range(int(sv['decode_tokens'])):
            decode()
        for slot in list(seqs):
            dec.release(slot)
        print('check: last turns opened on %s of %s tokens'
              % ([shared[s] for s in slots], [len(b) for _, b in sessions]))
        dec.reset()
        gc.collect()
        refs = serve_reference(self.seed, self.dims,
                               [seqs[s][:-1] for s in slots],
                               [len(got[s]) - 1 for s in slots])
        checks = gpt2.serve_comparisons(
            [np.stack(got[s]) for s in slots], [t for t, _ in refs],
            [s_ for _, s_ in refs], sv)
        # a last turn that opened elsewhere did not test the snapshot
        checks.append({
            'name': 'reopen_tokens_not_on_the_snapshot',
            'value': float(sum(abs(len(a) - shared[s])
                               for s, (a, _) in zip(slots, sessions))),
            'limit': 0.0})
        return checks


def build_serve(**kw):
    return ServeSystem(**kw).build()
