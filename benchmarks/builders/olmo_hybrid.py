"""Builder for the hybrid block (paddle_tpu/models/hybrid.py): a
configuration file in, the serving system under test out, through the
program's public API and nothing else:

    hybrid.language_model_logits -> save_inference_model ->
    AnalysisPredictor -> prepare_decoding(paged=True) -> ServingEngine.

The drive, the warm-up, the step probe and the comparisons are those of
builders/gpt2.py's ServeSystem; what differs is the model built, where
its seeded weights come from (reference/olmo_hybrid.py, a layer at a
time) and the reference the check compares with.
"""
from __future__ import annotations

import gc
import tempfile
import time

import numpy as np

from builders import gpt2
from reference import olmo_hybrid as ref


def _hybrid_config(dims):
    from paddle_tpu.models import hybrid
    return hybrid.HybridConfig(
        vocab=dims.vocab, dim=dims.dim, heads=dims.heads,
        layer_types=dims.kinds, ffn=dims.ffn, max_len=dims.positions,
        key_dim=dims.key_dim, value_dim=dims.value_dim,
        conv_kernel=dims.conv_kernel, eps=dims.eps,
        neg_eigval=dims.beta_scale == 2.0)


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
    for i, kind in enumerate(dims.kinds):
        values = ref.layer_tensors(key, i, kind, dims)
        for role, value in values.items():
            put(spec.blocks[i][role], value, '%s[%d]' % (role, i))
    jax.block_until_ready([scope.find_var(n) for n in spec.param_names()])


def serve_reference(seed, dims, lanes, n_decode, prec=None):
    """builders/gpt2.serve_reference for this block: for each lane the
    reference's logits at the last prompt position and at each decoded
    one; `n_decode` is a count a lane. Lanes are padded to one length
    (both mixers are causal), so each layer kind compiles once."""
    import jax.numpy as jnp
    key = ref.seed_key(seed)
    width = -(-max(len(t) for t in lanes) // 128) * 128
    out = []
    for toks, n in zip(lanes, n_decode):
        padded = np.zeros((width,), np.int32)
        padded[:len(toks)] = toks
        rows = slice(len(toks) - n - 1, len(toks))
        out.append(tuple(
            np.asarray(ref.logits(key, dims, jnp.asarray(padded), p, rows))
            for p in ((prec,) if prec else ('float32', 'float32_default'))))
    return out


def check_decoded(sv, chunk):
    """How many tokens each compared lane of `correct` decodes: a step
    between any two prefill chunks of every lane opened after it, then
    `decode_tokens` steps of all together."""
    between = [-(-int(n) // chunk) - 1 for n in sv['prompt_tokens']]
    return [sum(between[i + 1:]) + int(sv['decode_tokens'])
            for i in range(len(between))]


class ServeSystem(gpt2.ServeSystem):
    def __init__(self, config, traffic, devices, seed, phases, rehearse):
        self.config, self.traffic = config, traffic
        self.devices, self.seed = devices, int(seed)
        self.phases, self.rehearse = phases, rehearse
        self.dims = ref.dims_of(config)

    def build(self):
        import jax
        import paddle_tpu as fluid
        from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
        from paddle_tpu.models import hybrid
        from paddle_tpu.serving import ServingEngine
        cfg = self.config
        fluid.flags.set_flags(cfg.get('flags', {}))
        hc = _hybrid_config(self.dims)
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            tokens = fluid.layers.data(
                'tokens', shape=[1, hc.max_len, 1], dtype='int64',
                append_batch_size=False)
            logits = hybrid.language_model_logits(tokens, hc)
        self.main = main
        self.phases.mark('build')

        exe = fluid.Executor(fluid.TPUPlace())
        with tempfile.TemporaryDirectory(prefix='bench_model_') as tmp:
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                put_seeded_weights(scope, hybrid.spec_from_config(hc),
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
        """gpt2's two warm requests, then `preroll_s` seconds of the
        cell's own traffic, left running (set-up, phase `warm`)."""
        gpt2.ServeSystem.warm_up(self, plan)
        seconds = float(self.traffic['params'].get('preroll_s', 0))
        if seconds > 0:
            self.preroll(seconds)
            self.phases.mark('warm')
            self.phases.detail.append(('preroll', seconds))

    def preroll(self, seconds):
        """`seconds` of the generator's law and rate from another stream
        of the seed, submitted when due and left running: the window
        opens on a system that already carries the streams a steady
        load leaves in flight, so a judged request's token gap does not
        depend on whether it came in the first seconds. A step's time
        follows the lanes in flight here (recurrent state and K/V are a
        third of its bytes), which an empty start would make the seed's
        business."""
        from harness import manifest
        pre = manifest.resolve(self.traffic['generator'])(
            self.traffic['params'], self.seed + 1, self.config, seconds)
        t0 = time.perf_counter()
        for r in pre['requests'][:pre['judged']]:
            time.sleep(max(0.0, t0 + r['due'] - time.perf_counter()))
            self.engine.submit(r['prompt'], max_new_tokens=r['max_new'])
        time.sleep(max(0.0, t0 + seconds - time.perf_counter()))

    def counters(self, slice_since=None):
        """gpt2's, and what the recurrent state counted: the lanes whose
        state the decode steps updated (a running total) and the bytes
        the state holds."""
        from paddle_tpu.obs import telemetry
        snap = telemetry.snapshot()
        c = gpt2.ServeSystem.counters(self, slice_since)
        c['state_lanes'] = snap['counters'].get('serving.state_lanes', 0)
        c['recurrent_state_bytes_max'] = \
            snap['gauges'].get('serving.recurrent_state_bytes', 0)
        c['state_resets'] = snap['gauges'].get('serving.state_resets', 0)
        return c

    def check(self):
        """As gpt2.ServeSystem.check, with this block's reference, at
        the occupancy the window runs at: `filler_streams` short streams
        are opened first and stay live, then the compared streams, the
        longest last, each prefilled chunk by chunk with one decode step
        of every lane already prefilled between any two chunks (so a
        chunk's state write lands while other lanes are mid-decode, and
        a step skips a lane that is mid-prefill), then `decode_tokens`
        steps of all TOGETHER. Each compared lane's prefill logits and
        every one of its decode logits against the reference's full
        forward of that stream, lane by lane."""
        self.stop_engine()
        dec, sv = self.dec, self.config['correct']
        for slot in list(dec.slot_tokens()):
            dec.release(slot)
        prompts = gpt2.serve_probe(self.seed, self.dims, sv['prompt_tokens'])
        slots = [i * dec.slots // len(prompts) for i in range(len(prompts))]
        rng = np.random.default_rng([self.seed, 11])
        lo, hi = sv.get('filler_tokens', (1, 1))
        fillers = [s for s in range(dec.slots) if s not in slots]
        fillers = fillers[:int(sv.get('filler_streams', 0))]
        seqs, got = {}, {s: [] for s in slots}
        tokens = np.zeros((dec.slots,), np.int64)
        positions = np.zeros((dec.slots,), np.int32)

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
            dec.open_stream(slot, prompt)
            while True:
                out = dec.prefill_step(slot, return_logits=True)
                if out is not None:
                    break
                if seqs:
                    decode()
            seqs[slot] = list(prompt) + [int(out[0])]
            if slot in got:
                got[slot].append(np.asarray(out[1]))

        for slot in fillers:
            prefill(slot, rng.integers(1, self.dims.vocab,
                                       size=int(rng.integers(lo, hi + 1))))
        for slot, prompt in zip(slots, prompts):
            prefill(slot, prompt)
        for _ in range(int(sv['decode_tokens'])):
            decode()
        for slot in list(seqs):
            dec.release(slot)
        decoded = [len(got[s]) - 1 for s in slots]
        refs = serve_reference(self.seed, self.dims,
                               [seqs[s][:-1] for s in slots], decoded)
        return gpt2.serve_comparisons(
            [np.stack(got[s]) for s in slots], [t for t, _ in refs],
            [s_ for _, s_ in refs], sv)


def build_serve(**kw):
    return ServeSystem(**kw).build()
