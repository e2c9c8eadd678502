"""Builder for the Nemotron-H block (paddle_tpu/models/nemotron_h.py): a
configuration file in, the serving system under test out, through the
program's public API and nothing else:

    nemotron_h.language_model_logits -> save_inference_model ->
    AnalysisPredictor -> prepare_decoding(paged=True) -> ServingEngine.

The drive, the warm-up with its pre-rolled load, the step probe and the
occupancy check are builders/olmo_hybrid.py's ServeSystem; what differs
is the model built, where its seeded weights come from
(reference/nemotron_h.py, a layer at a time), the reference the check
compares with, and what the expert layers and the state-space state
count.
"""
from __future__ import annotations

import gc
import tempfile

import numpy as np

from builders import gpt2, olmo_hybrid
from reference import nemotron_h as ref


def _block():
    """models/nemotron_h; a program from before the block says so and
    leaves at once, with a message and exit code 1."""
    try:
        from paddle_tpu.models import nemotron_h
    except ImportError as e:
        raise SystemExit('this program cannot run the nemotron_h block: '
                         '%s' % (e,))
    return nemotron_h


def model_config(dims):
    return _block().NemotronHConfig(
        vocab=dims.vocab, dim=dims.dim, heads=dims.heads,
        kv_heads=dims.kv_heads, head_dim=dims.head_dim,
        layer_types=dims.kinds, max_len=dims.positions,
        mamba_heads=dims.mamba_heads, mamba_head_dim=dims.mamba_head_dim,
        groups=dims.groups, state=dims.state, conv_kernel=dims.conv_kernel,
        chunk=dims.chunk, experts=dims.experts, experts_held=dims.held,
        expert_offset=dims.offset, top_k=dims.top_k,
        routed_scale=dims.scale, latent=dims.latent,
        expert_ffn=dims.expert_ffn, shared_ffn=dims.shared_ffn,
        eps=dims.eps)


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
    """builders/olmo_hybrid.serve_reference for this block: for each
    lane the reference's logits at the last prompt position and at each
    decoded one; `n_decode` is a count a lane. Lanes are padded to one
    length (every mixer is causal), so each layer kind compiles once."""
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
        nemotron_h = _block()
        cfg = self.config
        fluid.flags.set_flags(cfg.get('flags', {}))
        mc = model_config(self.dims)
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            tokens = fluid.layers.data(
                'tokens', shape=[1, mc.max_len, 1], dtype='int64',
                append_batch_size=False)
            logits = nemotron_h.language_model_logits(tokens, mc)
        self.main = main
        self.phases.mark('build')

        exe = fluid.Executor(fluid.TPUPlace())
        with tempfile.TemporaryDirectory(prefix='bench_model_') as tmp:
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                put_seeded_weights(scope, nemotron_h.spec_from_config(mc),
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

    def counters(self, slice_since=None):
        """olmo_hybrid's (what the expert layers counted is the step
        probe's: `moe_*` the decode program's running totals over the
        steps that have ended, `moe_prefill_*` the prefill program's,
        `slice_moe_*` both over the slice's seconds) and the bytes the
        state-space state holds."""
        from paddle_tpu.obs import telemetry
        c = olmo_hybrid.ServeSystem.counters(self, slice_since)
        c['ssm_state_bytes_max'] = telemetry.snapshot()['gauges'].get(
            'serving.ssm.state_bytes', 0)
        return c

    def check(self):
        """olmo_hybrid.ServeSystem.check with this block's reference
        (that method names its reference by a module global, so its
        drive is written out again here): `filler_streams` short streams
        are opened first and stay live, then the compared streams, the
        longest last, each prefilled chunk by chunk with one decode step
        of every lane already prefilled between any two chunks (so a
        chunk's state write lands while other lanes are mid-decode, and
        a step skips a lane that is mid-prefill: its state stays and its
        row chooses no expert), then `decode_tokens` steps of all
        TOGETHER. Each compared lane's prefill logits and every one of
        its decode logits against the reference's full forward of that
        stream, lane by lane."""
        self.stop_engine()
        dec, sv = self.dec, self.config['correct']
        for slot in list(dec.slot_tokens()):
            dec.release(slot)
        prompts = gpt2.serve_probe(self.seed, self.dims, sv['prompt_tokens'])
        slots = [i * dec.slots // len(prompts) for i in range(len(prompts))]
        rng = np.random.default_rng([self.seed, 11])
        lo, hi = sv['filler_tokens']
        fillers = [s for s in range(dec.slots) if s not in slots]
        fillers = fillers[:int(sv['filler_streams'])]
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
        refs = serve_reference(self.seed, self.dims,
                               [seqs[s][:-1] for s in slots],
                               [len(got[s]) - 1 for s in slots])
        return gpt2.serve_comparisons(
            [np.stack(got[s]) for s in slots], [t for t, _ in refs],
            [s_ for _, s_ in refs], sv)


def build_serve(**kw):
    return ServeSystem(**kw).build()
