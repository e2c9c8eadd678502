"""Builder for the SDAR block with routed experts
(paddle_tpu/models/sdar_moe.py): a configuration file in, the serving
system under test out, through the program's public API and nothing
else:

    sdar_moe.language_model_logits -> save_inference_model ->
    AnalysisPredictor -> prepare_decoding(paged=True) -> ServingEngine.

The drive, the two warm requests and the pre-rolled load are those of
builders/gpt2.py and builders/olmo_hybrid.py; what differs is the model
built, where its seeded weights come from (reference/sdar_moe.py, a
layer at a time), a step probe that names a BLOCK pass a lane step
(gpt2._StepProbe names one by the tokens a stream gained, and four of a
block's five passes gain none), what the block steps count, and a check
that compares every pass's rows of every compared lane with the
reference's full forward over exactly the ids that pass was fed.
"""
from __future__ import annotations

import gc
import tempfile

import numpy as np

from builders import gpt2, olmo_hybrid, smallthinker
from reference import sdar_moe as ref

# the compared lanes of `correct`, in the order they are opened
LANES = ('mod0', 'mod1', 'mod2', 'mod3', 'long', 'parent', 'followup')


def _block():
    """models/sdar_moe; a program from before the block says so and
    leaves at once, with a message and exit code 1."""
    try:
        from paddle_tpu.models import sdar_moe
    except ImportError as e:
        raise SystemExit('this program cannot run the sdar_moe block: %s'
                         % (e,))
    return sdar_moe


def model_config(dims):
    return _block().SdarMoeConfig(
        vocab=dims.vocab, dim=dims.dim, heads=dims.heads,
        kv_heads=dims.kv_heads, head_dim=dims.head_dim, layers=dims.layers,
        rope_theta=dims.rope_theta, max_len=dims.positions,
        experts=dims.experts, experts_held=dims.held,
        expert_offset=dims.offset, top_k=dims.top_k,
        expert_ffn=dims.expert_ffn, eps=dims.eps, block_length=dims.block,
        denoising_steps=dims.steps, remasking=dims.rule,
        threshold=dims.threshold, mask_id=dims.mask_id)


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


class BlockProbe(gpt2._StepProbe):
    """gpt2._StepProbe for a decoder whose lanes hold blocks. The base
    names a step a lane step where `slot_tokens()` grew across the call;
    a block grows nothing in its denoising passes and B tokens at its
    commit, so the base would see a fifth of the steps and none of their
    rows. Here a pass's lanes and the tokens they held (committed and
    the block's own rows) are what the pass itself left behind:
    `block_stats()`'s lane-passes and live tokens, the same numbers as
    the `block_rows` / `live_tokens` attrs of its `paged.decode.tables`
    span. A chunk's rows are still the growth of a stream that stands
    inside its prompt's whole blocks. `slot_tokens()` keeps meaning
    committed tokens."""

    def __init__(self, dec, slice_s):
        gpt2._StepProbe.__init__(self, dec, slice_s)
        self._stats = dec.block_stats()

    def _see(self, t0, t1, before, after):
        dec = self._dec
        stats = dec.block_stats()
        lanes = stats['passes'] - self._stats['passes']
        live = stats['live_tokens'] - self._stats['live_tokens']
        self._stats = stats
        chunk = 0
        for slot, n in after.items():
            whole = self._prompt.get(slot, 0)
            whole -= whole % dec.block_tokens
            if before.get(slot, 0) < whole:
                chunk += n - before.get(slot, 0)
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
            self.moe_at.append((t1, dec.moe_counters()))
        while self.steps[0][1] < t1 - 2 * self.slice_s:
            self.steps.popleft()
        while self.moe_at[0][0] < t1 - 2 * self.slice_s:
            self.moe_at.popleft()
        self.pages_max = max(self.pages_max,
                             dec.pool_stats()['pages_in_use'])
        self.live_pages_max = max(self.live_pages_max, sum(
            -(-n // dec.page_tokens) for n in after.values()))
        for call in self.on_step:
            call()


def check_prompts(seed, dims, sv, page_tokens):
    """The compared streams' prompts by name (LANES, the order they are
    opened in): `mod0`..`mod3` of lengths 0, 1, 2 and 3 modulo the block
    length (the last tokens open the first block as fixed tokens),
    `long` of several chunks, `parent`, lengthened until it ends on a
    whole block half way into a page, and `followup`, that whole prompt
    and `followup_tokens` more: it opens on the parent's registered
    pages, the partly filled last one among them, and forks it."""
    rng = np.random.default_rng([int(seed), 9])
    out = {}
    for name, n in sv['lanes'].items():
        n = int(n)
        if name == 'parent':
            n += (page_tokens // 2 - n) % page_tokens
        if name.startswith('mod') and n % dims.block != int(name[3:]):
            raise ValueError('lane %s of %d tokens' % (name, n))
        out[name] = rng.integers(1, dims.vocab - 1, size=n)
    out['followup'] = np.concatenate([out['parent'], rng.integers(
        1, dims.vocab - 1, size=int(sv['followup_tokens']))])
    return [out[name] for name in LANES]


# the sequence lengths and the passes a lane the reference is compiled
# for: every compared lane is padded to the next of them, so that the
# reference compiles a few programs and not one a lane
_WIDTHS = (128, 512, 1024, 1536)
_PASSES = 8


def passes_reference(seed, dims, passes, prefill_row, prec, mask='block'):
    """The reference's logits for one lane's passes: `passes` are (ids
    so far [T], block start) as each pass of the lane was fed; every
    pass is a full forward of its own over exactly those ids (padded to
    one length: what lies behind a row's block it never sees), read at
    the block's rows. `prefill_row` (None: no chunk ran) is read from
    the first pass's forward. Returns [0 | 1 + passes * B, vocab]. The
    passes are padded to a multiple of _PASSES (the last one again) and
    the length to one of _WIDTHS, and every pass reads one row more (the
    prefill row, or its block's first again): a few shapes for all
    lanes."""
    key = ref.seed_key(seed)
    b, n = dims.block, len(passes)
    longest = max(len(ids) for ids, _ in passes)
    width = next((w for w in _WIDTHS if w >= longest),
                 ref.padded_length(longest))
    padded = list(passes) + [passes[-1]] * (-n % _PASSES)
    toks = np.zeros((len(padded), width), np.int32)
    for i, (ids, _) in enumerate(padded):
        toks[i, :len(ids)] = ids
    extra = padded[0][1] if prefill_row is None else prefill_row
    rows = np.stack([np.append(start + np.arange(b), extra)
                     for _, start in padded])
    out = np.asarray(ref.logits_rows(key, dims, toks, rows, prec, mask))
    blocks = out[:n, :b].reshape(-1, dims.vocab)
    if prefill_row is None:
        return blocks
    return np.concatenate([out[0, b:], blocks])


def uncommitted(passes, whole, dims):
    """`passes` ((ids so far, block start) of one lane, in order) as a
    program that LEFT OUT the commit pass would have held them: every
    generated block behind a pass's own stands as it was fed to its
    LAST DENOISING pass, some rows still the mask id (the K/V such a
    program leaves in the cache), not as it was committed. The prompt's
    whole blocks (`whole` tokens) were prefilled and stand."""
    b = dims.block
    stale = {}              # block start -> ids fed to its last denoise
    for ids, start in passes:
        block = [int(t) for t in ids[start:start + b]]
        if dims.mask_id in block:
            stale[start] = block
    out = []
    for ids, start in passes:
        seq = [int(t) for t in ids]
        for s, block in stale.items():
            if whole <= s < start:
                seq[s:s + b] = block
        out.append((seq, start))
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
            prefill_chunk=int(sv['prefill_chunk']))
        self.phases.note('prepare_decoding')
        jax.block_until_ready(jax.live_arrays())
        self.phases.note('device_transfers')
        self.probe = BlockProbe(self.dec, self.slice_s)
        self.engine = ServingEngine(self.dec).start()
        self._jax = jax
        self.phases.mark('weights')
        return self

    def counters(self, slice_since=None):
        """olmo_hybrid's (the step probe's among them: the slice's own
        counts and what the expert sublayers counted), the prefix
        cache's counters beside the prompt tokens admitted, and what the
        block steps counted: lane-passes, commits, tokens delivered,
        rows carried and rows that went in masked (running totals)."""
        from paddle_tpu.obs import telemetry
        c = olmo_hybrid.ServeSystem.counters(self, slice_since)
        snap = telemetry.snapshot()
        for key in ('prefix_hits', 'prefix_tokens_reused',
                    'prompt_tokens_admitted'):
            c[key] = snap['counters'].get('serving.' + key, 0)
        for key in ('passes', 'commits', 'tokens', 'rows', 'masked_rows'):
            c['block_' + key] = snap['counters'].get(
                'serving.block.' + key, 0)
        return c

    def check(self):
        """`filler_streams` streams are opened first and stay live; then
        the compared streams (check_prompts), each prefilled chunk by
        chunk with one block pass of every lane already prefilled
        between any two chunks, then passes of all TOGETHER, lanes at
        different passes of their blocks and lanes committing in the
        same step, until every compared lane has committed `blocks`
        blocks (later blocks read earlier commits). Each compared lane's
        prefill logits (its last chunk's last row) and EVERY pass's B
        rows against the reference's full forward over exactly the ids
        the pass was fed: fixed, unmasked and mask ids as they stood.
        Logits, never which row was unmasked. The pools are given up
        before the reference runs: it needs their room."""
        return self.compare(self.check_run())

    def check_run(self):
        """What the timed path produced for `correct` (drive_check's
        record, with the compared slots and their prompts)."""
        self.stop_engine()
        dec, sv = self.dec, self.config['correct']
        for slot in list(dec.slot_tokens()):
            dec.release(slot)
        prompts = check_prompts(self.seed, self.dims, sv, dec.page_tokens)
        slots = [i * dec.slots // len(prompts) for i in range(len(prompts))]
        rng = np.random.default_rng([self.seed, 11])
        lo, hi = sv['filler_tokens']
        fillers = [s for s in range(dec.slots) if s not in slots]
        fillers = fillers[:int(sv['filler_streams'])]
        run = drive_check(dec, fillers, [
            rng.integers(1, self.dims.vocab - 1,
                         size=int(rng.integers(lo, hi + 1)))
            for _ in fillers], slots, prompts, int(sv['blocks']))
        follow = LANES.index('followup')
        print('check: %d lanes a pass at the end; the follow-up stream '
              'opened on %d of its %d prompt tokens; pages in use %d'
              % (run['lanes'], run['shared'][slots[follow]],
                 len(prompts[follow]), dec.pool_stats()['pages_in_use']))
        for slot in list(dec.slot_tokens()):
            dec.release(slot)
        dec.reset()
        gc.collect()
        run.update(slots=slots, prompts=prompts)
        return run

    def reference(self, run, prec, passes_of=None, mask_of=None):
        """The reference's rows for every compared lane of `run`, in the
        order of its slots; `passes_of(lane index, passes)` and
        `mask_of(lane index)` where a control computes them wrongly."""
        out = []
        for i, slot in enumerate(run['slots']):
            passes = run['passes'][slot]
            out.append(passes_reference(
                self.seed, self.dims,
                passes_of(i, passes) if passes_of else passes,
                run['prefill_row'][slot], prec,
                mask_of(i) if mask_of else 'block'))
        return out

    def compare(self, run, got=None, truth=None, same=None, shared=None):
        """The comparisons of `correct` over a check_run() record; `got`
        (the timed path's rows where not given) against `truth` (the
        reference at highest) and `same` (at the program's own matmul
        precision), which are computed where not given."""
        sv, slots = self.config['correct'], run['slots']
        if got is None:
            got = [np.concatenate([r.reshape(-1, self.dims.vocab)
                                   for r in run['got'][s]]) for s in slots]
        truth = truth or self.reference(run, 'float32')
        same = same or self.reference(run, 'float32_default')
        # smallthinker's medians at the program's own precision (the
        # prefill rows over lanes; a pass's rows within each lane, worst
        # lane: a lane that went wrong alone is seen) and its share of
        # swapped rows. Against "highest" the median is over ALL
        # compared rows, not the worst lane's: that comparison is about
        # precision, which no lane has alone, and a lane's own median
        # moves with its prompt (one bf16 pass turns an expert choice of
        # some prompt token in 24 layers of 8 of 128, its K/V move, and
        # every later row of a SHORT stream reads 0.0125 where the other
        # lanes read 0.007: seed 5700043, PERF.md section 6, PR 57)
        checks = [c for c in smallthinker.comparisons(
            got, truth, same,
            dict(sv, row_median_rel_l2_to_highest=float('inf')))
            if c['name'] != 'row_median_rel_l2_to_highest']
        rows = np.concatenate([
            np.linalg.norm(g - t, axis=-1) / np.linalg.norm(t, axis=-1)
            for g, t in zip(got, truth)])
        name = 'all_rows_median_rel_l2_to_highest'
        checks.insert(2, {'name': name, 'value': float(np.median(rows)),
                          'limit': sv[name]})
        shared = shared or run['shared']
        follow = slots[LANES.index('followup')]
        # a follow-up that opened anywhere but on its parent's whole
        # blocks did not test the fork; a boundary inside a block holds
        # K/V that were computed without the rest of their block
        want = len(run['prompts'][LANES.index('parent')])
        checks.append({'name': 'followup_tokens_not_on_the_parent_end',
                       'value': float(abs(want - shared[follow])),
                       'limit': 0.0})
        checks.append({'name': 'prefix_boundary_inside_a_block',
                       'value': float(max(n % self.dims.block
                                          for n in shared.values())),
                       'limit': 0.0})
        return checks

    def controls(self, run, truth, same):
        """{control: the comparisons of `correct`} with the reference,
        computed wrongly, in the program's place, over the very passes
        the program made (the ids as it held them): stored in bfloat16;
        the mask causal inside a block; the commit pass left out (every
        generated block behind a pass stands as its last denoising pass
        was fed it); the follow-up's prefix adopted two tokens short of
        its parent's end, inside a block."""
        whole = {i: len(p) - len(p) % self.dims.block
                 for i, p in enumerate(run['prompts'])}
        follow = LANES.index('followup')
        cut = run['shared'][run['slots'][follow]] - 2
        arms = {
            'bfloat16': dict(prec='bfloat16'),
            'causal_in_block': dict(prec='float32_default',
                                    mask_of=lambda i: 'causal'),
            'commit_left_out': dict(
                prec='float32_default',
                passes_of=lambda i, p: uncommitted(p, whole[i], self.dims)),
            'misaligned_prefix': dict(
                prec='float32_default',
                mask_of=lambda i: ('misaligned', cut) if i == follow
                else 'block')}
        out = {}
        for name, kw in arms.items():
            shared = dict(run['shared'])
            if name == 'misaligned_prefix':
                shared[run['slots'][follow]] = cut
            out[name] = self.compare(run, self.reference(run, **kw), truth,
                                     same, shared)
        return out


def drive_check(dec, fillers, filler_prompts, slots, prompts, blocks):
    """The passes of `correct`, directly on the decoder (the engine is
    stopped): returns {'got': {slot: [prefill row [1, V]] + one [B, V]
    a pass}, 'passes': {slot: [(ids so far, block start) a pass]},
    'prefill_row': {slot: the row of the prefill logits or None},
    'shared': {slot: tokens it opened on}, 'lanes': lanes of the last
    pass}, for the compared `slots`; a compared lane is recorded until
    it has committed `blocks` blocks and steps on with the others."""
    b, sched = dec.block_tokens, dec.block_schedule
    tokens = np.zeros((dec.slots, b), np.int64)
    starts = np.zeros((dec.slots,), np.int32)
    transfer = np.zeros((dec.slots,), np.int32)
    live = {}                       # slot -> [committed ids, BlockState]
    got = {s: [] for s in slots}
    passes = {s: [] for s in slots}
    done = dict.fromkeys(slots, 0)
    prefill_row, shared = {}, {}

    def one_pass():
        plan = {}
        for slot, (seq, blk) in live.items():
            n, commit = blk.plan(sched)
            tokens[slot], starts[slot], transfer[slot] = \
                blk.ids, blk.start, n
            plan[slot] = (n, commit)
        ids, left, lg = dec.block_step(
            tokens, starts, transfer, list(live),
            commit=[s for s, (_, c) in plan.items() if c],
            return_logits=True)
        for slot, (n, commit) in plan.items():
            seq, blk = live[slot]
            if slot in got and done[slot] < blocks:
                passes[slot].append((seq + blk.ids, blk.start))
                got[slot].append(lg[slot])
            blk.ids = [int(t) for t in ids[slot]]
            if commit:
                seq.extend(blk.ids)
                live[slot][1] = dec.new_block(blk.start + b)
                if slot in done:
                    done[slot] += 1
            else:
                blk.passed(n, left[slot])

    def prefill(slot, prompt):
        shared[slot] = dec.open_stream(slot, prompt)['shared_tokens']
        while True:
            out = dec.prefill_step(slot, return_logits=True)
            if out is not None:
                break
            if live:
                one_pass()
        start, lg = out
        live[slot] = [[int(t) for t in prompt[:start.start]],
                      dec.new_block(start.start, start.tail)]
        if slot in got:
            prefill_row[slot] = None if lg is None else start.start - 1
            if lg is not None:
                got[slot].append(np.asarray(lg).reshape(1, -1))

    for slot, prompt in zip(fillers, filler_prompts):
        prefill(slot, prompt)
    for slot, prompt in zip(slots, prompts):
        prefill(slot, prompt)
    while min(done.values()) < blocks:
        one_pass()
    return {'got': got, 'passes': passes, 'prefill_row': prefill_row,
            'shared': shared, 'lanes': len(live)}


def build_serve(**kw):
    return ServeSystem(**kw).build()
