"""The Solar-Open2 block (models/solar_open2.py) at tiny widths with
seeded weights: its whole-sequence program and its paged serving pair
(pages, delta state and an adopted snapshot) against the plain
reference (benchmarks/reference/solar_open2.py), the bf16-stored
control, the sigmoid gate with its selection bias, the share of the
experts a chip holds against the uncut layer, the transpiler's reading
of a saved model, what refuses the family by name, and the step kernel
inside the decode program."""
import os
import sys

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
from paddle_tpu.models import solar_open2
from paddle_tpu.obs import telemetry
from paddle_tpu.transpiler.decode_transpiler import (
    DecodeTranspileError, extract_decode_spec)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmarks'))
from reference import solar_open2 as ref            # noqa: E402
from builders import solar_open2 as builder         # noqa: E402

MODEL = {'model_type': 'solar_open2', 'vocab_size': 64, 'hidden_size': 32,
         'num_attention_heads': 4, 'num_key_value_heads': 2, 'head_dim': 8,
         'linear_attn_config': {'short_conv_kernel_size': 4, 'head_dim': 16,
                                'num_heads': 2, 'num_kv_heads': None},
         'kda_gate_rank': 8, 'use_rope': False, 'use_gqa_gate': True,
         'kda_use_full_proj': False, 'kda_allow_neg_eigval': True,
         'gqa_layers': [0, 4, 8], 'num_hidden_layers': 5,
         'n_routed_experts': 4, 'router_experts': 16, 'expert_offset': 8,
         'num_experts_per_tok': 4, 'n_shared_experts': 1,
         'moe_intermediate_size': 24, 'norm_topk_prob': True,
         'routed_scaling_factor': 1, 'rms_norm_eps': 1e-5,
         'n_positions': 64,
         # wide enough weights that these narrow layers, the routed
         # experts among them, each move the logits by tens of percent
         'initializer_range': 0.3}
DIMS = ref.dims_of(MODEL)
SEED = 5200000011
# float32 both sides on the CPU; the program's chunked rule, its batched
# expert products and its fused gather/where orders differ from the
# reference's loops by rounding only. The bf16-stored control reads more
# than 30 times this.
TOL = 3e-5


def _build(tmp, dims=DIMS, seed=SEED):
    cfg = builder.model_config(dims)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        tokens = fluid.layers.data('tokens', shape=[1, cfg.max_len, 1],
                                   dtype='int64', append_batch_size=False)
        logits = solar_open2.language_model_logits(tokens, cfg)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        builder.put_seeded_weights(
            scope, solar_open2.spec_from_config(cfg), dims, seed)
        toks = np.random.default_rng(0).integers(
            1, dims.vocab, size=(1, cfg.max_len, 1))
        full, = exe.run(main, feed={'tokens': toks}, fetch_list=[logits])
        fluid.io.save_inference_model(str(tmp), ['tokens'], [logits], exe,
                                      main_program=main)
    pred = AnalysisPredictor(AnalysisConfig(str(tmp),
                                            place=fluid.CPUPlace()))
    return pred, toks[0, :, 0], full[0]


@pytest.fixture(scope='module')
def model(tmp_path_factory):
    return _build(tmp_path_factory.mktemp('solar2_lm'))


@pytest.fixture(scope='module')
def reference_logits(model):
    return np.asarray(ref.logits(ref.seed_key(SEED), DIMS, model[1]))


def _decoder(pred, **kw):
    kw = dict(dict(slots=3, page_tokens=4, kv_pages=60,
                   prefill_chunk=16), **kw)
    return pred.prepare_decoding(**kw)


def _prefill(dec, slot, prompt):
    dec.open_stream(slot, prompt)
    out = None
    while out is None:
        out = dec.prefill_step(slot, return_logits=True)
    return out[1]


def _decode(dec, slot, token, position):
    tokens = np.zeros(dec.slots, np.int64)
    positions = np.zeros(dec.slots, np.int32)
    tokens[slot], positions[slot] = token, position
    return dec.decode_step(tokens, positions, return_logits=True,
                           lanes=[slot])[1][slot]


def test_the_layers_run_start_on_attention():
    assert DIMS.kinds == ('full_attention', 'kda', 'kda', 'kda',
                          'full_attention')


def test_whole_sequence_program_is_the_reference(model, reference_logits):
    assert ref.rel_l2(model[2], reference_logits) < TOL


def test_chunked_prefill_then_decode_is_the_reference(model, reference_logits):
    pred, toks, _ = model
    dec = _decoder(pred)
    n = 21           # two chunks, the second padded; no multiple of 8
    rows = [_prefill(dec, 1, toks[:n])]
    rows += [_decode(dec, 1, toks[j], j) for j in range(n, n + 8)]
    assert ref.rel_l2(np.stack(rows), reference_logits[n - 1:n + 8]) < TOL


def test_the_bf16_stored_control_fails_the_tolerance(model, reference_logits):
    control = np.asarray(ref.logits(ref.seed_key(SEED), DIMS, model[1],
                                    'bfloat16'))
    assert ref.rel_l2(control[20:29], reference_logits[20:29]) > 30 * TOL


def test_the_transpiler_reads_the_model_back(model):
    spec = extract_decode_spec(model[0]._program)
    assert isinstance(spec, solar_open2.SolarOpen2DecodeSpec)
    assert spec.kinds == DIMS.kinds
    assert spec.recurrent_layers == [1, 2, 3] and spec.kv_layers == [0, 4]
    assert spec.expert_layers == [0, 1, 2, 3, 4]
    assert dict(vars(spec.cfg)) == dict(vars(builder.model_config(DIMS)))
    assert (spec.heads, spec.kv_heads, spec.dh) == (4, 2, 8)
    assert spec.pool_shape(10, 4) == (10, 4, 2, 8)
    assert spec.state_shapes(3) == ((3, 2, 16, 16), (3, 3, 2 * 48))
    assert spec.state_family == 'kda'
    assert len(set(spec.param_names())) == len(spec.param_names())


def test_the_pair_runs_the_rule_by_its_own_ops(model):
    pair = _decoder(model[0])._pair
    decode = [op.type for op in pair.decode_program.global_block().ops]
    prefill = [op.type for op in pair.prefill_program.global_block().ops]
    assert decode.count('kda_step') == 3 and 'kda_chunk' not in decode
    assert prefill.count('kda_chunk') == 3 and 'kda_step' not in prefill
    assert 'kv_page_cow' not in decode
    assert decode.count('moe_experts') == prefill.count('moe_experts') == 5
    assert not {'gated_delta_step', 'gated_delta_chunk'} \
        & set(decode + prefill)


@pytest.mark.parametrize('what, kw', [
    ('speculative decoding', dict(speculative=True, spec_k=2,
                                  draft_layers=1)),
    ('mesh', dict(mesh='tp=2'))])
def test_what_knows_state_as_pages_only_refuses_the_family(model, what, kw):
    with pytest.raises(DecodeTranspileError, match='kda'):
        _decoder(model[0], **kw)


def test_page_shipping_refuses_the_family(model):
    dec = _decoder(model[0])
    with pytest.raises(DecodeTranspileError, match='kda'):
        dec.export_prefix(model[1][:9])


def test_save_and_restore_carry_the_state_with_the_pages(model):
    """As for the Olmo hybrid: a preempted stream's delta state and
    convolution rows go to the host with its pages and come back."""
    pred, toks, _ = model
    dec = _decoder(pred)
    n = 22
    _prefill(dec, 0, toks[:n])
    _decode(dec, 0, toks[n], n)
    snap = dec.save_stream(0)
    assert len(snap['state']) == 2 * DIMS.kinds.count('kda')
    want = _decode(dec, 0, toks[n + 1], n + 1)
    dec.release(0)
    _prefill(dec, 0, toks[5:30])         # another stream soils slot 0
    dec.restore_stream(2, snap)          # and the snapshot moves to slot 2
    got = _decode(dec, 2, toks[n + 1], n + 1)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_the_sigmoid_gate_with_its_bias_is_the_reference_formula():
    from paddle_tpu.ops import moe_ops
    key = ref.seed_key(SEED)
    p = ref.layer_weights(key, 1, 'kda', DIMS)
    assert np.abs(np.asarray(p['bias'])).min() > 0
    u = jax.random.normal(jax.random.PRNGKey(3), (37, DIMS.dim))
    w = np.asarray(moe_ops.served_weights(u, p['router'], p['bias'],
                                          DIMS.top_k, DIMS.scale))
    idx, g = (np.asarray(a) for a in ref.route(u, p, DIMS))
    want = np.zeros_like(w)
    np.put_along_axis(want, idx, g, axis=-1)
    assert ((w != 0).sum(-1) == DIMS.top_k).all()
    np.testing.assert_allclose(w, want, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-6)
    # the bias chooses and does not weigh: without it other experts win
    plain = np.asarray(moe_ops.served_weights(u, p['router'], None,
                                              DIMS.top_k, DIMS.scale))
    assert ((plain != 0) != (w != 0)).any()


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts that the four shares of 4 experts each give,
    plus what every chip computes alike (the shared expert) counted
    once, are the uncut expert sublayer of the reference; through the
    program's op for the shares, and the reference's loop for the
    whole."""
    from paddle_tpu.ops import moe_ops
    key = ref.seed_key(SEED)
    whole = DIMS._replace(held=DIMS.experts, offset=0)
    p = ref.layer_weights(key, 1, 'kda', whole)
    u = jax.random.normal(jax.random.PRNGKey(5), (29, DIMS.dim))
    want = ref.routed_part(
        u, p, whole, 'float32',
        lambda e: ref.expert_weights(key, 1, e, whole)) \
        + ref.shared_part(u, p, whole, 'float32')
    w_all = moe_ops.served_weights(u, p['router'], p['bias'], DIMS.top_k,
                                   DIMS.scale)
    total = np.zeros(u.shape, np.float32)
    for offset in range(0, DIMS.experts, DIMS.held):
        share = DIMS._replace(offset=offset)
        w1, w3, w2 = (ref.layer_tensors(key, 1, 'kda', share)[r]
                      for r in ('w1', 'w3', 'w2'))
        part = moe_ops.held_gated_experts(
            u, w_all[:, offset:offset + DIMS.held], w1, w3, w2)
        # the reference given the same share
        mine = ref.routed_part(
            u, p, share, 'float32',
            lambda e: ref.expert_weights(key, 1, e, share))
        assert ref.rel_l2(np.asarray(part), np.asarray(mine)) < TOL
        total += np.asarray(part)
    got = total + np.asarray(ref.shared_part(u, p, whole, 'float32'))
    # float32 sums in another order: rounding only
    assert ref.rel_l2(got, np.asarray(want)) < TOL


def test_the_seeded_decays_differ_between_the_channels_of_a_head():
    p = ref.layer_weights(ref.seed_key(SEED), 1, 'kda', DIMS)
    dt = np.log1p(np.exp(np.asarray(p['dt_bias']))).reshape(
        DIMS.kda_heads, DIMS.key_dim)
    alpha = np.exp(-np.exp(np.asarray(p['a_log']))[:, None] * dt)
    assert alpha.min() > 0.85 and alpha.max() < 0.9995
    assert (alpha.max(-1) - alpha.min(-1)).min() > 0.02


# -- a prefix that is pages and state -----------------------------------------

@pytest.mark.parametrize('first, more', [
    (20, 9),        # the boundary on a page's edge, inside a chunk
    (22, 11),       # inside a page (4 tokens) and inside a chunk (16)
    (32, 7),        # on a chunk's edge
    (16, 30)])      # a system prompt of whole pages, two chunks behind it
def test_a_stream_opens_on_the_snapshot_a_shared_prompt_left(
        model, reference_logits, first, more):
    """The stream's logits are the whole prompt's: against the
    reference, and against the same prompt prefilled whole by a decoder
    that keeps no snapshot."""
    pred, toks, _ = model
    dec = _decoder(pred, snapshot_rows=4)
    _prefill(dec, 0, toks[:first])
    dec.release(0)
    _prefill(dec, 1, toks[7:30])        # foreign state in the slot
    dec.release(1)
    plan = dec.open_stream(1, toks[:first + more])
    assert plan['shared_tokens'] == first
    out = None
    while out is None:
        out = dec.prefill_step(1, return_logits=True)
    rows = [out[1]] + [_decode(dec, 1, toks[j], j)
                       for j in range(first + more, first + more + 4)]
    want = reference_logits[first + more - 1:first + more + 4]
    assert ref.rel_l2(np.stack(rows), want) < TOL
    cold = _decoder(pred)
    whole = [_prefill(cold, 1, toks[:first + more])]
    whole += [_decode(cold, 1, toks[j], j)
              for j in range(first + more, first + more + 4)]
    assert ref.rel_l2(np.stack(rows), np.stack(whole)) < TOL
    assert dec.pool_stats()['prefix_hits'] == 1


def test_pages_without_their_state_would_not_pass(model, reference_logits):
    pred, toks, _ = model
    first, more = 22, 11
    dec = _decoder(pred, snapshot_rows=4)
    _prefill(dec, 0, toks[:first])
    dec.release(0)
    for name in dec._pair.snapshot_names:
        snap = np.array(dec._scope.find_var(name))
        dec._scope.set_var(name, np.zeros_like(snap))
    dec.open_stream(1, toks[:first + more])
    out = None
    while out is None:
        out = dec.prefill_step(1, return_logits=True)
    assert ref.rel_l2(out[1], reference_logits[first + more - 1]) > 100 * TOL


def test_many_readers_adopt_one_system_prompt_through_the_engine(
        model, reference_logits):
    """The cell's traffic in small: a system prompt prefilled alone,
    then three single turns behind it at once through the engine; each
    adopts, none compiles anything after the first, and the counters
    say what happened."""
    from paddle_tpu.serving import ServingEngine
    pred, toks, _ = model
    telemetry.enable()
    before = telemetry.snapshot()['counters']
    dec = _decoder(pred, snapshot_rows=4)
    eng = ServingEngine(dec).start()
    try:
        eng.submit(toks[:16], max_new_tokens=1).result(120)
        reqs = [eng.submit(np.concatenate([toks[:16], toks[20 + i:27 + i]]),
                           max_new_tokens=4) for i in range(3)]
        outs = [r.result(120) for r in reqs]
    finally:
        eng.stop()
    assert all(len(o) == 4 for o in outs)
    moe = dec.moe_counters()        # brings serving.moe.* up to date
    assert moe['decode.layer_calls'] % 5 == 0 and moe['decode.pairs'] > 0
    after = telemetry.snapshot()
    delta = {k: after['counters'].get(k, 0) - before.get(k, 0)
             for k in ('serving.state.snapshots_adopted',
                       'serving.prefix_tokens_reused',
                       'serving.state_chunk_tokens',
                       'serving.moe.decode.layer_calls')}
    assert delta['serving.state.snapshots_adopted'] == 3
    assert delta['serving.prefix_tokens_reused'] == 3 * 16
    assert delta['serving.state_chunk_tokens'] == 16 + 3 * 7
    assert delta['serving.moe.decode.layer_calls'] > 0
    # the family's gauge beside the generic one: the same bytes
    assert after['gauges']['serving.kda.state_bytes'] == \
        after['gauges']['serving.recurrent_state_bytes'] == \
        3 * 3 * 4 * (2 * 16 * 16 + 3 * 96)


@pytest.mark.parametrize('readers', [1, 2])
def test_a_shared_prefix_outlives_the_requests_that_run_over_it(model,
                                                                readers):
    """Three snapshot rows, two system prompts, each prefilled alone and
    then opened on by `readers` short turns (what the cell's set-up
    does), then single turns behind them in turn, each leaving a
    boundary of its own that nobody will read. A boundary that ONE
    stream has run over is a conversation that moved on: the first
    prompt's row goes to the second prompt's reader, and no later turn
    finds it. Two readers make it a shared prefix: every turn opens on
    its prompt, and the requests' own boundaries take turns in the
    third row."""
    pred, toks, _ = model
    dec = _decoder(pred, snapshot_rows=3)
    system = [toks[:16], toks[40:56]]
    for prompt in system:
        for turn in [prompt] + [np.concatenate([prompt, [1 + j], toks[:4]])
                                for j in range(readers)]:
            _prefill(dec, 0, turn)
            dec.release(0)
    shared = []
    for i in range(6):
        plan = dec.open_stream(1, np.concatenate([system[i % 2],
                                                  toks[20 + i:29 + i]]))
        shared.append(plan['shared_tokens'])
        out = None
        while out is None:
            out = dec.prefill_step(1)
        dec.release(1)
    assert (shared == [16] * 6) if readers == 2 else (0 in shared)
    assert dec.pool_stats()['snapshots'] == 3


def test_rows_go_spent_first_then_unshared_then_shared():
    """PrefixCache.register_state's order when every row is taken, on
    the host alone: a boundary one stream ran over, then the least
    recently used of those fewer than two streams opened on, and a
    shared prefix last, however long ago it was read."""
    from paddle_tpu.serving.paging import PagePool, PageTable, PrefixCache
    pool = PagePool(64, 4)
    cache = PrefixCache(pool, snapshot_rows=3)

    def turn(prompt):
        table = PageTable(pool, 8)
        pages, shared, snap = cache.match_state(prompt, len(prompt) - 1)
        if shared:
            table.adopt_shared(pages, shared)
            cache.unpin(snap)
        table.ensure(len(prompt))
        table.length = len(prompt)
        row = cache.register_state(prompt, table)
        table.release()
        return shared, row

    system, other = list(range(1, 9)), list(range(101, 109))
    assert turn(system) == (0, 2)                   # rows pop from the end
    assert turn(system + [9, 10])[0] == 8           # one reader: spent
    assert turn(system + [11, 12, 13])[0] == 8      # two: shared
    # full: the unread ends go, oldest first, not the system prompt
    assert turn(other) == (0, 1)
    assert turn(system + [14])[0] == 8
    # one stream runs over `other`: spent, it goes before any unread end
    assert turn(other + [109])[0] == 8
    assert turn(list(range(201, 206)))[0] == 0
    assert turn(other + [110])[0] == 0              # `other` is gone
    assert turn(system + [15, 16])[0] == 8          # the shared one is not


# -- the step kernel inside the decode program --------------------------------

KERNEL_MODEL = dict(MODEL, num_hidden_layers=2,
                    linear_attn_config={'short_conv_kernel_size': 4,
                                        'head_dim': 128, 'num_heads': 2,
                                        'num_kv_heads': None},
                    n_positions=32)


def test_the_decode_program_takes_the_step_kernel_under_the_flag(
        tmp_path):
    dims = ref.dims_of(KERNEL_MODEL)
    pred, toks, _ = _build(tmp_path, dims, SEED + 1)
    want = np.asarray(ref.logits(ref.seed_key(SEED + 1), dims, toks))
    fluid.set_flags({'pallas_interpret': True})
    try:
        dec = _decoder(pred, slots=2)
        rows = [_prefill(dec, 1, toks[:9])]
        rows += [_decode(dec, 1, toks[j], j) for j in range(9, 12)]
    finally:
        fluid.set_flags({'pallas_interpret': False})
    assert ref.rel_l2(np.stack(rows), want[8:12]) < TOL
