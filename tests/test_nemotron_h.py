"""The Nemotron-H block (models/nemotron_h.py) at tiny widths with seeded
weights: its whole-sequence program and its paged serving pair against
the plain reference (benchmarks/reference/nemotron_h.py), the share of
the experts a chip holds against the uncut layer, the state-space
state's life through PagedDecodePredictor, the transpiler's reading of a
saved model, and the loud refusals where a stream's state is pages
only."""
import os
import sys

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
from paddle_tpu.models import nemotron_h
from paddle_tpu.models.transformer import build_verify_program
from paddle_tpu.obs import telemetry
from paddle_tpu.transpiler.decode_transpiler import (
    DecodeTranspileError, extract_decode_spec)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmarks'))
from reference import nemotron_h as ref           # noqa: E402
from builders import nemotron_h as builder        # noqa: E402

MODEL = {'vocab_size': 64, 'hidden_size': 32, 'num_attention_heads': 4,
         'num_key_value_heads': 2, 'head_dim': 8, 'expand': 2,
         'mamba_num_heads': 4, 'mamba_head_dim': 16, 'n_groups': 2,
         'ssm_state_size': 16, 'conv_kernel': 4, 'chunk_size': 8,
         'n_routed_experts': 4, 'router_experts': 16, 'expert_offset': 8,
         'num_experts_per_tok': 5, 'routed_scaling_factor': 2.5,
         'n_group': 1, 'n_shared_experts': 1, 'moe_latent_size': 16,
         'moe_intermediate_size': 24,
         'moe_shared_expert_intermediate_size': 40,
         'layer_norm_epsilon': 1e-5, 'time_step_min': 0.001,
         'time_step_max': 0.1, 'hybrid_override_pattern': 'MEM*EME*',
         'num_hidden_layers': 7, 'n_positions': 48,
         # wide enough weights that these narrow layers, the routed
         # experts among them, each move the logits by tens of percent
         'initializer_range': 0.3}
DIMS = ref.dims_of(MODEL)
SEED = 3600000011
# float32 both sides on the CPU; the program's chunked recurrence, its
# batched expert products and its fused gather/where orders differ from
# the reference's loops by rounding only. The bf16-stored control reads
# more than 30 times this.
TOL = 2e-5


def _build(tmp):
    cfg = builder.model_config(DIMS)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        tokens = fluid.layers.data('tokens', shape=[1, cfg.max_len, 1],
                                   dtype='int64', append_batch_size=False)
        logits = nemotron_h.language_model_logits(tokens, cfg)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        builder.put_seeded_weights(
            scope, nemotron_h.spec_from_config(cfg), DIMS, SEED)
        toks = np.random.default_rng(0).integers(
            1, DIMS.vocab, size=(1, cfg.max_len, 1))
        full, = exe.run(main, feed={'tokens': toks}, fetch_list=[logits])
        fluid.io.save_inference_model(str(tmp), ['tokens'], [logits], exe,
                                      main_program=main)
    pred = AnalysisPredictor(AnalysisConfig(str(tmp),
                                            place=fluid.CPUPlace()))
    return pred, toks[0, :, 0], full[0]


@pytest.fixture(scope='module')
def model(tmp_path_factory):
    return _build(tmp_path_factory.mktemp('nemotron_lm'))


@pytest.fixture(scope='module')
def reference_logits(model):
    return np.asarray(ref.logits(ref.seed_key(SEED), DIMS, model[1]))


def _decoder(pred, **kw):
    kw = dict(dict(slots=3, page_tokens=4, kv_pages=40,
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
    return dec.decode_step(tokens, positions, return_logits=True)[1][slot]


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


def test_a_chunk_lands_between_the_steps_of_running_lanes(model,
                                                         reference_logits):
    """A stream prefilled chunk by chunk while two others decode between
    its chunks: its chunks write one slot's state beside lanes in mid
    decode, and the steps skip the lane that is mid prefill (its state
    stays, its rows choose no expert). Every lane's logits are the
    reference's, then all three decode together."""
    pred, toks, _ = model
    dec = _decoder(pred, prefill_chunk=8)
    at = {0: 6, 2: 11}
    for slot, n in at.items():
        _prefill(dec, slot, toks[:n])
    tokens, positions = np.zeros(3, np.int64), np.zeros(3, np.int32)

    def step(lanes):
        for slot in lanes:
            tokens[slot], positions[slot] = toks[at[slot]], at[slot]
        lg = dec.decode_step(tokens, positions, return_logits=True)[1]
        for slot in lanes:
            assert ref.rel_l2(lg[slot], reference_logits[at[slot]]) < TOL
            at[slot] += 1

    dec.open_stream(1, toks[:19])                  # three chunks of 8
    out = None
    while out is None:
        out = dec.prefill_step(1, return_logits=True)
        if out is None:
            step([0, 2])
    assert ref.rel_l2(out[1], reference_logits[18]) < TOL
    at[1] = 19
    for _ in range(3):
        step([0, 1, 2])


def test_a_slot_is_reset_for_the_stream_that_takes_it(model,
                                                      reference_logits):
    pred, toks, _ = model
    dec = _decoder(pred)
    _prefill(dec, 0, toks[5:30])                   # leaves its state
    dec.release(0)
    got = _prefill(dec, 0, toks[:9])
    assert ref.rel_l2(got, reference_logits[8]) < TOL
    assert dec.pool_stats()['state_resets'] == 2


def test_save_and_restore_carry_the_state_with_the_pages(model,
                                                         reference_logits):
    pred, toks, _ = model
    dec = _decoder(pred)
    n = 13
    _prefill(dec, 2, toks[:n])
    snap = dec.save_stream(2)
    assert len(snap['state']) == 2 * DIMS.kinds.count('mamba')
    assert snap['state'][0].shape == (DIMS.mamba_heads,
                                      DIMS.mamba_head_dim, DIMS.state)
    dec.release(2)
    _prefill(dec, 2, toks[20:40])                  # another stream's state
    dec.release(2)
    dec.restore_stream(0, snap)                    # into another slot
    got = _decode(dec, 0, toks[n], n)
    assert ref.rel_l2(got, reference_logits[n]) < TOL


def test_the_transpiler_reads_the_model_back(model):
    spec = extract_decode_spec(model[0]._program)
    want = builder.model_config(DIMS)
    assert spec.kinds == DIMS.kinds
    assert spec.recurrent_layers == [0, 2, 5]
    assert spec.kv_layers == [3]
    assert spec.expert_layers == [1, 4, 6]
    assert vars(spec.cfg) == vars(want)
    assert (spec.heads, spec.kv_heads, spec.dh) == (4, 2, 8)
    assert spec.pool_shape(10, 4) == (10, 4, 2, 8)
    assert spec.state_shapes(3) == ((3, 4, 16, 16), (3, 3, 64 + 2 * 2 * 16))


def test_the_expert_layers_count_on_the_device(model):
    pred, toks, _ = model
    telemetry.enable()
    before = telemetry.counter('serving.moe.pairs').value
    dec = _decoder(pred)
    assert dec.moe_counters()['pairs'] == 0
    _prefill(dec, 1, toks[:21])                    # chunks of 16 and 5 rows
    for j in range(21, 24):
        _decode(dec, 1, toks[j], j)
    jax.block_until_ready(jax.live_arrays())
    c = dec.moe_counters()
    layers = DIMS.kinds.count('experts')
    assert c['layer_calls'] == 5 * layers
    assert c['decode.layer_calls'] == 3 * layers
    assert c['pairs_dropped'] == 0
    # 24 rows, 5 experts each of 16, 4 held: about 24 * 5 / 4 a layer
    assert 0 < c['pairs'] <= 24 * 4 * layers
    assert 0 < c['decode.experts_touched'] <= c['decode.pairs']
    assert telemetry.counter('serving.moe.pairs').value - before == c['pairs']


def test_counts_are_summed_once_whoever_asks(model):
    """The engine's thread queues a step's counts and takes one a step
    off the queue; a window's edge asks from another thread. More askers
    than cores, a short switch interval: no step is counted twice or
    lost."""
    import sys as _sys
    import threading
    pred, toks, _ = model
    dec = _decoder(pred)
    _prefill(dec, 0, toks[:9])
    stop = threading.Event()

    def ask():
        while not stop.is_set():
            dec.moe_counters()
    askers = [threading.Thread(target=ask) for _ in range(8)]
    interval = _sys.getswitchinterval()
    _sys.setswitchinterval(1e-5)
    try:
        for t in askers:
            t.start()
        for j in range(9, 40):
            _decode(dec, 0, toks[j], j)
    finally:
        stop.set()
        for t in askers:
            t.join(30)
        _sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in askers)
    jax.block_until_ready(jax.live_arrays())
    c = dec.moe_counters()
    layers = DIMS.kinds.count('experts')
    assert c['decode.layer_calls'] == 31 * layers
    assert c['layer_calls'] == 32 * layers


@pytest.mark.parametrize('what', ['verify', 'speculative', 'prefix',
                                  'export', 'mesh'])
def test_what_knows_state_as_pages_only_refuses_the_model(model, what):
    pred, toks, _ = model
    if what == 'verify':
        with pytest.raises(DecodeTranspileError, match='mamba'):
            build_verify_program(extract_decode_spec(pred._program),
                                 2, 3, 10, 4, 12)
    elif what == 'speculative':
        with pytest.raises(DecodeTranspileError, match='recurrent state'):
            pred.prepare_decoding(slots=2, page_tokens=4, kv_pages=40,
                                  speculative=True, spec_k=2,
                                  draft_layers=1)
    elif what == 'prefix':
        dec = _decoder(pred)
        _prefill(dec, 0, toks[:17])
        dec.release(0)
        _prefill(dec, 1, toks[:17])                # the same prompt again
        assert dec.pool_stats()['prefix_hits'] == 0
    elif what == 'export':
        with pytest.raises(DecodeTranspileError, match='page shipping'):
            _decoder(pred).export_prefix(toks[:17])
    else:
        with pytest.raises(DecodeTranspileError, match='mesh serving'):
            pred.prepare_decoding(slots=2, page_tokens=4, kv_pages=40,
                                  mesh='tp=2')


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts that the four shares of 4 experts each give,
    plus what every chip computes alike (the shared expert) counted
    once, are the uncut layer of the reference; through the program's op
    for the shares, and the reference's loop for the whole."""
    from paddle_tpu.ops import moe_ops
    key = ref.seed_key(SEED)
    whole = DIMS._replace(held=DIMS.experts, offset=0)
    p = ref.layer_weights(key, 1, 'experts', whole)
    u = jax.random.normal(jax.random.PRNGKey(5), (29, DIMS.dim))
    want = ref.experts_mixer(
        u, p, whole, 'float32', lambda e: ref.expert_weights(key, 1, e,
                                                             whole))
    lat = np.asarray(u @ p['down'])
    w_all = moe_ops.served_weights(u, p['router'], p['bias'], DIMS.top_k,
                                   DIMS.scale)
    assert (np.asarray(w_all != 0).sum(-1) == DIMS.top_k).all()
    total = np.zeros_like(lat)
    for offset in range(0, DIMS.experts, DIMS.held):
        share = DIMS._replace(offset=offset)
        w1, w2 = (ref.layer_tensors(key, 1, 'experts', share)[r]
                  for r in ('w1', 'w2'))
        part = moe_ops.held_experts(
            lat, w_all[:, offset:offset + DIMS.held], w1, w2)
        # the reference given the same share
        mine = ref.routed_part(
            u, p, share, 'float32',
            lambda e: ref.expert_weights(key, 1, e, share))
        assert ref.rel_l2(np.asarray(part), np.asarray(mine)) < TOL
        total += np.asarray(part)
    got = total @ np.asarray(p['up']) + np.asarray(
        ref.shared_part(u, p, whole, 'float32'))
    # float32 sums in another order: rounding only
    assert ref.rel_l2(got, np.asarray(want)) < TOL
