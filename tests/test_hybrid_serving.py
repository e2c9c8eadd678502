"""The hybrid block (models/hybrid.py) at tiny widths with seeded weights:
its whole-sequence program and its paged serving pair against the plain
reference (benchmarks/reference/olmo_hybrid.py), the recurrent state's
life through PagedDecodePredictor, the transpiler's reading of a saved
model, and the loud refusals where a stream's state is pages only."""
import os
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
from paddle_tpu.models import hybrid
from paddle_tpu.models.transformer import build_verify_program
from paddle_tpu.transpiler.decode_transpiler import (
    DecodeTranspileError, extract_decode_spec)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmarks'))
from reference import olmo_hybrid as ref          # noqa: E402
from builders import olmo_hybrid as builder       # noqa: E402

KINDS = ['linear_attention', 'linear_attention', 'full_attention',
         'linear_attention', 'full_attention']
MODEL = {'vocab_size': 64, 'hidden_size': 32, 'num_attention_heads': 2,
         'num_key_value_heads': 2, 'linear_num_key_heads': 2,
         'linear_num_value_heads': 2, 'linear_key_head_dim': 8,
         'linear_value_head_dim': 16, 'linear_conv_kernel_dim': 4,
         'linear_allow_neg_eigval': True, 'intermediate_size': 48,
         'rms_norm_eps': 1e-6, 'num_hidden_layers': len(KINDS),
         'layer_types': KINDS, 'n_positions': 48}
DIMS = ref.dims_of(MODEL)
SEED = 2600000011
# float32 both sides on the CPU; the program's chunked rule and its
# fused gather/where orders differ from the reference's token loop by
# rounding only. The bf16-stored control reads 30 to 100 times this.
TOL = 2e-5


def _build(tmp):
    cfg = builder._hybrid_config(DIMS)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        tokens = fluid.layers.data('tokens', shape=[1, cfg.max_len, 1],
                                   dtype='int64', append_batch_size=False)
        logits = hybrid.language_model_logits(tokens, cfg)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        builder.put_seeded_weights(scope, hybrid.spec_from_config(cfg), DIMS,
                                   SEED)
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
    return _build(tmp_path_factory.mktemp('hybrid_lm'))


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
    n = 21                               # two chunks, the second padded
    rows = [_prefill(dec, 1, toks[:n])]
    rows += [_decode(dec, 1, toks[j], j) for j in range(n, n + 8)]
    assert ref.rel_l2(np.stack(rows), reference_logits[n - 1:n + 8]) < TOL


def test_the_bf16_stored_control_fails_the_tolerance(model, reference_logits):
    control = np.asarray(ref.logits(ref.seed_key(SEED), DIMS, model[1],
                                    'bfloat16'))
    assert ref.rel_l2(control[20:29], reference_logits[20:29]) > 30 * TOL


def test_lanes_decode_together_each_from_its_own_state(model,
                                                       reference_logits):
    pred, toks, _ = model
    dec = _decoder(pred)
    starts = {0: 5, 2: 18}
    for slot, n in starts.items():
        _prefill(dec, slot, toks[:n])
    tokens, positions = np.zeros(3, np.int64), np.zeros(3, np.int32)
    for step in range(4):
        for slot, n in starts.items():
            tokens[slot], positions[slot] = toks[n + step], n + step
        lg = dec.decode_step(tokens, positions, return_logits=True)[1]
        for slot, n in starts.items():
            assert ref.rel_l2(lg[slot], reference_logits[n + step]) < TOL


def test_a_chunk_lands_between_the_steps_of_running_lanes(model,
                                                         reference_logits):
    """A stream prefilled chunk by chunk while two others decode between
    its chunks: its chunks write one slot's state beside lanes in mid
    decode, and the steps skip the lane that is mid prefill. Every
    lane's logits are the reference's, then all three decode together."""
    pred, toks, _ = model
    dec = _decoder(pred, prefill_chunk=8)
    at = {0: 6, 2: 11}
    for slot, n in at.items():
        _prefill(dec, slot, toks[:n])
    tokens, positions = np.zeros(3, np.int64), np.zeros(3, np.int32)

    def step():
        for slot, n in at.items():
            tokens[slot], positions[slot] = toks[n], n
        lg = dec.decode_step(tokens, positions, return_logits=True)[1]
        for slot, n in at.items():
            assert ref.rel_l2(lg[slot], reference_logits[n]) < TOL, slot
            at[slot] = n + 1

    n, chunks = 29, 0                    # four chunks, the last padded
    dec.open_stream(1, toks[:n])
    while True:
        out = dec.prefill_step(1, return_logits=True)
        chunks += 1
        if out is not None:
            break
        step()
    assert chunks == 4
    assert ref.rel_l2(out[1], reference_logits[n - 1]) < TOL
    at[1] = n
    for _ in range(3):
        step()


def test_a_slot_released_and_opened_again_starts_from_zero(model):
    pred, toks, _ = model
    dec = _decoder(pred)
    first = _prefill(dec, 1, toks[:19])
    for j in range(19, 25):              # leave state behind
        _decode(dec, 1, toks[j], j)
    dec.release(1)
    again = _prefill(dec, 1, toks[:19])
    np.testing.assert_array_equal(again, first)
    assert dec.pool_stats()['state_resets'] == 2


def test_save_and_restore_carry_the_state_with_the_pages(model):
    pred, toks, _ = model
    dec = _decoder(pred)
    n = 22
    _prefill(dec, 0, toks[:n])
    _decode(dec, 0, toks[n], n)
    snap = dec.save_stream(0)
    assert len(snap['state']) == 2 * KINDS.count('linear_attention')
    want = _decode(dec, 0, toks[n + 1], n + 1)
    dec.release(0)
    _prefill(dec, 0, toks[5:30])         # another stream soils slot 0
    dec.restore_stream(2, snap)          # and the snapshot moves to slot 2
    got = _decode(dec, 2, toks[n + 1], n + 1)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_a_repeated_prompt_gives_the_same_logits(model):
    """The prefix cache hands out nothing for a model with recurrent
    state: the second stream prefills every token again."""
    pred, toks, _ = model
    dec = _decoder(pred)
    first = _prefill(dec, 0, toks[:27])
    dec.release(0)
    plan = dec.open_stream(1, toks[:27])
    assert plan['shared_tokens'] == 0
    out = None
    while out is None:
        out = dec.prefill_step(1, return_logits=True)
    np.testing.assert_array_equal(out[1], first)
    assert dec.pool_stats()['prefix_entries'] == 0


@pytest.mark.parametrize('first, more', [(22, 11), (16, 20)])
def test_with_snapshot_rows_a_turn_reopens_on_pages_and_state(
        model, reference_logits, first, more):
    """The same model given snapshot rows (a size of the deployment;
    test_a_repeated_prompt_gives_the_same_logits is the 0 it has without
    them): the delta rule's state and its convolution rows are copied
    like any other family's, and a second turn opened on them gives the
    whole conversation's logits, the boundary inside a page or on one."""
    pred, toks, _ = model
    dec = _decoder(pred, snapshot_rows=3)
    _prefill(dec, 0, toks[:first])
    dec.release(0)
    plan = dec.open_stream(1, toks[:first + more])
    assert plan['shared_tokens'] == first
    out = None
    while out is None:
        out = dec.prefill_step(1, return_logits=True)
    rows = [out[1]] + [_decode(dec, 1, toks[j], j)
                       for j in range(first + more, first + more + 3)]
    want = reference_logits[first + more - 1:first + more + 3]
    assert ref.rel_l2(np.stack(rows), want) < TOL
    stats = dec.pool_stats()
    assert stats['prefix_hits'] == 1 and stats['state_resets'] == 1
    assert stats['snapshots'] == 2 and stats['snapshot_rows'] == 3


def test_pool_stats_report_the_state_beside_the_pages(model):
    dec = _decoder(model[0])
    n_lin = KINDS.count('linear_attention')
    want = 4 * n_lin * 3 * (2 * 8 * 16 + 3 * 2 * (2 * 8 + 16))
    assert dec.pool_stats()['recurrent_state_bytes'] == want
    # pools for the two attention layers only
    assert len(dec._pair.cache_names) == 2 * KINDS.count('full_attention')
    assert len(dec._pair.state_names) == 2 * n_lin


def test_transpiler_recovers_the_layer_kinds_from_a_saved_model(model):
    spec = extract_decode_spec(model[0]._program)
    assert list(spec.kinds) == KINDS
    assert spec.recurrent_layers == [0, 1, 3] and spec.kv_layers == [2, 4]
    assert (spec.heads, spec.key_dim, spec.value_dim, spec.conv_kernel,
            spec.beta_scale) == (2, 8, 16, 4, 2.0)
    assert (spec.vocab, spec.dim, spec.ffn, spec.max_len) == (64, 32, 48, 48)
    assert spec.blocks[0]['conv'] == 'layer0.conv.w'
    assert spec.blocks[2]['q_norm'] == 'layer2.q_norm.w'
    assert spec.pool_heads == 8         # whole tiles of heads in a page


def test_engine_serves_the_hybrid_model(model):
    from paddle_tpu.serving import ServingEngine
    pred, toks, _ = model
    solo = _decoder(pred).generate(toks[:13], 6)
    engine = ServingEngine(_decoder(pred)).start()
    try:
        handles = [engine.submit(toks[:13], max_new_tokens=6),
                   engine.submit(toks[3:30], max_new_tokens=4)]
        outs = [list(h.result(120)) for h in handles]
    finally:
        engine.stop(drain=True, timeout=5.0)
    assert outs[0] == list(solo) and len(outs[1]) == 4


def _refusals():
    def speculative(pred):
        pred.prepare_decoding(slots=2, speculative=True, spec_k=2,
                              draft_layers=1, page_tokens=4, kv_pages=20,
                              prefill_chunk=16)

    def verify(pred):
        build_verify_program(extract_decode_spec(pred._program), 2, 3, 20,
                             4, 12)

    def mesh(pred):
        _decoder(pred, mesh='tp=2')

    def export(pred):
        _decoder(pred).export_prefix(list(range(1, 20)))

    def install(pred):
        _decoder(pred).install_prefix(list(range(1, 20)), ['00'], [])

    return [speculative, verify, mesh, export, install]


@pytest.mark.parametrize('ask', _refusals(), ids=lambda f: f.__name__)
def test_what_knows_state_as_pages_only_refuses_by_the_layer_kind(model, ask):
    with pytest.raises(DecodeTranspileError, match='linear_attention'):
        ask(model[0])
