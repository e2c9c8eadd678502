"""The GraniteMoeHybrid block (models/granite_h.py) at tiny widths with
seeded weights: its whole-sequence program and its paged serving pair
against the plain reference (benchmarks/reference/granite_h.py), the
softmax-over-chosen gate, the share of the experts a chip holds against
the uncut layer, the transpiler's reading of a saved model, and a
prefix that is pages AND state: turns that reopen on a snapshot of the
recurrent state, its eviction, its save and restore, and that none of it
compiles anything."""
import os
import sys

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
from paddle_tpu.models import granite_h
from paddle_tpu.obs import telemetry
from paddle_tpu.transpiler.decode_transpiler import (
    DecodeTranspileError, extract_decode_spec)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmarks'))
from reference import granite_h as ref            # noqa: E402
from builders import granite_h as builder         # noqa: E402

MODEL = {'vocab_size': 64, 'hidden_size': 32, 'num_attention_heads': 4,
         'num_key_value_heads': 2, 'mamba_expand': 2,
         'mamba_n_heads': 4, 'mamba_d_head': 16, 'mamba_n_groups': 1,
         'mamba_d_state': 16, 'mamba_d_conv': 4, 'mamba_chunk_size': 8,
         'num_local_experts': 4, 'router_experts': 16, 'expert_offset': 8,
         'num_experts_per_tok': 5, 'intermediate_size': 24,
         'shared_intermediate_size': 40, 'rms_norm_eps': 1e-5,
         'embedding_multiplier': 12, 'residual_multiplier': 0.22,
         'attention_multiplier': 0.0078125, 'logits_scaling': 16,
         'position_embedding_type': 'nope',
         'layer_types': ['mamba', 'mamba', 'attention', 'mamba', 'mamba'],
         'num_hidden_layers': 4, 'n_positions': 64,
         # wide enough weights that these narrow layers, the routed
         # experts among them, each move the logits by tens of percent
         'initializer_range': 0.3}
DIMS = ref.dims_of(MODEL)
SEED = 4500000011
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
        logits = granite_h.language_model_logits(tokens, cfg)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        builder.put_seeded_weights(
            scope, granite_h.spec_from_config(cfg), DIMS, SEED)
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
    return _build(tmp_path_factory.mktemp('granite_lm'))


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
    want = builder.model_config(DIMS)
    assert isinstance(spec, granite_h.GraniteHDecodeSpec)
    assert spec.kinds == ('mamba', 'mamba', 'full_attention', 'mamba')
    assert spec.recurrent_layers == [0, 1, 3]
    assert spec.kv_layers == [2]
    got, expect = dict(vars(spec.cfg)), dict(vars(want))
    for key in ('embedding_multiplier', 'residual_multiplier',
                'attention_multiplier', 'logits_scaling'):
        assert got.pop(key) == pytest.approx(expect.pop(key), rel=1e-6)
    assert got == expect
    assert spec.sm_scale == 0.0078125
    assert (spec.heads, spec.kv_heads, spec.dh) == (4, 2, 8)
    assert spec.pool_shape(10, 4) == (10, 4, 2, 8)
    assert spec.state_shapes(3) == ((3, 4, 16, 16), (3, 3, 64 + 2 * 16))
    # the head is the embedding: one parameter, named once
    assert spec.head[0] == spec.emb_w
    assert len(set(spec.param_names())) == len(spec.param_names())


def test_no_page_is_copied_inside_the_decode_program(model):
    pair = _decoder(model[0])._pair
    ops = [op.type for op in pair.decode_program.global_block().ops]
    assert 'kv_page_cow' not in ops and 'ssd_step' in ops


def test_softmax_over_the_chosen_is_the_reference_formula():
    from paddle_tpu.ops import moe_ops
    key = ref.seed_key(SEED)
    p = ref.layer_weights(key, 0, 'mamba', DIMS)
    u = jax.random.normal(jax.random.PRNGKey(3), (37, DIMS.dim))
    w = np.asarray(moe_ops.served_weights(u, p['router'], None, DIMS.top_k,
                                          1.0, gate='softmax'))
    idx, g = (np.asarray(a) for a in ref.route(u, p, DIMS))
    want = np.zeros_like(w)
    np.put_along_axis(want, idx, g, axis=-1)
    assert ((w != 0).sum(-1) == DIMS.top_k).all()
    np.testing.assert_allclose(w, want, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-6)
    # the sigmoid gate is what it was: other weights, the same ranking
    s = np.asarray(moe_ops.served_weights(u, p['router'],
                                          np.zeros(DIMS.experts, np.float32),
                                          DIMS.top_k, 1.0))
    assert ((s != 0) == (w != 0)).all() and not np.allclose(s, w)


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts that the four shares of 4 experts each give,
    plus what every chip computes alike (the shared expert) counted
    once, are the uncut expert sublayer of the reference; through the
    program's op for the shares, and the reference's loop for the
    whole."""
    from paddle_tpu.ops import moe_ops
    key = ref.seed_key(SEED)
    whole = DIMS._replace(held=DIMS.experts, offset=0)
    p = ref.layer_weights(key, 1, 'mamba', whole)
    u = jax.random.normal(jax.random.PRNGKey(5), (29, DIMS.dim))
    want = ref.routed_part(
        u, p, whole, 'float32',
        lambda e: ref.expert_weights(key, 1, e, whole)) \
        + ref.shared_part(u, p, whole, 'float32')
    w_all = moe_ops.served_weights(u, p['router'], None, DIMS.top_k, 1.0,
                                   gate='softmax')
    total = np.zeros(u.shape, np.float32)
    for offset in range(0, DIMS.experts, DIMS.held):
        share = DIMS._replace(offset=offset)
        w1, w3, w2 = (ref.layer_tensors(key, 1, 'mamba', share)[r]
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


# -- a prefix that is pages and state -----------------------------------------

@pytest.mark.parametrize('first, more', [
    (20, 9),        # the boundary on a page's edge, inside a chunk
    (22, 11),       # inside a page (4 tokens) and inside a chunk (16)
    (32, 7),        # on a chunk's edge
    (3, 30)])       # shorter than a page: the tail alone
def test_a_turn_reopens_on_the_snapshot_its_earlier_turn_left(
        model, reference_logits, first, more):
    """The second turn's logits are the whole conversation's: against
    the reference, and against the same conversation prefilled whole by
    a decoder that keeps no snapshot, within the rounding of a chunked
    recurrence whose chunks start elsewhere."""
    pred, toks, _ = model
    dec = _decoder(pred, snapshot_rows=4)
    _prefill(dec, 0, toks[:first])
    dec.release(0)
    plan = dec.open_stream(1, toks[:first + more])
    assert plan['shared_tokens'] == first
    assert plan['chunks'] == -(-more // 16)
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
    stats = dec.pool_stats()
    assert stats['prefix_hits'] == 1 and stats['state_resets'] == 1
    assert stats['snapshots'] == 2


def test_a_wrong_boundary_or_pages_without_state_would_not_pass(
        model, reference_logits):
    """What the invariant protects: the same second turn over the right
    pages with ANOTHER boundary's state, or with no state at all, is far
    outside the tolerance."""
    pred, toks, _ = model
    first, more = 22, 11
    for wrong in ('zero', 'other'):
        dec = _decoder(pred, snapshot_rows=4)
        _prefill(dec, 0, toks[:first])
        dec.release(0)
        _prefill(dec, 2, toks[5:5 + first])         # another stream's rows
        dec.release(2)
        for name in dec._pair.snapshot_names:
            snap = np.array(dec._scope.find_var(name))
            snap[0] = snap[1] if wrong == 'other' else 0.0
            snap[3] = snap[0]       # whichever row the cache handed out
            dec._scope.set_var(name, snap)
        rows = dec._prefix._snaps
        assert sorted(s.row for at in rows.values()
                      for s in at.values()) in ([2, 3], [0, 1])
        dec.open_stream(1, toks[:first + more])
        out = None
        while out is None:
            out = dec.prefill_step(1, return_logits=True)
        assert ref.rel_l2(out[1], reference_logits[first + more - 1]) \
            > 100 * TOL


def test_a_match_ends_at_a_boundary_that_has_a_snapshot(model):
    pred, toks, _ = model
    dec = _decoder(pred, snapshot_rows=4)
    _prefill(dec, 0, toks[:30])
    dec.release(0)
    # a prompt that shares 7 whole pages with it and no boundary
    other = np.concatenate([toks[:28], toks[40:50]])
    assert dec.open_stream(1, other)['shared_tokens'] == 0
    dec.release(1)
    # one that runs past the boundary reopens on it, not on more pages
    assert dec.open_stream(1, toks[:45])['shared_tokens'] == 30
    dec.release(1)
    # the boundary itself is not shared: the last token is computed
    assert dec.open_stream(1, toks[:30])['shared_tokens'] == 0


def test_a_snapshot_evicted_for_its_row_takes_its_pages_and_falls_back(
        model, reference_logits):
    pred, toks, _ = model
    telemetry.enable()
    gone = telemetry.counter('serving.state.snapshots_evicted').value
    dec = _decoder(pred, snapshot_rows=2)
    _prefill(dec, 0, toks[:8])                      # the system prompt
    dec.release(0)
    _prefill(dec, 0, toks[:20])                     # a first turn on it
    dec.release(0)
    assert dec.pool_stats()['snapshots'] == 2
    in_use = dec.pool_stats()['pages_in_use']
    assert dec.open_stream(1, toks[:12])['shared_tokens'] == 8   # touch
    dec.release(1)
    _prefill(dec, 0, np.concatenate([toks[:8], toks[50:60]]))    # third
    dec.release(0)
    # the least recently used (20 tokens) went, with pages 2..4 that
    # only it kept; the system prompt's two pages serve both survivors
    stats = dec.pool_stats()
    assert stats['snapshots'] == 2
    assert stats['pages_in_use'] == in_use
    assert telemetry.counter('serving.state.snapshots_evicted').value \
        == gone + 1
    plan = dec.open_stream(1, toks[:33])
    assert plan['shared_tokens'] == 8               # falls back
    out = None
    while out is None:
        out = dec.prefill_step(1, return_logits=True)
    assert ref.rel_l2(out[1], reference_logits[32]) < TOL
    dec._pool.check()


def test_pages_evicted_under_a_snapshot_take_it_along(model,
                                                      reference_logits):
    pred, toks, _ = model
    dec = _decoder(pred, snapshot_rows=4, kv_pages=14)
    _prefill(dec, 0, toks[:22])                     # 6 pages, registered
    dec.release(0)
    assert dec.pool_stats()['snapshots'] == 1
    _prefill(dec, 1, toks[10:55])                   # 12 of the 13 pages
    # the tail went first, leaf first, and the snapshot with it; the
    # one there is now is the new prompt's own
    assert dec._prefix.snapshots_dropped == 1
    assert dec.pool_stats()['snapshots'] == 1
    dec.release(1)
    # never pages without their state: whatever nodes of the old chain
    # are left, a stream on the old prompt opens on nothing and is right
    assert dec._prefix.chain(list(toks[:22]))[1]
    plan = dec.open_stream(2, toks[:33])
    assert plan['shared_tokens'] == 0
    out = None
    while out is None:
        out = dec.prefill_step(2, return_logits=True)
    assert ref.rel_l2(out[1], reference_logits[32]) < TOL
    dec._pool.check()


def test_a_pinned_row_is_not_handed_out_before_it_is_copied(
        model, reference_logits):
    pred, toks, _ = model
    dec = _decoder(pred, snapshot_rows=1)
    _prefill(dec, 0, toks[:22])
    dec.release(0)
    assert dec.open_stream(1, toks[:33])['shared_tokens'] == 22   # pins
    _prefill(dec, 2, toks[30:50])       # would want the only row
    assert dec.pool_stats()['snapshots'] == 1       # and took none
    assert dec.open_stream(0, toks[30:55])['shared_tokens'] == 0
    dec.release(0)
    out = None
    while out is None:
        out = dec.prefill_step(1, return_logits=True)
    assert ref.rel_l2(out[1], reference_logits[32]) < TOL
    # a stream given up before its first chunk gives its pin back
    dec.release(1)
    dec.release(2)
    assert dec.open_stream(1, toks[:40])['shared_tokens'] == 33
    dec.release(1)
    _prefill(dec, 2, toks[30:50])
    assert dec.open_stream(1, toks[30:60])['shared_tokens'] == 20


def test_zero_rows_share_nothing_and_add_nothing(model):
    pred, toks, _ = model
    dec = _decoder(pred)
    assert dec._pair.snapshot_program is None
    assert not [n for n in dec._scope.local_var_names()
                if n.endswith('.snapshot')]
    _prefill(dec, 0, toks[:17])
    dec.release(0)
    assert dec.open_stream(1, toks[:30])['shared_tokens'] == 0
    stats = dec.pool_stats()
    assert stats['prefix_hits'] == 0 and stats['prefix_entries'] == 0
    assert dec.jit_cache_stats()['compiled_segments'] == 1


def test_save_and_restore_of_a_stream_that_adopted(model, reference_logits):
    pred, toks, _ = model
    dec = _decoder(pred, snapshot_rows=4)
    _prefill(dec, 0, toks[:22])
    dec.release(0)
    n = 33
    _prefill(dec, 2, toks[:n])
    assert dec.pool_stats()['prefix_hits'] == 1
    snap = dec.save_stream(2)
    dec.release(2)
    _prefill(dec, 2, toks[20:40])                   # another stream's state
    dec.release(2)
    dec.restore_stream(0, snap)
    got = _decode(dec, 0, toks[n], n)
    assert ref.rel_l2(got, reference_logits[n]) < TOL


def test_admission_adoption_snapshot_and_eviction_compile_nothing(model):
    pred, toks, _ = model
    dec = _decoder(pred, snapshot_rows=2, kv_pages=30)
    _prefill(dec, 0, toks[:9])
    _decode(dec, 0, toks[9], 9)
    dec.release(0)
    # the prefill program and the two state copies; the decode program
    # and the page copy
    compiled = dec.jit_cache_stats()['compiled_segments']
    assert compiled == 5
    for start in (0, 0, 3, 0, 7, 3, 0):
        n = 9 + (start * 5) % 14
        _prefill(dec, 1, toks[start:start + n])
        _decode(dec, 1, toks[start + n], n)          # forks a shared tail
        dec.release(1)
        _prefill(dec, 2, toks[start:start + n + 6])
        dec.release(2)
    stats = dec.pool_stats()
    assert stats['prefix_hits'] >= 7 and stats['snapshots'] == 2
    assert dec._prefix.snapshots_dropped >= 5
    assert dec.jit_cache_stats()['compiled_segments'] == compiled


def test_the_fleet_is_told_nothing_of_such_pages(model):
    pred, toks, _ = model
    dec = _decoder(pred, snapshot_rows=2)
    _prefill(dec, 0, toks[:22])
    assert dec.prefix_report() == {'new': [], 'evicted': []}
    assert dec.resident_keys(toks[:22]) == []
    with pytest.raises(DecodeTranspileError, match='page shipping'):
        dec.export_prefix(toks[:22])
    with pytest.raises(ValueError, match='speculation'):
        pred.prepare_decoding(slots=2, speculative=True, snapshot_rows=2)


def test_snapshot_rows_are_for_recurrent_state_only(tmp_path):
    from paddle_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab=32, dim=16, heads=2, layers=1, ffn=32,
                                max_len=16)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        tokens = fluid.layers.data('tokens', shape=[1, 16, 1], dtype='int64',
                                   append_batch_size=False)
        logits = tfm.language_model_logits(tokens, cfg)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(str(tmp_path), ['tokens'], [logits],
                                      exe, main_program=main)
    pred = AnalysisPredictor(AnalysisConfig(str(tmp_path),
                                            place=fluid.CPUPlace()))
    with pytest.raises(ValueError, match='pages alone'):
        pred.prepare_decoding(slots=2, snapshot_rows=2)
