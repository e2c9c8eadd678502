"""The SmallThinker block (models/smallthinker.py) at tiny widths with
seeded weights: its whole-sequence program and its paged serving pair
(two page tables: the sliding layers' gives up the pages behind the
window) against the plain reference (benchmarks/reference/
smallthinker.py), a follow-up that opens on a cached prefix in both
pools, the router ahead of the attention against one in the usual
place, the ReGLU experts, the transpiler's reading of a saved model,
the refusals, and that none of it compiles anything twice. Window 8 and
pages of 4 tokens, so that every edge is crossed."""
import os
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
from paddle_tpu.models import smallthinker
from paddle_tpu.obs import telemetry
from paddle_tpu.transpiler.decode_transpiler import (
    DecodeTranspileError, extract_decode_spec)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmarks'))
from reference import smallthinker as ref          # noqa: E402
from builders import smallthinker as builder       # noqa: E402

MODEL = {'vocab_size': 64, 'hidden_size': 32, 'num_attention_heads': 4,
         'num_key_value_heads': 2, 'head_dim': 8,
         'moe_num_primary_experts': 8, 'moe_num_active_primary_experts': 3,
         'moe_ffn_hidden_size': 24, 'moe_primary_router_apply_softmax': True,
         'norm_topk_prob': True, 'rms_norm_eps': 1e-6,
         'rope_layout': [0, 1, 1, 1, 0, 1, 1, 1],
         'sliding_window_layout': [0, 1, 1, 1, 0, 1, 1, 1],
         'sliding_window_size': 8, 'rope_theta': 1500000,
         'rope_scaling': None, 'tie_word_embeddings': False,
         'num_hidden_layers': 4, 'n_positions': 64,
         # wide enough weights that these narrow layers, the routed
         # experts among them, each move the logits by tens of percent
         'initializer_range': 0.3}
DIMS = ref.dims_of(MODEL)
SEED = 4900000013
# float32 both sides on the CPU; the program's batched expert products,
# its gathered pages and its fused orders differ from the reference's
# loops by rounding only. The bf16-stored control reads hundreds of
# times this.
TOL = 2e-5


def _build(tmp, score_h=False):
    cfg = builder.model_config(DIMS)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        tokens = fluid.layers.data('tokens', shape=[1, cfg.max_len, 1],
                                   dtype='int64', append_batch_size=False)
        logits = smallthinker.language_model_logits(tokens, cfg)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        builder.put_seeded_weights(
            scope, smallthinker.spec_from_config(cfg), DIMS, SEED)
        toks = np.random.default_rng(0).integers(
            1, DIMS.vocab, size=(1, cfg.max_len, 1))
        full, = exe.run(main, feed={'tokens': toks}, fetch_list=[logits])
        if score_h:
            return None, toks[0, :, 0], full[0]
        fluid.io.save_inference_model(str(tmp), ['tokens'], [logits], exe,
                                      main_program=main)
    pred = AnalysisPredictor(AnalysisConfig(str(tmp),
                                            place=fluid.CPUPlace()))
    return pred, toks[0, :, 0], full[0]


@pytest.fixture(scope='module')
def model(tmp_path_factory):
    return _build(tmp_path_factory.mktemp('smallthinker_lm'))


@pytest.fixture(scope='module')
def reference_logits(model):
    return np.asarray(ref.logits(ref.seed_key(SEED), DIMS, model[1]))


def _decoder(pred, **kw):
    kw = dict(dict(slots=3, page_tokens=4, kv_pages=60, window_pages=40,
                   prefill_chunk=8), **kw)
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


@pytest.mark.parametrize('n, steps', [
    (21, 8),     # three chunks, the last padded; decode crosses two pages
    (5, 12),     # shorter than the window: decode crosses its edge
    (8, 4),      # the prompt ends on the window's edge and a page's
    (37, 6),     # chunks that free pages behind them; no multiple of 4
])
def test_chunked_prefill_then_decode_is_the_reference(model, reference_logits,
                                                      n, steps):
    pred, toks, _ = model
    dec = _decoder(pred)
    rows = [_prefill(dec, 1, toks[:n])]
    rows += [_decode(dec, 1, toks[j], j) for j in range(n, n + steps)]
    assert ref.rel_l2(np.stack(rows),
                      reference_logits[n - 1:n + steps]) < TOL
    # the sliding layers' table holds the window and no more
    wtable = dec._wtables[1]
    assert wtable.first == (n + steps - DIMS.window + 1) // 4
    assert len(dec._tables[1].pages) == -(-(n + steps) // 4)
    dec.release(1)
    dec._pool.check(), dec._wpool.check()
    assert dec._wpool.pages_in_use == len(
        [e for e in dec._prefix._nodes.values() if e.wpage is not None]) \
        + len([t for ts in dec._prefix._tails.values() for t in ts.values()
               if t.wpage is not None])


def test_the_controls_fail_the_tolerance(model, reference_logits):
    key = ref.seed_key(SEED)
    for kw in ({'prec': 'bfloat16'}, {'full_window': True}):
        control = np.asarray(ref.logits(key, DIMS, model[1], **kw))
        assert ref.rel_l2(control, reference_logits) > 30 * TOL, kw


def test_a_router_behind_the_attention_is_another_model(model, tmp_path,
                                                        monkeypatch):
    """The expert op scores the ATTENTION's input: a variant that scores
    the expert sublayer's own input chooses other experts."""
    experts = smallthinker._experts
    monkeypatch.setattr(smallthinker, '_experts',
                        lambda u, h, *a: experts(h, h, *a))
    _, _, other = _build(tmp_path, score_h=True)
    assert ref.rel_l2(other, model[2]) > 1e-2


def test_followup_opens_on_a_cached_prefix_in_both_pools(model,
                                                         reference_logits):
    """A stream of 30 tokens is prefilled and released; a second whose
    prompt is those 30 and two more (it ends inside the same page)
    opens on all 30: the full pool's 8 pages, the window pool's last
    three, the partly filled last page of both, which it forks."""
    pred, toks, _ = model
    dec = _decoder(pred)
    telemetry.enable()
    _prefill(dec, 0, toks[:30])
    dec.release(0)
    plan = dec.open_stream(2, toks[:32])
    assert plan['shared_tokens'] == 30 and plan['chunks'] == 1
    table, wtable = dec._tables[2], dec._wtables[2]
    assert len(table.pages) == 8 and table.shared == set(range(8))
    # a row at 30 reads 23..29 of a sliding layer: pages 5, 6, 7
    assert wtable.first == 5 and len(wtable.pages) == 3
    shared_full, shared_window = table.pages[7], wtable.pages[2]
    out = None
    while out is None:
        out = dec.prefill_step(2, return_logits=True)
    assert table.pages[7] != shared_full        # forked in both pools
    assert wtable.pages[-1] != shared_window
    rows = [out[1]] + [_decode(dec, 2, toks[j], j) for j in range(32, 40)]
    assert ref.rel_l2(np.stack(rows), reference_logits[31:40]) < TOL
    assert dec._prefix.hits == 1 and dec._prefix.window_tail_misses == 0
    # the parent's pages are as they were: a third stream opens on them
    dec.release(2)
    plan = dec.open_stream(1, toks[:31])
    assert plan['shared_tokens'] == 30
    out = dec.prefill_step(1, return_logits=True)
    assert ref.rel_l2(out[1][None], reference_logits[30:31]) < TOL


def test_a_boundary_without_its_window_tail_is_not_handed_out(model):
    """The window pool's eviction takes an entry's window page alone;
    the deepest boundary that still has its tail is handed out, and
    where none has, nothing."""
    pred, toks, _ = model
    dec = _decoder(pred)
    _prefill(dec, 0, toks[:30])
    dec.release(0)
    cache = dec._prefix
    assert cache.evict_window_one()             # page 4: the oldest held
    pages, tokens, wpages, wfirst = cache.match_window(list(toks[:32]), 31)
    assert tokens == 30 and wfirst == 5 and len(wpages) == 3
    node5 = [n for n in cache._nodes.values() if n.wpage == wpages[0]][0]
    dec._wpool.unref(node5.wpage)
    node5.wpage = None
    # 30 needs pages 5..7 and 28 needs 5, 6: both lack 5. 24 needs 4, 5;
    # only boundaries whose whole window lies behind page 5 are left:
    # none was ever registered with its tail (the stream had given those
    # pages up), so nothing is handed out
    misses = cache.window_tail_misses
    assert cache.match_window(list(toks[:32]), 31) == ([], 0, [], 0)
    assert cache.window_tail_misses == misses + 1
    plan = dec.open_stream(1, toks[:32])
    assert plan['shared_tokens'] == 0
    dec.release(1)
    dec._pool.check(), dec._wpool.check()


def test_the_saved_model_is_read_from_its_description(model):
    spec = extract_decode_spec(model[0]._program)
    assert type(spec).__name__ == 'SmallThinkerDecodeSpec'
    assert spec.kinds == ('full_attention',) + ('sliding_attention',) * 3
    assert spec.kv_layers == [0, 1, 2, 3] and spec.window_layers == [1, 2, 3]
    assert spec.full_layers == [0] and spec.window == 8
    assert spec.expert_layers == [0, 1, 2, 3] and not spec.state_names()
    assert spec.pool_shape(10, 4) == (10, 4, 2, 8)
    # window - 1 tokens behind a chunk of 8 and the chunk: 15 tokens
    # from anywhere in a page lie on 5 pages
    assert spec.window_table_pages(8, 4) == 5
    assert spec.cfg.rope_layout == (0, 1, 1, 1)


def test_the_pair_feeds_two_tables_and_compiles_once(model):
    pred, toks, _ = model
    dec = _decoder(pred)
    pair = dec._pair
    assert pair.window_pages_per_slot == 5 and pair.window_num_pages == 40
    assert pair.prefill_feeds[-4:] == [
        'prefill_window_table', 'prefill_window_positions',
        'prefill_window_cow_src', 'prefill_window_cow_dst']
    assert pair.decode_feeds[-2:] == ['decode_window_table',
                                      'decode_window_step_idx']
    assert pair.copy_feeds == ['page_copy_src', 'page_copy_dst',
                               'page_copy_window_src',
                               'page_copy_window_dst']
    shapes = dict(pair.cache_shapes())
    assert shapes['kv_pool.layer0.k'] == (60, 4, 2, 8)
    assert shapes['kv_pool.layer2.v'] == (40, 4, 2, 8)
    ops = [op.type for op in pair.decode_program.global_block().ops]
    assert ops.count('paged_attention') == 1
    assert ops.count('paged_window_attention') == 3
    assert ops.count('rotary_yarn') == 6 and ops.count('moe_experts') == 4
    for n in (21, 9, 33):
        _prefill(dec, 0, toks[:n])
        _decode(dec, 0, toks[n], n)
        dec.release(0)
    assert dec.jit_cache_stats()['compiled_segments'] == 3   # + the copy


def test_what_reads_one_table_refuses_the_second(model):
    pred, toks, _ = model
    with pytest.raises(DecodeTranspileError, match='sliding_attention'):
        pred.prepare_decoding(slots=2, page_tokens=4, kv_pages=40,
                              prefill_chunk=8, speculative=True)
    with pytest.raises(DecodeTranspileError, match='sliding_attention'):
        pred.prepare_decoding(slots=2, page_tokens=4, kv_pages=40,
                              prefill_chunk=8, mesh='tp=2')
    dec = _decoder(pred)
    assert not dec.swappable
    _prefill(dec, 0, toks[:12])
    for call in (lambda: dec.save_stream(0),
                 lambda: dec.restore_stream(1, {}),
                 lambda: dec.export_prefix(toks[:12]),
                 lambda: dec.install_prefix(toks[:12], ['00'], [])):
        with pytest.raises(DecodeTranspileError, match='sliding_attention'):
            call()
    assert dec.resident_keys(toks[:12]) == []


def test_counters_of_the_second_table(model):
    pred, toks, _ = model
    telemetry.enable()
    before = telemetry.snapshot()['counters'].get(
        'serving.window_pages_freed', 0)
    dec = _decoder(pred)
    _prefill(dec, 0, toks[:37])
    for j in range(37, 41):
        _decode(dec, 0, toks[j], j)
    snap = telemetry.snapshot()
    # a row at 41 reads from 34 on: pages 0..7 of 11 are gone
    assert snap['counters']['serving.window_pages_freed'] - before == 8
    assert snap['gauges']['serving.window_pages_live'] == 3
    stats = dec.pool_stats()
    assert stats['window_pages_live'] == 3
    assert stats['window_num_pages'] == 40
    assert stats['window_pages_in_use'] >= 3


def test_engine_serves_the_block(model):
    from paddle_tpu.serving import ServingEngine
    pred, toks, _ = model
    dec = _decoder(pred, slots=3)
    eng = ServingEngine(dec).start()
    try:
        reqs = [eng.submit(toks[:n], max_new_tokens=6) for n in (19, 7, 30)]
        outs = [r.result(120) for r in reqs]
    finally:
        eng.stop(drain=True, timeout=5.0)
    solo = _decoder(pred, slots=1, kv_pages=30, window_pages=12)
    for n, out in zip((19, 7, 30), outs):
        assert list(out) == solo.generate(toks[:n], 6)
