"""Serving subsystem: KV-cached decode parity + continuous batching.

The contract under test (ISSUE 6 acceptance):
- greedy decode over the page pool is BIT-EXACT against the
  full-recompute predictor (same weights, same ops, same reduction
  lengths — np.array_equal, not allclose)
- each of the two serving programs compiles exactly once across a
  whole generation loop (executor jit_cache_stats)
- a request admitted mid-stream into a running pool produces exactly
  the tokens it would have produced alone (lane isolation)
- clone()d workers share weights but never cross-talk
plus unit tests for the gather ops and the Predictor dict-input
validation satellite.
"""
import threading

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import unique_name
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.models.transformer import (TransformerConfig,
                                           language_model_logits)
from op_test import OpTest

CFG = TransformerConfig(vocab=64, dim=32, heads=2, layers=2, ffn=64,
                        max_len=16, use_tp=False, use_sp=False)


# --------------------------------------------------------------------------
# gather op units (ops/attention_ops.py)
# --------------------------------------------------------------------------

class TestPositionEmbeddingAt(OpTest):
    def test_gather_and_wrap(self):
        pos = np.arange(20, dtype='f4').reshape(5, 4)
        idx = np.array([0, 3, 7], 'int32')            # 7 % 5 wraps to 2
        self.op_type = 'position_embedding_at'
        self.inputs = {'Pos': pos, 'Index': idx}
        self.outputs = {'Out': pos[[0, 3, 2]][:, None, :]}
        self.check_output()


class TestGatherTime(OpTest):
    def test_per_row_time_gather(self):
        rng = np.random.RandomState(2)
        x = rng.rand(3, 5, 4).astype('f4')
        idx = np.array([0, 4, 2], 'int32')
        self.op_type = 'gather_time'
        self.inputs = {'X': x, 'Index': idx}
        self.outputs = {'Out': x[[0, 1, 2], [0, 4, 2]]}
        self.check_output()


# --------------------------------------------------------------------------
# shared tiny-LM predictor
# --------------------------------------------------------------------------

@pytest.fixture(scope='module')
def lm_predictor(tmp_path_factory):
    tmp = tmp_path_factory.mktemp('serving_lm')
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = 7
    with unique_name.guard(), program_guard(prog, startup):
        toks = fluid.layers.data(name='tokens',
                                 shape=[1, CFG.max_len, 1],
                                 dtype='int64', append_batch_size=False)
        logits = language_model_logits(toks, CFG)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(str(tmp), ['tokens'], [logits],
                                      exe, main_program=prog)
    from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
    return AnalysisPredictor(AnalysisConfig(str(tmp),
                                            place=fluid.CPUPlace()))


def _decoder(pred, slots):
    # four pages a window: the default 16-token page would be the whole
    # window of this model
    return pred.prepare_decoding(slots=slots, page_tokens=4)


def _ref_step(pred, toks):
    """Full-recompute next-token logits for a token list (len <= T)."""
    feed = np.zeros((1, CFG.max_len, 1), np.int64)
    feed[0, :len(toks), 0] = toks
    lg = pred.run({'tokens': feed})[0]
    return lg[0, len(toks) - 1]


def _ref_generate(pred, prompt, n):
    toks, out = list(prompt), []
    for _ in range(n):
        t = int(np.argmax(_ref_step(pred, toks)))
        out.append(t)
        toks.append(t)
    return out


# --------------------------------------------------------------------------
# transpiler
# --------------------------------------------------------------------------

def test_extract_decode_spec(lm_predictor):
    from paddle_tpu.transpiler import extract_decode_spec
    spec = extract_decode_spec(lm_predictor._program)
    assert (spec.vocab, spec.dim, spec.heads, spec.layers, spec.ffn,
            spec.max_len) == (CFG.vocab, CFG.dim, CFG.heads, CFG.layers,
                              CFG.ffn, CFG.max_len)
    assert len(spec.blocks) == CFG.layers
    assert spec.pool_shape(9, 4) == (9, 4, CFG.heads,
                                     CFG.dim // CFG.heads)


def test_transpile_rejects_non_lm():
    from paddle_tpu.transpiler import (DecodeTranspiler,
                                       DecodeTranspileError)
    prog, startup = Program(), Program()
    with unique_name.guard(), program_guard(prog, startup):
        x = fluid.layers.data(name='x', shape=[8], dtype='float32')
        fluid.layers.fc(input=x, size=4)
    with pytest.raises(DecodeTranspileError, match='cannot transpile'):
        DecodeTranspiler().transpile(prog)


# --------------------------------------------------------------------------
# cached decode: bit-exact parity + compile-once
# --------------------------------------------------------------------------

def test_greedy_parity_bit_exact_and_compiles_once(lm_predictor):
    dec = _decoder(lm_predictor, 3)
    prompt = [3, 1, 4, 1, 5]
    ids, logits = dec.prefill([prompt], [1], return_logits=True)
    ref = _ref_step(lm_predictor, prompt)
    assert np.array_equal(logits[0], ref), \
        'prefill logits diverge from full recompute'
    tok, pos = int(ids[0]), len(prompt)
    toks = np.zeros((3,), np.int64)
    poss = np.zeros((3,), np.int32)
    stream = [tok]
    for _ in range(CFG.max_len - len(prompt)):
        toks[1], poss[1] = tok, pos
        nxt, lg = dec.decode_step(toks, poss, return_logits=True)
        ref = _ref_step(lm_predictor, prompt + stream)
        assert np.array_equal(lg[1], ref), \
            'decode step %d logits diverge (pos %d)' % (len(stream), pos)
        tok = int(nxt[1])
        stream.append(tok)
        pos += 1
    assert stream == _ref_generate(lm_predictor, prompt,
                                   CFG.max_len - len(prompt) + 1)
    # the whole loop compiled exactly two programs: prefill + decode
    # (and with decode the page copy program); every further dispatch
    # was a jit-cache hit
    stats = dec.jit_cache_stats()
    assert stats['prepared_programs'] == 3
    assert stats['compiled_segments'] == 3
    assert stats['segment_misses'] == 3
    assert stats['segment_hits'] >= 1


def test_generate_fills_the_window_stops_at_eos_and_reuses_its_slot(
        lm_predictor):
    # (past the window: test_paged.test_generate_past_window_raises_typed_
    # not_slides)
    dec = _decoder(lm_predictor, 1)
    prompt = [5, 9, 2]
    n = CFG.max_len - len(prompt) + 1     # up to the window's last row
    want = _ref_generate(lm_predictor, prompt, n)
    assert dec.generate(prompt, n) == want
    # the next prefill on the slot releases the stream it held
    eos = want[2]
    assert dec.generate(prompt, n, eos_id=eos) == \
        want[:want.index(eos) + 1]
    assert list(dec.slot_tokens()) == [0]


def test_prefill_validation(lm_predictor):
    dec = _decoder(lm_predictor, 2)
    with pytest.raises(ValueError, match='max_len'):
        dec.prefill([list(range(CFG.max_len + 1))], [0])
    with pytest.raises(ValueError, match='slot'):
        dec.prefill([[1, 2]], [2])
    with pytest.raises(ValueError, match='max_len'):
        dec.open_stream(0, list(range(CFG.max_len + 1)))
    with pytest.raises(ValueError, match='slot'):
        dec.open_stream(2, [1, 2])
    with pytest.raises(ValueError, match='prompts'):
        dec.prefill([[1], [2]], [0])


# --------------------------------------------------------------------------
# continuous batching
# --------------------------------------------------------------------------

def test_midstream_admission_matches_solo(lm_predictor):
    """A request admitted while another stream is mid-decode produces
    exactly its solo token stream — first at the predictor level
    (deterministic interleaving), then through the engine."""
    solo_a = _ref_generate(lm_predictor, [3, 1, 4], 8)
    solo_b = _ref_generate(lm_predictor, [2, 7], 6)

    dec = _decoder(lm_predictor, 2)
    ids = dec.prefill([[3, 1, 4]], [0])
    a, pos_a = [int(ids[0])], 3
    toks = np.zeros((2,), np.int64)
    poss = np.zeros((2,), np.int32)
    b, pos_b = [], None
    for step in range(10):
        if step == 3:                      # admit B mid-stream
            ids = dec.prefill([[2, 7]], [1])
            b, pos_b = [int(ids[0])], 2
        toks[0], poss[0] = a[-1], pos_a
        if b:
            toks[1], poss[1] = b[-1], pos_b
        nxt = dec.decode_step(toks, poss)
        if len(a) < 8:
            a.append(int(nxt[0]))
            pos_a += 1
        if b and len(b) < 6:
            b.append(int(nxt[1]))
            pos_b += 1
    assert a == solo_a, 'running stream disturbed by admission'
    assert b == solo_b, 'admitted stream differs from its solo run'


def test_engine_concurrent_requests_match_solo(lm_predictor):
    from paddle_tpu.serving import ServingEngine
    prompts = [[3, 1, 4], [2, 7], [9, 9, 1, 5], [6]]
    budgets = [8, 6, 5, 7]
    solo = [_ref_generate(lm_predictor, p, n)
            for p, n in zip(prompts, budgets)]
    dec = _decoder(lm_predictor, 2)
    with ServingEngine(dec) as eng:       # 4 requests over 2 slots
        reqs = [eng.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, budgets)]
        outs = [r.result(120) for r in reqs]
    assert outs == solo
    assert all(r.state == 'DONE' for r in reqs)


def test_engine_cancel_and_queue_drain(lm_predictor):
    from paddle_tpu.serving import ServingEngine
    dec = _decoder(lm_predictor, 1)
    eng = ServingEngine(dec)              # not started: both stay queued
    keep = eng.submit([3, 1, 4], max_new_tokens=4)
    drop = eng.submit([2, 7], max_new_tokens=4)
    eng.cancel(drop)
    eng.start()
    assert keep.result(120) == _ref_generate(lm_predictor, [3, 1, 4], 4)
    assert drop.wait(120) and drop.state == 'CANCELLED'
    assert drop.result(1) == []           # partial stream, no raise
    eng.stop()


def test_clone_workers_no_crosstalk(lm_predictor):
    """Two clone()d decode workers generating different prompts in
    parallel threads agree with their solo streams, and share the
    weight scope (one HBM copy) while owning private cache scopes."""
    prompts = [[3, 1, 4, 1], [11, 2]]
    solo = [_ref_generate(lm_predictor, p, 7) for p in prompts]
    base = _decoder(lm_predictor, 2)
    workers = [base, base.clone()]
    assert workers[1]._weight_scope is base._weight_scope
    assert workers[1]._scope is not base._scope

    results, errors = [None, None], []
    gate = threading.Barrier(2)

    def run(i):
        try:
            gate.wait(timeout=30)
            results[i] = workers[i].generate(prompts[i], 7)
        except Exception as e:            # surface, don't hang
            errors.append((i, repr(e)))

    threads = [threading.Thread(target=run, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive(), 'decode worker thread hung'
    assert not errors, errors
    assert results == solo


# --------------------------------------------------------------------------
# LMServer + telemetry + Predictor.run validation satellite
# --------------------------------------------------------------------------

def test_lmserver_api_surface(lm_predictor):
    from paddle_tpu.serving import LMServer
    solo = _ref_generate(lm_predictor, [3, 1, 4], 5)
    dec = _decoder(lm_predictor, 2)
    with LMServer(dec) as srv:
        assert srv.generate([3, 1, 4], max_new_tokens=5) == solo
        h = srv.submit([3, 1, 4], max_new_tokens=5)
        assert srv.result(h, timeout=120) == solo
        snap = srv.poll(h)
        assert snap['state'] == 'DONE' and snap['tokens'] == solo
        stats = srv.stats()
        assert stats['slots_per_worker'] == 2
        assert stats['jit']['compiled_segments'] == 3   # + the page copy
        with pytest.raises(KeyError):
            srv.poll('nope')
        with pytest.raises(ValueError, match='max_len'):
            srv.submit(list(range(CFG.max_len + 1)))


def test_lmserver_with_no_option_serves_from_a_page_pool(lm_predictor):
    from paddle_tpu.serving import LMServer
    solo = _ref_generate(lm_predictor, [3, 1, 4], 5)
    with LMServer(lm_predictor._config.model_dir,
                  place=fluid.CPUPlace()) as srv:
        before = srv._decode.pool_stats()
        assert before['pages_in_use'] == 0 and before['prefix_entries'] == 0
        assert srv.generate([3, 1, 4], max_new_tokens=5) == solo
        after = srv._decode.pool_stats()
        # the finished stream's prompt stays in the prefix cache, on a page
        assert after['prefix_entries'] >= 1 and after['pages_in_use'] >= 1
        assert srv.stats()['kv']['num_pages'] == before['num_pages']


def test_prepare_decoding_refuses_the_dense_cache(lm_predictor):
    with pytest.raises(ValueError, match='dense ring KV cache was removed'):
        lm_predictor.prepare_decoding(slots=2, paged=False)
    with pytest.raises(TypeError):
        lm_predictor.prepare_decoding(slots=2, prefill_batch=1)


def test_serving_metrics_flow_into_rollup(lm_predictor):
    from paddle_tpu.obs import telemetry
    from paddle_tpu.obs.report import rollup
    from paddle_tpu.serving import ServingEngine
    dec = _decoder(lm_predictor, 2)
    telemetry.enable()
    try:
        telemetry.reset()
        with ServingEngine(dec) as eng:
            eng.generate([3, 1, 4], max_new_tokens=4)
        snap = telemetry.snapshot()
    finally:
        telemetry.disable()
        telemetry.reset()
    assert snap['counters']['serving.requests.submitted'] == 1
    assert snap['counters']['serving.requests.completed'] == 1
    assert snap['counters']['serving.tokens_generated'] == 4
    assert snap['counters']['serving.decode_steps'] >= 3
    assert snap['hists']['serving.ttft']['count'] == 1
    assert snap['hists']['serving.token_latency']['count'] >= 3
    # the name-agnostic obs rollup picks the series up unchanged
    snap['role'] = 'server'
    ru = rollup([snap])
    assert ru['totals']['serving.requests.completed'] == 1
    assert 'serving.ttft' in ru['roles']['server']['hists']


@pytest.mark.slow
def test_serve_bench_quick_smoke():
    """tools/serve_bench.py --quick runs end to end and emits the
    acceptance summary row (the leg tools/bench_suite.py shells out
    to for the transformer local-mode decode_speedup stamp)."""
    import json
    import os
    import subprocess
    import sys
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), 'tools', 'serve_bench.py')
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    out = subprocess.run([sys.executable, tool, '--quick'],
                         capture_output=True, text=True, timeout=600,
                         env=env)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    rows = [json.loads(ln) for ln in out.stdout.splitlines()
            if ln.startswith('{')]
    summary = [r for r in rows if r.get('summary') == 'acceptance']
    assert summary, rows
    assert summary[0]['infer_decode_cached_tokens_per_sec'] > 0
    assert {'recompute', 'cached', 'engine'} <= \
        {r.get('mode') for r in rows}


def test_predictor_run_dict_validation(lm_predictor):
    good = np.zeros((1, CFG.max_len, 1), np.int64)
    with pytest.raises(ValueError) as ei:
        lm_predictor.run({'bogus': good})
    msg = str(ei.value)
    assert 'bogus' in msg and 'tokens' in msg
    assert 'get_input_names' in msg
    with pytest.raises(ValueError, match='missing input'):
        lm_predictor.run({})
    with pytest.raises(ValueError, match='unknown input'):
        lm_predictor.run({'tokens': good, 'extra': good})
