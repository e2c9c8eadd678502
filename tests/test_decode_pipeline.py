"""One decode step in flight: the paged engine dispatches step n+1 before
it fetches step n (serving/engine.py `_iterate`, serving/paged.py
`decode_step(defer=True)` / `collect()`).

What must hold, for the GPT-2 block and for the hybrid block alike:

- the pipelined engine's streams equal, token for token, those of the
  synchronous `generate()`, with lanes joining and leaving mid-run;
- a lane that ends on what only the token or the clock can tell (an
  eos_id, a cancel, a deadline) drops exactly one lane result and
  leaves the pool as the serial loop leaves it;
- a weight swap between two iterations sees no step in flight;
- stop(drain=True, timeout=0.0) and drain() return with nothing in
  flight, and no worker thread is left;
- the decode program still compiles once, and a call's spans are what
  benchmarks/harness/spans.py `decode_calls` parses.
"""
import os
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu.models.transformer import TransformerConfig
from paddle_tpu.obs import telemetry, trace
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.paging import CacheExhaustedError

from test_hybrid_serving import _build as _build_hybrid
from test_paged import _save_lm
from test_spans import registry_on          # noqa: F401 (a fixture)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmarks'))
from harness import spans as bench_spans          # noqa: E402

GPT2 = TransformerConfig(vocab=64, dim=32, heads=2, layers=2, ffn=64,
                         max_len=48, use_tp=False, use_sp=False)
CALL = ['paged.decode.tables', 'exe.run', 'paged.decode.book',
        'paged.decode.fetch']


@pytest.fixture(scope='module')
def gpt2_served(tmp_path_factory):
    rng = np.random.RandomState(27)
    return _save_lm(tmp_path_factory.mktemp('pipeline_gpt2'), GPT2, 9), \
        [int(t) for t in rng.randint(1, GPT2.vocab, size=48)]


@pytest.fixture(scope='module')
def hybrid_served(tmp_path_factory):
    pred, toks, _ = _build_hybrid(tmp_path_factory.mktemp('pipeline_hybrid'))
    return pred, [int(t) for t in toks]


@pytest.fixture(scope='module', params=['gpt2', 'hybrid'])
def served(request):
    """(predictor, 48 token ids to cut prompts from), a block kind."""
    return request.getfixturevalue(request.param + '_served')


def _decoder(pred, **kw):
    kw = dict(dict(slots=3, page_tokens=4, kv_pages=40,
                   prefill_chunk=8), **kw)
    return pred.prepare_decoding(**kw)


def _counter(name):
    return telemetry.snapshot()['counters'].get(name, 0)


def _on_call(dec, n, fn):
    """Run fn() at the start of the n-th decode_step call (1-based), on
    the INSTANCE, as the benchmark's probe wraps it: the engine has to
    look the method up there at call time."""
    calls = [0]
    step = dec.decode_step

    def decode_step(*a, **kw):
        calls[0] += 1
        if calls[0] == n:
            fn()
        return step(*a, **kw)
    dec.decode_step = decode_step
    return calls


def _no_worker_left():
    return not [t for t in threading.enumerate()
                if t.name.startswith('serving-worker')]


# --------------------------------------------------------------------------
# (a) the streams
# --------------------------------------------------------------------------

def test_pipelined_streams_equal_generate(served, registry_on):
    pred, toks = served
    # prompts of one to four chunks, budgets from one token (ends at
    # its prefill) to most of the window: seven requests on three slots
    asks = [(toks[:5], 9), (toks[3:30], 12), (toks[10:12], 1),
            (toks[7:20], 2), (toks[1:18], 17), (toks[20:29], 5),
            (toks[:33], 14)]
    solo = _decoder(pred)
    want = [list(solo.generate(p, n)) for p, n in asks]
    dec = _decoder(pred)
    engine = ServingEngine(dec).start()
    try:
        reqs = [engine.submit(p, max_new_tokens=n) for p, n in asks[:4]]
        while not reqs[1].tokens:        # the rest join a running batch
            time.sleep(0.001)
        reqs += [engine.submit(p, max_new_tokens=n) for p, n in asks[4:]]
        got = [list(r.result(240)) for r in reqs]
    finally:
        assert engine.stop(drain=True, timeout=30.0)
    assert got == want
    steps = _counter('serving.decode_steps')
    over = _counter('serving.decode_steps_overlapped')
    assert 0 < over < steps
    assert _counter('serving.decode_lanes_dropped') == 0
    tables = [s for s in trace.spans() if s['name'] == 'paged.decode.tables']
    assert len(tables) == steps + sum(n - 1 for _, n in asks)   # + solo's
    assert sum(t['overlapped'] for t in tables) == over
    assert sum(t['carried'] for t in tables) > 0
    assert all(t['carried'] == 0 for t in tables if not t['overlapped'])
    assert not dec.in_flight and not dec.slot_tokens()
    # (f) both programs compiled once, whatever form the calls took
    # (the third is the page copy program, compiled with the decode one)
    stats = dec.jit_cache_stats()
    assert stats['prepared_programs'] == stats['compiled_segments'] == 3


# --------------------------------------------------------------------------
# (b) a lane that ends early
# --------------------------------------------------------------------------

def _ends_early(pred, toks, how, pipelined):
    """One long request beside a short one; the long one ends by `how`
    at its fourth decode call. Returns (tokens, state, error, lane
    results dropped, pages in use afterwards)."""
    prompt, budget = toks[:6], 30
    dec = _decoder(pred)
    if not pipelined:
        dec.deferred_decode = False     # what the engine looks at
    ref = list(_decoder(pred).generate(prompt, budget))
    eos = None
    if how == 'eos':
        # the first token from the fourth on that no earlier one equals
        k = next(i for i in range(3, budget) if ref[i] not in ref[:i])
        eos = ref[k]
    engine = ServingEngine(dec)
    dropped = _counter('serving.decode_lanes_dropped')
    req = engine.submit(prompt, max_new_tokens=budget, eos_id=eos)

    def end():
        if how == 'cancel':
            engine.cancel(req)
        elif how == 'deadline':
            req.deadline_at = time.perf_counter() - 1.0
    _on_call(dec, 4, end)
    engine.start()
    try:
        other = engine.submit(toks[2:9], max_new_tokens=3)
        assert req.wait(240) and other.wait(240)
    finally:
        assert engine.stop(drain=True, timeout=30.0)
    assert list(other.tokens) == list(_decoder(pred).generate(toks[2:9], 3))
    assert req.tokens == ref[:len(req.tokens)]
    if how == 'eos':
        assert req.tokens == ref[:k + 1]
    assert not dec.in_flight and not dec.slot_tokens()
    return (req.state, req.error,
            _counter('serving.decode_lanes_dropped') - dropped,
            dec.pool_stats()['pages_in_use'])


@pytest.mark.parametrize('how', ['eos', 'cancel', 'deadline'])
def test_a_lane_that_ends_early_drops_one_result(served, registry_on, how):
    pred, toks = served
    state, error, dropped, pages = _ends_early(pred, toks, how, True)
    assert dropped == 1
    assert state == {'eos': 'DONE', 'cancel': 'CANCELLED',
                     'deadline': 'FAILED'}[how]
    assert how != 'deadline' or 'DeadlineExceededError' in error
    serial = _ends_early(pred, toks, how, False)
    assert serial == (state, error, 0, pages)


# --------------------------------------------------------------------------
# (d) a swap, (e) stop and drain
# --------------------------------------------------------------------------

def test_a_swap_between_two_iterations_sees_no_step_in_flight(served,
                                                              registry_on):
    pred, toks = served
    want = list(_decoder(pred).generate(toks[:6], 36))
    dec = _decoder(pred)
    seen = []
    engine = ServingEngine(dec)
    # from the decode calls themselves, so that every swap lands on a
    # worker that has a step in flight; the swap runs on another thread
    # (the worker is the gate's reader and cannot wait for itself)
    swaps = []

    def swap():
        t = threading.Thread(target=lambda: seen.append(
            engine.request_swap(lambda: (dec.in_flight,
                                         len(dec.slot_tokens())))))
        t.start()
        swaps.append(t)
    for n in (5, 9, 20):
        _on_call(dec, n, swap)
    engine.start()
    try:
        got = list(engine.submit(toks[:6], max_new_tokens=36).result(240))
        for t in swaps:
            t.join(60)
    finally:
        assert engine.stop(drain=True, timeout=30.0)
    assert got == want
    assert len(seen) == 3 and all(s == (False, 1) for s in seen)
    assert _counter('serving.decode_steps_overlapped') > 20
    assert engine.stats()['weight_swaps'] == 3


@pytest.mark.parametrize('how', ['stop', 'drain'])
def test_stop_and_drain_leave_nothing_in_flight(served, how):
    pred, toks = served
    dec = _decoder(pred)
    engine = ServingEngine(dec).start()
    reqs = [engine.submit(toks[:6], max_new_tokens=40),
            engine.submit(toks[4:20], max_new_tokens=25)]
    while not all(r.tokens for r in reqs):
        time.sleep(0.001)
    t0 = time.monotonic()
    if how == 'stop':
        # gives up at once: the running lanes are cancelled at the next
        # accept, with a step of theirs already dispatched behind it
        engine.stop(drain=True, timeout=0.0)
        assert all(r.state in ('CANCELLED', 'DONE') for r in reqs)
    else:
        assert engine.drain(timeout=120.0)
        assert [len(r.tokens) for r in reqs] == [40, 25]
        assert not dec.in_flight        # an idle worker holds no step
        assert engine.stop(drain=True, timeout=30.0)
    assert time.monotonic() - t0 < 60.0
    assert not dec.in_flight and not dec.slot_tokens()
    assert _no_worker_left()
    # the predictor is whole: the synchronous form runs at once
    assert len(dec.generate(toks[:6], 3)) == 3


# --------------------------------------------------------------------------
# (f) the call's spans, as the benchmark reads them; the predictor's forms
# --------------------------------------------------------------------------

def test_a_call_s_spans_are_what_the_benchmark_parses(served, registry_on):
    pred, toks = served
    dec = _decoder(pred)
    with ServingEngine(dec) as engine:
        reqs = [engine.submit(toks[:11], max_new_tokens=8),
                engine.submit(toks[5:8], max_new_tokens=6)]
        for r in reqs:
            r.result(240)
    spans = trace.spans()
    iters = {s['sid'] for s in spans if s['name'] == 'serve.iter'}
    steps = _counter('serving.decode_steps')
    calls = bench_spans.decode_calls(spans)
    assert len(calls) == steps > 0
    for call in calls:
        assert call['tables'] > 0 and call['run'] > 0 and call['book'] > 0 \
            and call['fetch'] > 0 and call['prep'] >= call['tables']
    # under one serve.iter: tables, exe.run, book, fetch, in this order
    for it in iters:
        mine = [s['name'] for s in sorted(
            (s for s in spans if s['psid'] == it), key=lambda s: s['t0'])
            if s['name'] in CALL]
        assert mine[:4] in ([], CALL) or mine == ['paged.decode.fetch']
        assert mine[4:] in ([], ['paged.decode.fetch'])
    hist = telemetry.snapshot()['hists']
    assert hist['serving.decode_batch']['count'] == steps \
        == hist['serving.token_latency']['count']


def test_the_predictor_s_deferred_form(served):
    pred, toks = served
    sync, dec = _decoder(pred), _decoder(pred)
    S = dec.slots
    tokens, positions = np.zeros(S, np.int64), np.zeros(S, np.int32)
    first = {}
    for d in (sync, dec):
        first[d] = [int(d.prefill([toks[:9]], [0])[0]),
                    int(d.prefill([toks[4:10]], [2])[0])]
    assert first[sync] == first[dec]
    want = []                           # three synchronous steps
    tok, pos = list(first[sync]), [9, 6]
    for _ in range(3):
        tokens[[0, 2]], positions[[0, 2]] = tok, pos
        ids = sync.decode_step(tokens, positions)
        tok, pos = [int(ids[0]), int(ids[2])], [p + 1 for p in pos]
        want.append(tok)
    # the same three, each dispatched before the last is fetched; lane 2
    # sits the third out
    tokens[[0, 2]], positions[[0, 2]] = first[dec], [9, 6]
    assert dec.collect() is None and not dec.in_flight
    assert dec.decode_step(tokens, positions, defer=True) is None
    assert dec.in_flight
    with pytest.raises(RuntimeError, match='in flight'):
        dec.decode_step(tokens, positions)
    tokens[:] = 0                       # a carried lane's entry is not read
    positions[[0, 2]] = [10, 7]
    got = [dec.decode_step(tokens, positions, defer=True, carry=[0, 2])]
    positions[0] = 11
    got.append(dec.decode_step(tokens, positions, defer=True, lanes=[0],
                               carry=[0]))
    assert dec.slot_tokens() == {0: 12, 2: 8}
    got.append(dec.collect())
    assert not dec.in_flight and dec.collect() is None
    assert [[int(g[0]), int(g[2])] for g in got[:2]] == want[:2]
    assert int(got[2][0]) == want[2][0]
    with pytest.raises(ValueError, match='no step is in flight'):
        dec.decode_step(tokens, positions, defer=True, carry=[0])
    with pytest.raises(ValueError, match='ids only'):
        dec.decode_step(tokens, positions, defer=True, return_logits=True)
    assert dec.jit_cache_stats()['compiled_segments'] == 3


def test_exhaustion_at_a_deferred_step_leaves_the_one_in_flight(served):
    pred, toks = served
    # 8 usable pages of 4 tokens, two streams of 12: three pages each,
    # a fourth at position 12, and none left for a fifth at position 16
    dec = _decoder(pred, slots=2, kv_pages=9)
    solo = _decoder(pred, slots=2)
    tokens, positions = np.zeros(2, np.int64), np.zeros(2, np.int32)
    prompts = (toks[:12], toks[20:32])
    for d in (dec, solo):
        tokens[:] = [int(d.prefill([p], [s])[0])
                     for s, p in enumerate(prompts)]
        for pos in (12, 13, 14):
            positions[:] = pos
            tokens[:] = d.decode_step(tokens, positions)
    positions[:] = 15
    want = solo.decode_step(tokens, positions)
    assert dec.decode_step(tokens, positions, defer=True) is None
    before = dec.pool_stats()['pages_in_use']
    assert before == 8
    positions[:] = 16
    with pytest.raises(CacheExhaustedError) as e:
        dec.decode_step(tokens, positions, defer=True, carry=[0, 1])
    assert sorted(e.value.slots) == [0, 1]
    assert dec.pool_stats()['pages_in_use'] == before    # rolled back
    assert dec.slot_tokens() == {0: 16, 1: 16}
    assert dec.in_flight                # ... and still to be collected
    assert np.array_equal(dec.collect(), want)
    # a stream gives way, and the other's retry is the step it would
    # have been: its token now comes from the host
    dec.release(1)
    tokens[:] = want
    assert int(dec.decode_step(tokens, positions)[0]) \
        == int(solo.decode_step(tokens, positions, lanes=[0])[0])


def test_a_mesh_predictor_takes_the_pipelined_loop(gpt2_served,
                                                   registry_on):
    """One SPMD program over tp=2 (serving/mesh.py): the ids a step
    leaves on the mesh are the next step's carried tokens. (Mesh serving
    refuses recurrent state, so the GPT-2 block alone.)"""
    pred, toks = gpt2_served
    want = [list(_decoder(pred).generate(p, n))
            for p, n in ((toks[:7], 10), (toks[9:30], 6))]
    dec = _decoder(pred, mesh='tp=2')
    assert dec.mesh_devices == 2 and dec.deferred_decode
    with ServingEngine(dec) as engine:
        reqs = [engine.submit(toks[:7], max_new_tokens=10),
                engine.submit(toks[9:30], max_new_tokens=6)]
        got = [list(r.result(240)) for r in reqs]
    assert got == want
    assert _counter('serving.decode_steps_overlapped') > 0
    assert dec.jit_cache_stats()['compiled_segments'] == 3
