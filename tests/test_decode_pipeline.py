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
  benchmarks/harness/spans.py `decode_calls` parses;
- a prompt's first token stays on the device (`prefill_step(defer=True)`
  / `first_token()`; a decode step that carries the slot takes it from
  the chunk): the step behind a prompt's last chunk is dispatched behind
  the chunk, the streams are still those of `generate()`, and whatever
  ends a lane or needs the lanes whole finds no first token unfetched.
"""
import os
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu.models.transformer import TransformerConfig
from paddle_tpu.obs import telemetry, trace
from paddle_tpu.flags import set_flags
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


# --------------------------------------------------------------------------
# (g) a prompt's first token stays on the device
# --------------------------------------------------------------------------

def _tables():
    return sorted((s for s in trace.spans()
                   if s['name'] == 'paged.decode.tables'),
                  key=lambda s: s['t0'])


def _on_last_chunk(dec, fn):
    """Run fn(handle) when a deferred last chunk has returned, before
    the engine looks at it: wrapped on the INSTANCE, passing the
    arguments through, as the benchmark's probe does."""
    step = dec.prefill_step

    def prefill_step(slot, *a, **kw):
        out = step(slot, *a, **kw)
        if out is not None:
            fn(out)
        return out
    dec.prefill_step = prefill_step


def test_a_deferred_last_chunk_hands_back_a_handle_not_none(served,
                                                            registry_on):
    pred, toks = served
    sync, dec = _decoder(pred), _decoder(pred)
    prompt = toks[:13]                  # two chunks of 8
    want = list(sync.generate(prompt, 4))
    seen = []                           # the probe's `out is not None`
    _on_last_chunk(dec, seen.append)
    dec.open_stream(1, prompt)
    assert dec.prefill_step(1, defer=True) is None and not seen
    assert dec.slot_tokens() == {1: 8}
    first = dec.prefill_step(1, defer=True)
    assert first is not None and seen == [first]
    # the prompt is in: its tokens are held, nothing is pending
    assert dec.slot_tokens() == {1: 13} and 1 not in dec._pending
    with pytest.raises(ValueError, match='token only'):
        dec.prefill_step(1, defer=True, return_logits=True)
    # the step behind the chunk takes the token on the device; no step
    # is in flight, and the slot may be carried all the same
    S = dec.slots
    tokens, positions = np.zeros(S, np.int64), np.zeros(S, np.int32)
    positions[1] = 13
    assert dec.decode_step(tokens, positions, lanes=[1], carry=[1],
                           defer=True) is None
    got = [dec.first_token(first)]
    positions[1] = 14
    got.append(int(dec.decode_step(tokens, positions, lanes=[1], carry=[1],
                                   defer=True)[1]))
    got.append(int(dec.collect()[1]))
    assert got == want[:3]
    mine = _tables()[-2:]
    assert [(t['carried_prefill'], t['carried'], t['overlapped'])
            for t in mine] == [(1, 0, 0), (0, 1, 1)]
    assert not dec._first
    # a slot that no chunk and no step holds a token for is refused
    dec.release(1)
    with pytest.raises(ValueError, match='no step is in flight'):
        dec.decode_step(tokens, positions, lanes=[], carry=[1], defer=True)
    # the synchronous step takes it too, and a token fetched first is fed
    # from the host; a released slot forgets its handle
    for fetch_first in (False, True):
        dec.open_stream(0, prompt[:6])
        first = dec.prefill_step(0, defer=True)
        tokens[:], positions[0] = 0, 6
        if fetch_first:
            tokens[0] = dec.first_token(first)
            assert tokens[0] == sync.generate(prompt[:6], 1)[0]
        ids = dec.decode_step(tokens, positions, lanes=[0],
                              carry=[] if fetch_first else [0])
        assert [dec.first_token(first), int(ids[0])] \
            == list(sync.generate(prompt[:6], 2))
        assert not dec._first
        dec.release(0)
    dec.open_stream(0, prompt[:6])
    dec.prefill_step(0, defer=True)
    dec.release(0)
    assert not dec._first and not dec.slot_tokens()
    assert dec.jit_cache_stats()['compiled_segments'] == 3


def test_prompts_that_end_at_different_passes_equal_generate(served,
                                                             registry_on):
    pred, toks = served
    # beside a decoding stream: a prompt of one chunk, one of three,
    # one whose budget is its first token, one of two tokens
    asks = [(toks[:6], 24), (toks[2:9], 5), (toks[10:30], 6),
            (toks[7:12], 1), (toks[20:33], 2)]
    solo = _decoder(pred)
    want = [list(solo.generate(p, n)) for p, n in asks]
    telemetry.reset()
    trace.clear()
    dec = _decoder(pred)
    engine = ServingEngine(dec)
    reqs = [engine.submit(asks[0][0], max_new_tokens=asks[0][1])]
    _on_call(dec, 3, lambda: reqs.extend(
        engine.submit(p, max_new_tokens=n) for p, n in asks[1:]))
    engine.start()
    try:
        while len(reqs) < len(asks):
            time.sleep(0.001)
        got = [list(r.result(240)) for r in reqs]
    finally:
        assert engine.stop(drain=True, timeout=30.0)
    assert got == want
    # every prompt but the one-token request's fed its first token to
    # the step behind its last chunk; nothing was dropped for it
    assert _counter('serving.first_tokens_carried') == 4 \
        == sum(t['carried_prefill'] for t in _tables())
    assert _counter('serving.requests.admitted') == 5
    assert _counter('serving.decode_lanes_dropped') == 0
    # only the burst's first step found the pipeline empty
    assert sum(1 - t['overlapped'] for t in _tables()) == 1
    assert sum(r.gap_sync[0] for r in reqs[1:] if r.gap_sync) == 0
    assert not dec.in_flight and not dec._first and not dec.slot_tokens()
    stats = dec.jit_cache_stats()
    assert stats['prepared_programs'] == stats['compiled_segments'] == 3


def _first_token_ends_it(pred, toks, how, pipelined):
    """A stream decodes; a second prompt's first token ends its request
    (`how`) while, in the pipelined loop, it is pending behind the last
    chunk. Returns (tokens, state, error, lane results dropped, pages in
    use afterwards)."""
    prompt, budget = toks[3:14], 9
    dec = _decoder(pred)
    if not pipelined:
        dec.deferred_decode = False     # what the engine looks at
    ref = list(_decoder(pred).generate(prompt, budget))
    engine = ServingEngine(dec)
    dropped = _counter('serving.decode_lanes_dropped')
    other = engine.submit(toks[:6], max_new_tokens=14)
    reqs = []

    def end(_out):
        if not reqs:
            return                      # the other prompt's last chunk
        if how == 'cancel':
            engine.cancel(reqs[0])
        elif how == 'deadline':
            reqs[0].deadline_at = time.perf_counter() - 1.0
    _on_last_chunk(dec, end)
    _on_call(dec, 3, lambda: reqs.append(engine.submit(
        prompt, max_new_tokens=budget,
        eos_id=ref[0] if how == 'eos' else None)))
    engine.start()
    try:
        while not reqs:
            time.sleep(0.001)
        assert reqs[0].wait(240) and other.wait(240)
    finally:
        assert engine.stop(drain=True, timeout=30.0)
    assert list(other.tokens) == list(_decoder(pred).generate(toks[:6], 14))
    req = reqs[0]
    # a cancel is seen before the token is taken; the others keep it
    assert req.tokens == ([] if how == 'cancel' else ref[:1])
    assert not dec.in_flight and not dec.slot_tokens() and not dec._first
    return (req.state, req.error,
            _counter('serving.decode_lanes_dropped') - dropped,
            dec.pool_stats()['pages_in_use'])


@pytest.mark.parametrize('how', ['eos', 'cancel', 'deadline'])
def test_a_first_token_that_ends_its_lane_drops_one_result(served,
                                                           registry_on, how):
    pred, toks = served
    state, error, dropped, pages = _first_token_ends_it(pred, toks, how, True)
    assert dropped == 1                 # it was fed to the step behind
    assert state == {'eos': 'DONE', 'cancel': 'CANCELLED',
                     'deadline': 'FAILED'}[how]
    assert how != 'deadline' or 'DeadlineExceededError' in error
    serial = _first_token_ends_it(pred, toks, how, False)
    assert serial == (state, error, 0, pages)


@pytest.fixture()
def preempt_flags():
    yield
    set_flags({'FLAGS_serving_preempt_policy': 'swap'})


@pytest.mark.parametrize('policy', ['swap', 'reprefill', 'off'])
def test_exhaustion_at_the_step_behind_a_last_chunk(served, registry_on,
                                                    preempt_flags, policy):
    """Five usable pages of 4 tokens. A stream of 8 prompt tokens is at
    its third page when a prompt of 8 ends: its chunk takes the last two
    pages, and the step dispatched behind the chunk finds none for the
    new lane's first append. The first token is fetched with the step in
    flight before a victim is picked (the longer stream gives way and
    resumes) or, with no preemption, before the new lane fails with the
    token its prefill earned."""
    pred, toks = served
    set_flags({'FLAGS_serving_preempt_policy': policy})
    pa, pb = toks[:8], toks[20:28]
    want_a = list(_decoder(pred).generate(pa, 9))
    want_b = list(_decoder(pred).generate(pb, 3))
    telemetry.reset()
    dec = _decoder(pred, slots=2, kv_pages=6)
    engine = ServingEngine(dec)
    a = engine.submit(pa, max_new_tokens=9)
    reqs = []
    _on_call(dec, 3, lambda: reqs.append(
        engine.submit(pb, max_new_tokens=3, priority=1)))
    engine.start()
    try:
        while not reqs:
            time.sleep(0.001)
        b = reqs[0]
        assert a.wait(240) and b.wait(240)
    finally:
        assert engine.stop(drain=True, timeout=30.0)
    assert _counter('serving.cache_exhausted') >= 1
    assert list(a.tokens) == want_a
    if policy == 'off':
        assert b.state == 'FAILED' and 'CacheExhaustedError' in b.error
        assert list(b.tokens) == want_b[:1]
    else:
        assert list(b.tokens) == want_b and a.preemptions >= 1
    # the step that failed carried nothing, and the new lane's token
    # came from the host: the first stream's own first token was carried,
    # and the one its re-prefill's last chunk made
    assert _counter('serving.first_tokens_carried') \
        == 1 + (a.preemptions if policy == 'reprefill' else 0)
    assert not dec.in_flight and not dec._first and not dec.slot_tokens()
    assert dec.pool_stats()['pages_in_use'] \
        == dec.pool_stats()['prefix_pages']


@pytest.mark.parametrize('how', ['stop', 'drain', 'swap'])
def test_stop_drain_and_swap_with_a_first_token_pending(served, how):
    pred, toks = served
    asks = [(toks[:6], 30), (toks[4:20], 12)]
    want = [list(_decoder(pred).generate(p, n)) for p, n in asks]
    dec = _decoder(pred)
    engine = ServingEngine(dec)
    seen, threads, acted = [], [], []

    def act():
        if how == 'stop':
            seen.append(engine.stop(drain=True, timeout=0.0))
        elif how == 'drain':
            seen.append(engine.drain(timeout=120.0))
        else:
            seen.append(engine.request_swap(
                lambda: (dec.in_flight, len(dec._first),
                         len(dec.slot_tokens()))))

    def pending(_out):
        # the second prompt's last chunk is dispatched and not waited
        # for: the other thread acts now, and the worker goes on only
        # once it has (a stop has cancelled, a swap waits at the gate)
        if acted or len(reqs) < 2:
            return
        acted.append(True)
        thread = threading.Thread(target=act)
        thread.start()
        threads.append(thread)
        t0 = time.monotonic()
        while time.monotonic() - t0 < 30.0 and not (
                {'stop': not engine._running, 'drain': True,
                 'swap': engine._gate.writer_waiting}[how]):
            time.sleep(0.001)
    _on_last_chunk(dec, pending)
    reqs = [engine.submit(*asks[0][:1], max_new_tokens=asks[0][1])]
    _on_call(dec, 4, lambda: reqs.append(
        engine.submit(asks[1][0], max_new_tokens=asks[1][1])))
    engine.start()
    while not threads:
        time.sleep(0.001)
    threads[0].join(120)
    assert not threads[0].is_alive()
    if how == 'stop':
        assert all(r.state in ('CANCELLED', 'DONE') for r in reqs)
        assert [list(r.tokens) for r in reqs] \
            == [w[:len(r.tokens)] for w, r in zip(want, reqs)]
    else:
        assert seen == [True] if how == 'drain' else seen == [(False, 0, 2)]
        assert [list(r.result(240)) for r in reqs] == want
        assert engine.stop(drain=True, timeout=30.0)
    assert not dec.in_flight and not dec._first and not dec.slot_tokens()
    assert _no_worker_left()
    assert len(dec.generate(toks[:6], 3)) == 3


def test_first_tokens_carried_past_a_snapshot_and_an_adoption(hybrid_served,
                                                              registry_on):
    """A model with recurrent state and snapshot rows: the snapshot is
    dispatched behind a prompt's last chunk and the adoption in front of
    a first one, as before, and the step that takes the first token on
    the device runs behind both. A second turn opens on the snapshot its
    first left, beside a stream that decodes all the while."""
    pred, toks = hybrid_served
    solo = _decoder(pred)
    first_turn = list(solo.generate(toks[:14], 4))
    again = toks[:14] + first_turn + toks[30:35]
    want = [list(solo.generate(toks[3:9], 30)), first_turn,
            list(solo.generate(again, 5))]
    telemetry.reset()
    dec = _decoder(pred, snapshot_rows=3)
    with ServingEngine(dec) as engine:
        beside = engine.submit(toks[3:9], max_new_tokens=30)
        while len(beside.tokens) < 3:
            time.sleep(0.001)
        one = engine.submit(toks[:14], max_new_tokens=4)
        got = [list(one.result(240))]
        two = engine.submit(again, max_new_tokens=5)
        got.append(list(two.result(240)))
        got.insert(0, list(beside.result(240)))
    assert got == want
    assert _counter('serving.state.snapshots_taken') == 3
    assert _counter('serving.state.snapshots_adopted') == 1
    assert _counter('serving.prefix_tokens_reused') == 14
    assert _counter('serving.first_tokens_carried') == 3
    assert _counter('serving.decode_lanes_dropped') == 0
    assert beside.gap_sync.count(1) == 1    # its own first step alone


def test_a_first_append_that_forks_takes_its_token_on_the_device(
        gpt2_served, registry_on):
    """A's prompt ends inside a page the prefix cache registers, so the
    step behind its last chunk forks it: chunk, the page copy, the step,
    with the chunk's token never on the host in between. B opens on that
    page and forks the tail it registers the same way, beside A."""
    pred, toks = gpt2_served
    pa, pb = toks[:6], toks[:7]
    want = [list(_decoder(pred).generate(pa, 9)),
            list(_decoder(pred).generate(pb, 5))]
    telemetry.reset()
    trace.clear()
    dec = _decoder(pred)
    with ServingEngine(dec) as engine:
        a = engine.submit(pa, max_new_tokens=9)
        while not a.tokens:
            time.sleep(0.001)
        b = engine.submit(pb, max_new_tokens=5)
        got = [list(a.result(240)), list(b.result(240))]
    assert got == want
    assert _counter('serving.prefix_tokens_reused') == 6
    assert _counter('serving.cow.dispatches') == 2 \
        == _counter('serving.first_tokens_carried')
    # each copy stands between the tables and the step of a pass that
    # carried a chunk's token
    for cow in (s for s in trace.spans() if s['name'] == 'paged.cow'):
        mine = [s for s in trace.spans() if s['psid'] == cow['psid']]
        names = [s['name'] for s in sorted(mine, key=lambda s: s['t0'])
                 if s['name'] != 'paged.cow.compile']
        at = names.index('paged.cow')
        assert names[at - 1] == 'paged.decode.tables'
        assert names[at + 1:at + 3] == ['paged.carry', 'exe.run']
        tables = mine[[s['name'] for s in mine]
                      .index('paged.decode.tables')]
        assert tables['carried_prefill'] == 1


# --------------------------------------------------------------------------
# (h) a mesh; last in the file: it re-pins the shared weights onto two
# devices, and no single-chip decoder of the fixture runs after it
# --------------------------------------------------------------------------

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
