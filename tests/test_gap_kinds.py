"""What stood in front of a token: every decode step's record of its
dispatch, `(chunks, lanes, sync)`, on the requests whose tokens it made
(serving/engine.py: `Request.gap_chunks` / `gap_lanes` / `gap_sync`, the
attrs of `serve.decode` beside `gaps_ms`, `gap_kind`), the two token
counters, and the host's section of a pass (`wait_ms`, `chunk`, `step`
of `serve.iter`; serving.loop.seconds / serving.loop.wait_seconds; the
predictor's `fetch_wait_s`).
"""
import time

import numpy as np
import pytest

from paddle_tpu.flags import set_flags
from paddle_tpu.obs import telemetry, trace
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.engine import gap_kind

import fleet_worker as fw
from test_decode_pipeline import (_counter, _decoder, _on_call,  # noqa: F401
                                  gpt2_served)
from test_spans import registry_on          # noqa: F401 (a fixture)


def _kinds(req):
    return [gap_kind(c, s) for c, s in zip(req.gap_chunks, req.gap_sync)]


def _drive(dec, asks, join_at=None):
    """The requests of `asks` ((prompt, budget) pairs) through an engine
    over `dec`; with `join_at`, all but the first are submitted at the
    start of that decode call (1-based), from the worker's own thread."""
    engine = ServingEngine(dec)
    reqs = [engine.submit(*asks[0][:1], max_new_tokens=asks[0][1])]

    def join():
        reqs.extend(engine.submit(p, max_new_tokens=n) for p, n in asks[1:])
    if join_at is None:
        join()
    else:
        _on_call(dec, join_at, join)
    engine.start()
    try:
        while len(reqs) < len(asks):
            time.sleep(0.001)
        for r in reqs:
            r.result(240)
    finally:
        assert engine.stop(drain=True, timeout=30.0)
    return reqs


def _decode_spans():
    return {s['sid']: s for s in trace.spans() if s['name'] == 'serve.decode'}


# --------------------------------------------------------------------------
# (a) one entry a gap, in both loops
# --------------------------------------------------------------------------

@pytest.mark.parametrize('loop', ['deferred', 'serial'])
def test_the_three_lists_are_as_long_as_the_gaps(gpt2_served, registry_on,
                                                 loop):
    pred, toks = gpt2_served
    dec = _decoder(pred)
    if loop == 'serial':
        dec.deferred_decode = False     # what the engine looks at
    asks = [(toks[:5], 9), (toks[3:30], 12), (toks[10:12], 1),
            (toks[7:20], 2), (toks[1:18], 17)]
    reqs = _drive(dec, asks)
    spans = _decode_spans()
    for req in reqs:
        n = len(req.tokens) - 1
        assert len(req.gap_chunks) == len(req.gap_lanes) \
            == len(req.gap_sync) == n
        span = spans[req.id]
        assert len(span['gaps_ms']) == n
        assert (span['gap_chunks'], span['gap_lanes'], span['gap_sync']) \
            == (req.gap_chunks, req.gap_lanes, req.gap_sync)
        assert all(1 <= m <= dec.slots for m in req.gap_lanes)
        # a request's own first decode step stands behind its last chunk
        assert not n or req.gap_chunks[0] >= 1
        if loop == 'serial':
            assert set(_kinds(req)) <= {'sync'}
    if loop == 'deferred':
        # ... with the step before still in flight, but for the burst's
        # first: the pipeline was empty once
        assert [r.gap_sync[0] for r in reqs if r.gap_sync] \
            == [1] + [0] * (len(reqs) - 2)
        assert {'plain', 'chunk', 'sync'} \
            == {k for r in reqs for k in _kinds(r)}


def test_gap_kind_is_sync_before_chunk_before_plain():
    assert [gap_kind(c, s) for c, s in ((0, 0), (1, 0), (3, 0), (0, 1),
                                        (1, 1))] \
        == ['plain', 'chunk', 'chunk', 'sync', 'sync']


# --------------------------------------------------------------------------
# (b) alone, (c) beside another prompt's chunks, (d) the lanes
# --------------------------------------------------------------------------

def test_a_stream_alone_reads_plain_gaps_after_its_first(gpt2_served,
                                                         registry_on):
    pred, toks = gpt2_served
    req, = _drive(_decoder(pred), [(toks[:6], 20)])
    # no step was in flight at its first (the module's docstring: the
    # chunk was, and the record still reads `sync`)
    assert _kinds(req) == ['sync'] + ['plain'] * 18
    assert req.gap_chunks == [1] + [0] * 18
    assert req.gap_lanes == [1] * 19


def test_a_prompt_of_three_chunks_beside_a_decoding_stream(gpt2_served,
                                                           registry_on):
    pred, toks = gpt2_served
    dec = _decoder(pred)                # chunks of 8: 20 tokens are three
    n = 5
    first, second = _drive(dec, [(toks[:6], 16), (toks[10:30], 4)],
                           join_at=n)
    assert second.prefill_chunks == 3
    # the second prompt arrives while step n is dispatched. The pass
    # after it dispatches chunk 1 and step n + 1 and only then accepts
    # step n's token: that token's gap (index n - 1) had no chunk in
    # front; the chunks stand in front of steps n + 1, n + 2 and n + 3,
    # each with a step in flight: the last chunk is not waited for, and
    # step n + 3 is dispatched behind it
    assert first.gap_chunks == [1] + [0] * (n - 1) + [1, 1, 1] \
        + [0] * (15 - n - 3)
    assert first.gap_sync == [1] + [0] * 14
    assert _kinds(first)[n - 1:n + 4] \
        == ['plain', 'chunk', 'chunk', 'chunk', 'plain']
    # the second stream's own first gap is that same step's
    assert (second.gap_chunks[0], second.gap_sync[0]) == (1, 0)
    assert _kinds(second) == ['chunk'] + ['plain'] * 2


def _tables(spans):
    """The decode steps' `paged.decode.tables` spans, in dispatch order."""
    return sorted((s for s in spans if s['name'] == 'paged.decode.tables'),
                  key=lambda s: s['t0'])


def test_the_step_behind_a_last_chunk_carries_its_token(gpt2_served,
                                                        registry_on):
    pred, toks = gpt2_served
    dec = _decoder(pred)
    n = 5
    first, second, third = _drive(
        dec, [(toks[:6], 16), (toks[10:30], 4), (toks[3:9], 1)], join_at=n)
    # every prompt kept the pipeline full but the one whose budget was
    # its first token, which sat the step behind its chunk out
    assert _counter('serving.first_tokens_carried') == 2
    assert _counter('serving.requests.admitted') == 3
    assert len(third.tokens) == 1 and third.gap_sync == []
    tables = _tables(trace.spans())
    took = [i for i, t in enumerate(tables) if t['carried_prefill']]
    # the lone stream's own (nothing in flight but its chunk), and step
    # n + 3, behind the second prompt's third chunk and a step in flight
    assert took == [0, n + 2]
    assert [(tables[i]['carried_prefill'], tables[i]['carried'],
             tables[i]['overlapped']) for i in took] \
        == [(1, 0, 0), (1, 1, 1)]
    assert _counter('serving.tokens_behind_sync') == 1
    assert _counter('serving.decode_lanes_dropped') == 0


def test_first_token_at_is_taken_before_the_next_pass_dispatches(
        gpt2_served, registry_on):
    """The fetch of a prompt's first token is not put off to the next
    step's: it is issued in the pass that dispatched the last chunk,
    behind that pass's decode call, and `first_token_at` is its end."""
    pred, toks = gpt2_served
    dec = _decoder(pred)
    n = 5
    first, second = _drive(dec, [(toks[:6], 16), (toks[10:30], 4)],
                           join_at=n)
    spans = trace.spans()
    tables = _tables(spans)
    at = [i for i, t in enumerate(tables) if t['carried_prefill']][1]
    step, after = tables[at], tables[at + 1]
    fetch, = [s for s in spans if s['name'] == 'paged.prefill.fetch'
              and s['psid'] == step['psid']]
    # one pass: the chunk, the step behind it, then the wait for the
    # chunk's token; the next pass's dispatch comes after all three
    assert step['t1'] <= fetch['t0'] <= fetch['t1'] \
        <= second.first_token_at <= after['t0']
    assert second.first_token_at - fetch['t1'] < 0.05
    # the step in flight was fetched first: its token is the older one
    # (step n + 2's; step n + 3's comes a pass later)
    assert first.token_at[n + 2] <= second.first_token_at \
        <= first.token_at[n + 3]
    # and the pass's wait holds both fetches
    it, = [s for s in spans if s['name'] == 'serve.iter'
           and s['sid'] == step['psid']]
    assert (it['chunk'], it['step']) == (1, 1)
    assert it['wait_ms'] >= 1e3 * (fetch['t1'] - fetch['t0'])


def test_gap_lanes_is_the_ready_the_step_was_packed_with(gpt2_served,
                                                         registry_on):
    pred, toks = gpt2_served
    dec = _decoder(pred)
    first, second = _drive(dec, [(toks[:6], 16), (toks[10:30], 6)],
                           join_at=4)
    passes = sorted((s for s in trace.spans() if s['name'] == 'serve.iter'),
                    key=lambda s: s['t0'])
    ready = [s['ready'] for s in passes if s['step']]
    assert len(ready) == _counter('serving.decode_steps')
    # the first stream takes part in every step from the first on
    assert first.gap_lanes == ready[:15]
    assert sorted(set(first.gap_lanes)) == [1, 2]
    # ... and the second in those from its own first on, both live
    at = first.gap_lanes.index(2)
    assert second.gap_lanes == ready[at:at + 5] == [2] * 5
    tables = _tables(trace.spans())
    # a step dispatched with nothing in flight is one the predictor
    # counts as not overlapped: the same steps
    sync_steps = [1 - t['overlapped'] for t in tables]
    assert first.gap_sync == sync_steps[:15]


# --------------------------------------------------------------------------
# (e) the counters and the pass's wait
# --------------------------------------------------------------------------

def test_the_token_counters_add_up_to_the_gaps_of_their_kinds(gpt2_served,
                                                              registry_on):
    pred, toks = gpt2_served
    asks = [(toks[:5], 9), (toks[3:30], 12), (toks[7:20], 2),
            (toks[1:18], 17), (toks[20:29], 5)]
    reqs = _drive(_decoder(pred), asks, join_at=3)
    kinds = [k for r in reqs for k in _kinds(r)]
    assert _counter('serving.tokens_behind_prefill') == kinds.count('chunk')
    assert _counter('serving.tokens_behind_sync') == kinds.count('sync')
    assert kinds.count('chunk') and kinds.count('sync')
    assert len(kinds) == _counter('serving.tokens_generated') - len(reqs)


def test_a_pass_s_wait_is_part_of_the_pass(gpt2_served, registry_on):
    pred, toks = gpt2_served
    dec = _decoder(pred)
    before = dec.fetch_wait_s
    _drive(dec, [(toks[:6], 12), (toks[10:30], 4)], join_at=3)
    seconds = _counter('serving.loop.seconds')
    wait = _counter('serving.loop.wait_seconds')
    assert 0 < wait <= seconds
    assert wait == pytest.approx(dec.fetch_wait_s - before)
    passes = [s for s in trace.spans() if s['name'] == 'serve.iter']
    assert sum(s['wait_ms'] for s in passes) == pytest.approx(1e3 * wait)
    assert sum(s['t1'] - s['t0'] for s in passes) >= seconds
    for s in passes:
        assert 0 <= s['wait_ms'] <= 1e3 * (s['t1'] - s['t0'])
        assert s['chunk'] in (0, 1) and s['step'] in (0, 1)
    assert sum(s['chunk'] for s in passes) == _counter('serving.prefills') \
        == 4
    assert sum(s['step'] for s in passes) == _counter('serving.decode_steps')
    # the synchronous forms wait inside the same attribute
    solo = _decoder(pred)
    solo.generate(toks[:6], 3)
    assert solo.fetch_wait_s > 0


# --------------------------------------------------------------------------
# (f) off, nothing grows
# --------------------------------------------------------------------------

def test_with_the_registry_off_no_list_grows(gpt2_served):
    pred, toks = gpt2_served
    assert not telemetry._enabled
    trace.clear()
    want = list(_decoder(pred).generate(toks[:6], 10))
    first, second = _drive(_decoder(pred), [(toks[:6], 10), (toks[10:30], 4)],
                           join_at=3)
    assert list(first.tokens) == want
    for req in (first, second):
        assert req.state == 'DONE' and len(req.token_at) == len(req.tokens)
        assert req.gap_chunks == req.gap_lanes == req.gap_sync == []
    assert not trace.spans()
    assert _counter('serving.loop.seconds') == 0


# --------------------------------------------------------------------------
# a speculative step's further tokens; a gap that spans a preemption
# --------------------------------------------------------------------------

def test_a_speculative_step_gives_its_record_to_its_first_token(
        gpt2_served, registry_on):
    pred, toks = gpt2_served
    dec = pred.prepare_decoding(slots=2, page_tokens=4, kv_pages=40,
                                prefill_chunk=8, speculative=True,
                                spec_k=3, draft_layers=1)
    first, second = _drive(dec, [(toks[:6], 14), (toks[10:22], 9)])
    for req in (first, second):
        assert len(req.gap_sync) == len(req.tokens) - 1
        assert set(req.gap_sync) == {1}          # the serial loop
        assert all(1 <= m <= 2 for m in req.gap_lanes)
    # some step made several tokens for a lane ...
    gaps = len(first.gap_sync) + len(second.gap_sync)
    lane_steps = telemetry.snapshot()['hists']['serving.decode_batch']['sum']
    assert lane_steps < gaps
    # ... and only the first of them carries the chunk in front of the
    # step: the first prompt's one chunk and the second's two stood in
    # front of three steps, the last of which both lanes took part in
    assert [c for r in (first, second) for c in r.gap_chunks if c] == [1] * 4


@pytest.fixture()
def policy_flags():
    yield
    set_flags({'FLAGS_serving_preempt_policy': 'swap',
               'FLAGS_serving_swap_host_mb': 64})


@pytest.mark.timeout(600)
@pytest.mark.parametrize('policy', ['swap', 'reprefill'])
def test_a_resumed_request_s_first_gap_is_sync(tmp_path_factory,
                                               registry_on, policy_flags,
                                               policy):
    from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
    set_flags({'FLAGS_serving_preempt_policy': policy})
    model_dir = str(tmp_path_factory.mktemp('gap_preempt'))
    fw.build_model(model_dir)
    pred = AnalysisPredictor(AnalysisConfig(model_dir))
    # two slots over a pool too small for two full streams
    dec = pred.prepare_decoding(slots=2, page_tokens=4, kv_pages=6,
                                prefill_chunk=fw.CFG.max_len)
    engine = ServingEngine(dec).start()
    try:
        low = engine.submit([1, 2, 3, 4, 5, 6, 7, 8], max_new_tokens=8,
                            priority=0)
        while not low.tokens:
            time.sleep(0.002)
        high = engine.submit([8, 7, 6, 5, 4, 3, 2, 1], max_new_tokens=8,
                             priority=1)
        high.result(240)
        low.result(240)
    finally:
        engine.stop()
    assert low.preemptions >= 1
    requeue = [s for s in trace.spans() if s['name'] == 'serve.requeue'
               and s['sid'] == low.id]
    assert len(requeue) == low.preemptions
    for req in (low, high):
        assert len(req.gap_sync) == len(req.gap_chunks) \
            == len(req.gap_lanes) == len(req.tokens) - 1
    # the gap that spans the (first) preemption: between the last token
    # accepted before it and the first after the slot was taken again
    at = int(np.searchsorted(low.token_at, requeue[0]['t1']))
    assert 0 < at < len(low.tokens)
    assert low.gap_sync[at - 1] == 1
    assert _counter('serving.tokens_behind_sync') \
        == sum(low.gap_sync) + sum(high.gap_sync)
