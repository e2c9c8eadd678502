"""A lane that is not one token a step: the serving engine over a
predictor of a model that generates by diffusion over blocks
(tests/test_sdar_moe.py builds it). The engine's emitted tokens are
reference.block_diffusion_generate's, token for token, under the three
unmasking rules, with lanes at different passes of their blocks in one
step, an eos and a budget that fall inside a block, a cancel, and a pool
that runs dry in the middle of a block (rolled back, retried, the same
tokens); the pipelined loop under the static rules and the serial one
under the dynamic rule; what a delivery leaves on its request; the spans
and counters of a block step."""
import time

import numpy as np
import pytest

from paddle_tpu.obs import telemetry
from paddle_tpu.profiler import RecordEvent  # noqa: F401  (registry on)
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.paging import CacheExhaustedError

from test_sdar_moe import SEED, build, decoder, model_of, ref

PROMPTS = (21, 8, 3, 30, 14)            # 1, 0, 3, 2 and 2 modulo 4
BUDGETS = (10, 7, 9, 12, 5)             # inside a block, all but 12


@pytest.fixture(scope='module', params=[
    ('low_confidence_static', {}),
    ('sequential', {}),
    ('low_confidence_dynamic', {'threshold': 0.17})],
    ids=lambda p: p[0])
def served(request, tmp_path_factory):
    rule, more = request.param
    model = model_of(remasking=rule, **more)
    pred, toks, _ = build(tmp_path_factory.mktemp('sdar_' + rule), model)
    return rule, ref.dims_of(model), pred, toks


def want(dims, prompt, budget, eos=None):
    return ref.block_diffusion_generate(ref.seed_key(SEED), dims, prompt,
                                        budget, eos)


def test_engine_tokens_are_the_references(served):
    rule, dims, pred, toks = served
    dec = decoder(pred)
    assert dec.block_defers == (rule != 'low_confidence_dynamic')
    telemetry.enable()
    telemetry.reset()
    with ServingEngine(dec) as eng:
        reqs = [eng.submit(toks[:n], max_new_tokens=m)
                for n, m in zip(PROMPTS, BUDGETS)]      # 5 for 4 slots
        outs = [r.result(120) for r in reqs]
    passes = []
    for n, m, out in zip(PROMPTS, BUDGETS, outs):
        seen = []
        ref.block_diffusion_generate(
            ref.seed_key(SEED), dims, toks[:n], m,
            on_pass=lambda *a: seen.append(a[3]))
        passes.append(len(seen))
        assert out == want(dims, toks[:n], m), (rule, n, m)
    stats = dec.block_stats()
    # every pass the routine makes, the engine made, and no other
    assert stats['passes'] == sum(passes)
    assert stats['steps'] < stats['passes']     # lanes shared their steps
    snap = telemetry.snapshot()['counters']
    assert snap['serving.block.passes'] == stats['passes']
    assert snap['serving.block.commits'] == stats['commits']
    assert snap['serving.block.tokens'] == sum(BUDGETS)
    assert snap['serving.block.masked_rows'] == stats['masked_rows']
    if rule == 'low_confidence_dynamic':
        # the threshold fired in some passes and not in others
        full = sum(-(-(m - (4 - n % 4) % 4) // 4) + bool(n % 4)
                   for n, m in zip(PROMPTS, BUDGETS))
        assert stats['commits'] == full
        assert stats['commits'] * 2 < stats['passes'] < sum(
            5 * -(-m // 4) + 5 for m in BUDGETS)
    else:
        assert snap['serving.decode_steps_overlapped'] > 0
    # a delivery a block: the gaps' lists hold one entry a delivery
    for req in reqs:
        assert len(req.token_at) == len(req.tokens)
        assert req.first_token_at == req.token_at[0] == req.delivered_at[0]
        assert len(req.gap_chunks) == len(req.gap_lanes) \
            == len(req.gap_sync) == len(req.delivered_at) - 1
        assert len(set(req.token_at)) == len(req.delivered_at)
    telemetry.disable()


def test_eos_inside_a_block_ends_the_stream_there(served):
    rule, dims, pred, toks = served
    free = want(dims, toks[:21], 12)
    eos = free[5]                   # inside the second or third block
    cut = want(dims, toks[:21], 12, eos)
    assert cut == free[:free.index(eos) + 1] and len(cut) < 12
    dec = decoder(pred)
    with ServingEngine(dec) as eng:
        a = eng.submit(toks[:21], max_new_tokens=12, eos_id=eos)
        b = eng.submit(toks[:8], max_new_tokens=9)
        assert a.result(120) == cut
        assert b.result(120) == want(dims, toks[:8], 9)
    assert dec.slot_tokens() == {}


def test_a_cancel_and_a_dry_pool_in_the_middle_of_a_block(served):
    """12 pages for two streams that need 8 and 6: the second's block
    finds the pool dry, the step is rolled back, a stream gives way and
    re-prefills (its tokens so far are whole blocks), and both end with
    the reference's tokens. A cancelled stream keeps what it had."""
    rule, dims, pred, toks = served
    dec = decoder(pred, kv_pages=13)
    telemetry.enable()
    telemetry.reset()
    with ServingEngine(dec) as eng:
        a = eng.submit(toks[:20], max_new_tokens=12)
        b = eng.submit(toks[30:46], max_new_tokens=12)
        assert a.result(120) == want(dims, toks[:20], 12)
        assert b.result(120) == want(dims, toks[30:46], 12)
        snap = telemetry.snapshot()['counters']
        assert snap.get('serving.cache_exhausted', 0) >= 1
        assert a.preemptions + b.preemptions >= 1
        c = eng.submit(toks[:8], max_new_tokens=40)
        while len(c.tokens) < 4:
            time.sleep(0.01)
        eng.cancel(c)
        assert c.wait(60) and c.state == 'CANCELLED'
        full = want(dims, toks[:8], 40)
        assert c.tokens == full[:len(c.tokens)] and len(c.tokens) % 4 == 0
    telemetry.disable()
    assert dec.slot_tokens() == {}


def test_block_step_rolls_back_and_retries_the_same_feed(served):
    rule, dims, pred, toks = served
    dec = decoder(pred, kv_pages=8, slots=2)        # 7 pages to hand out
    for slot, n in ((0, 16), (1, 12)):
        dec.open_stream(slot, toks[20 * slot:20 * slot + n])
        while dec.prefill_step(slot) is None:
            pass
    b0, b1 = dec.new_block(16), dec.new_block(12)
    ids = np.array([b0.ids, b1.ids], np.int64)
    starts, transfer = np.array([16, 12], np.int32), np.ones(2, np.int32)
    before = dec.pool_stats()['pages_free']
    assert before == 0                              # 4 + 3 pages hold them
    with pytest.raises(CacheExhaustedError) as err:
        dec.block_step(ids, starts, transfer, [0, 1])
    assert sorted(err.value.slots) == [0, 1]
    assert dec.pool_stats()['pages_free'] == before
    assert dec.slot_tokens() == {0: 16, 1: 12}
    dec.release(1)
    got, left, lg = dec.block_step(ids, starts, transfer, [0],
                                   return_logits=True)
    assert int(left[0]) == 3 or rule == 'low_confidence_dynamic'
    assert dec.slot_tokens() == {0: 16}
    # the same pass again lands on the same page: a block grows once
    free = dec.pool_stats()['pages_free']
    again, _, lg2 = dec.block_step(ids, starts, transfer, [0],
                                   return_logits=True)
    assert dec.pool_stats()['pages_free'] == free
    assert np.array_equal(lg[0], lg2[0]) and np.array_equal(got[0], again[0])


def test_a_block_step_leaves_the_decode_spans(served):
    from paddle_tpu.obs import trace
    rule, dims, pred, toks = served
    dec = decoder(pred)
    telemetry.enable()
    telemetry.reset()
    trace.clear()
    with ServingEngine(dec) as eng:
        eng.submit(toks[:9], max_new_tokens=8).result(120)
    spans = [dict(s, **s.get('attrs', {})) for s in trace.spans()]
    telemetry.disable()
    tables = [s for s in spans if s['name'] == 'paged.decode.tables']
    stats = dec.block_stats()
    assert len(tables) == stats['steps']
    assert sum(s['block_rows'] for s in tables) == stats['rows'] == 4 * \
        stats['passes']
    assert sum(s['masked_rows'] for s in tables) == stats['masked_rows']
    assert sum(s['commit_lanes'] for s in tables) == stats['commits'] == 3
    assert all(s['pages_read'] >= 1 for s in tables)
    accepts = [s for s in spans if s['name'] == 'serve.accept']
    assert sum(s.get('blocks', 0) for s in accepts) == 3
    hist = telemetry.snapshot()['hists']['serving.block.passes_per_block']
    assert hist['count'] == 3
    gauge = telemetry.snapshot()['gauges']['serving.effective_tokens_per_step']
    assert 0 < gauge <= 4
